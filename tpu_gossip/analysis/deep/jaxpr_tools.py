"""Shared jaxpr traversal helpers for the deep tier.

The deep passes operate on traced jaxprs (analysis/entrypoints.py), which
nest: ``pjit``/``scan``/``while``/``cond``/``shard_map``/``pallas_call``
equations carry sub-jaxprs in their params. This module centralizes

- :func:`subjaxprs` — every sub-jaxpr of one equation, with the param key;
- :func:`iter_eqns` — a flattened walk of (eqn, inside_shard_map) pairs;
- :func:`src_of` — the equation's source anchor: the innermost traceback
  frame inside ``tpu_gossip/`` (the harness's own frames in
  ``analysis/`` excluded), so findings point at the repo line that
  emitted the op, not at jax internals or the tracing lambda.

Imports of jax are function-local: the analysis package must import on a
tree whose runtime is broken (registry.py's contract); only the deep
passes themselves — which trace by definition — pull jax in.
"""

from __future__ import annotations

from typing import Iterator, Tuple

__all__ = ["subjaxprs", "iter_eqns", "src_of", "SrcFrame"]


def _core():
    from jax._src import core

    return core


def subjaxprs(eqn) -> Iterator[Tuple[str, object]]:
    """(param_name, Jaxpr) for every sub-jaxpr in ``eqn.params``."""
    core = _core()
    for k, v in eqn.params.items():
        if isinstance(v, core.ClosedJaxpr):
            yield k, v.jaxpr
        elif isinstance(v, core.Jaxpr):
            yield k, v
        elif isinstance(v, (tuple, list)):
            for x in v:
                if isinstance(x, core.ClosedJaxpr):
                    yield k, x.jaxpr
                elif isinstance(x, core.Jaxpr):
                    yield k, x


def iter_eqns(jaxpr, inside_shard_map: bool = False):
    """Depth-first (eqn, inside_shard_map) over a jaxpr and its sub-jaxprs.

    ``inside_shard_map`` is True for every equation lexically inside a
    ``shard_map`` body — the region where an op sees PER-SHARD operands
    and the bit-identity contract's "global shape outside shard_map"
    discipline applies.
    """
    for eqn in jaxpr.eqns:
        yield eqn, inside_shard_map
        inner_sm = inside_shard_map or eqn.primitive.name == "shard_map"
        for _, sub in subjaxprs(eqn):
            yield from iter_eqns(sub, inner_sm)


class SrcFrame:
    """Where an equation came from: repo-relative file, function, line."""

    __slots__ = ("file", "function", "line")

    def __init__(self, file: str, function: str, line: int):
        self.file = file
        self.function = function
        self.line = line


def _rel(file_name: str) -> str:
    p = file_name.replace("\\", "/")
    i = p.rfind("/tpu_gossip/")
    return p[i + 1:] if i >= 0 else p


def src_of(eqn) -> SrcFrame | None:
    """The innermost user frame of ``eqn`` inside the package (harness
    frames in analysis/ excluded), else the innermost user frame of any
    file (test-defined functions), else None."""
    try:
        from jax._src import source_info_util as siu

        frames = list(siu.user_frames(eqn.source_info.traceback))
    except Exception:  # noqa: BLE001 — source info is best-effort
        return None
    for fr in frames:
        f = fr.file_name.replace("\\", "/")
        if "/tpu_gossip/" in f and "/tpu_gossip/analysis/" not in f:
            return SrcFrame(_rel(f), _bare(fr.function_name), fr.start_line)
    for fr in frames:
        return SrcFrame(
            _rel(fr.file_name), _bare(fr.function_name), fr.start_line
        )
    return None


def _bare(function_name: str) -> str:
    """The function's own name: jax reports the qualified name
    (``_exchange.<locals>.ex``), the allowlists key on ``ex``."""
    return function_name.rsplit(".", 1)[-1]
