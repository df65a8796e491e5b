"""raw-shard-map: all shard_map use routes through dist/_compat.py.

The invariant: ``shard_map_compat`` (tpu_gossip/dist/_compat.py) is the
one place allowed to touch ``jax.shard_map`` or the retired
``jax.experimental.shard_map`` spelling; everything else imports it, so a
jax API change is absorbed in one file (the ``check_rep``→``check_vma``
rename once broke all 23 dist tests, CHANGES.md PR 1). Docstrings and comments are
naturally exempt (this is an AST pass, not a grep).
"""

from __future__ import annotations

import ast

from tpu_gossip.analysis.registry import Finding, rule
from tpu_gossip.analysis.walker import ModuleInfo

__all__ = ["check_raw_shard_map"]

_ALLOWED_FILES = ("tpu_gossip/dist/_compat.py",)
_HINT = (
    "route through tpu_gossip.dist._compat.shard_map_compat (the repo's "
    "one shard_map call site)"
)


def _finding(module: ModuleInfo, node: ast.AST, what: str) -> Finding:
    return Finding(
        file=module.rel,
        line=node.lineno,
        col=node.col_offset + 1,
        rule="raw-shard-map",
        message=f"raw shard_map reference ({what}) outside dist/_compat.py",
        hint=_HINT,
    )


@rule("raw-shard-map")
def check_raw_shard_map(module: ModuleInfo):
    if module.rel in _ALLOWED_FILES:
        return
    for node in ast.walk(module.tree):
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod in ("jax.experimental.shard_map", "jax._src.shard_map"):
                yield _finding(module, node, f"from {mod} import ...")
            elif mod == "jax" and any(
                a.name == "shard_map" for a in node.names
            ):
                yield _finding(module, node, "from jax import shard_map")
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name.startswith("jax.experimental.shard_map") or (
                    a.name.startswith("jax._src.shard_map")
                ):
                    yield _finding(module, node, f"import {a.name}")
        elif isinstance(node, ast.Attribute):
            dotted = module.dotted(node)
            if dotted in (
                "jax.shard_map",
                "jax.experimental.shard_map.shard_map",
                "jax._src.shard_map.shard_map",
            ):
                yield _finding(module, node, dotted)
