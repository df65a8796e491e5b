"""Profiler tracing hook (SURVEY.md §5.1).

The reference's only visibility into runtime behavior is timestamped log
lines (reference Peer.py:40-49, Seed.py:78-87) — "log-line archaeology".
The TPU-native replacement is a device trace of the real composed round:
:func:`trace` wraps any region (a run, a ``simulate()`` horizon) and XLA
records per-op device timelines, viewable in TensorBoard / Perfetto
(``xprof``). Exposed as ``--profile DIR`` on ``cli/run_sim.py``.

The round names its parts with ``jax.named_scope`` (``sim/stages.py``):
``round`` with ``roles``, ``delivery``, ``stats`` and each stage of the
DAG inside it, the run-to-coverage predicate ``coverage``, and the
delivery kernels ``lane_shuffle`` and ``fold_planes``. XLA keeps the scope
in each instruction's ``op_name`` metadata, so an op of the trace maps to
its stage through the compiled program's text, under names that hold
across XLA's renumbering of fusions (docs/round_tail_profile.md).
"""

from __future__ import annotations

import contextlib
from pathlib import Path
from typing import Iterator

__all__ = ["trace"]


@contextlib.contextmanager
def trace(log_dir: str | Path | None) -> Iterator[None]:
    """Record a ``jax.profiler`` device trace into ``log_dir``.

    No-op when ``log_dir`` is falsy, so call sites can pass the CLI flag
    straight through. The caller is responsible for making the traced region
    representative (warmed-up, compile excluded) — tracing a cold run records
    mostly compilation.
    """
    if not log_dir:
        yield
        return
    import jax

    path = Path(log_dir)
    path.mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(str(path))
    try:
        yield
    finally:
        jax.profiler.stop_trace()
