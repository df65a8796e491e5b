"""deep-float-reduction: cross-replica float reductions need a license.

Floating-point addition is not associative: any reduction whose bracketing
depends on the device LAYOUT (a ``psum`` across shards, an SPMD-partitioned
global ``reduce_sum`` over a sharded operand, a float scatter-add inside a
shard_map body) can differ between the local and sharded engines — the
exact hole the bit-identity contract cannot tolerate silently. Integer
reductions are exact under any order and are never flagged; float
``max``/``min`` are order-insensitive and exempt too.

Flagged, per traced entry point of the shared matrix:

- ``psum`` with a floating dtype, anywhere (the collective itself
  brackets per shard; ``pmax``/``pmin`` are order-exact and exempt);
- ``scatter-add`` with floating updates inside a ``shard_map`` body;
- ``reduce_sum``/``reduce_prod``/``dot_general`` with floating dtype
  OUTSIDE shard_map in a DIST entry — at global shape over sharded
  operands, XLA's SPMD partitioner lowers these to per-shard partials plus
  a cross-replica combine, i.e. an implicit float psum.

The allowlist (:data:`REDUCTION_ALLOWLIST`) maps a source anchor —
(repo-relative file, function name), read off the equation's traceback —
to the REASON the site is licensed. Today's single entry is the γ-MLE
degree track, the one documented float reduction in the round path
(bit-exact state, γ to 1 ULP — docs/growth_engine.md). Adding an entry
means writing down why the reduction's layout-dependence is acceptable;
an entry that stops matching anything is dead and should be removed.
"""

from __future__ import annotations

from typing import Dict, Tuple

from tpu_gossip.analysis.deep.jaxpr_tools import iter_eqns, src_of
from tpu_gossip.analysis.registry import Finding

__all__ = ["reduction_findings", "REDUCTION_ALLOWLIST", "RULE"]

RULE = "deep-float-reduction"

# (repo-relative file, function) -> reason the float reduction is licensed
REDUCTION_ALLOWLIST: Dict[Tuple[str, str], str] = {
    ("tpu_gossip/growth/engine.py", "hill_gamma_device"): (
        "the γ-MLE degree track — the ONE documented float reduction in "
        "the round path; XLA brackets the sharded sum per shard, engines "
        "agree to 1 ULP while state and integer stats stay bit-exact "
        "(docs/growth_engine.md, determinism contract)"
    ),
}

# "psum2" and "psum_invariant" (a psum of a varying value under check_vma)
# are other spellings of the psum primitive; all must match or the pass
# goes silently blind on the collective it most exists to catch. pmax/pmin
# are NOT here: max/min are associative and commutative exactly, so their
# bracketing cannot depend on layout (the docstring's order-exact carve-out)
_COLLECTIVES = ("psum", "psum2", "psum_invariant")
_GLOBAL_REDUCES = ("reduce_sum", "reduce_prod", "dot_general")


def _is_float(aval) -> bool:
    import numpy as np

    try:
        return np.issubdtype(aval.dtype, np.floating)
    except Exception:  # noqa: BLE001 — non-array avals
        return False


def _flag(eqn, category: str) -> tuple | None:
    """(file, function, line, message) for a flagged eqn, or None."""
    dtypes = sorted({
        str(v.aval.dtype) for v in list(eqn.invars) + list(eqn.outvars)
        if hasattr(v, "aval") and _is_float(v.aval)
    })
    src = src_of(eqn)
    file = src.file if src else "<unknown>"
    func = src.function if src else "<unknown>"
    line = src.line if src else 0
    msg = (
        f"float {eqn.primitive.name} ({','.join(dtypes)}) in {func}: "
        f"{category}"
    )
    return file, func, line, msg


def reduction_findings(traced, allowlist=None) -> list[Finding]:
    """Run the reduction pass over every traced entry; deduped findings.

    A canonical run (``allowlist=None``) also reports DEAD allowlist
    entries — a license that stops matching any traced site is stale
    documentation and must be removed, not accumulate (skipped when the
    matrix carries no dist entries: a single-device host cannot trace the
    sites the licenses anchor to)."""
    allow = REDUCTION_ALLOWLIST if allowlist is None else allowlist
    findings: dict = {}
    allow_used: set = set()

    def add(file, func, line, msg, entry):
        if (file, func) in allow:
            allow_used.add((file, func))
            return
        key = (file, msg)
        if key not in findings:
            findings[key] = Finding(
                file=file,
                line=line,
                col=0,
                rule=RULE,
                message=msg,
                hint=(
                    "cross-replica float bracketing is layout-dependent: "
                    "keep the hot path integer, or license the site in "
                    "analysis/deep/reductions.py REDUCTION_ALLOWLIST with "
                    "the reason its tolerance is acceptable "
                    f"(first seen tracing {entry})"
                ),
                qualname=func,
            )

    for name, te in traced.items():
        if te.jaxpr is None:
            continue
        is_dist = te.ep.engine.startswith("dist") if te.ep else False
        for eqn, inside_sm in iter_eqns(te.jaxpr.jaxpr):
            prim = eqn.primitive.name
            hit = None
            if prim in _COLLECTIVES:
                if any(_is_float(v.aval) for v in eqn.outvars):
                    hit = _flag(eqn, "cross-replica float collective")
            elif prim == "scatter-add" and inside_sm:
                if any(_is_float(v.aval) for v in eqn.outvars):
                    hit = _flag(
                        eqn, "float scatter-add inside a shard_map body"
                    )
            elif prim in _GLOBAL_REDUCES and is_dist and not inside_sm:
                if any(_is_float(v.aval) for v in eqn.outvars):
                    hit = _flag(
                        eqn,
                        "global-shape float reduction over sharded "
                        "operands (SPMD lowers to an implicit psum)",
                    )
            if hit is not None:
                add(*hit, name)
    has_dist = any(
        te.ep is not None and te.ep.engine.startswith("dist")
        for te in traced.values()
    )
    if allowlist is None and has_dist:
        for (file, func) in sorted(set(allow) - allow_used):
            findings[(file, f"dead:{func}")] = Finding(
                file=file, line=0, col=0, rule=RULE,
                message=(
                    f"REDUCTION_ALLOWLIST entry ({file!r}, {func!r}) "
                    "matches no traced float reduction — a dead license"
                ),
                hint="remove the entry (or fix the anchor): a license "
                "that matches nothing documents a reduction that no "
                "longer exists",
                qualname=func,
            )
    return sorted(findings.values(), key=lambda f: f.sort_key)
