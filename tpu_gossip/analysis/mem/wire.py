"""Pass 3 — static wire audit: traced collectives vs the analytic model.

The sparse transport's analytic counters (``IciRound`` /
``ici_bytes_per_round``, dist/transport.py) are hand-written models of
what the dist engines ship. Models drift. This pass recomputes the
shipped words of every collective in the traced jaxpr of BOTH dense dist
entries — ``all_to_all`` payloads at their per-shard operand shapes x the
mesh size — and cross-checks the total against each engine's wire
declaration (``dense_wire_words`` in dist/mesh.py and
dist/matching_mesh.py, which share their formulas with the traced
counters). Any skew — a hand-edited counter, or an engine change that
grows the wire without updating its declaration — is a
``mem-wire-drift`` finding.

Only the DENSE entries are audited: their all_to_all set is exactly the
payload exchange (the sparse entries nest both lanes under ``lax.cond``,
so their traced collectives deliberately over-count the executed wire).
The per-type word census (psum/pmax/ppermute/all_gather headers and
stats) rides the report for the budget record, uncompared — those are
O(S) housekeeping, not payload.
"""

from __future__ import annotations

from tpu_gossip.analysis.registry import Finding

__all__ = ["wire_findings", "collective_census"]

WIRE_RULE = "mem-wire-drift"

# dense entries audited: name -> engine family (mode/slots fixed by the
# matrix: push_pull, msg_slots=16, forward_once False). The 2-D cluster
# entries compare against the SAME declarations — the (hosts, devices)
# fold is the flat program, so its dense wire is the flat wire; their
# census additionally carries the per_axis ici/dcn byte split
_WIRE_ENTRIES = {
    "dist[bucketed]": "bucketed",
    "dist[matching]": "matching",
    "dist[bucketed,2d]": "bucketed",
    "dist[matching,2d]": "matching",
}

# psum2/pmax2/pmin2 and psum_invariant (a psum of a varying value under
# check_vma) are other spellings jax traces for the same wire ops —
# censused under their base name so the report columns stay stable
_COLLECTIVES = ("all_to_all", "psum", "pmax", "pmin", "ppermute",
                "all_gather")
_PRIM_ALIASES = {"psum2": "psum", "pmax2": "pmax", "pmin2": "pmin",
                 "psum_invariant": "psum"}


def _aval_words(aval) -> int:
    """4-byte words of one operand (sub-word dtypes round up)."""
    try:
        item = aval.dtype.itemsize
    except Exception:  # noqa: BLE001 — extended dtypes
        item = 4
    return -(-int(aval.size) * int(item) // 4)


def collective_census(te, n_shards: int) -> dict:
    """Per-primitive global shipped words of one entry's trace.

    The special ``per_axis`` row splits the same global volume into
    BYTE columns keyed by interconnect class (``dist.mesh.axis_kind``:
    ici vs dcn) — the static metric split the multi-host transport work
    budgets against (mirrors the columns of ``collectives.lock``).
    """
    from tpu_gossip.analysis.deep.collectives import _axes_of
    from tpu_gossip.analysis.deep.jaxpr_tools import iter_eqns
    from tpu_gossip.dist.mesh import axis_kind

    census: dict = {k: 0 for k in _COLLECTIVES}
    per_axis: dict = {}
    for eqn, inside in iter_eqns(te.jaxpr.jaxpr):
        prim = _PRIM_ALIASES.get(eqn.primitive.name, eqn.primitive.name)
        if prim not in _COLLECTIVES:
            continue
        # each of the S shards ships its (per-shard-shaped) operand; the
        # global wire is S x the block (psum/pmax reductions move the
        # same order — the census is a word count, not a topology model)
        words = sum(
            _aval_words(a.aval) for a in eqn.invars if hasattr(a, "aval")
        )
        census[prim] += n_shards * words
        for ax in _axes_of(eqn):
            kind = axis_kind(ax)
            per_axis[kind] = per_axis.get(kind, 0) + n_shards * words * 4
    out = {k: v for k, v in census.items() if v}
    if per_axis:
        out["per_axis"] = dict(sorted(per_axis.items()))
    return out


def wire_findings(traced) -> tuple[list, dict]:
    """(findings, report) — the cross-check over the dense dist entries.

    The engine declarations are resolved through their modules AT CALL
    TIME (``mesh_mod.dense_wire_words``), so tests can monkeypatch a
    skewed counter and assert this audit reports it.
    """
    findings: list[Finding] = []
    report: dict = {}
    names = [n for n in _WIRE_ENTRIES if n in traced]
    if not names:
        return findings, report
    from tpu_gossip.analysis.entrypoints import _dist_ctx, dist_guard
    from tpu_gossip.dist import matching_mesh as matching_mod
    from tpu_gossip.dist import mesh as mesh_mod

    if dist_guard() is not None:
        return findings, report
    dctx = _dist_ctx()
    n_shards = dctx["mesh"].size
    for name in names:
        te = traced[name]
        if te.jaxpr is None:
            continue
        census = collective_census(te, n_shards)
        traced_words = census.get("all_to_all", 0)
        if _WIRE_ENTRIES[name] == "bucketed":
            declared = mesh_mod.dense_wire_words(
                dctx["sg"], 16, "push_pull", forward_once=False
            )
        else:
            declared = matching_mod.dense_wire_words(
                dctx["plan"], 16, "push_pull", forward_once=False
            )
        report[name] = {
            "declared_words": int(declared),
            "traced_words": int(traced_words),
            "census_words": census,
        }
        if traced_words != declared:
            findings.append(Finding(
                file=f"<mem:{name}>", line=0, col=0, rule=WIRE_RULE,
                message=(
                    f"analytic wire model declares {declared} dense words "
                    f"per round but the traced all_to_all operands ship "
                    f"{traced_words} — the hand-written ICI counter has "
                    "drifted from the exchange it describes"
                ),
                hint="update dense_wire_words (and the shared transport "
                "formula the IciRound counter reads) in the same commit "
                "as the exchange change",
                qualname=name,
            ))
    return findings, report
