"""The shared entry-point matrix: ONE parametrization, TWO consumers.

The contract audit (contracts.py) and the jaxpr deep tier (deep/) must
walk the same matrix — an engine/mode added to one and silently skipped
by the other re-opens the gap this harness closed. These tests pin (a)
the matrix's structural coverage, (b) that every entry is owned by a
registered audit check (the union covers the matrix), (c) that one trace
cache serves both consumers, and (d) that a broken entry is reported by
BOTH tiers (behavioral proof they read the same matrix).
"""

import pytest

from tpu_gossip.analysis.contracts import AUDIT_CHECKS, audit_contracts
from tpu_gossip.analysis.entrypoints import (
    EntryPoint,
    entry_points,
    trace_matrix,
)

EPS = entry_points()


def test_every_entry_owned_by_a_registered_audit_check():
    unowned = [ep.name for ep in EPS if ep.audit_check not in AUDIT_CHECKS]
    assert unowned == [], f"matrix entries no audit check owns: {unowned}"


def test_matrix_structural_coverage():
    """The product the bit-identity contract quantifies over: every local
    delivery engine, every mode, both slot widths, churn/SIR/compact,
    every tail, scenario and growth planes, both dist engines, sparse
    transport, and the jitted loop entries."""
    names = {ep.name for ep in EPS}
    engines = {ep.engine for ep in EPS}
    assert {"xla", "pallas", "matching"} <= engines
    for mode in ("push", "push_pull", "flood"):
        for eng in ("xla", "pallas", "matching"):
            for m in (1, 16):
                assert f"local[{eng},{mode},m={m}]" in names
    for extra in ("churn", "sir", "churn-compact", "scenario", "growth",
                  "stream", "scenario+growth", "scenario+growth+stream",
                  "control", "scenario+growth+stream+control",
                  "adversary", "scenario+growth+stream+control+adversary"):
        assert f"local[xla,{extra}]" in names
    for tail in ("reference", "fused", "packed"):
        assert f"local[xla,tail={tail}]" in names
    assert "local[matching,scenario]" in names
    assert "local[matching,growth]" in names and "local[pallas,growth]" in names
    assert "local[matching,stream]" in names and "local[pallas,stream]" in names
    # the SERVED round (serve/ live-ingestion window) on every engine
    assert "local[matching,ingest]" in names and "local[pallas,ingest]" in names
    assert "local[xla,ingest]" in names
    assert "local[matching,control]" in names and "local[pallas,control]" in names
    assert "local[simulate]" in names and "local[run_until_coverage]" in names
    # the PACKED loop entries (core/packed.py): packed carries must be
    # fixed points too, and the mem tier prices the packed residency
    assert "local[simulate,packed]" in names
    assert "local[run_until_coverage,packed]" in names
    # the batched fleet entry (fleet/): composed campaign at batch rank
    assert "fleet[simulate,composed]" in names
    # dist half (present on this 8-device test host)
    assert {"dist-matching", "dist-bucketed"} <= engines
    for n in (
        "dist[matching]", "dist[matching,scenario]", "dist[matching,growth]",
        "dist[matching,stream]", "dist[matching,ingest]",
        "dist[bucketed]", "dist[bucketed,growth]", "dist[bucketed,stream]",
        "dist[matching,simulate]", "dist[bucketed,run_until_coverage]",
        "dist[matching,sparse]", "dist[bucketed,sparse]",
        "dist[matching,control]", "dist[bucketed,control]",
        "dist[matching,pipeline]", "dist[bucketed,pipeline]",
        "dist[matching,pipeline+scenario+stream]",
        "dist[matching,adversary+scenario]",
        "dist[matching,simulate,packed]",
    ):
        assert n in names, n


def test_jitted_loop_entries_declare_their_pjit_name():
    """Every simulate/coverage entry must carry jit_name — that is the
    hook the deep tier's donation pass verifies donated_invars through."""
    for ep in EPS:
        if ep.kind in ("simulate", "coverage"):
            assert ep.jit_name, (
                f"{ep.name}: jitted loop entry without jit_name"
            )
        else:
            assert ep.kind == "round"


def test_entry_names_unique():
    names = [ep.name for ep in EPS]
    assert len(names) == len(set(names))


def test_every_entry_declares_n_peers():
    """Every matrix entry carries an explicit n_peers (the mem tier's
    bytes/peer denominator) matching its built state's slot count — n
    used to be implicit in each builder closure, which a scale metric
    cannot read. Priced at BATCH RANK: a fleet entry's alive plane is
    (K, N), and its denominator is the AGGREGATE K*N slot count (the
    plane-registry pricing convention, core.state.state_bytes_per_peer)."""
    import numpy as np

    for ep in EPS:
        assert ep.n_peers > 0, f"{ep.name}: n_peers undeclared"
        _, st = ep.build()
        # packed entries carry the six masks in the shared flags word
        lead = st.alive.shape if hasattr(st, "alive") else st.flags.shape
        slots = int(np.prod(lead))
        assert slots == ep.n_peers, (
            f"{ep.name}: declared n_peers={ep.n_peers} but the built "
            f"state has {slots} slots"
        )


def test_trace_cache_shared_across_consumers():
    """The same cache dict must make the second consumer reuse the first's
    TracedEntry objects — the CLI's one-matrix-per-invocation guarantee."""
    eps = [ep for ep in EPS if ep.name == "local[xla,push,m=1]"]
    cache: dict = {}
    first = trace_matrix(eps, cache=cache)
    second = trace_matrix(eps, cache=cache)
    assert first["local[xla,push,m=1]"] is second["local[xla,push,m=1]"]
    assert first["local[xla,push,m=1]"].jaxpr is not None


def test_broken_entry_reported_by_both_tiers(monkeypatch):
    """Seed ONE broken matrix entry and assert the audit AND the deep tier
    both surface it — the behavioral pin that they consume the same
    parametrization, not two drifting copies."""
    from tpu_gossip.analysis import contracts as contracts_mod
    from tpu_gossip.analysis import entrypoints as ep_mod

    def boom_build():
        raise RuntimeError("synthetic matrix-entry break")

    broken = EntryPoint(
        name="synthetic[broken]", engine="xla", kind="round",
        audit_check="gossip_round_local", build=boom_build,
    )
    tiny = (broken,)
    monkeypatch.setattr(ep_mod, "entry_points", lambda: tiny)
    # contracts.py binds the names at import: patch its view too — the
    # production CLI resolves both through the same module function
    monkeypatch.setattr(contracts_mod, "entry_points", lambda: tiny)

    cache: dict = {}
    audit = audit_contracts(names=["gossip_round_local"], cache=cache)
    assert any(
        "synthetic[broken]" in f.message and "abstract eval failed"
        in f.message for f in audit
    ), [f.message for f in audit]

    from tpu_gossip.analysis.deep import run_deep

    deep = [f for f in run_deep(cache=cache) if f.rule == "deep-trace-error"]
    assert any(f.qualname == "synthetic[broken]" for f in deep), [
        f.render() for f in deep
    ]
    # and the shared cache means the broken build was attempted ONCE per
    # consumer-visible entry, not re-raised into divergent matrices
    assert "synthetic[broken]" in cache


@pytest.mark.parametrize("check", sorted(
    {ep.audit_check for ep in EPS if ep.kind in ("round", "simulate",
                                                 "coverage")}
))
def test_round_audit_checks_exist(check):
    assert check in AUDIT_CHECKS
