"""graftmem — the jaxpr memory tier (analysis/mem/).

Pins: (a) the liveness ledger's byte arithmetic on hand-computed
micro-jaxprs (chain, donation credit, scan-carry credit); (b) the plane
registry's coverage of SwarmState and its bytes/peer arithmetic;
(c) break-and-detect for every pass — a widened plane, a widening cast,
a hot-path clone, a dropped donation, and a skewed wire counter each
surface as a finding; (d) the budget file round-trip and its regression/
missing gates; (e) CLI exit codes and the identity-stable json ordering,
on a monkeypatched two-entry matrix so the tests stay fast.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_gossip.analysis.entrypoints import entry_points, trace_matrix
from tpu_gossip.analysis.mem.budget import (
    budget_findings,
    load_budget,
    write_budget,
)
from tpu_gossip.analysis.mem.ledger import (
    EntryLedger,
    _analyze,
    entry_ledger,
    ledger_findings,
)
from tpu_gossip.analysis.mem.widths import (
    plane_width_findings,
    widening_cast_findings,
)
from tpu_gossip.analysis.mem.wire import wire_findings

from tests.analysis._tracecache import CACHE as _CACHE

EPS = {ep.name: ep for ep in entry_points()}


def _traced(name):
    return trace_matrix([EPS[name]], cache=_CACHE)[name]


# ----------------------------------------------------------- micro ledger
def test_peak_of_straight_chain():
    """y = x + x; z = y * y: at each eqn exactly two (1024,) f32 buffers
    coexist — peak 8192 B."""

    def f(x):
        y = x + x
        return y * y

    closed = jax.make_jaxpr(f)(jnp.zeros((1024,), jnp.float32))
    peak, breakdown = _analyze(closed.jaxpr, {closed.jaxpr.invars[0]: "x"})
    assert peak == 8192, breakdown


def test_peak_counts_fanout_liveness():
    """x stays live across both uses: at the second eqn x, y, z coexist."""

    def f(x):
        y = x + 1.0
        z = x * 2.0
        return y, z

    closed = jax.make_jaxpr(f)(jnp.zeros((1024,), jnp.float32))
    peak, _ = _analyze(closed.jaxpr, {})
    assert peak == 3 * 4096


def test_donation_credit_collapses_pjit_footprint():
    """A donated pjit aliases its input: footprint 1x, not 2x."""
    g = jax.jit(lambda x: x + 1.0, donate_argnums=(0,))

    closed = jax.make_jaxpr(lambda x: g(x))(jnp.zeros((1024,), jnp.float32))
    [eqn] = closed.jaxpr.eqns
    assert eqn.primitive.name == "jit" and any(
        eqn.params["donated_invars"]
    )
    peak, _ = _analyze(closed.jaxpr, {})
    assert peak == 4096  # in+out 8192 minus the 4096 donation credit

    h = jax.jit(lambda x: x + 1.0)  # undonated twin: the copy survives
    closed2 = jax.make_jaxpr(lambda x: h(x))(jnp.zeros((1024,), jnp.float32))
    peak2, _ = _analyze(closed2.jaxpr, {})
    assert peak2 == 8192


def test_scan_carry_credit():
    """A scan carry aliases in place: the loop costs one carry, not two."""

    def f(c):
        return jax.lax.scan(lambda c, _: (c + 1.0, None), c, None, length=3)[0]

    closed = jax.make_jaxpr(f)(jnp.zeros((1024,), jnp.float32))
    peak, _ = _analyze(closed.jaxpr, {})
    assert peak == 4096


# ---------------------------------------------------------- the registry
def test_registry_covers_swarm_state_exactly():
    from tpu_gossip.core.state import PLANES, SwarmState

    assert {p.name for p in PLANES} == {
        f.name for f in dataclasses.fields(SwarmState)
    }


def test_registry_bytes_per_peer_arithmetic():
    from tpu_gossip.core.state import state_bytes_per_peer, state_plane_bytes

    by_plane = state_plane_bytes(100, 16, rewire_slots=1)
    # hand sums at (N=100, M=16, S=1): five (N, M) bool planes, one
    # (N, M) int32, five (N,) bool, int32/int16 rows, scalars
    assert by_plane["seen"] == 100 * 16
    assert by_plane["infected_round"] == 100 * 16 * 2  # narrowed int16
    assert by_plane["last_hb"] == 100 * 2  # narrowed int16
    assert by_plane["join_round"] == 100 * 2  # the narrowed plane
    assert by_plane["slot_lease"] == 16 * 2
    assert by_plane["row_ptr"] == 101 * 4
    assert by_plane["rng"] == 8
    total = sum(by_plane.values())
    assert state_bytes_per_peer(100, 16) == total / 100


def test_narrowed_planes_materialize_declared_widths():
    te = _traced("local[xla,push,m=1]")
    assert str(te.state.join_round.dtype) == "int16"
    assert str(te.state.slot_lease.dtype) == "int16"
    assert str(te.state.infected_round.dtype) == "int16"
    assert str(te.state.last_hb.dtype) == "int16"


def test_entry_ledger_state_bytes_match_flattened_leaves():
    te = _traced("local[xla,push,m=1]")
    led = entry_ledger("local[xla,push,m=1]", te)
    want = sum(
        8 if jnp.issubdtype(leaf.dtype, jax.dtypes.prng_key)
        else leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree.leaves(te.state)
    )
    assert led.state_bytes == want
    assert led.peak_bytes >= led.state_bytes  # the state is live at entry
    assert led.n_peers == EPS["local[xla,push,m=1]"].n_peers
    assert led.top and led.top[0][1] >= led.top[-1][1]


# ------------------------------------------------------- break-and-detect
def test_widened_plane_detected():
    """Re-widen join_round to int32 on a traced state: mem-plane-width."""
    te = _traced("local[xla,push,m=1]")
    doctored = dataclasses.replace(
        te, state=dataclasses.replace(
            te.state, join_round=te.state.join_round.astype(jnp.int32)
        )
    )
    findings = plane_width_findings({"x": doctored})
    assert any(
        f.rule == "mem-plane-width"
        and f.qualname == "SwarmState.join_round"
        and "WIDER" in f.message
        for f in findings
    ), [f.render() for f in findings]
    # the honest tree is width-clean
    assert not plane_width_findings({"x": te})


def test_widening_cast_detected():
    """An (N,)-scale int16->int32 cast inside a round body is a finding."""
    ep = EPS["local[xla,push,m=1]"]
    te = _traced("local[xla,push,m=1]")

    def widening(s):
        return jnp.sum(s.join_round.astype(jnp.int32) * 2)

    jaxpr = jax.make_jaxpr(widening)(te.state)
    doctored = dataclasses.replace(te, jaxpr=jaxpr)
    findings = widening_cast_findings({"x": doctored})
    assert any(
        f.rule == "mem-widening-cast" and "int16->int32" in f.message
        for f in findings
    ), [f.render() for f in findings]
    assert ep.n_peers > 0
    # the honest trace is cast-clean
    assert not widening_cast_findings({"x": te})


def test_hot_path_clone_detected():
    """clone_state traced inside the round: mem-hot-clone."""
    from tpu_gossip.core.state import clone_state
    from tpu_gossip.sim import engine

    name = "local[xla,push,m=1]"
    te = _traced(name)
    fn, st = EPS[name].build()
    jaxpr = jax.make_jaxpr(lambda s: fn(clone_state(s)))(st)
    doctored = dataclasses.replace(te, jaxpr=jaxpr)
    findings, _ = ledger_findings({name: doctored})
    assert any(f.rule == "mem-hot-clone" for f in findings), [
        f.render() for f in findings
    ]
    assert engine is not None
    findings_clean, _ = ledger_findings({name: te})
    assert not [f for f in findings_clean if f.rule == "mem-hot-clone"]


def test_dropped_donation_detected():
    """A jitted loop entry whose pjit stops donating re-materializes the
    state copy: mem-donation-residency."""
    name = "local[simulate]"
    te = _traced(name)
    # an undonated twin with the same pjit name: the call-site footprint
    # is state-in + state-out with no aliasing credit (the barrier keeps
    # the identity from being forwarded — a bare x would trace with no
    # pjit outvars at all)
    undonated = jax.jit(
        lambda state: jax.lax.optimization_barrier(state)
    )

    def fn(s):
        return undonated(s)

    jaxpr = jax.make_jaxpr(fn)(te.state)
    ep = dataclasses.replace(te.ep, jit_name="<lambda>")
    doctored = dataclasses.replace(te, ep=ep, jaxpr=jaxpr)
    findings, _ = ledger_findings({name: doctored})
    assert any(f.rule == "mem-donation-residency" for f in findings), [
        f.render() for f in findings
    ]
    # the honest donating entry is clean
    clean, _ = ledger_findings({name: te})
    assert not [f for f in clean if f.rule == "mem-donation-residency"]


def test_skewed_wire_counter_detected(monkeypatch):
    """Skew the bucketed engine's wire declaration: mem-wire-drift."""
    from tpu_gossip.dist import mesh as mesh_mod

    traced = trace_matrix([EPS["dist[bucketed]"]], cache=_CACHE)
    clean, report = wire_findings(traced)
    assert clean == [] and report["dist[bucketed]"]["traced_words"] == \
        report["dist[bucketed]"]["declared_words"]

    real = mesh_mod.dense_wire_words
    monkeypatch.setattr(
        mesh_mod, "dense_wire_words",
        lambda *a, **kw: real(*a, **kw) + 64,
    )
    findings, _ = wire_findings(traced)
    assert any(f.rule == "mem-wire-drift" for f in findings), [
        f.render() for f in findings
    ]


# ------------------------------------------------------------- the budget
def _tiny_ledgers():
    return {
        "a": EntryLedger(name="a", n_peers=100, state_bytes=1000,
                         const_bytes=50, peak_bytes=2000, top=[["x", 2000]]),
        "b": EntryLedger(name="b", n_peers=200, state_bytes=4000,
                         const_bytes=0, peak_bytes=6000, top=[["y", 6000]]),
    }


def test_budget_round_trip(tmp_path):
    path = tmp_path / "memory_budget.toml"
    ledgers = _tiny_ledgers()
    write_budget(path, ledgers)
    budget = load_budget(path)
    assert set(budget) == {"a", "b"}
    assert budget["a"]["peak_bytes"] == 2000
    assert budget["b"]["bytes_per_peer"] == 30.0
    findings, stale = budget_findings(ledgers, budget)
    assert findings == [] and stale == []


def test_budget_regression_and_missing(tmp_path):
    path = tmp_path / "memory_budget.toml"
    ledgers = _tiny_ledgers()
    write_budget(path, ledgers)
    budget = load_budget(path)
    # 10% growth > the 5% tolerance
    grown = dict(ledgers)
    grown["a"] = dataclasses.replace(ledgers["a"], peak_bytes=2200)
    findings, _ = budget_findings(grown, budget)
    assert any(f.rule == "mem-budget-regression" and f.qualname == "a"
               for f in findings), [f.render() for f in findings]
    # 4% stays inside tolerance
    ok = dict(ledgers)
    ok["a"] = dataclasses.replace(ledgers["a"], peak_bytes=2080)
    findings, _ = budget_findings(ok, budget)
    assert findings == []
    # an unbudgeted entry fails; a stale budget line only reports
    extra = dict(ledgers)
    extra["c"] = dataclasses.replace(ledgers["a"], name="c")
    findings, _ = budget_findings(extra, budget)
    assert any(f.rule == "mem-budget-missing" and f.qualname == "c"
               for f in findings)
    findings, stale = budget_findings({"a": ledgers["a"]}, budget)
    assert findings == [] and stale == ["b"]


def test_committed_budget_covers_current_matrix():
    """Every current matrix entry has a line in the committed budget (the
    gate CI runs; regenerating on a matrix edit is part of the PR)."""
    from tpu_gossip.analysis.cli import repo_root

    budget = load_budget(repo_root() / "memory_budget.toml")
    missing = [ep.name for ep in entry_points() if ep.name not in budget]
    assert missing == [], missing


# ------------------------------------------------------------------- CLI
@pytest.fixture
def tiny_matrix(monkeypatch):
    """Shrink the matrix to two local entries so CLI tests stay fast."""
    from tpu_gossip.analysis import entrypoints as ep_mod

    tiny = (EPS["local[xla,push,m=1]"], EPS["local[simulate]"])
    monkeypatch.setattr(ep_mod, "entry_points", lambda: tiny)
    return tiny


def test_cli_mem_budget_gate(tiny_matrix, tmp_path, capsys):
    from tpu_gossip.analysis.cli import main

    budget = tmp_path / "budget.toml"
    # price the tiny matrix, then gate against it: clean
    assert main(["--mem-only", "--write-budget", f"--budget={budget}"]) == 0
    capsys.readouterr()
    assert main(["--mem-only", f"--budget={budget}"]) == 0
    capsys.readouterr()
    # deflate one budget line 10%: the same tree now regresses -> exit 1
    text = budget.read_text()
    entries = load_budget(budget)
    peak = entries["local[simulate]"]["peak_bytes"]
    budget.write_text(text.replace(
        f"peak_bytes = {peak}", f"peak_bytes = {int(peak * 0.9)}", 1
    ))
    rc = main(["--mem-only", f"--budget={budget}", "--format=json"])
    data = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert any(f["rule"] == "mem-budget-regression" for f in data["new"])


def test_cli_mem_json_report_ordering(tiny_matrix, tmp_path, capsys):
    from tpu_gossip.analysis.cli import main

    budget = tmp_path / "budget.toml"
    assert main(["--mem-only", "--write-budget", f"--budget={budget}"]) == 0
    capsys.readouterr()
    rc = main(["--mem-only", f"--budget={budget}", "--format=json"])
    data = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert data["mem"] is True
    names = list(data["mem_report"]["entries"])
    assert names == sorted(names)
    entry = data["mem_report"]["entries"][names[0]]
    assert {"n_peers", "peak_bytes", "const_bytes", "bytes_per_peer",
            "state_bytes", "top"} <= set(entry)
    assert isinstance(data["mem_report"]["state_bytes_per_peer_1m"], float)
    assert data["mem_seconds"] is not None


def test_cli_mem_only_with_explicit_paths_is_a_usage_error(capsys):
    """--mem-only/--write-budget with explicit paths must refuse (exit 2),
    not exit 0 having analyzed nothing: the memory tier is trace-only."""
    from tpu_gossip.analysis.cli import main

    assert main(["--mem-only", "tpu_gossip/core/state.py"]) == 2
    capsys.readouterr()
    assert main(["--write-budget", "tpu_gossip/core/state.py"]) == 2
    capsys.readouterr()


def test_round_cap_saturates_narrow_plane_writes():
    """Past ROUND_CAP the round cursor saturates into the int16 planes
    (a late lease/join, never a wrap into the -1 sentinels)."""
    from tpu_gossip.core.state import ROUND_CAP
    from tpu_gossip.traffic import compile_stream
    from tpu_gossip.traffic.engine import apply_stream

    sp = compile_stream(
        rate=50.0, msg_slots=4, ttl=4, origin_rows=np.arange(4)
    )
    ones = jnp.ones((4,), bool)
    _, ir, lease, _ = apply_stream(
        sp, jax.random.key(0),
        jnp.asarray(ROUND_CAP + 100, jnp.int32), jnp.asarray(0, jnp.int32),
        seen=jnp.zeros((4, 4), bool),
        infected_round=jnp.full((4, 4), -1, jnp.int16),
        slot_lease=jnp.full((4,), -1, jnp.int16),
        row_ptr=jnp.zeros((5,), jnp.int32),
        col_idx=jnp.zeros((1,), jnp.int32),
        exists=ones, alive=ones, declared_dead=~ones,
    )
    lease = np.asarray(lease)
    assert (lease >= 0).any(), "rate 50 over 4 slots must land something"
    assert (lease[lease >= 0] == ROUND_CAP).all()
    ir = np.asarray(ir)
    assert str(ir.dtype) == "int16"
    assert (ir[ir >= 0] == ROUND_CAP).all(), "injection latch must saturate"

    from tpu_gossip.growth import compile_growth
    from tpu_gossip.growth.engine import apply_growth

    n = 8
    gp = compile_growth(n_initial=4, target=6, n_slots=n,
                        joins_per_round=2, attach_m=1)
    exists = jnp.arange(n) < 4
    out = apply_growth(
        gp, jax.random.key(0),
        jnp.asarray(ROUND_CAP + 100, jnp.int32),
        jnp.asarray(0, jnp.int32),
        row_ptr=jnp.asarray(np.arange(n + 1) * 2, jnp.int32),
        exists=exists, alive=exists, silent=jnp.zeros((n,), bool),
        last_hb=jnp.zeros((n,), jnp.int16), declared_dead=~exists,
        rewired=jnp.zeros((n,), bool),
        rewire_targets=jnp.full((n, 1), -1, jnp.int32),
        join_round=jnp.where(exists, 0, -1).astype(jnp.int16),
        admitted_by=jnp.full((n,), -1, jnp.int32),
        degree_credit=jnp.zeros((n,), jnp.int32),
    )
    jr = np.asarray(out["join_round"])
    joined = jr[np.asarray(out["exists"]) & ~np.asarray(exists)]
    assert joined.size and (joined == ROUND_CAP).all(), jr
    hb = np.asarray(out["last_hb"])
    assert str(hb.dtype) == "int16"
    admitted = hb[np.asarray(out["exists"]) & ~np.asarray(exists)]
    assert (admitted == ROUND_CAP).all(), "admission heartbeat must saturate"

    # the heartbeat refresh and the dedup latch saturate the same way
    from tpu_gossip.kernels.liveness import emit_heartbeats
    from tpu_gossip.kernels.round_tail import round_tail

    ones4 = jnp.ones((4,), bool)
    hb2 = emit_heartbeats(
        jnp.zeros((4,), jnp.int16), ones4, ~ones4, jnp.zeros((4,), bool),
        jnp.asarray(ROUND_CAP + 100, jnp.int32), 1,
    )
    assert str(hb2.dtype) == "int16" and (np.asarray(hb2) == ROUND_CAP).all()
    for impl in ("fused", "reference", "pallas"):
        _, _, ir2, _ = round_tail(
            jnp.zeros((4, 2), bool), jnp.zeros((4, 2), bool),
            jnp.full((4, 2), -1, jnp.int16), jnp.zeros((4, 2), bool),
            jnp.ones((4, 2), bool), jnp.ones((4, 2), bool),
            jnp.zeros((4, 2), bool), None,
            jnp.asarray(ROUND_CAP + 100, jnp.int32),
            forward_once=False, sir_recover_rounds=0, impl=impl,
        )
        ir2 = np.asarray(ir2)
        assert str(ir2.dtype) == "int16" and (ir2 == ROUND_CAP).all(), impl


def test_checkpoint_narrow_plane_round_trip(tmp_path):
    """A pre-narrowing checkpoint (int32 join_round/slot_lease) loads at
    the declared int16 widths with values intact — both formats."""
    from tpu_gossip.core.state import load_swarm, save_swarm

    te = _traced("local[xla,push,m=1]")
    st = te.state
    path = tmp_path / "ck.npz"
    save_swarm(path, st)
    data = dict(np.load(path))
    # forge the pre-narrowing format: re-widen the planes on disk
    for plane in ("join_round", "slot_lease", "infected_round", "last_hb"):
        data[f"field_{plane}"] = data[f"field_{plane}"].astype(np.int32)
    np.savez(path, **data)
    restored = load_swarm(path)
    for plane in ("join_round", "slot_lease", "infected_round", "last_hb"):
        assert str(getattr(restored, plane).dtype) == "int16", plane
        np.testing.assert_array_equal(
            np.asarray(getattr(restored, plane)),
            np.asarray(getattr(st, plane)),
        )
