"""deep-collective-uniformity + collectives.lock (analysis/deep/collectives).

Pins (a) program extraction: every mesh entry in the committed lock,
non-empty, with per-axis ici/dcn byte columns; (b) the traced program of
a representative entry matches the lock byte-for-byte and drift/stale
split correctly; (c) uniformity semantics: a collective under a
shard-varying cond arm fires, identical-arms and uniform-pred conds
don't (the sparse transport's psum'd-header lanes depend on it); (d) the
rules_shardmap.py blind spot: a lambda-wrapped arm collective the AST
tier provably cannot see, caught by the trace walk; (e) the adversarial
self-test harness stays green (CI runs it via --deep-selftest).
"""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from tpu_gossip.analysis.cli import lint_paths, repo_root
from tpu_gossip.analysis.deep.collectives import (
    RULE,
    collective_report,
    entry_program,
    load_lock,
    lock_findings,
    program_summary,
    write_lock,
)
from tpu_gossip.analysis.deep.selftest import (
    divergent_collective_entry,
    run_selftest,
    unpack_spike_entry,
)
from tpu_gossip.analysis.entrypoints import (
    EntryPoint,
    TracedEntry,
    dist_guard,
    entry_points,
    trace_matrix,
)
from tpu_gossip.dist._compat import shard_map_compat
from tpu_gossip.dist.mesh import AXIS, make_mesh

FIXTURES = Path(__file__).parent / "fixtures"

EPS = {ep.name: ep for ep in entry_points()}
MESH_NAMES = sorted(n for n in EPS if n.startswith("dist["))

needs_mesh = pytest.mark.skipif(
    dist_guard() is not None, reason="needs a multi-device host"
)


# one process-wide trace cache: repeated entry traces are read, not paid
from tests.analysis._tracecache import CACHE as _CACHE


def _traced(name):
    return trace_matrix([EPS[name]], cache=_CACHE)[name]


def _entry_of(fn, state, name="synthetic"):
    ep = EntryPoint(
        name=name, engine="synthetic", kind="round", audit_check="synthetic",
        build=lambda: (fn, state), n_peers=32,
    )
    te = TracedEntry(ep=ep, state=state)
    te.jaxpr, te.out_shape = jax.make_jaxpr(fn, return_shape=True)(state)
    return name, te


# -------------------------------------------------------- committed lock
def test_lock_covers_every_mesh_entry_nonempty():
    """Acceptance pin: the committed collectives.lock carries a NON-EMPTY
    program with per-axis byte columns for every mesh entry the matrix
    declares — without tracing anything (the lock IS the witness)."""
    lock = load_lock(repo_root() / "collectives.lock")
    assert lock, "collectives.lock missing or empty"
    missing = [n for n in MESH_NAMES if n not in lock]
    assert not missing, f"mesh entries absent from collectives.lock: {missing}"
    for name in MESH_NAMES:
        ent = lock[name]
        assert ent["program"], f"{name}: empty collective program"
        assert int(ent["ops"]) == len(ent["program"])
        wire = int(ent["ici_bytes"]) + int(ent["dcn_bytes"])
        assert wire > 0, f"{name}: zero wire bytes in lock"


@needs_mesh
def test_traced_program_matches_lock():
    """Freshness of the committed lock for a representative entry: the
    trace-order op renders and the per-axis byte totals agree."""
    lock = load_lock(repo_root() / "collectives.lock")
    name = "dist[matching]"
    ops, findings = entry_program(name, _traced(name))
    assert findings == []
    assert [op.render() for op in ops] == lock[name]["program"]
    summ = program_summary({name: ops})[name]
    assert summ["ici_bytes"] == int(lock[name]["ici_bytes"])
    assert summ["dcn_bytes"] == int(lock[name]["dcn_bytes"])


def test_lock_round_trip(tmp_path):
    name, te = divergent_collective_entry()
    ops, _ = entry_program(name, te)
    programs = {name: ops}
    p = tmp_path / "c.lock"
    write_lock(p, programs)
    loaded = load_lock(p)
    assert loaded[name]["program"] == [op.render() for op in ops]
    drift, stale = lock_findings(programs, loaded)
    assert drift == [] and stale == []


def test_lock_drift_and_stale_split(tmp_path):
    name, te = divergent_collective_entry()
    ops, _ = entry_program(name, te)
    p = tmp_path / "c.lock"
    write_lock(p, {name: ops, "ghost[entry]": ops})
    lock = load_lock(p)
    # drifted program (dropped op) fails; unlocked entry fails; the
    # ghost entry (locked but not traced here) reports stale, NON-failing
    drift, stale = lock_findings(
        {name: ops[:-1], "fresh[entry]": ops}, lock
    )
    assert stale == ["ghost[entry]"]
    rules = {f.rule for f in drift}
    assert rules == {"deep-collective-lock-drift"}
    assert {f.qualname for f in drift} == {name, "fresh[entry]"}


# -------------------------------------------------- uniformity semantics
def test_divergent_collective_fires():
    name, te = divergent_collective_entry()
    ops, findings = entry_program(name, te)
    assert ops, "divergent fixture traced an empty program"
    assert any(f.rule == RULE and "diverges" in f.message for f in findings)


def test_identical_arms_are_uniform():
    """Both arms posting the SAME collective sequence rendezvous on every
    shard regardless of the branch — no finding."""
    mesh = make_mesh()

    def body(x):
        return jax.lax.cond(
            x[0] > 0.0,
            lambda v: jax.lax.psum(v * 2.0, AXIS),
            lambda v: jax.lax.psum(v + 1.0, AXIS),
            x,
        )

    fn = shard_map_compat(
        body, mesh=mesh, in_specs=P(AXIS), out_specs=P(AXIS)
    )
    state = jnp.arange(float(mesh.size * 4))
    _, findings = entry_program(*_entry_of(fn, state))
    assert findings == []


def test_uniform_pred_cond_may_diverge():
    """A cond whose predicate is itself collective-agreed (psum'd header)
    takes the SAME arm on every shard — the sparse transport's two-lane
    design. Divergent arms under it must NOT fire."""
    mesh = make_mesh()

    def body(x):
        total = jax.lax.psum(jnp.sum(x), AXIS)  # mesh-agreed scalar
        return jax.lax.cond(
            total > 0.0,
            lambda v: jax.lax.pcast(
                jax.lax.psum(v, AXIS), AXIS, to="varying"
            ),
            lambda v: v,
            x,
        )

    fn = shard_map_compat(
        body, mesh=mesh, in_specs=P(AXIS), out_specs=P(AXIS)
    )
    state = jnp.arange(float(mesh.size * 4))
    _, findings = entry_program(*_entry_of(fn, state))
    assert findings == []


@needs_mesh
def test_sparse_entries_lint_uniform():
    """The real two-lane sparse transports (both engines) must classify
    clean: their cond predicates are psum'd, so the asymmetric lanes are
    sanctioned. The acceptance's 'real tree lints clean' pin."""
    traced = trace_matrix(
        [EPS["dist[matching,sparse]"], EPS["dist[bucketed,sparse]"]],
        cache=_CACHE,
    )
    findings, programs = collective_report(traced)
    assert findings == []
    assert all(programs.values())


# ----------------------------------- rules_shardmap.py mode-arm blind spot
def test_lambda_arm_collective_blind_spot():
    """The fixture routes through the compat shim and hides a psum in a
    lambda-wrapped cond arm: the WHOLE AST tier is silent on the source
    (raw-shard-map included — its 65 lines only chase raw references),
    while the deep walk over the trace reports the divergence."""
    fix = FIXTURES / "lambda_arm_collective.py"
    ast_findings = lint_paths([str(fix)], project_wide=False)
    assert ast_findings == [], [f.render() for f in ast_findings]

    spec = importlib.util.spec_from_file_location("lambda_arm_fix", fix)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mesh = make_mesh()
    fn = mod.build(mesh)
    state = jnp.arange(float(mesh.size * 4))
    ops, findings = entry_program(*_entry_of(fn, state))
    assert ops, "lambda-arm psum missing from the extracted program"
    assert any(f.rule == RULE and "diverges" in f.message for f in findings)


# ----------------------------------------------------- adversarial harness
def test_selftest_harness_green():
    """CI's --deep-selftest step: both deliberately broken fixtures must
    keep firing (a dead rail reports, an alive one stays silent)."""
    assert run_selftest() == []


def test_unpack_fixture_has_no_collectives():
    """The spike fixture exercises ONLY the liveness rail — its program
    must be empty so the two self-tests stay independent."""
    name, te = unpack_spike_entry()
    ops, findings = entry_program(name, te)
    assert ops == [] and findings == []
