"""graftlint deep tier: each jaxpr pass must CATCH a seeded violation
(break-and-detect — an analyzer that cannot fail is not analyzing) and
stay silent on the sanctioned twin, on SYNTHETIC traced entries so the
violations are precise and the tests stay fast. The clean-on-repo
enforcement run rides test_selflint/CI; the donation AST side has its own
fixture pair (fixtures/deep_{good,bad}_use_after_donate.py).
"""

import functools
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from tpu_gossip.analysis.deep.donation import (
    donation_ast_findings,
    donation_jaxpr_findings,
)
from tpu_gossip.analysis.deep.lineage import lineage_findings
from tpu_gossip.analysis.deep.reductions import reduction_findings
from tpu_gossip.analysis.entrypoints import EntryPoint, TracedEntry
from tpu_gossip.analysis.walker import ModuleInfo
from tpu_gossip.core.streams import FAULT_STREAM_SALT, GROWTH_STREAM_SALT
from tpu_gossip.dist._compat import shard_map_compat
from tpu_gossip.dist.mesh import AXIS, make_mesh

FIXTURES = Path(__file__).parent / "fixtures"


def _trace(fn, *args, engine="xla", jit_name=None, name="synthetic"):
    """One synthetic TracedEntry, shaped like trace_matrix's output."""
    ep = EntryPoint(
        name=name, engine=engine, kind="round", audit_check="synthetic",
        build=lambda: (fn, args[0]), jit_name=jit_name,
    )
    te = TracedEntry(ep=ep)
    te.state = args[0]
    te.jaxpr, te.out_shape = jax.make_jaxpr(fn, return_shape=True)(*args)
    return {name: te}


# ------------------------------------------------------- deep-rng-lineage
def test_lineage_clean_on_registered_streams():
    def good(key):
        kf = jax.random.fold_in(key, FAULT_STREAM_SALT)
        kg = jax.random.fold_in(key, GROWTH_STREAM_SALT)
        k1, k2 = jax.random.split(key)
        return (
            jax.random.uniform(kf, (4,))
            + jax.random.uniform(kg, (4,))
            + jax.random.uniform(k1, (4,))
            + jax.random.uniform(k2, (4,))
        )

    assert lineage_findings(_trace(good, jax.random.key(0))) == []


def test_unregistered_salt_detected():
    def bad(key):
        k = jax.random.fold_in(key, 0x7777AAAA)  # nobody registered this
        return jax.random.uniform(k, (4,))

    fs = lineage_findings(_trace(bad, jax.random.key(0)))
    assert fs, "unregistered constant salt not flagged"
    assert any("not registered" in f.message for f in fs)
    assert all(f.rule == "deep-rng-lineage" for f in fs)


def test_key_reuse_detected():
    def bad(key):
        return jax.random.uniform(key, (4,)) + jax.random.normal(key, (4,))

    fs = lineage_findings(_trace(bad, jax.random.key(0)))
    assert any("consumed by 2 draws" in f.message for f in fs)


def test_salt_collision_detected():
    def bad(key):
        ka = jax.random.fold_in(key, FAULT_STREAM_SALT)
        kb = jax.random.fold_in(key, FAULT_STREAM_SALT)  # same stream twice
        return jax.random.uniform(ka, (4,)) + jax.random.uniform(kb, (4,))

    fs = lineage_findings(_trace(bad, jax.random.key(0)))
    assert any("folded from the same parent" in f.message for f in fs)


def test_minted_root_key_detected():
    def bad(x):
        k = jax.random.key(7)  # replays the same bits every round
        return x + jax.random.uniform(k, x.shape)

    fs = lineage_findings(_trace(bad, jnp.ones(4)))
    assert any("minted inside" in f.message for f in fs)


def test_constant_key_detected():
    baked = jax.random.key(3)

    def bad(x):
        return x + jax.random.uniform(baked, x.shape)  # closure constant

    fs = lineage_findings(_trace(bad, jnp.ones(4)))
    assert any("does not derive" in f.message for f in fs)


def test_draw_inside_shard_map_detected_and_licensable():
    mesh = make_mesh()

    def bad(key, x):
        def body(kb, xb):
            return xb + jax.random.uniform(kb[0], xb.shape)  # per-shard bits

        return shard_map_compat(
            body, mesh=mesh, in_specs=(P(), P(AXIS)), out_specs=P(AXIS),
        )(key[None], x)

    traced = _trace(bad, jax.random.key(0), jnp.ones(8), name="sm-draw")
    fs = lineage_findings(traced)
    hits = [f for f in fs if "inside a shard_map body" in f.message]
    assert hits, "per-shard draw inside shard_map not flagged"
    # the allowlist licenses EXACTLY that source site (the bucketed
    # engine's documented in-shard draw uses this), and only the
    # in-shard-map check — same semantics as the reduction allowlist
    lic = {(h.file, h.qualname): "test license" for h in hits}
    fs2 = lineage_findings(traced, allowlist=lic)
    assert not any("inside a shard_map body" in f.message for f in fs2)


def test_loop_invariant_key_draw_detected():
    """A key captured as a scan/while CONST is the same value every
    iteration — a draw off it inside the body replays identical bits per
    round even though the body traces once (the hoisted-key bug the
    per-round split discipline exists to prevent)."""
    def bad(key, xs):
        k = jax.random.fold_in(key, FAULT_STREAM_SALT)  # loop-invariant

        def body(c, x):
            return c + x * jax.random.uniform(k, x.shape), None

        out, _ = jax.lax.scan(body, jnp.zeros(4), xs)
        return out

    fs = lineage_findings(_trace(bad, jax.random.key(0), jnp.ones((3, 4))))
    assert any("loop-invariant key" in f.message for f in fs)


def test_loop_carried_split_key_is_clean():
    """The sanctioned twin: the key rides the carry and splits per
    iteration — fresh bits every round, no finding."""
    def good(key, xs):
        def body(carry, x):
            k, acc = carry
            k, kd = jax.random.split(k)
            return (k, acc + x * jax.random.uniform(kd, x.shape)), None

        (_, out), _ = jax.lax.scan(body, (key, jnp.zeros(4)), xs)
        return out

    assert lineage_findings(
        _trace(good, jax.random.key(0), jnp.ones((3, 4)))
    ) == []


def test_loop_invariant_key_with_iteration_fold_is_clean():
    """fold_in(k, i) with the traced iteration index derives a distinct
    child per iteration — the other sanctioned spelling."""
    def good(key, xs):
        k = jax.random.fold_in(key, FAULT_STREAM_SALT)

        def body(c, xi):
            x, i = xi
            kd = jax.random.fold_in(k, i)
            return c + x * jax.random.uniform(kd, x.shape), None

        out, _ = jax.lax.scan(
            body, jnp.zeros(4), (xs, jnp.arange(xs.shape[0]))
        )
        return out

    assert lineage_findings(
        _trace(good, jax.random.key(0), jnp.ones((3, 4)))
    ) == []


def test_draws_in_exclusive_cond_branches_are_not_reuse():
    """lax.cond branches are mutually exclusive at runtime — one executes
    per round — so each branch drawing off the same parent key is NOT
    reuse (the repo's runtime-gated stages pattern: has_loss_delay, the
    sparse-transport fallback); reuse WITHIN one branch still is."""
    def good(key, pred):
        return jax.lax.cond(
            pred,
            lambda k: jax.random.uniform(k, (4,)),
            lambda k: jax.random.normal(k, (4,)),
            key,
        )

    fs = lineage_findings(
        _trace(good, jax.random.key(0), jnp.bool_(True))
    )
    assert not any("consumed by" in f.message for f in fs), [
        f.render() for f in fs
    ]

    def bad(key, pred):
        def arm(k):
            return jax.random.uniform(k, (4,)) + jax.random.normal(k, (4,))

        return jax.lax.cond(
            pred, arm, lambda k: jax.random.uniform(k, (4,)), key
        )

    fs = lineage_findings(_trace(bad, jax.random.key(0), jnp.bool_(True)))
    assert any("consumed by 2 draws" in f.message for f in fs)


def test_same_salt_in_exclusive_cond_branches_not_collision():
    def good(key, pred):
        def arm(k):
            return jax.random.uniform(
                jax.random.fold_in(k, FAULT_STREAM_SALT), (4,)
            )

        return jax.lax.cond(pred, arm, arm, key)

    fs = lineage_findings(
        _trace(good, jax.random.key(0), jnp.bool_(True))
    )
    assert not any("folded from the same parent" in f.message for f in fs), [
        f.render() for f in fs
    ]


def test_split_children_are_distinct_not_reused():
    def good(key):
        keys = jax.random.split(key, 3)
        return (
            jax.random.uniform(keys[0], (2,))
            + jax.random.uniform(keys[1], (2,))
            + jax.random.uniform(keys[2], (2,))
        )

    fs = lineage_findings(_trace(good, jax.random.key(0)))
    assert not any("consumed by" in f.message for f in fs), [
        f.render() for f in fs
    ]


# --------------------------------------------------- deep-float-reduction
def test_float_psum_detected_int_psum_clean():
    mesh = make_mesh()

    def collective(x):
        return shard_map_compat(
            lambda b: jax.lax.psum(b, AXIS),
            mesh=mesh, in_specs=(P(AXIS),), out_specs=P(),
        )(x)

    fs = reduction_findings(_trace(collective, jnp.ones(8, jnp.float32)))
    assert any("float collective" in f.message for f in fs)
    assert all(f.rule == "deep-float-reduction" for f in fs)
    # integer bracketing is exact under any order: never flagged
    assert reduction_findings(
        _trace(collective, jnp.ones(8, jnp.int32))
    ) == []


def test_float_pmax_is_order_exact_and_clean():
    """max/min are associative and commutative EXACTLY — their bracketing
    cannot depend on layout — so float pmax/pmin are never flagged (the
    docstring's order-exact carve-out; only psum-family collectives are
    layout-dependent)."""
    mesh = make_mesh()

    def collective(x):
        return shard_map_compat(
            lambda b: jax.lax.pmax(b, AXIS),
            mesh=mesh, in_specs=(P(AXIS),), out_specs=P(),
        )(x)

    fs = reduction_findings(_trace(collective, jnp.ones(8, jnp.float32)))
    assert fs == [], [f.render() for f in fs]


def test_global_float_reduce_flagged_only_for_dist_entries():
    def f(x):
        return jnp.sum(x)

    x = jnp.ones(8, jnp.float32)
    # a dist entry's global-shape float sum is an implicit psum under SPMD
    fs = reduction_findings(_trace(f, x, engine="dist-matching"))
    assert any("implicit psum" in f.message for f in fs)
    # the same reduction in a LOCAL entry has one device order: clean
    assert reduction_findings(_trace(f, x, engine="xla")) == []


def test_reduction_allowlist_licenses_by_source_site():
    mesh = make_mesh()

    def collective(x):
        return shard_map_compat(
            lambda b: jax.lax.psum(b, AXIS),
            mesh=mesh, in_specs=(P(AXIS),), out_specs=P(),
        )(x)

    traced = _trace(collective, jnp.ones(8, jnp.float32))
    fs = reduction_findings(traced)
    assert fs
    lic = {(f.file, f.qualname): "test license" for f in fs}
    assert reduction_findings(traced, allowlist=lic) == []


def test_dead_allowlist_entry_detected(monkeypatch):
    """A license that stops matching any traced site must itself become a
    finding (on matrices that trace dist entries) — stale documentation
    accumulating in the allowlists is the suppression-debt failure mode
    the empty-baseline policy exists to prevent."""
    from tpu_gossip.analysis.deep import lineage, reductions

    monkeypatch.setitem(
        reductions.REDUCTION_ALLOWLIST, ("gone.py", "nope"), "stale",
    )
    monkeypatch.setitem(
        lineage.LINEAGE_ALLOWLIST, ("gone.py", "nope"), "stale",
    )
    traced = _trace(lambda x: x + 1, jnp.ones(4), engine="dist-matching")
    assert any(
        "dead license" in f.message for f in reduction_findings(traced)
    )
    assert any(
        "dead license" in f.message for f in lineage_findings(traced)
    )
    # a local-only matrix cannot anchor the dist licenses: no dead-entry
    # reporting there (single-device hosts must not cry wolf)
    local = _trace(lambda x: x + 1, jnp.ones(4), engine="xla")
    assert reduction_findings(local) == []
    assert lineage_findings(local) == []


# -------------------------------------------------- deep-use-after-donate
def test_undonated_jit_entry_detected():
    @jax.jit
    def loop(state):  # the forgotten-donation refactor
        return state * 2.0

    fs = donation_jaxpr_findings(
        _trace(lambda s: loop(s), jnp.ones(4), jit_name="loop")
    )
    assert fs and any("NOT donated" in f.message for f in fs)
    assert all(f.rule == "deep-use-after-donate" for f in fs)


def test_donating_jit_entry_clean():
    @functools.partial(jax.jit, donate_argnums=(0,))
    def loop(state):
        return state * 2.0

    fs = donation_jaxpr_findings(
        _trace(lambda s: loop(s), jnp.ones(4), jit_name="loop")
    )
    assert fs == [], [f.render() for f in fs]


def test_missing_jit_call_detected():
    fs = donation_jaxpr_findings(
        _trace(lambda s: s + 1.0, jnp.ones(4), jit_name="loop")
    )
    assert fs and "did not trace as a jit call" in fs[0].message


def _ast_findings(fixture: str):
    mod = ModuleInfo(FIXTURES / fixture, fixture)
    return donation_ast_findings([mod])


def test_ast_read_after_donate_fixture_flagged():
    fs = _ast_findings("deep_bad_use_after_donate.py")
    assert {f.rule for f in fs} == {"deep-use-after-donate"}
    # every bad function flagged: straight-line, fall-through branch,
    # error path, loop cross-iteration (the read AND the re-donation of
    # the deleted name — both reads of deleted buffers), keyword form
    assert {f.qualname for f in fs} == {
        "straight_line_read", "branch_falls_through", "read_in_error_path",
        "loop_cross_iteration", "keyword_form",
    }, [f.render() for f in fs]
    assert len(fs) == 6 and len({f.line for f in fs}) == 6


def test_ast_donation_idioms_clean():
    fs = _ast_findings("deep_good_use_after_donate.py")
    assert fs == [], [f.render() for f in fs]


def test_ast_pass_covers_the_real_scope():
    """The live callers (cli/run_sim.py, sim/, dist/) are clean
    against the real donating entry points — the enforcement half of the
    pass, with tracing off (pure AST)."""
    from tpu_gossip.analysis.deep import run_deep

    fs = run_deep(trace=False)
    assert fs == [], [f.render() for f in fs]


def test_loop_body_read_reported_once(tmp_path):
    """The two-pass loop scan re-checks reads on pass 2 (the
    cross-iteration trick) — a read that fires on BOTH passes must
    surface as ONE finding, not two identical ones. `print(state)` is
    flagged on pass 1 (same-iteration read) and again on pass 2; the
    re-donating `step(state)` call is itself a read of deleted buffers
    on pass 2 (the fixture's loop_cross_iteration contract)."""
    src = (
        "import functools\n\n"
        "import jax\n\n\n"
        "@functools.partial(jax.jit, donate_argnames=('state',))\n"
        "def step(state):\n"
        "    return state\n\n\n"
        "def f(state, n):\n"
        "    for _ in range(n):\n"
        "        step(state)\n"
        "        print(state)\n"
    )
    p = tmp_path / "loop_donate.py"
    p.write_text(src)
    fs = donation_ast_findings([ModuleInfo(p, "loop_donate.py")])
    assert sorted(f.line for f in fs) == [13, 14], [f.render() for f in fs]


def test_pragma_suppresses_ast_side(tmp_path):
    src = (
        "import functools\n\n"
        "import jax\n\n\n"
        "@functools.partial(jax.jit, donate_argnames=('state',))\n"
        "def step(state):\n"
        "    return state\n\n\n"
        "def f(state):\n"
        "    out = step(state)\n"
        "    # graftlint: disable=deep-use-after-donate -- fixture: test\n"
        "    return out, state\n"
    )
    p = tmp_path / "pragma_donate.py"
    p.write_text(src)
    fs = donation_ast_findings([ModuleInfo(p, "pragma_donate.py")])
    assert fs == [], [f.render() for f in fs]


# ------------------------------------------------------- the full tier
@pytest.mark.slow
def test_run_deep_clean_on_repo():
    """The whole tier on the real tree: 0 findings (CI runs this same
    budgeted invocation as the lint-deep job; slow-marked so the tier-1
    loop doesn't pay the matrix trace twice)."""
    from tpu_gossip.analysis.deep import run_deep

    fs = run_deep(cache={})
    assert fs == [], [f.render() for f in fs]
