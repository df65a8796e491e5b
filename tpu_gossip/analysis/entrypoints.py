"""The shared entry-point matrix: one harness, two consumers.

Both dynamic analysis tiers walk the SAME matrix of public round entry
points — the eval_shape contract audit (contracts.py, shape/dtype
fixed-point checks) and the jaxpr deep tier (deep/, dataflow passes over
the traced equations). The matrix is the product the repo's bit-identity
contract quantifies over: 3 local delivery engines × modes × msg_slots ×
churn/SIR/compact × every protocol-tail implementation × chaos scenarios
× growth schedules × streaming workloads × control policies × both mesh
engines × sparse transport, plus the jitted loop entries (``simulate``/
``run_until_coverage`` and their dist twins). A new engine or mode added here is traced by BOTH tiers; a
matrix entry added to one tier only cannot exist
(tests/analysis/test_entrypoints.py pins the shared parametrization).

Each :class:`EntryPoint` resolves its callable through the owning module
AT TRACE TIME (``engine.gossip_round``, never a captured reference) so
tests can monkeypatch a deliberate break and assert both tiers report it.
:func:`trace_matrix` runs ``jax.make_jaxpr(..., return_shape=True)``
once per entry and hands the audit its output specs and the deep tier its
jaxpr from the SAME trace — callers sharing a ``cache`` dict (the CLI)
pay the matrix once per invocation.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Tuple

__all__ = [
    "EntryPoint",
    "TracedEntry",
    "entry_points",
    "trace_matrix",
    "dist_guard",
]

_N_MATCH = 256  # tiny matching build (compile cost: seconds, CPU)
_N_DEV = 512  # tiny device-CSR build
_MSG_SLOTS = (1, 16)  # one word group / multi-slot packed group
_MODES = ("push", "push_pull", "flood")
_SIM_ROUNDS = 3  # simulate's stacked-stats leading dim
_DIST_SIM_ROUNDS = 2
_FLEET_LANES = 3  # batched campaign lanes (fleet/)
_FLEET_PEERS = 64
_FLEET_ROUNDS = 2


@dataclasses.dataclass(frozen=True)
class EntryPoint:
    """One traceable public entry point of the round machinery.

    ``build()`` returns ``(fn, state)`` with ``fn(state)`` traceable and
    every non-state operand closed over; ``fn`` must resolve the target
    through its module at call time. ``audit_check`` names the contract
    check that owns this entry — the union over checks must cover the
    whole matrix (test-pinned), so the audit can't silently skip an entry
    the deep tier traces (or vice versa).
    """

    name: str
    engine: str  # xla | pallas | matching | dist-matching | dist-bucketed
    kind: str  # round | simulate | coverage
    audit_check: str
    build: Callable[[], Tuple[Callable, Any]]
    stats_leading: tuple | None = ()  # None: entry returns no stats
    has_ici: bool = False
    jit_name: str | None = None  # jitted+donating entries: pjit name param
    # state-slot count of the entry's swarm (== the traced state's leading
    # dim, test-pinned) — the mem tier's bytes/peer denominator; 0 would
    # mean a matrix entry whose scale nobody declared, which cannot exist
    n_peers: int = 0
    # the traced state is a PackedSwarm (core/packed.py): the deep
    # transient-liveness pass holds these entries to the codec contract —
    # packed words may only be decoded inside core/packed.py
    packed: bool = False


@dataclasses.dataclass
class TracedEntry:
    """One entry's trace: jaxpr + output shape pytree, or the error."""

    ep: EntryPoint
    state: Any = None
    jaxpr: Any = None  # jax.core.ClosedJaxpr
    out_shape: Any = None  # pytree of jax.ShapeDtypeStruct
    error: str | None = None


@functools.lru_cache(maxsize=None)
def _ctx():
    """Tiny concrete graphs/plans/states shared by all entries (built once)."""
    import jax
    import numpy as np

    from tpu_gossip.core.device_topology import device_powerlaw_graph
    from tpu_gossip.core.matching_topology import matching_powerlaw_graph
    from tpu_gossip.core.state import SwarmConfig, init_swarm
    from tpu_gossip.kernels.pallas_segment import build_staircase_plan

    dg = device_powerlaw_graph(_N_DEV, gamma=2.5, key=jax.random.key(0))
    mg, mplan = matching_powerlaw_graph(
        _N_MATCH, gamma=2.5, fanout=1, key=jax.random.key(0), export_csr=True
    )
    splan = build_staircase_plan(
        np.asarray(dg.row_ptr), np.asarray(dg.col_idx), fanout=1
    )

    def state_for(graph, m: int, **cfg_kw):
        cfg = SwarmConfig(
            n_peers=graph.n_pad, msg_slots=m, fanout=1, **cfg_kw
        )
        st = init_swarm(
            graph.as_padded_graph(), cfg, origins=[0], exists=graph.exists,
            key=jax.random.key(0),
        )
        return st, cfg

    return {
        "dg": dg, "mg": mg, "mplan": mplan, "splan": splan,
        "state_for": state_for,
    }


def _chaos_scenario(n_slots: int, n_real: int):
    """A non-trivial compiled scenario — every fault class active (loss,
    delay, partition, blackout, churn burst) — so the scenario-threaded
    round traces its full structure (two-pass delivery, held buffer,
    burst churn) under the fixed-point contract."""
    from tpu_gossip.faults import compile_scenario, scenario_from_dict

    spec = scenario_from_dict({
        "name": "audit-chaos",
        "phases": [
            {"name": "lossy", "start": 0, "end": 2, "loss": 0.2,
             "delay": 0.2},
            {"name": "split", "start": 2, "end": 4, "partition": "half"},
            {"name": "storm", "start": 4, "end": 6, "churn_leave": 0.05,
             "churn_join": 0.2, "blackout": {"frac": 0.1, "seed": 1}},
        ],
    })
    return compile_scenario(
        spec, n_peers=n_real, n_slots=n_slots, total_rounds=8
    )


def _adversary_scenario(n_slots: int, n_real: int):
    """A compiled scenario with every Byzantine attack class active —
    accusers, forgers, floods — composed with a blackout (true-eviction
    ground truth), so the adversarial round traces its full structure
    (the accusation scatter, the forged-heartbeat scatter, the flood
    replay, the quorum/quarantine state machine) under the fixed-point
    contract."""
    from tpu_gossip.faults import compile_scenario, scenario_from_dict

    spec = scenario_from_dict({
        "name": "audit-byzantine",
        "phases": [
            {"name": "dark", "start": 0, "end": 2,
             "blackout": {"frac": 0.1, "seed": 2}},
            {"name": "siege", "start": 2, "end": 6,
             "accusers": {"frac": 0.05, "seed": 3},
             "forgers": {"frac": 0.02, "seed": 4},
             "floods": {"frac": 0.03, "seed": 5},
             "forge_fanout": 2, "flood_fanout": 3},
        ],
    })
    return compile_scenario(
        spec, n_peers=n_real, n_slots=n_slots, total_rounds=8
    )


def _quorum_spec():
    """The quorum-defense spec the adversarial entries trace under —
    active quarantine budget so the strike/release paths are in the
    jaxpr."""
    from tpu_gossip.kernels.liveness import compile_quorum

    return compile_quorum(quorum_k=3, window=4, budget=2)


def _growth_plan(n_slots: int, n_initial: int):
    """A small compiled growth schedule so the growing round traces its
    full structure (admission slice, Gumbel-top-k draw, registry
    scatters) under the fixed-point contract — pinning the growth plane
    exactly the way the chaos scenario pins ``fault_held``."""
    import numpy as np

    from tpu_gossip.growth import compile_growth

    target = min(n_initial + 32, n_slots)
    return compile_growth(
        n_initial=n_initial,
        target=target,
        n_slots=n_slots,
        joins_per_round=4,
        attach_m=2,
        admit_rows=np.arange(n_initial, target),
        max_join_burst=4,
    )


def _stream_plan(msg_slots: int, exists, *, k_hashes: int = 2):
    """A small compiled streaming workload (traffic/) so the loaded round
    traces its full structure — Poisson arrival draw, origin gather, the
    sequential landing scan over the lease table, the expired-column mask
    through the fused tail — under the fixed-point contract. Bursty
    cadence + k>=2 Bloom landing exercise both static branches."""
    import numpy as np

    from tpu_gossip.traffic import compile_stream

    return compile_stream(
        rate=2.0,
        msg_slots=msg_slots,
        ttl=8,
        origin_rows=np.flatnonzero(np.asarray(exists)),
        k_hashes=min(k_hashes, msg_slots),
        burst_every=4,
    )


def _ingest_batch(msg_slots: int, *, max_inject: int = 4):
    """One live round window as the serving driver builds it (serve/ →
    traffic/ingest.py): a static-shape InjectBatch with real FNV-hashed
    payload identities, a short window, and a non-zero overflow bill —
    the post-tail landing scatter the recorded-trace replay contract
    re-runs bit for bit."""
    from tpu_gossip.serve import payload_hash64
    from tpu_gossip.traffic.ingest import IngestPlan, make_batch

    plan = IngestPlan(msg_slots=msg_slots, max_inject=max_inject, k_hashes=1)
    hashes = [payload_hash64(f"2025-01-01 00:00:0{i}:10.0.0.{i}:6000:{i}")
              for i in range(3)]
    return make_batch(plan, [1, 2, 3], hashes, overflow=2)


def _control_plan(ttl: int = 0):
    """A small compiled control policy (control/) so the CONTROLLED round
    traces its full structure — the level resolve, the width-``hi``
    masked draws / scaled Bernoulli gates, the AIMD feedback reductions,
    the PeerSwap refresh scatters — under the fixed-point contract.
    Active bounds (lo < base < hi) + a refresh cadence exercise every
    static branch; ``ttl`` > 0 adds the streaming lag signal."""
    from tpu_gossip.control import compile_control

    return compile_control(
        target_ratio=0.9, fanout=1, lo=1, hi=3, refresh_every=2, ttl=ttl,
    )


def dist_guard() -> str | None:
    """None when the host mesh can verify the dist contracts, else why not."""
    from tpu_gossip import dist as dist_pkg

    mesh = dist_pkg.make_mesh()
    if 128 % mesh.size:
        return (
            f"mesh size {mesh.size} does not divide 128 — dist contracts "
            "unverifiable on this host (run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8)"
        )
    return None


@functools.lru_cache(maxsize=None)
def _dist_ctx():
    """Mesh, sharded graphs/plans/states shared by the dist entries."""
    import jax
    import numpy as np

    from tpu_gossip import dist as dist_pkg
    from tpu_gossip.core import matching_topology as mt
    from tpu_gossip.core.state import SwarmConfig, init_swarm
    from tpu_gossip.core.topology import (
        build_csr, configuration_model, powerlaw_degree_sequence,
    )
    from tpu_gossip.dist import mesh as mesh_mod

    mesh = dist_pkg.make_mesh()
    g, plan = mt.matching_powerlaw_graph_sharded(
        _N_MATCH, mesh.size, gamma=2.5, fanout=1, key=jax.random.key(0),
        export_csr=False,
    )

    def m_state(**cfg_kw):
        cfg = SwarmConfig(
            n_peers=plan.n, msg_slots=16, fanout=1, mode="push_pull", **cfg_kw
        )
        st = init_swarm(
            g.as_padded_graph(), cfg, origins=[0], exists=g.exists,
            key=jax.random.key(0),
        )
        return st, cfg

    rng = np.random.default_rng(0)
    graph = build_csr(
        _N_DEV,
        configuration_model(
            powerlaw_degree_sequence(_N_DEV, gamma=2.5, rng=rng), rng=rng
        ),
    )
    sg, relabeled, position = mesh_mod.partition_graph(graph, mesh.size, seed=0)

    def b_state(**cfg_kw):
        cfg = SwarmConfig(
            n_peers=sg.n_pad, msg_slots=16, fanout=1, mode="push_pull",
            **cfg_kw,
        )
        st = mesh_mod.init_sharded_swarm(
            sg, relabeled, position, cfg, origins=[0]
        )
        return st, cfg

    # the 2-D (hosts, devices) cluster fold of the SAME device order —
    # row-major, so every 2-D entry is the flat entry's program
    # (cluster/topology.py); None when the host mesh has no even fold
    from tpu_gossip.cluster import make_cluster_mesh

    mesh2 = (
        make_cluster_mesh(hosts=2)
        if mesh.size >= 2 and mesh.size % 2 == 0 else None
    )
    return {
        "mesh": mesh, "mesh2": mesh2, "g": g, "plan": plan, "sg": sg,
        "m_state": m_state, "b_state": b_state,
    }


def _local_entries() -> list[EntryPoint]:
    from tpu_gossip.sim import engine  # resolved through the module below

    ctx = _ctx()
    eps: list[EntryPoint] = []
    engines = (
        ("xla", ctx["dg"], None),
        ("pallas", ctx["dg"], ctx["splan"]),
        ("matching", ctx["mg"], ctx["mplan"]),
    )

    def round_ep(name, eng, graph, m, plan, cfg_kw, round_kw):
        def build(graph=graph, m=m, plan=plan, cfg_kw=cfg_kw,
                  round_kw=round_kw):
            st, cfg = ctx["state_for"](graph, m, **cfg_kw)
            return (
                lambda s: engine.gossip_round(s, cfg, plan, **round_kw),
                st,
            )

        return EntryPoint(
            name=name, engine=eng, kind="round",
            audit_check="gossip_round_local", build=build,
            n_peers=graph.n_pad,
        )

    for m in _MSG_SLOTS:
        for mode in _MODES:
            for eng, graph, plan in engines:
                eps.append(round_ep(
                    f"local[{eng},{mode},m={m}]", eng, graph, m, plan,
                    dict(mode=mode), {},
                ))
    # churn + SIR shapes ride the same fixed-point contract
    churn = dict(
        churn_leave_prob=0.002, churn_join_prob=0.02, rewire_slots=2,
    )
    eps.append(round_ep(
        "local[xla,churn]", "xla", ctx["dg"], 16, None,
        dict(mode="push_pull", **churn), {},
    ))
    eps.append(round_ep(
        "local[xla,sir]", "xla", ctx["dg"], 16, None,
        dict(mode="push_pull", sir_recover_rounds=8), {},
    ))
    eps.append(round_ep(
        "local[xla,churn-compact]", "xla", ctx["dg"], 16, None,
        dict(mode="push_pull", rewire_compact_cap=64, **churn), {},
    ))
    # every tail implementation (kernels/round_tail.py) must keep the round
    # a state fixed point — the rail that makes aggressive fusion safe: a
    # tail that drops, reshapes, or re-types a slot array cannot reach a
    # scan/while_loop carry without failing here first. Churn + SIR ride
    # along so the fresh-mask and recovery branches are traced too.
    for tail in ("reference", "fused", "packed"):
        eps.append(round_ep(
            f"local[xla,tail={tail}]", "xla", ctx["dg"], 16, None,
            dict(mode="push_pull", sir_recover_rounds=4, **churn),
            dict(tail=tail),
        ))
    # chaos scenarios (faults/): a round with every fault class active —
    # two-pass partition delivery, the delay buffer, blackout masks, burst
    # churn — must still be a state fixed point on every delivery engine,
    # or a scenario could never ride a scan/while carry
    for eng, graph, plan, n_real in (
        ("xla", ctx["dg"], None, _N_DEV),
        ("matching", ctx["mg"], ctx["mplan"], _N_MATCH),
    ):
        def build_scen(graph=graph, plan=plan, n_real=n_real):
            st, cfg = ctx["state_for"](
                graph, 16, mode="push_pull", rewire_slots=2,
                churn_join_prob=0.02, churn_leave_prob=0.002,
            )
            sc = _chaos_scenario(graph.n_pad, n_real)
            return (
                lambda s: engine.gossip_round(s, cfg, plan, scenario=sc),
                st,
            )

        eps.append(EntryPoint(
            name=f"local[{eng},scenario]", engine=eng, kind="round",
            audit_check="gossip_round_local", build=build_scen,
            n_peers=graph.n_pad,
        ))
    # the GROWING round (growth/): admission slice + Gumbel-top-k +
    # registry scatters must keep the round a state fixed point on every
    # local delivery engine — a growth plane that reshapes or drops a
    # registry leaf could never ride a scan/while carry or a checkpoint
    for eng, graph, plan in engines:
        def build_grow(graph=graph, plan=plan):
            st, cfg = ctx["state_for"](
                graph, 16, mode="push_pull", rewire_slots=2,
            )
            gp = _growth_plan(graph.n_pad, graph.n_pad - 40)
            return (
                lambda s: engine.gossip_round(s, cfg, plan, growth=gp),
                st,
            )

        eps.append(EntryPoint(
            name=f"local[{eng},growth]", engine=eng, kind="round",
            audit_check="gossip_round_local", build=build_grow,
            n_peers=graph.n_pad,
        ))

    # the LOADED round (traffic/): Poisson injection + lease age-out must
    # keep the round a state fixed point on every local delivery engine —
    # the slot_lease table rides scan/while carries and checkpoints
    for eng, graph, plan in engines:
        def build_stream(graph=graph, plan=plan):
            st, cfg = ctx["state_for"](graph, 16, mode="push_pull")
            sp = _stream_plan(16, graph.exists)
            return (
                lambda s: engine.gossip_round(s, cfg, plan, stream=sp),
                st,
            )

        eps.append(EntryPoint(
            name=f"local[{eng},stream]", engine=eng, kind="round",
            audit_check="gossip_round_local", build=build_stream,
            n_peers=graph.n_pad,
        ))

    # the SERVED round (serve/ → traffic/ingest): a live round window's
    # static-shape InjectBatch lands post-tail on every local delivery
    # engine, beside an active lease table — the injection path the
    # recorded-trace replay contract holds bit-identical to the live run
    for eng, graph, plan in engines:
        def build_ingest(graph=graph, plan=plan):
            st, cfg = ctx["state_for"](graph, 16, mode="push_pull")
            sp = _stream_plan(16, graph.exists)
            batch = _ingest_batch(16)
            return (
                lambda s: engine.gossip_round(s, cfg, plan, stream=sp,
                                              inject=batch),
                st,
            )

        eps.append(EntryPoint(
            name=f"local[{eng},ingest]", engine=eng, kind="round",
            audit_check="gossip_round_local", build=build_ingest,
            n_peers=graph.n_pad,
        ))

    # scenario + growth COMPOSED (join_burst phases ride the fault tables;
    # both parallel streams fold in the same trace — the salt-collision
    # surface the deep tier's lineage pass audits)
    def build_both():
        st, cfg = ctx["state_for"](
            ctx["dg"], 16, mode="push_pull", rewire_slots=2,
            churn_join_prob=0.02, churn_leave_prob=0.002,
        )
        sc = _chaos_scenario(ctx["dg"].n_pad, _N_DEV)
        gp = _growth_plan(ctx["dg"].n_pad, ctx["dg"].n_pad - 40)
        return (
            lambda s: engine.gossip_round(s, cfg, scenario=sc, growth=gp),
            st,
        )

    eps.append(EntryPoint(
        name="local[xla,scenario+growth]", engine="xla", kind="round",
        audit_check="gossip_round_local", build=build_both,
        n_peers=ctx["dg"].n_pad,
    ))

    # scenario + growth + stream FULLY COMPOSED — "flash crowd joins
    # while a rack fails under full traffic" as one trace: THREE parallel
    # fold_in streams beside the protocol's 5-way split, the maximal
    # salt-collision surface the deep lineage pass audits
    def build_all_three():
        st, cfg = ctx["state_for"](
            ctx["dg"], 16, mode="push_pull", rewire_slots=2,
            churn_join_prob=0.02, churn_leave_prob=0.002,
        )
        sc = _chaos_scenario(ctx["dg"].n_pad, _N_DEV)
        gp = _growth_plan(ctx["dg"].n_pad, ctx["dg"].n_pad - 40)
        sp = _stream_plan(16, ctx["dg"].exists)
        return (
            lambda s: engine.gossip_round(s, cfg, scenario=sc, growth=gp,
                                          stream=sp),
            st,
        )

    eps.append(EntryPoint(
        name="local[xla,scenario+growth+stream]", engine="xla", kind="round",
        audit_check="gossip_round_local", build=build_all_three,
        n_peers=ctx["dg"].n_pad,
    ))

    # the CONTROLLED round (control/): the feedback stage — masked
    # width-hi draws, scaled Bernoulli gates, the AIMD reductions, the
    # PeerSwap refresh — must keep the round a state fixed point on every
    # local delivery engine (the level cursor rides scan/while carries
    # and checkpoints)
    for eng, graph, plan in engines:
        def build_ctl(graph=graph, plan=plan):
            st, cfg = ctx["state_for"](
                graph, 16, mode="push_pull", rewire_slots=2,
                churn_join_prob=0.02, churn_leave_prob=0.002,
            )
            cp = _control_plan()
            return (
                lambda s: engine.gossip_round(s, cfg, plan, control=cp),
                st,
            )

        eps.append(EntryPoint(
            name=f"local[{eng},control]", engine=eng, kind="round",
            audit_check="gossip_round_local", build=build_ctl,
            n_peers=graph.n_pad,
        ))

    # scenario + growth + stream + control: the FULL composition — FOUR
    # parallel fold_in streams beside the protocol's split, the maximal
    # salt-collision surface the deep lineage pass audits
    def build_all_four():
        st, cfg = ctx["state_for"](
            ctx["dg"], 16, mode="push_pull", rewire_slots=2,
            churn_join_prob=0.02, churn_leave_prob=0.002,
        )
        sc = _chaos_scenario(ctx["dg"].n_pad, _N_DEV)
        gp = _growth_plan(ctx["dg"].n_pad, ctx["dg"].n_pad - 40)
        sp = _stream_plan(16, ctx["dg"].exists)
        cp = _control_plan(ttl=8)
        return (
            lambda s: engine.gossip_round(s, cfg, scenario=sc, growth=gp,
                                          stream=sp, control=cp),
            st,
        )

    eps.append(EntryPoint(
        name="local[xla,scenario+growth+stream+control]", engine="xla",
        kind="round", audit_check="gossip_round_local", build=build_all_four,
        n_peers=ctx["dg"].n_pad,
    ))

    # the ADVERSARIAL round (faults/ Byzantine plane + kernels/liveness.py
    # quorum machine): accusation/forgery/flood scatters and the
    # suspicion/quarantine planes must keep the round a state fixed point
    # — the new planes ride scan/while carries and checkpoints
    def build_adv():
        st, cfg = ctx["state_for"](
            ctx["dg"], 16, mode="push_pull", rewire_slots=2,
            churn_join_prob=0.02, churn_leave_prob=0.002,
        )
        sc = _adversary_scenario(ctx["dg"].n_pad, _N_DEV)
        q = _quorum_spec()
        return (
            lambda s: engine.gossip_round(s, cfg, scenario=sc, liveness=q),
            st,
        )

    eps.append(EntryPoint(
        name="local[xla,adversary]", engine="xla", kind="round",
        audit_check="gossip_round_local", build=build_adv,
        n_peers=ctx["dg"].n_pad,
    ))

    # the maximal composed cell: adversary × scenario × growth × stream ×
    # control — FIVE parallel fold_in streams beside the protocol's
    # 5-way split, the widest salt-collision surface the deep lineage
    # pass audits
    def build_all_five():
        st, cfg = ctx["state_for"](
            ctx["dg"], 16, mode="push_pull", rewire_slots=2,
            churn_join_prob=0.02, churn_leave_prob=0.002,
        )
        sc = _adversary_scenario(ctx["dg"].n_pad, _N_DEV)
        gp = _growth_plan(ctx["dg"].n_pad, ctx["dg"].n_pad - 40)
        sp = _stream_plan(16, ctx["dg"].exists)
        cp = _control_plan(ttl=8)
        q = _quorum_spec()
        return (
            lambda s: engine.gossip_round(s, cfg, scenario=sc, growth=gp,
                                          stream=sp, control=cp,
                                          liveness=q),
            st,
        )

    eps.append(EntryPoint(
        name="local[xla,scenario+growth+stream+control+adversary]",
        engine="xla", kind="round", audit_check="gossip_round_local",
        build=build_all_five, n_peers=ctx["dg"].n_pad,
    ))

    # the jitted loop entries (donating: state aliases the carry)
    def build_sim():
        st, cfg = ctx["state_for"](ctx["dg"], 16, mode="push_pull")
        return (lambda s: engine.simulate(s, cfg, _SIM_ROUNDS), st)

    eps.append(EntryPoint(
        name="local[simulate]", engine="xla", kind="simulate",
        audit_check="simulate_and_coverage", build=build_sim,
        stats_leading=(_SIM_ROUNDS,), jit_name="simulate",
        n_peers=ctx["dg"].n_pad,
    ))

    def build_cov():
        st, cfg = ctx["state_for"](ctx["dg"], 16, mode="push_pull")
        return (
            lambda s: engine.run_until_coverage(s, cfg, 0.99, 10), st,
        )

    eps.append(EntryPoint(
        name="local[run_until_coverage]", engine="xla", kind="coverage",
        audit_check="simulate_and_coverage", build=build_cov,
        stats_leading=None, jit_name="run_until_coverage",
        n_peers=ctx["dg"].n_pad,
    ))

    # PACKED loop entries (core/packed.py): the scan/while carry is the
    # registry's packed storage ledger — the packed pytree must be a
    # fixed point of the packed round map (or a packed carry could never
    # ride the loops/checkpoints), the donating jit must cover every
    # packed leaf, and the mem tier prices the packed residency
    def build_sim_packed():
        from tpu_gossip.core.packed import pack_state

        st, cfg = ctx["state_for"](ctx["dg"], 16, mode="push_pull")
        return (lambda s: engine.simulate(s, cfg, _SIM_ROUNDS),
                pack_state(st))

    eps.append(EntryPoint(
        name="local[simulate,packed]", engine="xla", kind="simulate",
        audit_check="simulate_and_coverage", build=build_sim_packed,
        stats_leading=(_SIM_ROUNDS,), jit_name="simulate",
        n_peers=ctx["dg"].n_pad, packed=True,
    ))

    def build_cov_packed():
        from tpu_gossip.core.packed import pack_state

        st, cfg = ctx["state_for"](ctx["dg"], 16, mode="push_pull")
        return (
            lambda s: engine.run_until_coverage(s, cfg, 0.99, 10),
            pack_state(st),
        )

    eps.append(EntryPoint(
        name="local[run_until_coverage,packed]", engine="xla",
        kind="coverage", audit_check="simulate_and_coverage",
        build=build_cov_packed, stats_leading=None,
        jit_name="run_until_coverage", n_peers=ctx["dg"].n_pad,
        packed=True,
    ))

    # the PACKED-NATIVE round: a PackedSwarm input routes through
    # sim/packed_engine, so this trace IS the word-level round — the
    # deep codec rail walks it (bitwise/popcount licensed in the kernel
    # tier, decode only through core/packed.py) and the fixed-point
    # check pins PackedSwarm -> PackedSwarm with the scalar int32 stats
    # contract unchanged. forward_once engages the word-level latch
    # (ANDN), SIR the recovered stale filter — the full dedup algebra
    def build_round_packed():
        from tpu_gossip.core.packed import pack_state

        st, cfg = ctx["state_for"](
            ctx["dg"], 16, mode="push_pull", sir_recover_rounds=4,
            forward_once=True,
        )
        return lambda s: engine.gossip_round(s, cfg, None), pack_state(st)

    eps.append(EntryPoint(
        name="local[xla,round,packed-native]", engine="xla", kind="round",
        audit_check="gossip_round_local", build=build_round_packed,
        n_peers=ctx["dg"].n_pad, packed=True,
    ))

    # the BATCHED fleet entry (fleet/): a composed scenario×stream×
    # control campaign vmapped over _FLEET_LANES lanes — the batched
    # round must stay a state fixed point AT BATCH RANK (the stacked
    # state rides the scan carry), the stats contract holds with the
    # (K, R) leading dims, and the donating jit covers every batched
    # state leaf; the deep tiers trace the vmapped composed round's full
    # lineage (four parallel fold_in streams per lane)
    def build_fleet():
        from tpu_gossip.fleet import engine as fleet_eng
        from tpu_gossip.fleet import plan as fleet_plan

        spec = fleet_plan.campaign_from_dict({
            "name": "audit-fleet", "seed": 0,
            "base": {
                "peers": _FLEET_PEERS, "rounds": _FLEET_ROUNDS,
                "slots": 16, "fanout": 1, "mode": "push_pull",
                "stream_rate": 1.0, "slot_ttl": 12,
                "control": 0.9, "control_hi": 3, "rewire_slots": 3,
                "churn_join": 0.02,
            },
            "families": [{
                "name": "chaos",
                "scenario": {
                    "name": "audit-fleet-chaos",
                    "phases": [
                        {"name": "lossy", "start": 0, "end": 1,
                         "loss": 0.2, "delay": 0.2},
                        {"name": "split", "start": 1, "end": 2,
                         "partition": "half",
                         "blackout": {"frac": 0.1, "seed": 1}},
                    ],
                },
                "seeds": _FLEET_LANES,
                "sweeps": [{"axis": "phase.loss", "dist": "uniform",
                            "lo": 0.1, "hi": 0.4}],
            }],
        })
        camp = fleet_plan.compile_campaign(spec)
        return (
            lambda s: fleet_eng.simulate_fleet(
                s, camp.cfg, camp.rounds, camp.scenario, camp.growth,
                camp.stream, camp.control,
            ),
            camp.states,
        )

    eps.append(EntryPoint(
        name="fleet[simulate,composed]", engine="xla", kind="simulate",
        audit_check="simulate_and_coverage", build=build_fleet,
        stats_leading=(_FLEET_LANES, _FLEET_ROUNDS),
        jit_name="simulate_fleet",
        n_peers=_FLEET_LANES * _FLEET_PEERS,
    ))
    return eps


def _dist_entries() -> list[EntryPoint]:
    from tpu_gossip.dist import mesh as mesh_mod  # call-time resolution

    dctx = _dist_ctx()
    mesh, plan, sg = dctx["mesh"], dctx["plan"], dctx["sg"]
    eps: list[EntryPoint] = []

    def dist_ep(name, eng, audit_check, state_kw, round_kw, *,
                kind="round", stats_leading=(), has_ici=False, jit_name=None,
                mesh2=False):
        mk_state = dctx["m_state"] if eng == "dist-matching" else dctx["b_state"]
        graph_plan = plan if eng == "dist-matching" else sg
        mesh = dctx["mesh2"] if mesh2 else dctx["mesh"]

        def build():
            st, cfg = mk_state(**state_kw)
            kw = dict(round_kw)
            if "scenario" in kw and kw["scenario"] is True:
                kw["scenario"] = _chaos_scenario(
                    plan.n if eng == "dist-matching" else sg.n_pad,
                    _N_MATCH if eng == "dist-matching" else _N_DEV,
                )
            if kw.pop("adversary", False):
                kw["scenario"] = _adversary_scenario(
                    plan.n if eng == "dist-matching" else sg.n_pad,
                    _N_MATCH if eng == "dist-matching" else _N_DEV,
                )
                kw["liveness"] = _quorum_spec()
            if "growth" in kw and kw["growth"] is True:
                n_slots = plan.n if eng == "dist-matching" else sg.n_pad
                kw["growth"] = _growth_plan(n_slots, n_slots - 40)
            if kw.pop("sparse", False):
                from tpu_gossip.dist import transport as tp

                kw["transport"] = tp.build_transport(graph_plan, mode="sparse")
            if kw.pop("hier", False):
                from tpu_gossip.cluster.topology import mesh_hosts
                from tpu_gossip.dist import transport as tp

                kw["transport"] = tp.build_transport(
                    graph_plan, mode="hier", hosts=mesh_hosts(mesh)[0]
                )
            if kw.pop("stream", False):
                kw["stream"] = _stream_plan(16, st.exists)
            if kw.pop("ingest", False):
                kw["stream"] = _stream_plan(16, st.exists)
                kw["inject"] = _ingest_batch(16)
            if kw.pop("control", False):
                kw["control"] = _control_plan()
            if kw.pop("pipeline", False):
                from tpu_gossip.sim.stages import compile_pipeline

                kw["pipeline"] = compile_pipeline(1)
            if kind == "round":
                fn = lambda s: mesh_mod.gossip_round_dist(  # noqa: E731
                    s, cfg, graph_plan, mesh, **kw
                )
            elif kind == "simulate":
                fn = lambda s: mesh_mod.simulate_dist(  # noqa: E731
                    s, cfg, graph_plan, mesh, _DIST_SIM_ROUNDS, **kw
                )
            else:
                fn = lambda s: mesh_mod.run_until_coverage_dist(  # noqa: E731
                    s, cfg, graph_plan, mesh, 0.99, 6, **kw
                )
            return fn, st

        return EntryPoint(
            name=name, engine=eng, kind=kind, audit_check=audit_check,
            build=build, stats_leading=stats_leading, has_ici=has_ici,
            jit_name=jit_name,
            n_peers=plan.n if eng == "dist-matching" else sg.n_pad,
        )

    eps.append(dist_ep(
        "dist[matching]", "dist-matching", "gossip_round_dist", {}, {},
    ))
    # the mesh round under an active chaos scenario (faults/) — the
    # bit-identity contract's distributed half must trace with the same
    # fixed point the local scenario round keeps
    eps.append(dist_ep(
        "dist[matching,scenario]", "dist-matching", "gossip_round_dist",
        {}, dict(scenario=True),
    ))
    # the GROWING mesh round — the membership half of the bit-identity
    # contract must trace with the same state fixed point on the mesh
    # (growth edges ride the re-wiring plane, so the config carries slots)
    eps.append(dist_ep(
        "dist[matching,growth]", "dist-matching", "gossip_round_dist",
        dict(rewire_slots=2), dict(growth=True),
    ))
    # the LOADED mesh round (traffic/) — streaming injection draws at
    # global shape outside shard_map must keep the mesh round a state
    # fixed point on both engine families (the serving half of the
    # bit-identity contract)
    eps.append(dist_ep(
        "dist[matching,stream]", "dist-matching", "gossip_round_dist",
        {}, dict(stream=True),
    ))
    # the SERVED mesh round (serve/): a live window's InjectBatch lands
    # at global shape post-tail — the sharded serving engine's half of
    # the recorded-trace replay contract
    eps.append(dist_ep(
        "dist[matching,ingest]", "dist-matching", "gossip_round_dist",
        {}, dict(ingest=True),
    ))
    # the ADVERSARIAL mesh round: the Byzantine scatters and the quorum
    # machine run at global shape outside shard_map — the adversarial
    # extension of the bit-identity contract must trace with the same
    # fixed point the local adversarial round keeps
    eps.append(dist_ep(
        "dist[matching,adversary+scenario]", "dist-matching",
        "gossip_round_dist", {}, dict(adversary=True),
    ))
    eps.append(dist_ep(
        "dist[bucketed]", "dist-bucketed", "gossip_round_dist", {}, {},
    ))
    eps.append(dist_ep(
        "dist[bucketed,growth]", "dist-bucketed", "gossip_round_dist",
        dict(rewire_slots=2), dict(growth=True),
    ))
    eps.append(dist_ep(
        "dist[bucketed,stream]", "dist-bucketed", "gossip_round_dist",
        {}, dict(stream=True),
    ))
    # the CONTROLLED mesh round (control/) — feedback reductions at
    # global shape, the per-shard activation rescale, the PeerSwap
    # scatters: both engine families must stay a state fixed point under
    # an active controller (the adaptive half of the bit-identity
    # contract)
    # (the matching fixture graph is built without a CSR export, so its
    # controlled entry runs without churn re-wiring — the PeerSwap
    # refresh + churn composition traces on the bucketed entry instead)
    eps.append(dist_ep(
        "dist[matching,control]", "dist-matching", "gossip_round_dist",
        {}, dict(control=True),
    ))
    eps.append(dist_ep(
        "dist[bucketed,control]", "dist-bucketed", "gossip_round_dist",
        dict(rewire_slots=2, churn_join_prob=0.02, churn_leave_prob=0.002),
        dict(control=True),
    ))
    # the PIPELINED mesh round (sim/stages.py): the double-buffered
    # exchange must keep both engine families a state fixed point — the
    # in-flight buffer (pipe_buf) rides scan/while carries and
    # checkpoints like any other cursor, and the issue-side draws keep
    # the lineage contract (same keys as serial, test-pinned depth-0
    # identity)
    eps.append(dist_ep(
        "dist[matching,pipeline]", "dist-matching", "gossip_round_dist",
        {}, dict(pipeline=True),
    ))
    eps.append(dist_ep(
        "dist[bucketed,pipeline]", "dist-bucketed", "gossip_round_dist",
        {}, dict(pipeline=True),
    ))
    # pipelined × scenario × stream composed: the overlap schedule under
    # an active fault head and a loaded lease table — the maximal
    # pipelined carry surface (held buffer + lease cursor + pipe_buf)
    eps.append(dist_ep(
        "dist[matching,pipeline+scenario+stream]", "dist-matching",
        "gossip_round_dist", {}, dict(pipeline=True, scenario=True,
                                      stream=True),
    ))
    # the jitted dist loop entries (donating) — scan/while over shard_map
    eps.append(dist_ep(
        "dist[matching,simulate]", "dist-matching", "gossip_round_dist",
        {}, {}, kind="simulate", stats_leading=(_DIST_SIM_ROUNDS,),
        jit_name="simulate_dist",
    ))

    # the PACKED dist loop entry: the sharded scan carry is the packed
    # storage ledger — fixed point + donation + mem pricing at the
    # packed rank on the mesh (the 100M residency shape)
    def build_dist_sim_packed():
        from tpu_gossip.core.packed import pack_state

        st, cfg = dctx["m_state"]()
        from tpu_gossip.dist import mesh as mm

        return (
            lambda s: mm.simulate_dist(
                s, cfg, plan, mesh, _DIST_SIM_ROUNDS
            ),
            pack_state(st),
        )

    eps.append(EntryPoint(
        name="dist[matching,simulate,packed]", engine="dist-matching",
        kind="simulate", audit_check="gossip_round_dist",
        build=build_dist_sim_packed, stats_leading=(_DIST_SIM_ROUNDS,),
        jit_name="simulate_dist", n_peers=plan.n, packed=True,
    ))

    # the PACKED-NATIVE mesh rounds: a PackedSwarm input routes each
    # engine through its word-native exchange — the matching pipeline
    # moves uint8 byte planes end to end (rewire_slots == 0), the
    # bucketed engine ships packed words on the wire and decodes once at
    # the delivery boundary. The deep codec rail audits both traces; the
    # wire audit prices the uint8 operands against dense_wire_words
    def build_dist_round_packed():
        from tpu_gossip.core.packed import pack_state

        st, cfg = dctx["m_state"]()
        from tpu_gossip.dist import mesh as mm

        return (
            lambda s: mm.gossip_round_dist(s, cfg, plan, mesh),
            pack_state(st),
        )

    eps.append(EntryPoint(
        name="dist[matching,round,packed-native]", engine="dist-matching",
        kind="round", audit_check="gossip_round_dist",
        build=build_dist_round_packed, n_peers=plan.n, packed=True,
    ))

    def build_dist_round_packed_bucketed():
        from tpu_gossip.core.packed import pack_state

        st, cfg = dctx["b_state"]()
        from tpu_gossip.dist import mesh as mm

        return (
            lambda s: mm.gossip_round_dist(s, cfg, sg, mesh),
            pack_state(st),
        )

    eps.append(EntryPoint(
        name="dist[bucketed,round,packed-native]", engine="dist-bucketed",
        kind="round", audit_check="gossip_round_dist",
        build=build_dist_round_packed_bucketed, n_peers=sg.n_pad,
        packed=True,
    ))
    eps.append(dist_ep(
        "dist[bucketed,run_until_coverage]", "dist-bucketed",
        "gossip_round_dist", {}, {}, kind="coverage", stats_leading=None,
        jit_name="run_until_coverage_dist",
    ))
    # sparse transport: both engines under transport=sparse must stay a
    # state fixed point with IciRound declared scalar int32
    eps.append(dist_ep(
        "dist[matching,sparse]", "dist-matching", "sparse_transport",
        {}, dict(sparse=True, collect_ici=True), has_ici=True,
    ))
    eps.append(dist_ep(
        "dist[bucketed,sparse]", "dist-bucketed", "sparse_transport",
        {}, dict(sparse=True, collect_ici=True), has_ici=True,
    ))
    # the 2-D (hosts, devices) cluster mesh (cluster/topology.py): the
    # dense rounds over the axis TUPLE are the flat rounds' programs —
    # same fixed point, same wire declaration (the wire audit compares
    # them against the SAME dense_wire_words) — and the hier entries run
    # the two-level ICI/DCN transport (cluster/hier.py) with its
    # host-axis collectives under the shard-uniformity rail
    if dctx["mesh2"] is not None:
        eps.append(dist_ep(
            "dist[matching,2d]", "dist-matching", "gossip_round_dist",
            {}, {}, mesh2=True,
        ))
        eps.append(dist_ep(
            "dist[bucketed,2d]", "dist-bucketed", "gossip_round_dist",
            {}, {}, mesh2=True,
        ))
        eps.append(dist_ep(
            "dist[matching,hier]", "dist-matching", "sparse_transport",
            {}, dict(hier=True, collect_ici=True), has_ici=True,
            mesh2=True,
        ))
        eps.append(dist_ep(
            "dist[bucketed,hier]", "dist-bucketed", "sparse_transport",
            {}, dict(hier=True, collect_ici=True), has_ici=True,
            mesh2=True,
        ))
    return eps


def entry_points() -> tuple[EntryPoint, ...]:
    """The full matrix. Dist entries are omitted (with the reason left to
    :func:`dist_guard`) on hosts whose device count cannot mesh 128."""
    eps = _local_entries()
    if dist_guard() is None:
        eps.extend(_dist_entries())
    return tuple(eps)


def trace_matrix(
    eps,
    cache: Dict[str, TracedEntry] | None = None,
) -> Dict[str, TracedEntry]:
    """``jax.make_jaxpr(..., return_shape=True)`` over ``eps``.

    Returns name -> :class:`TracedEntry`; a failed build/trace records its
    error instead of raising (the consumer turns it into a finding). Pass
    the same ``cache`` dict across consumers to trace each entry once per
    invocation — tests pass none and get monkeypatch-fresh traces.
    """
    import jax

    out: Dict[str, TracedEntry] = {}
    for ep in eps:
        if cache is not None and ep.name in cache:
            out[ep.name] = cache[ep.name]
            continue
        te = TracedEntry(ep=ep)
        try:
            fn, st = ep.build()
            te.state = st
            te.jaxpr, te.out_shape = jax.make_jaxpr(fn, return_shape=True)(st)
        except Exception as e:  # noqa: BLE001 — consumers report, not crash
            te.error = f"{e!r:.300}"
        out[ep.name] = te
        if cache is not None:
            cache[ep.name] = te
    return out
