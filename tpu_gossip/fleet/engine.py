"""The fleet engine: one vmapped compile runs K independent swarms.

``simulate_fleet`` is the batched twin of ``sim.engine.simulate``: a
``jax.vmap`` over the SHARED per-engine round driver
(``sim.stages.run_protocol_round`` via ``gossip_round``), scanned over a
fixed horizon. Every lane runs the full composed protocol — chaos
scenario, growth admission, streaming injection, adaptive control —
against its own stacked plan tables, and ONE compile serves all K lanes:
the lane axis is just one more array dimension to XLA, so the per-op
dispatch overhead that K serial processes pay K times is paid once.

The conformance contract (tests/sim/test_fleet.py): lane k of the
batched run is BIT-IDENTICAL — full state plus every integer stat — to a
solo ``simulate`` over ``campaign.lane(k)``'s plans. This is vmap's
semantic guarantee (batching is stacking) made test-pinned: every
protocol draw happens at the same per-lane shape from the same per-lane
key, integer reductions are exact at any batching, and the compiled
plans carry no lane cross-talk. Float stats (coverage, the growth γ
track) are excluded exactly as in the local↔sharded contract — batched
float reduction order may differ by 1 ULP.

Donation: ``simulate_fleet`` DONATES its batched state like every other
jitted loop entry (the ~K×N×M pytree aliases the scan carry instead of
copying); ``run_campaign`` clones internally when asked to keep the
campaign's states reusable.
"""

from __future__ import annotations

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "simulate_fleet",
    "run_campaign",
    "run_lane_solo",
    "state_digest",
    "stats_digest",
]


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "num_rounds", "liveness"),
    donate_argnames=("state",),
)
def simulate_fleet(
    state, cfg, num_rounds: int, scenario=None, growth=None, stream=None,
    control=None, liveness=None,
):
    """Run K stacked swarms ``num_rounds`` rounds in one batched program.

    ``state`` is a :func:`~tpu_gossip.core.state.stack_states` pytree
    (every leaf carries a leading lane axis); ``scenario``/``growth``/
    ``stream``/``control`` are the matching stacked compiled plans (or
    ``None`` — an absent subsystem is absent for every lane, the
    shared-static-structure rule). Returns ``(final_states, stats)``
    with every stats field shaped ``(K, num_rounds, ...)``.

    DONATES ``state`` (the ``simulate`` contract at batch rank): pass
    ``clone_state`` to keep the input alive.
    """
    from tpu_gossip.sim.engine import gossip_round

    def lane(st, sc, gr, sp, cp):
        def body(carry, _):
            return gossip_round(carry, cfg, scenario=sc, growth=gr,
                                stream=sp, control=cp, liveness=liveness)

        return jax.lax.scan(body, st, None, length=num_rounds)

    # absent plans broadcast as None (an empty pytree maps through any
    # in_axes); present plans batch on their stacked lane axis
    axes = tuple(
        None if p is None else 0
        for p in (scenario, growth, stream, control)
    )
    return jax.vmap(lane, in_axes=(0,) + axes)(
        state, scenario, growth, stream, control
    )


def run_campaign(campaign, *, keep_states: bool = True):
    """Run a :class:`~tpu_gossip.fleet.plan.CompiledCampaign` end to end.

    Returns ``(final_states, stats)`` — the batched final state and the
    ``(K, rounds, ...)`` stats the certification report
    (fleet/metrics.campaign_report) reduces. The default clones before
    the donating entry, so ``campaign.states`` stays usable afterwards
    (lane extraction, repeat runs — the bit-identity oracle's
    precondition). ``keep_states=False`` is the large-campaign path:
    the initial states are DONATED, ``campaign.states`` is replaced by
    the final states, and the campaign is marked ``consumed`` so
    ``campaign.lane()`` / :func:`run_lane_solo` refuse instead of
    silently handing out post-run state.
    """
    from tpu_gossip.core.state import clone_state

    st = clone_state(campaign.states) if keep_states else campaign.states
    fin, stats = simulate_fleet(
        st, campaign.cfg, campaign.rounds, campaign.scenario,
        campaign.growth, campaign.stream, campaign.control,
        campaign.liveness,
    )
    if not keep_states:
        campaign.states = fin  # the donated input is gone; keep the result
        campaign.consumed = True
    return fin, stats


def run_lane_solo(campaign, k: int):
    """The conformance oracle: lane ``k`` run UNBATCHED through the plain
    ``sim.engine.simulate`` over exactly the plans the batch compiled for
    it. Returns ``(final_state, stats)``; bit-identical (state + integer
    stats) to lane ``k`` of :func:`run_campaign` — test-pinned, and
    cross-checked across processes by the fleet-smoke CI digests.
    """
    from tpu_gossip.sim.engine import simulate

    st, sc, gr, sp, cp = campaign.lane(k)
    return simulate(st, campaign.cfg, campaign.rounds, None, "fused",
                    sc, gr, sp, cp, None, campaign.liveness)


def state_digest(state) -> str:
    """A platform-stable sha256 over every state leaf (PRNG keys via
    their raw key data) — the cross-process bit-identity fingerprint the
    fleet-smoke job compares between the batched run and a solo
    subprocess. Works on a solo state or one lane of a batch."""
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(state):
        if hasattr(leaf, "dtype") and jnp.issubdtype(
            leaf.dtype, jax.dtypes.prng_key
        ):
            leaf = jax.random.key_data(leaf)
        arr = np.asarray(leaf)
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()


def stats_digest(stats, k: int | None = None) -> str:
    """sha256 over the INTEGER stats tracks (the bit-exact half of the
    contract; float tracks — coverage, γ — are excluded like the
    local↔sharded matrix does). ``k`` selects one lane of batched stats.
    """
    h = hashlib.sha256()
    for name in stats._fields:
        arr = np.asarray(getattr(stats, name))
        if arr.dtype.kind not in "biu":
            continue
        if k is not None:
            arr = arr[k]
        h.update(name.encode())
        h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()
