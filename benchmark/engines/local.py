"""The local matching engine: the overlay, its plan and the round loop on one
device, as ``run_sim._run`` drives them without ``--shard``.

``build`` makes the overlay and its plan once with
``matching_powerlaw_graph``; a reset is ``init_swarm`` with fresh origins; a
run is ``run_until_coverage`` with the plan and the configured tail.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark import reference

HONOURED = frozenset({
    "graph", "mode", "fanout", "slots", "gamma", "target", "max_rounds",
    "forward_once", "tail",
})


def accepts(args) -> bool:
    return args.graph == "matching" and not args.shard


def _readout(state, rumors: int):
    """Final coverage, round and the rumor slots' infection rounds."""
    return state.coverage(0), state.round, state.infected_round[:, :rumors]


class Engine:
    """The program's calls for one cell. ``builder`` stands in for
    ``matching_powerlaw_graph`` (benchmark/control.py)."""

    def __init__(self, args, rumors: int, builder=None):
        import jax

        from tpu_gossip.core.matching_topology import matching_powerlaw_graph
        from tpu_gossip.core.state import init_swarm
        from tpu_gossip.sim.engine import run_until_coverage

        self.args, self.rumors = args, rumors
        self.builder = builder or matching_powerlaw_graph
        self.init_swarm, self.run_until_coverage = init_swarm, run_until_coverage
        self.readout = jax.jit(functools.partial(_readout, rumors=rumors))

    def build(self) -> None:
        """The overlay and its plan, on the device once this returns."""
        import jax

        from tpu_gossip.core.state import SwarmConfig

        args = self.args
        dgraph, self.plan = self.builder(
            args.peers, gamma=args.gamma,
            fanout=None if args.mode == "flood" else args.fanout,
            key=jax.random.key(args.seed),
        )
        self.graph, self.exists = dgraph.as_padded_graph(), dgraph.exists
        int(self.graph.row_ptr[-1])
        self.cfg = SwarmConfig(
            n_peers=self.graph.n, msg_slots=args.slots, fanout=args.fanout,
            mode=args.mode, forward_once=args.forward_once,
        )

    def reset(self, origins: np.ndarray, key):
        """A fresh state with one rumor slot at each origin."""
        return self.init_swarm(
            self.graph, self.cfg, key=key, origins=origins,
            origin_slots=np.arange(self.rumors), exists=self.exists,
        )

    def run(self, state):
        """The round loop to the coverage target."""
        return self.run_until_coverage(state, self.cfg, self.args.target,
                                       self.args.max_rounds, plan=self.plan,
                                       tail=self.args.tail)

    def overlay(self) -> tuple[np.ndarray, np.ndarray]:
        """The overlay's CSR on the host: its n peer rows."""
        n = self.args.peers
        rp, ci = (np.asarray(a) for a in (self.graph.row_ptr,
                                          self.graph.col_idx))
        return rp[: n + 1].astype(np.int64), ci[: rp[n]].astype(np.int64)

    def law(self) -> np.ndarray:
        """The degree law the reference holds the overlay to."""
        return reference.law_degrees(self.args.peers, self.args.gamma)

    def release(self) -> None:
        """Drop the device state (graph, plan)."""
        self.graph = self.plan = self.exists = None
