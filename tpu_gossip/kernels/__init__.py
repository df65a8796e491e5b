"""Per-round batched protocol ops (the TPU replacement for socket I/O loops).

The reference executes one gossip "send" per socket per thread
(reference Peer.py:395-408, recv loops Peer.py:180,261). Here a whole round —
every peer's fan-out, dedup, and liveness bookkeeping — is a handful of
gather/scatter array ops over the CSR adjacency, jit-compiled and shardable
on the peer axis. ``gossip`` holds dissemination ops, ``liveness`` the
heartbeat/failure-detector state machine.
"""

from tpu_gossip.kernels.gossip import push_fanout, pull_fanout, flood_all
from tpu_gossip.kernels.liveness import emit_heartbeats, detect_failures

# NOTE: tpu_gossip.kernels.pallas_segment (StaircasePlan, plan builders,
# segment_or/segment_sampled) is deliberately NOT re-exported here — every
# consumer (sim/engine.py, cli/run_sim.py) imports it lazily so
# the jax.experimental.pallas/.tpu stack loads only when a plan is used,
# and pure-XLA runs work even where that import can't.
__all__ = [
    "push_fanout",
    "pull_fanout",
    "flood_all",
    "emit_heartbeats",
    "detect_failures",
]
