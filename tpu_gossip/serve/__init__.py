"""Live ingestion frontend: serve the reference wire protocol from the
device swarm (docs/serving_frontend.md).

The reference is a socket program; the simulator modeled its traffic.
This package closes the loop — the TPU swarm as a digital twin serving
real clients:

- ``serve/protocol.py`` — the serving view of the reference's
  newline-framed wire protocol (compat/wire.py): total parse into typed
  events, the stable 64-bit payload hash that maps a gossip line to its
  dedup slots, response formatting.
- ``serve/frontend.py`` — an asyncio socket frontend accepting many
  concurrent clients, mapping each to a peer id, and batching the
  arrivals of each round window into the static-shape
  :class:`~tpu_gossip.traffic.InjectBatch` the injection stage
  (traffic/ingest.py) consumes. Overflow is carried, counted, never
  dropped silently.
- ``serve/driver.py`` — the round driver: ONE jitted step per engine
  (local / sharded matching, packed included) double-buffering the next
  window's batch against the in-flight device round the way
  ``pipe_buf`` double-buffers the exchange, and answering liveness/
  coverage/reliability queries from the steady-state metrics between
  rounds.
- ``serve/trace.py`` — the determinism plane: every accepted arrival is
  recorded as ``(round, origin, payload_hash)`` and a recorded trace
  replays through the pure-sim injection path bit for bit (state digest
  + integer-stat trajectory — the project's bit-identity discipline
  extended across the socket boundary).
- ``serve/loadgen.py`` — the scripted multi-client load generator the
  CI smoke job drives the frontend with.
"""

from tpu_gossip.serve.driver import (
    DriverReport,
    ServeDriver,
    build_step,
    stack_round_stats,
)
from tpu_gossip.serve.frontend import (
    FrontendCounters,
    ServeFrontend,
    origin_for_addr,
)
from tpu_gossip.serve.loadgen import LoadReport, run_load
from tpu_gossip.serve.protocol import (
    ServeEvent,
    parse_line,
    payload_hash64,
    slots_for_payload,
)
from tpu_gossip.serve.trace import ServeTrace, TraceRecorder, replay_trace

__all__ = [
    "DriverReport",
    "FrontendCounters",
    "LoadReport",
    "ServeDriver",
    "ServeEvent",
    "ServeFrontend",
    "ServeTrace",
    "TraceRecorder",
    "build_step",
    "origin_for_addr",
    "parse_line",
    "payload_hash64",
    "replay_trace",
    "run_load",
    "slots_for_payload",
    "stack_round_stats",
]
