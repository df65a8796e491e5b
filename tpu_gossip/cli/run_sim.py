"""Run a whole gossip swarm on the TPU: the minimum end-to-end slice.

Example (SURVEY.md §7.3: 1k-peer power-law swarm to 99% coverage):

    python -m tpu_gossip.cli.run_sim --peers 1000 --gamma 2.5 --target 0.99

Prints one JSONL row per round (coverage, msgs, liveness counts) and a final
summary with rounds-to-target and peers·rounds/sec. This single invocation
replaces the reference's N-terminal manual procedure (readme.md:1-9: one
process per node, logs tailed by hand).
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--peers", type=int, default=1000, help="swarm size N")
    p.add_argument(
        "--graph",
        choices=["pa", "chung-lu", "matching"],
        default="pa",
        help="pa: preferential attachment (Barabási–Albert); "
        "chung-lu: configuration model with P(d)~d^-gamma; "
        "matching: structured-matching erased configuration model "
        "(device-built, gather-free delivery — the fastest path; with "
        "--shard the pipeline runs per shard with transposes as "
        "all_to_all collectives, bit-identical to the local round)",
    )
    p.add_argument("--gamma", type=float, default=2.5, help="power-law exponent (chung-lu)")
    p.add_argument(
        "--m", type=int, default=3,
        help="edges per new node (pa graph build; also the fresh edges "
        "each --grow joiner attaches)",
    )
    p.add_argument("--mode", choices=["push", "push_pull", "flood"], default="push")
    p.add_argument("--fanout", type=int, default=3)
    p.add_argument("--slots", type=int, default=16, help="hash-dedup message slots")
    p.add_argument("--origins", type=int, default=1, help="number of initially infected peers")
    p.add_argument("--target", type=float, default=0.99, help="coverage target")
    p.add_argument("--rounds", type=int, default=0, help="fixed horizon (0 = run to target)")
    p.add_argument("--max-rounds", type=int, default=1000)
    p.add_argument("--forward-once", action="store_true")
    p.add_argument("--sir-recover", type=int, default=0, help="rounds until SIR recovery (0 = off)")
    p.add_argument("--silent-frac", type=float, default=0.0, help="fraction of peers made silent (fault injection)")
    p.add_argument("--churn-leave", type=float, default=0.0, help="per-round leave probability")
    p.add_argument("--churn-join", type=float, default=0.0, help="per-round rejoin probability")
    p.add_argument(
        "--rewire-slots", type=int, default=0,
        help="rejoiners attach this many fresh degree-preferential edges (0 = reuse slot edges)",
    )
    p.add_argument("--seed", type=int, default=0, help="RNG seed")
    p.add_argument(
        "--staircase",
        action="store_true",
        help="deliver via the Pallas staircase kernel: exact segment-OR for "
        "flood, Bernoulli-per-edge sampling for push/push_pull (any --slots "
        "width, one launch per 32 slots). Composes with --rewire-slots in "
        "push/push_pull: the static CSR rides the kernel, rejoiners' fresh "
        "edges go through the XLA side path. Flood ignores re-wiring on "
        "every delivery path (the flood is defined over the static CSR)",
    )
    p.add_argument(
        "--rewire-compact-cap", type=int, default=0, metavar="CAP",
        help="bound the fresh-edge side paths to a CAP-row table of rewired "
        "peers (O(CAP) instead of O(N) random access; at most CAP joiners "
        "re-wire per round — pair with --remat-every so the rewired set "
        "stays under CAP). 0 = exact dense paths",
    )
    p.add_argument(
        "--remat-every", type=int, default=0, metavar="R",
        help="every R rounds, fold rejoiners' fresh edges into the CSR and "
        "clear the rewired set (sim.engine.rematerialize_rewired) — churn "
        "rounds then run at static-topology cost between rebuilds; with "
        "--staircase the plan is rebuilt per segment; with --shard the "
        "fold is followed by a full epoch re-partition onto the mesh "
        "(dist.repartition_swarm: fresh bucket tables + shard plans), so "
        "the rewired set stays bounded — pair with --rewire-compact-cap "
        "(0 = off)",
    )
    p.add_argument(
        "--shard",
        action="store_true",
        help="run the sharded engine over ALL available devices (1-D peer "
        "mesh, bucketed all_to_all exchange — dist/mesh.py); composes with "
        "--staircase, which then routes each shard's receive side through "
        "the per-shard staircase kernel (the north-star fusion)",
    )
    p.add_argument(
        "--transport", choices=["dense", "sparse", "auto", "hier"],
        default="dense",
        help="sharded-exchange transport (dist/transport.py, docs/"
        "sparse_exchange.md): dense ships the full rectangular all_to_all "
        "payloads every round; sparse compacts occupied words into a "
        "static worst-case buffer behind a per-round occupancy header "
        "(hub rows ride a dense sub-lane on the matching family), falling "
        "back to the dense lane whenever the round's occupancy exceeds "
        "the budget; auto additionally requires the static geometry to "
        "predict a byte win; hier is the TWO-LEVEL ICI/DCN transport "
        "(cluster/hier.py, docs/multihost_mesh.md) — dense inside each "
        "fast intra-host slice, compacted across the slow host axis — "
        "and needs --hosts H > 1. Bit-identical to dense in every mode — "
        "the transport reorders bytes, never draws. Requires --shard; "
        "the summary JSON gains transport + realized occupancy/bytes "
        "fields (per-axis ici_bytes/dcn_bytes under --hosts)",
    )
    p.add_argument(
        "--hosts", type=int, default=1, metavar="H",
        help="fold the device mesh into a 2-D (hosts, devices) cluster "
        "mesh (cluster/topology.py, docs/multihost_mesh.md): collectives "
        "run over the axis tuple, which flattens row-major to the same "
        "shard order, so the trajectory is BIT-IDENTICAL to the flat "
        "1-D mesh — state and every integer stat. H must divide the "
        "device count. Requires --shard; enables --transport hier and "
        "splits the summary's wire accounting into per-axis ici/dcn "
        "bytes. 1 = flat mesh (the default)",
    )
    p.add_argument(
        "--coordinator", type=str, default="", metavar="ADDR",
        help="run as ONE process of a real multi-host jax.distributed "
        "cluster (cluster/launch.py): ADDR is the coordinator's "
        "host:port; needs --num-processes and --process-id, and --hosts "
        "must equal --num-processes (one process per mesh host row). "
        "Single-machine multi-process launches go through "
        "`python -m tpu_gossip.cluster.launch`",
    )
    p.add_argument(
        "--num-processes", type=int, default=0, metavar="P",
        help="total process count of the jax.distributed cluster "
        "(with --coordinator)",
    )
    p.add_argument(
        "--process-id", type=int, default=-1, metavar="I",
        help="this process's rank in [0, --num-processes) "
        "(with --coordinator)",
    )
    p.add_argument(
        "--tail", choices=["fused", "reference"], default="fused",
        help="protocol-tail implementation (kernels/round_tail.py): fused "
        "(single lax traversal, the default), reference (the historical "
        "multi-pass sequence — the bitwise oracle). Both are "
        "bit-identical; local engine only",
    )
    p.add_argument(
        "--pipeline", type=int, choices=[0, 1], default=None, metavar="DEPTH",
        help="pipelined sharded rounds (sim/stages.py, docs/"
        "pipelined_rounds.md): 1 double-buffers the exchange — the "
        "collective for this round's transmit plane is issued while the "
        "previous round's buffered exchange runs the shard-local tail "
        "(delivery one round stale; round throughput, not per-hop "
        "latency, is the win); 0 is the serial schedule, bit-identical "
        "to omitting the flag (the determinism contract's anchor). "
        "Requires --shard — the overlap targets the mesh collectives",
    )
    p.add_argument(
        "--grow", type=int, default=0, metavar="TARGET_N",
        help="grow the swarm to TARGET_N peers while gossiping (growth/, "
        "docs/growth_engine.md): per-round join batches are admitted "
        "INSIDE the jitted round, each joiner attaching --m fresh edges "
        "by preferential attachment over the current realized degree "
        "vector (Gumbel-top-k from a dedicated PRNG stream — the "
        "local/sharded bit-identity contract extends to growing swarms). "
        "Composes with --scenario join_burst phases (admission waves) "
        "and every delivery engine; node-scoped scenario sets stay "
        "declared over the INITIAL --peers ids",
    )
    p.add_argument(
        "--grow-rate", type=int, default=0, metavar="J",
        help="joins admitted per round (default: sized so TARGET_N is "
        "reached in about half of --rounds/--max-rounds)",
    )
    p.add_argument(
        "--grow-capacity", type=int, default=0, metavar="CAP",
        help="state capacity in peer slots (jit-static; >= TARGET_N; "
        "default TARGET_N). Slots beyond the target stay reserved — "
        "headroom for resuming the checkpoint into a later, larger "
        "growth schedule without a state rebuild",
    )
    p.add_argument(
        "--stream", type=float, default=0.0, metavar="RATE",
        help="streaming serving plane (tpu_gossip/traffic/, docs/"
        "streaming_plane.md): inject a sustained message stream at RATE "
        "Poisson arrivals per round, each message leasing dedup slot(s) "
        "that age out after --slot-ttl rounds — the (N, M) bitmap "
        "becomes a sliding window over live messages. Draws come from a "
        "dedicated PRNG stream on every engine (local and sharded "
        "loaded runs stay bit-identical; rate 0 = off). Needs a fixed "
        "--rounds horizon; the summary JSON gains steady-state serving "
        "metrics (delivered msgs/sec, p50/p99 rounds-to-coverage per "
        "message, conflation rate)",
    )
    p.add_argument(
        "--stream-origins", choices=["uniform", "degree", "hotspot"],
        default="uniform", metavar="DIST",
        help="origin law for injected messages: uniform over the initial "
        "membership, degree (degree-proportional — heavy users are the "
        "hubs), or hotspot (--stream-hot-frac of the lowest peer ids "
        "originate --stream-hot-weight of the traffic)",
    )
    p.add_argument(
        "--slot-ttl", type=int, default=0, metavar="R",
        help="rounds a message holds its dedup slot(s) before the "
        "age-out recycles them (default: 3x the feasible coverage "
        "horizon). A TTL below the feasible horizon cannot deliver "
        "anything and is rejected at parse time",
    )
    p.add_argument(
        "--stream-hashes", type=int, default=1, metavar="K",
        help="Bloom planes per message (core.state.message_slots "
        "semantics): 1 = slot conflation, >=2 = k-hash Bloom dedup "
        "(all-planes-leased arrivals are suppressed at ingestion)",
    )
    p.add_argument(
        "--stream-burst-every", type=int, default=0, metavar="B",
        help="bursty arrivals: every B-th round draws at RATE * "
        "--stream-burst-mult (0 = pure Poisson)",
    )
    p.add_argument("--stream-burst-mult", type=float, default=4.0, metavar="X")
    p.add_argument("--stream-hot-frac", type=float, default=0.01, metavar="F")
    p.add_argument("--stream-hot-weight", type=float, default=0.9, metavar="W")
    p.add_argument(
        "--control", type=float, default=0.0, metavar="TARGET_RATIO",
        help="adaptive protocol control (tpu_gossip/control/, docs/"
        "adaptive_control.md): close the fanout feedback loop inside the "
        "jitted round, defending the declared delivery-ratio target. Per "
        "round an AIMD policy widens the effective fanout when the "
        "observed delivery signals fall below TARGET_RATIO (realized "
        "loss, lagging stream slots) and shrinks it when the duplicate "
        "rate saturates; in push_pull mode the anti-entropy half runs "
        "only at-or-below the static --fanout. Runs on every engine from "
        "a dedicated PRNG stream (controlled local and sharded runs stay "
        "bit-identical); the summary JSON gains the reliability "
        "contract block on fixed-horizon runs",
    )
    p.add_argument(
        "--control-bounds", type=str, default="", metavar="LO,HI",
        help="the policy's fanout bounds (default: 1,2*--fanout — "
        "clamped to --rewire-slots when churn re-wiring is active). "
        "--fanout must lie inside; LO,HI = --fanout,--fanout is the "
        "zero-adjustment controller, bit-identical to the static run",
    )
    p.add_argument(
        "--refresh-every", type=int, default=0, metavar="K",
        help="PeerSwap neighbor refresh: every K rounds each live "
        "re-wired peer swaps one fresh-edge slot for a new degree-"
        "preferential draw (degree-credit bookkeeping preserved) — "
        "long-lived churned/grown swarms keep their randomness "
        "guarantees. Needs --control and the re-wiring plane "
        "(--rewire-slots/--grow); 0 = off",
    )
    p.add_argument(
        "--quorum-k", type=int, default=None, metavar="K",
        help="harden the failure detector into the witness-quorum "
        "suspicion machine (kernels/liveness.py, docs/"
        "adversarial_model.md): a stale peer is only SUSPECTED, and "
        "declared dead after K distinct witness confirmations inside the "
        "suspicion window. K=1 degrades to the reference's single-report "
        "purge (bit-identical to the unhardened detector with no "
        "adversaries); K>1 defends against Byzantine accusers — a "
        "scenario with accusers/forgers/floods phases REQUIRES this "
        "flag. The summary JSON gains a `liveness` block (evictions, "
        "false evictions, precision, quarantined count)",
    )
    p.add_argument(
        "--suspicion-window", type=int, default=None, metavar="W",
        help="rounds a suspicion may accumulate witness votes before it "
        "expires without quorum (default: 2x the detector sweep period). "
        "Must be at least the sweep period — the PING grace — or a "
        "suspicion would expire before its probe could refute. Needs "
        "--quorum-k",
    )
    p.add_argument(
        "--accusation-budget", type=int, default=None, metavar="B",
        help="false accusations (victim refutes inside the window) a "
        "peer may emit before the quarantine verdict latches: its sends "
        "are masked, its accusations ignored, its rewire slots released "
        "through the degree-credit book (default 3; 0 disables "
        "quarantine). Needs --quorum-k",
    )
    p.add_argument(
        "--scenario", type=str, default="", metavar="TOML",
        help="chaos scenario schedule (tpu_gossip/faults/, docs/"
        "fault_model.md): time-phased message loss, delivery delay, "
        "split-brain partitions, node/shard blackouts, churn bursts — "
        "injected deterministically from a dedicated PRNG stream on every "
        "engine (local and sharded rounds stay bit-identical). The "
        "schedule is validated BEFORE the run: phases beyond --rounds/"
        "--max-rounds or overlapping phases are config errors",
    )
    p.add_argument("--quiet", action="store_true", help="summary line only, no per-round JSONL")
    p.add_argument("--checkpoint", type=str, default="", help="save final SwarmState to this .npz")
    p.add_argument(
        "--checkpoint-every", type=int, default=0, metavar="K",
        help="durable periodic checkpointing (tpu_gossip/ckpt/, docs/"
        "checkpointing.md): every K rounds, write a sharded atomic "
        "checkpoint (temp-file + rename per shard, manifest with sha256 "
        "digests landing LAST) into --checkpoint-dir. The horizon runs "
        "as K-round segments OUTSIDE the jitted loop — bit-identical to "
        "the unsegmented run — and `run_sim resume D` continues from the "
        "newest complete checkpoint with the identical final state and "
        "integer-stat trajectory. Needs a fixed --rounds horizon; with "
        "--shard --remat-every R, K must be a multiple of R "
        "(checkpoints land at epoch boundaries, pre-fold)",
    )
    p.add_argument(
        "--checkpoint-dir", type=str, default="", metavar="D",
        help="directory the periodic checkpoints land in (one "
        "ckpt-<round> subdirectory each)",
    )
    p.add_argument(
        "--keep", type=int, default=0, metavar="N",
        help="retention: prune all but the newest N complete checkpoints "
        "after each save (0 = keep every checkpoint)",
    )
    p.add_argument(
        "--checkpoint-shards", type=int, default=0, metavar="S",
        help="file-level shard count per checkpoint (each shard file "
        "carries its row range of every peer plane + that range's CSR "
        "slice). A storage choice, not a run constraint — any S loads "
        "into any compatible run layout, including S'=1 (docs/"
        "checkpointing.md resharding contract). Default: the mesh size "
        "under --shard, else 1",
    )
    p.add_argument(
        "--packed", action="store_true",
        help="carry the swarm as PACKED state planes (core/packed.py, "
        "docs/memory_budget.md): the scan/while carry — what stays "
        "resident between rounds, and what checkpoints write — is the "
        "registry's packed storage ledger (67 B/peer at m=16 vs 142 "
        "unpacked); the round itself computes NATIVELY on the bit "
        "words (sim/packed_engine.py: word OR/AND/ANDN delivery and "
        "dedup, popcount counts, packed wire at ~1/8 the dist bytes), "
        "decoding full width only at licensed stages, and the "
        "trajectory — state AND integer stats — is BIT-IDENTICAL to "
        "the unpacked run (test-pinned across the composed matrix). "
        "Works on every engine path except the remat epoch loops "
        "(which fold the unpacked CSR between segments)",
    )
    p.add_argument(
        "--builder", choices=["local", "dist"], default="local",
        help="matching-graph construction route (--shard --graph "
        "matching only): 'local' builds the sharded layout globally on "
        "one device then places it; 'dist' builds it BORN on the mesh "
        "(dist/builder.py) — per-shard table derivation inside "
        "shard_map, per-shard peak build memory, conformance-tested "
        "bit-identical to the local block-keyed layout truth. The two "
        "routes realize different (both valid) graphs: 'dist' uses the "
        "per-shard-keyed derivation",
    )
    p.add_argument(
        "--digest", action="store_true",
        help="add state_digest (sha256 over the final state) to the "
        "summary, and on a fixed horizon stats_digest (over the integer "
        "stat trajectory) — the fields the recovery-smoke CI compares "
        "between a SIGKILLed-then-resumed run and an uninterrupted one. "
        "Implied by --checkpoint-every and by resume",
    )
    p.add_argument(
        "--profile", type=str, default="",
        help="record a jax.profiler device trace of the run into this directory "
        "(view with TensorBoard/xprof; SURVEY.md §5.1)",
    )
    return p


def main(argv: list[str] | None = None) -> int:
    from tpu_gossip.utils.compile_cache import use_compile_cache

    use_compile_cache()
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv and argv[0] == "fleet":
        # the campaign subpath: run_sim fleet campaign.toml — a batched
        # Monte Carlo certification run (tpu_gossip/fleet/,
        # docs/fleet_campaigns.md) instead of one swarm
        return _main_fleet(argv[1:])
    if argv and argv[0] == "resume":
        # crash recovery (tpu_gossip/ckpt/, docs/checkpointing.md):
        # pick the newest COMPLETE checkpoint under D — rolling back
        # past torn/corrupt ones with a logged reason — rebuild the run
        # from the manifest's recorded config, and continue to the
        # original horizon bit for bit
        return _main_resume(argv[1:])
    if argv and argv[0] == "serve":
        # the live ingestion frontend (tpu_gossip/serve/,
        # docs/serving_frontend.md): accept reference-wire clients on a
        # socket and disseminate their payloads through the device swarm
        return _main_serve(argv[1:])
    args = build_parser().parse_args(argv)
    return _run(args)


def _run(args, resume=None) -> int:
    """The single-swarm run body — parse-validated ``args`` in, exit
    code out. ``resume`` (set only by ``run_sim resume``) carries
    ``(state, stats_prefix, manifest)``: the engine paths swap the
    checkpointed state in after building plans/layouts deterministically
    from the recorded args, and seed their stats with the prefix."""
    import jax

    from tpu_gossip.core import topology
    from tpu_gossip.core.state import SwarmConfig, init_swarm, save_swarm
    from tpu_gossip.sim import metrics as M
    from tpu_gossip.sim.engine import simulate

    cluster_err = _validate_cluster(args)
    if cluster_err:
        print(cluster_err, file=sys.stderr)
        return 2
    if args.coordinator:
        # join the jax.distributed cluster BEFORE anything touches the
        # backend — the first jax.devices() call settles it
        from tpu_gossip.cluster.launch import init_distributed

        init_distributed(args.coordinator, args.num_processes,
                         args.process_id)
    if args.hosts > 1 and len(jax.devices()) % args.hosts:
        print(f"--hosts {args.hosts} does not divide the device count "
              f"{len(jax.devices())} (the cluster mesh folds the flat "
              "device order row-major into (hosts, devices))",
              file=sys.stderr)
        return 2

    rng = np.random.default_rng(args.seed)
    spec = None
    if args.scenario:
        from tpu_gossip.faults import ScenarioError, parse_scenario

        try:
            spec = parse_scenario(args.scenario)
            # reject impossible schedules BEFORE building anything: phases
            # naming rounds the run can never reach, overlapping phases,
            # bad node sets — a config error, not a silent mid-run no-op
            spec.validate(
                total_rounds=args.rounds if args.rounds > 0 else args.max_rounds,
                n_peers=args.peers,
                n_shards=len(jax.devices()) if args.shard else None,
            )
        except (ScenarioError, OSError) as e:
            # OSError: a typo'd path is as much a config error as a bad
            # schedule — same clean rejection, no traceback
            print(f"--scenario: {e}", file=sys.stderr)
            if args.grow and "outside" in str(e):
                # satellite of the growth plane: node sets bind to the
                # INITIAL membership — grown peers have no stable
                # scenario-addressable id, so declaring one is a config
                # error here, not a shape failure inside jit
                print(
                    "note: with --grow, node-scoped scenario sets are "
                    f"declared over the INITIAL --peers ids [0, {args.peers})"
                    " — grown peers are not scenario-addressable",
                    file=sys.stderr,
                )
            return 2
        if args.shard and args.remat_every > 0 and spec.uses_node_sets:
            print("--scenario with node-scoped faults cannot compose with "
                  "--shard --remat-every: the epoch re-partition permutes "
                  "peers, so compiled node masks would hit the wrong rows "
                  "after the first rebuild (scalar loss/delay/full-swarm "
                  "churn phases are fine)", file=sys.stderr)
            return 2
    grow_err = _validate_grow(args, spec)
    if grow_err:
        print(grow_err, file=sys.stderr)
        return 2
    stream_err = _validate_stream(args)
    if stream_err:
        print(stream_err, file=sys.stderr)
        return 2
    control_err = _validate_control(args)
    if control_err:
        print(control_err, file=sys.stderr)
        return 2
    liveness_err = _validate_liveness(args, spec)
    if liveness_err:
        print(liveness_err, file=sys.stderr)
        return 2
    ckpt_err = _validate_ckpt(args)
    if ckpt_err:
        print(ckpt_err, file=sys.stderr)
        return 2
    if args.packed and args.remat_every > 0:
        print("--packed cannot compose with --remat-every: the epoch "
              "fold (rematerialize_rewired / re-partition) rebuilds the "
              "unpacked CSR between segments; run the remat loop "
              "unpacked", file=sys.stderr)
        return 2
    if args.builder == "dist" and not (args.shard
                                       and args.graph == "matching"):
        print("--builder dist builds the matching layout born on the "
              "mesh (dist/builder.py); it needs --shard --graph matching",
              file=sys.stderr)
        return 2
    if args.builder == "dist" and args.remat_every > 0:
        print("--builder dist cannot compose with --remat-every: the "
              "remat path falls back to the bucketed-CSR engine, which "
              "rebuilds from a host partition", file=sys.stderr)
        return 2
    if args.pipeline is not None and not args.shard:
        print("--pipeline overlaps the SHARDED exchange with the "
              "shard-local tail (sim/stages.py); add --shard (the local "
              "engine has no collective to overlap)", file=sys.stderr)
        return 2
    if args.transport != "dense" and not args.shard:
        # parse-time rejection, like --scenario path errors: the transport
        # compacts the SHARDED exchanges — a local run has no collective
        # to compact, and silently ignoring the flag would fake the A/B
        print(f"--transport {args.transport} compacts the sharded "
              "exchanges (dist/transport.py); add --shard (the local "
              "engine moves no ICI bytes)", file=sys.stderr)
        return 2
    if args.tail != "fused" and args.shard:
        # the dist engines run advance_round's default tail; a summary that
        # silently measured the wrong tail would be worse than an error
        print(f"--tail {args.tail} selects the LOCAL engine's tail "
              "implementation; the sharded engines always run the fused "
              "tail (bit-identical, but not the A/B you asked for)",
              file=sys.stderr)
        return 2
    mplan = exists = None
    if args.graph == "matching":
        if args.shard:
            return _main_shard_matching(
                args, rng, spec, resume=resume,
                local=getattr(args, "_resume_local", False),
            )
        if args.remat_every > 0:
            print("--graph matching cannot re-materialize locally (its "
                  "pairing IS the delivery plan — a folded CSR has no "
                  "pipeline); use --shard, whose remat path falls back to "
                  "the bucketed-CSR engine on the exported CSR",
                  file=sys.stderr)
            return 2
        if args.grow:
            # the sharded-layout builder at 1 shard: its growth_rows are
            # reserved, class-gap capacity rows the pairing pipeline never
            # touches — the ONE matching growth layout, local and mesh
            from tpu_gossip.core.matching_topology import (
                matching_powerlaw_graph_sharded,
            )

            dgraph, mplan = matching_powerlaw_graph_sharded(
                args.peers, 1, gamma=args.gamma,
                fanout=None if args.mode == "flood" else args.fanout,
                key=jax.random.key(args.seed),
                growth_rows=args.grow_capacity - args.peers,
            )
        else:
            from tpu_gossip.core.matching_topology import (
                matching_powerlaw_graph,
            )

            dgraph, mplan = matching_powerlaw_graph(
                args.peers, gamma=args.gamma,
                fanout=None if args.mode == "flood" else args.fanout,
                key=jax.random.key(args.seed),
            )
        graph, exists = dgraph.as_padded_graph(), dgraph.exists
    elif args.graph == "pa":
        edges = topology.preferential_attachment(args.peers, m=args.m, rng=rng)
        graph = topology.build_csr(args.peers, edges)
    else:
        deg = topology.powerlaw_degree_sequence(args.peers, gamma=args.gamma, rng=rng)
        edges = topology.configuration_model(deg, rng=rng)
        graph = topology.build_csr(args.peers, edges)

    if args.shard:
        return _main_shard(args, graph, rng, spec, resume=resume)

    if args.grow and args.graph != "matching":
        from tpu_gossip.growth import pad_graph_for_growth

        graph, exists = pad_graph_for_growth(graph, args.grow_capacity)

    cfg = SwarmConfig(
        n_peers=graph.n,
        msg_slots=args.slots,
        fanout=args.fanout,
        mode=args.mode,
        forward_once=args.forward_once,
        sir_recover_rounds=args.sir_recover,
        churn_leave_prob=args.churn_leave,
        churn_join_prob=args.churn_join,
        rewire_slots=_rewire_slots(args),
        rewire_compact_cap=args.rewire_compact_cap,
    )
    plan = mplan
    if mplan is not None and args.staircase:
        print("note: --staircase is ignored with --graph matching (the "
              "matching pipeline IS the delivery plan)", file=sys.stderr)
    if mplan is None and args.staircase and args.remat_every == 0:
        # (with --remat-every the plan is rebuilt per segment instead)
        from tpu_gossip.kernels.pallas_segment import build_staircase_plan

        # block height: the library default (pallas_segment.ROWS), which
        # carries the on-TPU tuning re-sweep — no per-mode override needed
        plan = build_staircase_plan(
            graph.row_ptr, graph.col_idx,
            fanout=None if args.mode == "flood" else args.fanout,
        )

    origins, silent_ids = _sample_ids(args, rng)
    state = init_swarm(
        graph, cfg, key=jax.random.key(args.seed), origins=origins,
        exists=exists,
    )
    if silent_ids is not None:
        state.silent = state.silent.at[silent_ids].set(True)

    from tpu_gossip.utils.profiling import trace

    scen = _compile_cli_scenario(spec, args, n_slots=graph.n)
    grow = _compile_cli_growth(args, spec, n_slots=graph.n, mplan=mplan)
    strm = _compile_cli_stream(
        args,
        np.flatnonzero(np.asarray(exists)) if exists is not None
        else np.arange(graph.n),
    )
    ctl = _compile_cli_control(args)
    lqs = _compile_cli_liveness(args)
    policy = _ckpt_policy(args, shards=1)
    from tpu_gossip.core.packed import pack_state, unpack_state

    with trace(args.profile):
        if args.remat_every > 0:
            summary, fin = _run_with_remat(args, cfg, state, scen, grow,
                                           strm, ctl, lqs, policy=policy,
                                           resume=resume)
            summary.update(_scenario_summary(spec))
        elif args.rounds > 0:
            if policy is None and resume is None:
                st_in = pack_state(state) if args.packed else state
                fin, stats = simulate(st_in, cfg, args.rounds, plan,
                                      args.tail, scen, grow, strm, ctl,
                                      None, lqs)
            else:
                from tpu_gossip.ckpt import host_stats, run_checkpointed

                state, prefix = _swap_in_resume(resume, state, args)
                if args.packed:
                    # the segmented carry — and therefore every periodic
                    # checkpoint — is the packed storage ledger
                    state = pack_state(state)

                def seg_run(st, seg):
                    st, s = simulate(st, cfg, seg, plan, args.tail, scen,
                                     grow, strm, ctl, None, lqs)
                    return st, host_stats(s)

                fin, sd = run_checkpointed(
                    state, args.rounds, seg_run, policy=policy,
                    stats_prefix=prefix, log=_stderr_log,
                )
                stats, _ici = _split_host_stats(sd)
            if args.packed:
                fin = unpack_state(fin)
            if not args.quiet:
                M.write_jsonl(stats, sys.stdout)
            summary = _horizon_summary(args, stats,
                                       **_scenario_summary(spec, stats),
                                       **_stream_summary(args, cfg, stats),
                                       **_control_summary(args, cfg, stats),
                                       **_liveness_summary(args, stats))
            summary.update(_digest_summary(args, fin, stats, policy, resume))
        else:
            if args.packed or not (scen is None and grow is None
                                   and ctl is None and lqs is None):
                from tpu_gossip.sim.engine import run_until_coverage

                def cov_run(st):
                    st_in = pack_state(st) if args.packed else st
                    out = run_until_coverage(
                        st_in, cfg, args.target, args.max_rounds, plan=plan,
                        tail=args.tail, scenario=scen, growth=grow,
                        control=ctl, liveness=lqs,
                    )
                    return unpack_state(out) if args.packed else out

                result, fin = M.bench_swarm(
                    state, cfg, args.target, args.max_rounds, run=cov_run,
                )
            else:
                result, fin = M.bench_swarm(
                    state, cfg, args.target, args.max_rounds, plan=plan,
                    tail=args.tail,
                )
            summary = {"summary": True, "mode": args.mode,
                       **_scenario_summary(spec),
                       **_control_summary(args),
                       **_liveness_summary(args),
                       **json.loads(result.to_json())}
            summary.update(_digest_summary(args, fin, None))
    summary.update(_growth_summary(args, fin))
    summary.update(_layout_summary(args))
    if jax.process_index() == 0:
        print(json.dumps(summary))
        if args.checkpoint:
            save_swarm(args.checkpoint, fin)
    return 0


def _main_fleet(argv: list[str]) -> int:
    """``run_sim fleet campaign.toml``: compile + run a batched Monte
    Carlo certification campaign (tpu_gossip/fleet/) and emit the
    certification summary JSON.

    ``--lane K --solo`` instead runs lane K UNBATCHED through the plain
    ``simulate`` over exactly the plans the batch compiled for it and
    prints its state/stats digests — the cross-process half of the
    bit-identity contract (the fleet-smoke CI job compares these against
    the batched run's ``lane_digests``).
    """
    import time as _time

    import jax

    p = argparse.ArgumentParser(
        prog="run_sim fleet",
        description="Batched Monte Carlo certification campaigns "
        "(docs/fleet_campaigns.md)",
    )
    p.add_argument("campaign", help="campaign TOML (scenarios/campaigns/)")
    p.add_argument(
        "--report", default="", metavar="PATH",
        help="write the FULL certification report JSON here (per-lane "
        "detail included; stdout carries the compact summary)",
    )
    p.add_argument(
        "--lane", type=int, default=-1, metavar="K",
        help="with --solo: the lane to run unbatched",
    )
    p.add_argument(
        "--solo", action="store_true",
        help="run --lane K serially through sim.engine.simulate over the "
        "lane's compiled plans and print its digests (the conformance "
        "oracle; bit-identical to lane K of the batched run)",
    )
    p.add_argument("--quiet", action="store_true",
                   help="omit per-lane digests from the summary row")
    p.add_argument(
        "--checkpoint-every", type=int, default=0, metavar="K",
        help="durable periodic checkpointing of the whole lane stack "
        "(one file per LANE — per-lane recovery is loading one file); "
        "`run_sim resume D` finishes the campaign bit-identically, "
        "`resume D --lane K --solo` recovers one lane unbatched",
    )
    p.add_argument("--checkpoint-dir", type=str, default="", metavar="D")
    p.add_argument("--keep", type=int, default=0, metavar="N",
                   help="retention: keep the newest N checkpoints (0 = all)")
    args = p.parse_args(argv)

    from tpu_gossip import fleet
    from tpu_gossip.faults import ScenarioError

    try:
        spec = fleet.parse_campaign(args.campaign)
        camp = fleet.compile_campaign(spec)
    except (fleet.CampaignError, ScenarioError, OSError) as e:
        # a typo'd path, an unknown sampled axis, or a lane that would
        # change a static shape are all config errors — clean exit 2,
        # the --scenario rejection convention
        print(f"fleet: {e}", file=sys.stderr)
        return 2

    if args.solo:
        if args.lane < 0:
            print("fleet: --solo needs --lane K", file=sys.stderr)
            return 2
        try:
            fin, stats = fleet.run_lane_solo(camp, args.lane)
        except fleet.CampaignError as e:
            print(f"fleet: {e}", file=sys.stderr)
            return 2
        from tpu_gossip.sim import metrics as M

        print(json.dumps({
            "summary": True, "fleet": "solo", "campaign": camp.name,
            "lane": args.lane,
            "state_digest": fleet.state_digest(fin),
            "stats_digest": fleet.stats_digest(stats),
            "reliability": M.reliability_report(
                stats, target_ratio=camp.target_ratio,
                coverage_target=camp.coverage_target,
            ),
        }))
        return 0
    if args.lane >= 0:
        print("fleet: --lane selects the --solo lane; drop it for the "
              "batched run (every lane runs)", file=sys.stderr)
        return 2
    if args.checkpoint_every < 0 or args.keep < 0:
        print("fleet: --checkpoint-every and --keep must be >= 0",
              file=sys.stderr)
        return 2
    if args.checkpoint_every and not args.checkpoint_dir:
        print("fleet: --checkpoint-every needs --checkpoint-dir D",
              file=sys.stderr)
        return 2
    if args.checkpoint_dir and not args.checkpoint_every:
        print("fleet: --checkpoint-dir shapes periodic checkpointing; "
              "add --checkpoint-every K", file=sys.stderr)
        return 2
    if args.checkpoint_every and args.checkpoint_every >= camp.rounds:
        print(f"fleet: --checkpoint-every {args.checkpoint_every} must "
              f"be below the campaign horizon ({camp.rounds} rounds)",
              file=sys.stderr)
        return 2

    policy = _fleet_policy(args, camp, args.campaign,
                           report=args.report, quiet=args.quiet)
    if policy is not None:
        # the durable path: segmented simulate_fleet with per-lane
        # checkpoint files between segments (ckpt/driver.py) — the AOT
        # single-shot below cannot stop to save
        from tpu_gossip.ckpt import host_stats, run_checkpointed

        def seg_run(st, seg):
            st, s = fleet.simulate_fleet(
                st, camp.cfg, seg, camp.scenario, camp.growth,
                camp.stream, camp.control, camp.liveness,
            )
            return st, host_stats(s)

        t0 = _time.perf_counter()
        fin, sd = run_checkpointed(
            camp.states, camp.rounds, seg_run, policy=policy,
            round_axis=1, log=_stderr_log,
        )
        wall = _time.perf_counter() - t0
        camp.states, camp.consumed = fin, True  # the input was donated
        stats = _split_host_stats(sd)[0]
        return _emit_fleet_summary(camp, fin, stats, wall,
                                   quiet=args.quiet,
                                   report_path=args.report)

    # AOT-compile the one batched program, then run the horizon ONCE:
    # swarm_rounds_per_sec is the batching headline and a compile inside
    # it would be noise, but a full warm EXECUTION would double every
    # campaign's compute for a timing field — lowering compiles without
    # running, and the compiled executable is invoked directly (the jit
    # call cache is not populated by AOT compilation)
    compiled = fleet.simulate_fleet.lower(
        camp.states, camp.cfg, camp.rounds, camp.scenario, camp.growth,
        camp.stream, camp.control, camp.liveness,
    ).compile()
    t0 = _time.perf_counter()
    # the donating path: the CLI never touches camp.states again (lane
    # digests read the returned final states; --solo is its own process)
    fin, stats = compiled(
        camp.states, camp.scenario, camp.growth, camp.stream, camp.control
    )
    float(fin.round[0])  # fetch = completion barrier
    wall = _time.perf_counter() - t0
    camp.states, camp.consumed = fin, True  # the input was donated
    return _emit_fleet_summary(camp, fin, stats, wall, quiet=args.quiet,
                               report_path=args.report)


def _fleet_policy(a, camp, campaign_path, *, report="", quiet=False):
    """The fleet run's :class:`~tpu_gossip.ckpt.CheckpointPolicy` (one
    checkpoint file per lane), or None."""
    if not getattr(a, "checkpoint_every", 0):
        return None
    from tpu_gossip.ckpt import CheckpointPolicy

    return CheckpointPolicy(
        every=a.checkpoint_every,
        directory=a.checkpoint_dir,
        keep=a.keep,
        shards=camp.k,
        kind="fleet",
        run_config={
            "campaign": campaign_path, "report": report,
            "quiet": bool(quiet),
            "checkpoint_every": a.checkpoint_every,
            "checkpoint_dir": a.checkpoint_dir, "keep": a.keep,
        },
    )


def _emit_fleet_summary(camp, fin, stats, wall, *, quiet, report_path,
                        rounds_timed: int | None = None) -> int:
    """The campaign's certification summary + optional full report —
    one emitter for the AOT, checkpointed, and resumed paths, so a
    resumed campaign prints the identical schema (and identical lane
    digests) the uninterrupted one would. ``rounds_timed`` is how many
    rounds ``wall`` actually covers (a RESUMED run timed only the
    post-crash remainder — the throughput figure must not claim the
    whole horizon for it)."""
    import jax

    from tpu_gossip import fleet

    report = fleet.campaign_report(camp, stats)
    timed = camp.rounds if rounds_timed is None else rounds_timed
    summary = {
        "summary": True, "fleet": True, "campaign": camp.name,
        "lanes": camp.k, "rounds": camp.rounds,
        "n_peers": int(camp.base.get("peers", 0)),
        "wall_seconds": round(wall, 3),
        "swarm_rounds_per_sec": round(
            camp.k * timed / max(wall, 1e-9), 2
        ),
        "families": [
            {k: f.get(k) for k in (
                "family", "lanes", "lanes_judged", "reliability",
                "frontier",
            ) if f.get(k) is not None}
            for f in report["families"]
        ],
    }
    if not quiet:
        summary["lane_digests"] = {
            str(k): fleet.state_digest(jax.tree.map(lambda x: x[k], fin))
            for k in range(camp.k)
        }
        summary["stats_digests"] = {
            str(k): fleet.stats_digest(stats, k) for k in range(camp.k)
        }
    print(json.dumps(summary))
    if report_path:
        with open(report_path, "w") as f:
            json.dump(report, f, indent=1)
            f.write("\n")
    return 0


def _main_resume(argv: list[str]) -> int:
    """``run_sim resume D``: crash recovery from the newest COMPLETE
    checkpoint under ``D`` (tpu_gossip/ckpt/, docs/checkpointing.md).

    Torn/corrupt checkpoints — no manifest, missing or truncated shard,
    digest mismatch — are rolled back past with a logged reason. The
    run config recorded in the manifest rebuilds the exact layout
    (graphs and plans are deterministic in the seed), the checkpointed
    state drops in, and the horizon finishes: final state and
    integer-stat trajectory are bit-identical to the uninterrupted run
    (the summary carries state/stats digests to prove it). Resumed runs
    keep checkpointing into the same directory, so repeated crashes
    compose.
    """
    p = argparse.ArgumentParser(
        prog="run_sim resume",
        description="Resume a checkpointed run bit-exactly "
        "(docs/checkpointing.md)",
    )
    p.add_argument("directory", help="the run's --checkpoint-dir")
    p.add_argument("--quiet", action="store_true",
                   help="summary line only (overrides the recorded flag)")
    p.add_argument(
        "--local", action="store_true",
        help="restore a --shard --graph matching checkpoint into the "
        "LOCAL engine (S'=1): the recorded S-shard layout is rebuilt, "
        "the state drops in globally, and the horizon finishes without "
        "a mesh — bit-identical to finishing on the mesh (the s=1 "
        "layout-truth contract in reverse)",
    )
    p.add_argument("--hosts", type=int, default=-1, metavar="H",
                   help="override the recorded --hosts: resume onto a "
                   "different (hosts, devices) fold of the SAME device "
                   "count, or 1 for the flat mesh. The fold is row-major "
                   "— layout and trajectory stay bit-identical across "
                   "host counts (docs/multihost_mesh.md), the "
                   "resharding contract's cross-host leg")
    p.add_argument("--lane", type=int, default=-1, metavar="K",
                   help="fleet checkpoints: resume lane K solo (with "
                   "--solo) instead of the whole stack")
    p.add_argument("--solo", action="store_true",
                   help="with --lane K on a fleet checkpoint: finish lane "
                   "K unbatched through the plain simulate and print its "
                   "digests (the per-lane recovery oracle)")
    rargs = p.parse_args(argv)

    from tpu_gossip.ckpt import (
        CheckpointError,
        latest_complete,
        load_checkpoint,
    )

    try:
        path, manifest = latest_complete(rargs.directory, log=_stderr_log)
    except CheckpointError as e:
        print(f"resume: {e}", file=sys.stderr)
        return 2
    run_cfg = manifest.get("run")
    if not run_cfg:
        print("resume: the checkpoint manifest carries no run config "
              "(library-written checkpoint?) — resume rebuilds the run "
              "from the manifest's `run` section", file=sys.stderr)
        return 2
    if manifest.get("kind") == "fleet":
        if rargs.local:
            print("resume: --local restores a sharded-matching RUN "
                  "checkpoint; fleet checkpoints resume batched (or one "
                  "lane via --lane K --solo)", file=sys.stderr)
            return 2
        return _resume_fleet(rargs, path, manifest)
    if rargs.lane >= 0 or rargs.solo:
        print("resume: --lane/--solo select a fleet checkpoint's lane; "
              "this is a single-run checkpoint", file=sys.stderr)
        return 2

    base = vars(build_parser().parse_args([]))
    # layout facts the policy records beside the args (checked by the
    # engine paths, not parser flags) + the validators' settled extras
    known_extra = {"devices", "control_lo", "control_hi"}
    stale = sorted(set(run_cfg) - set(base) - known_extra)
    args = argparse.Namespace(**{**base, **run_cfg})
    if stale:
        # recorded-but-unknown keys ride along harmlessly (a removed
        # flag); note them so a format drift is visible
        print(f"resume: manifest records unknown args {stale} (ignored "
              "beyond layout checks)", file=sys.stderr)
    args.quiet = bool(rargs.quiet or args.quiet)
    if rargs.hosts >= 1:
        if not run_cfg.get("shard"):
            print("resume: --hosts re-folds a SHARDED checkpoint's mesh; "
                  "this run was local", file=sys.stderr)
            return 2
        args.hosts = rargs.hosts
        if args.hosts == 1 and args.transport == "hier":
            print("resume: the recorded --transport hier needs a host "
                  "axis; continuing on the flat mesh with --transport "
                  "sparse (trajectory unchanged — the transport reorders "
                  "bytes, never draws)", file=sys.stderr)
            args.transport = "sparse"
    if rargs.local:
        if not (run_cfg.get("shard") and run_cfg.get("graph") == "matching"
                and not run_cfg.get("remat_every")):
            print("resume: --local restores a --shard --graph matching "
                  "checkpoint (no --remat-every) into the local engine",
                  file=sys.stderr)
            return 2
        args._resume_local = True
    print(f"resume: {path.name} at round {manifest['round']} of "
          f"{args.rounds} ({manifest.get('kind', 'run')})",
          file=sys.stderr)
    try:
        state, prefix, _ = load_checkpoint(path, manifest=manifest)
        return _run(args, resume=(state, prefix, manifest))
    except (CheckpointError, ValueError) as e:
        print(f"resume: {e}", file=sys.stderr)
        return 2


def _resume_fleet(rargs, path, manifest) -> int:
    """Fleet crash recovery: rebuild the campaign from the recorded TOML,
    drop the checkpointed lane stack (or one lane, ``--lane K --solo``)
    in, finish the horizon, and emit the same certification summary the
    uninterrupted run would have — lane digests bit-identical."""
    from tpu_gossip import fleet
    from tpu_gossip.ckpt import (
        CheckpointError,
        host_stats,
        load_checkpoint,
        run_checkpointed,
    )
    from tpu_gossip.faults import ScenarioError

    run_cfg = manifest["run"]
    try:
        spec = fleet.parse_campaign(run_cfg["campaign"])
        camp = fleet.compile_campaign(spec)
    except (fleet.CampaignError, ScenarioError, OSError, KeyError) as e:
        print(f"resume: cannot rebuild campaign "
              f"{run_cfg.get('campaign')!r}: {e}", file=sys.stderr)
        return 2

    if rargs.solo or rargs.lane >= 0:
        if not (rargs.solo and rargs.lane >= 0):
            print("resume: per-lane recovery needs BOTH --lane K and "
                  "--solo", file=sys.stderr)
            return 2
        try:
            st, _prefix, _ = load_checkpoint(path, lane=rargs.lane,
                                             manifest=manifest)
        except CheckpointError as e:
            print(f"resume: {e}", file=sys.stderr)
            return 2
        from tpu_gossip.sim import metrics as M
        from tpu_gossip.sim.engine import simulate

        _st0, sc, gr, sp, cp = camp.lane(rargs.lane)
        remaining = camp.rounds - int(np.asarray(st.round))
        fin, _stats = simulate(st, camp.cfg, remaining, None, "fused",
                               sc, gr, sp, cp, None, camp.liveness)
        print(json.dumps({
            "summary": True, "fleet": "solo-resume",
            "campaign": camp.name, "lane": rargs.lane,
            "state_digest": fleet.state_digest(fin),
        }))
        return 0

    try:
        state, prefix, _ = load_checkpoint(path, manifest=manifest)
    except CheckpointError as e:
        print(f"resume: {e}", file=sys.stderr)
        return 2
    start_round = int(np.asarray(state.round).reshape(-1)[0])
    if start_round >= camp.rounds:
        print("resume: checkpoint round is past the campaign horizon — "
              "nothing to resume", file=sys.stderr)
        return 2
    policy = _fleet_policy(
        argparse.Namespace(
            checkpoint_every=run_cfg.get("checkpoint_every", 0),
            checkpoint_dir=run_cfg.get("checkpoint_dir", ""),
            keep=run_cfg.get("keep", 0),
        ),
        camp, run_cfg.get("campaign", ""),
        report=run_cfg.get("report", ""), quiet=run_cfg.get("quiet", False),
    )

    def seg_run(st, seg):
        st, s = fleet.simulate_fleet(
            st, camp.cfg, seg, camp.scenario, camp.growth, camp.stream,
            camp.control, camp.liveness,
        )
        return st, host_stats(s)

    import time as _time

    t0 = _time.perf_counter()
    fin, sd = run_checkpointed(
        state, camp.rounds, seg_run, policy=policy, stats_prefix=prefix,
        round_axis=1, log=_stderr_log,
    )
    wall = _time.perf_counter() - t0
    camp.states, camp.consumed = fin, True
    stats = _split_host_stats(sd)[0]
    quiet = bool(rargs.quiet or run_cfg.get("quiet"))
    return _emit_fleet_summary(
        camp, fin, stats, wall, quiet=quiet,
        report_path=run_cfg.get("report", ""),
        rounds_timed=camp.rounds - start_round,
    )


def _validate_grow(args, spec):
    """Normalize + reject impossible --grow configs; returns an error
    string (exit 2) or None. Mutates args: fills the rate/capacity
    defaults so every engine path reads one settled config."""
    if not args.grow:
        if spec is not None and spec.uses_join_burst:
            return ("--scenario: join_burst phases are admission waves for "
                    "a growing run; add --grow")
        return None
    total_rounds = args.rounds if args.rounds > 0 else args.max_rounds
    if args.grow <= args.peers:
        return (f"--grow {args.grow} must exceed --peers {args.peers} "
                "(the target is the grown swarm size)")
    if args.grow_capacity == 0:
        args.grow_capacity = args.grow
    if args.grow_capacity < args.grow:
        return (f"--grow-capacity {args.grow_capacity} below the growth "
                f"target {args.grow}")
    if args.grow_rate < 0:
        return "--grow-rate must be >= 0"
    if args.grow_rate == 0:
        # default pace: reach the target in about half the horizon, so
        # the grown swarm still gossips at full size for a while
        args.grow_rate = max(
            1, -(-(args.grow - args.peers) // max(total_rounds // 2, 1))
        )
    if args.m >= args.peers:
        return (f"--m {args.m} fresh edges per joiner needs at least that "
                f"many initial peers (--peers {args.peers})")
    if args.shard and args.remat_every > 0:
        return ("--grow cannot compose with --shard --remat-every: the "
                "epoch re-partition permutes peers, so the compiled "
                "admission schedule would admit the wrong rows after the "
                "first rebuild (local --remat-every composes fine)")
    return None


def _validate_stream(args):
    """Normalize + reject impossible --stream configs; returns an error
    string (exit 2) or None. Mutates args: fills the TTL default so
    every engine path reads one settled config — the streaming twin of
    :func:`_validate_grow`."""
    if args.stream == 0:
        set_flags = [
            name for name, dflt in (
                ("--slot-ttl", args.slot_ttl == 0),
                ("--stream-origins", args.stream_origins == "uniform"),
                ("--stream-hashes", args.stream_hashes == 1),
                ("--stream-burst-every", args.stream_burst_every == 0),
            ) if not dflt
        ]
        if set_flags:
            return (f"{set_flags[0]} shapes the streaming workload; add "
                    "--stream RATE")
        return None
    from tpu_gossip.traffic import min_feasible_ttl

    if args.stream < 0:
        return f"--stream {args.stream} must be a non-negative arrival rate"
    if args.rounds <= 0:
        return ("--stream measures a steady state over a fixed horizon — "
                "run-to-coverage stops on slot 0, which the age-out "
                "recycles; pass --rounds R (R >> --slot-ttl)")
    if args.shard and args.remat_every > 0:
        return ("--stream cannot compose with --shard --remat-every: the "
                "epoch re-partition permutes peers, so the compiled "
                "origin tables would inject at the wrong rows after the "
                "first rebuild (local --remat-every composes fine)")
    if not (1 <= args.stream_hashes <= args.slots):
        return (f"--stream-hashes {args.stream_hashes} outside "
                f"[1, --slots {args.slots}] — the Bloom planes live in "
                "the slot dimension")
    if args.stream_burst_every < 0 or args.stream_burst_mult <= 0:
        return "--stream-burst-every must be >= 0 and --stream-burst-mult > 0"
    if not (0 < args.stream_hot_frac <= 1) or not (
        0 <= args.stream_hot_weight <= 1
    ):
        return ("--stream-hot-frac must lie in (0, 1] and "
                "--stream-hot-weight in [0, 1]")
    feasible = min_feasible_ttl(args.peers, args.fanout, args.mode)
    if args.slot_ttl == 0:
        args.slot_ttl = 3 * feasible
    if args.slot_ttl < feasible:
        return (f"--slot-ttl {args.slot_ttl} is below the feasible "
                f"coverage horizon (~{feasible} rounds for {args.peers} "
                f"peers at fanout {args.fanout}): every message would be "
                "recycled before it could possibly cover — raise the TTL "
                "or the fanout")
    return None


def _validate_control(args):
    """Normalize + reject impossible --control configs; returns an error
    string (exit 2) or None. Mutates args: settles the bound defaults
    (args.control_lo / args.control_hi) so every engine path reads one
    config — the control twin of :func:`_validate_grow`."""
    if args.control == 0:
        set_flags = [
            name for name, dflt in (
                ("--control-bounds", args.control_bounds == ""),
                ("--refresh-every", args.refresh_every == 0),
            ) if not dflt
        ]
        if set_flags:
            return (f"{set_flags[0]} shapes the adaptive-control policy; "
                    "add --control TARGET_RATIO")
        return None
    if not (0.0 < args.control <= 1.0):
        return (f"--control {args.control} must be a delivery-ratio target "
                "in (0, 1]")
    if args.mode == "flood":
        # flood pushes every edge and has no pull half; re-wiring (the
        # refresh's substrate) is ignored on every flood path too — a
        # controller here would move its cursor and certify a contract
        # while modulating nothing
        return ("--control modulates the sampled fanout and the "
                "anti-entropy mix; flood delivery has neither — use "
                "--mode push or push_pull")
    rewire = _rewire_slots(args)
    if args.control_bounds:
        try:
            lo_s, hi_s = args.control_bounds.split(",")
            lo, hi = int(lo_s), int(hi_s)
        except ValueError:
            return (f"--control-bounds {args.control_bounds!r} must be "
                    "LO,HI (two integers)")
        if lo < 1:
            return f"--control-bounds lower bound {lo} must be >= 1"
        if hi < lo:
            return f"--control-bounds {lo},{hi} has LO > HI"
        if not (lo <= args.fanout <= hi):
            return (f"--control-bounds [{lo}, {hi}] must contain --fanout "
                    f"{args.fanout} — the policy must be able to express "
                    "the static rate")
        if rewire > 0 and hi > rewire:
            return (f"--control-bounds upper bound {hi} exceeds the "
                    f"re-wiring width --rewire-slots {rewire}: a widened "
                    "rejoiner would redraw its few fresh edges past their "
                    "useful multiplicity; raise --rewire-slots or lower HI")
    else:
        lo, hi = 1, max(2 * args.fanout, args.fanout)
        if rewire > 0:
            hi = max(args.fanout, min(hi, rewire))
        if rewire > 0 and hi > rewire:
            return (f"the default control bounds need HI >= --fanout "
                    f"{args.fanout}, but --rewire-slots is {rewire}; "
                    "raise --rewire-slots or pass --control-bounds")
    args.control_lo, args.control_hi = lo, hi
    if args.refresh_every < 0:
        return "--refresh-every must be >= 0"
    if args.refresh_every > 0 and rewire == 0:
        return ("--refresh-every rides the re-wiring plane "
                "(rewire_targets) — only re-wired peers carry swappable "
                "fresh edges; add --rewire-slots (with churn) or --grow")
    return None


def _validate_liveness(args, spec):
    """Normalize + reject impossible --quorum-k configs; returns an error
    string (exit 2) or None. Mutates args: fills the window/budget
    defaults so every engine path reads one settled config — the
    hardened-detector twin of :func:`_validate_grow`."""
    from tpu_gossip.core.state import SwarmConfig
    from tpu_gossip.kernels.liveness import (
        SUSPECT_STRIKE_CAP, SUSPECT_VOTE_CAP,
    )

    sweep = SwarmConfig.__dataclass_fields__["detect_period_rounds"].default
    if args.quorum_k is None:
        set_flags = [
            name for name, dflt in (
                ("--suspicion-window", args.suspicion_window is None),
                ("--accusation-budget", args.accusation_budget is None),
            ) if not dflt
        ]
        if set_flags:
            return (f"{set_flags[0]} shapes the quorum failure detector; "
                    "add --quorum-k K")
        if spec is not None and spec.uses_adversaries:
            return ("--scenario: Byzantine adversary phases (accusers/"
                    "forgers/floods) need the quorum-defense planes; add "
                    "--quorum-k K (K=1 reproduces the reference's "
                    "single-report purge — the unhardened baseline)")
        return None
    if args.quorum_k < 1:
        return (f"--quorum-k {args.quorum_k} must be >= 1 — at least one "
                "witness must confirm a suspicion (K=1 is the reference's "
                "single-report behavior)")
    if args.quorum_k > SUSPECT_VOTE_CAP:
        return (f"--quorum-k {args.quorum_k} exceeds the packed vote "
                f"counter's cap ({SUSPECT_VOTE_CAP})")
    if args.suspicion_window is None:
        args.suspicion_window = 2 * sweep
    if args.suspicion_window < sweep:
        return (f"--suspicion-window {args.suspicion_window} is shorter "
                f"than the detector sweep period ({sweep} rounds — the "
                "PING grace): a suspicion would expire before its probe "
                "could refute it")
    if args.accusation_budget is None:
        args.accusation_budget = 3
    if not 0 <= args.accusation_budget <= SUSPECT_STRIKE_CAP:
        return (f"--accusation-budget {args.accusation_budget} outside "
                f"[0, {SUSPECT_STRIKE_CAP}] (the packed strike counter's "
                "range; 0 disables quarantine)")
    return None


def _compile_cli_liveness(args):
    """Compile the --quorum-k detector spec — jit-static, so ONE spec
    serves every engine path (and every fleet lane)."""
    if args.quorum_k is None:
        return None
    from tpu_gossip.kernels.liveness import compile_quorum

    return compile_quorum(
        quorum_k=args.quorum_k,
        window=args.suspicion_window,
        budget=args.accusation_budget,
    )


def _liveness_summary(args, stats=None) -> dict:
    """Summary-row hardened-detector fields: the quorum config plus,
    when per-round stats exist, the eviction/quarantine report
    (sim.metrics.liveness_report)."""
    if args.quorum_k is None:
        return {}
    out = {"liveness": {
        "quorum_k": args.quorum_k,
        "suspicion_window": args.suspicion_window,
        "accusation_budget": args.accusation_budget,
    }}
    if stats is not None:
        from tpu_gossip.sim import metrics as M

        out["liveness"].update(M.liveness_report(stats))
    return out


def _validate_cluster(args):
    """Reject impossible --hosts/--coordinator configs; returns an error
    string (exit 2) or None — the multi-host twin of
    :func:`_validate_ckpt`. (The device-count divisibility check lives
    at the call site: it needs the backend, which must not be touched
    before ``jax.distributed`` initializes.)"""
    if args.hosts < 1:
        return f"--hosts {args.hosts} must be >= 1"
    if args.hosts > 1 and not args.shard:
        return ("--hosts folds the SHARDED device mesh into a 2-D "
                "(hosts, devices) cluster mesh; add --shard (the local "
                "engine has no mesh to fold)")
    if args.hosts > 1 and args.remat_every > 0:
        return ("--hosts cannot compose with --remat-every: the epoch "
                "re-partition rebuilds bucket tables for the flat shard "
                "order only — run the remat loop on the flat mesh")
    if args.transport == "hier" and args.hosts <= 1:
        return ("--transport hier is the two-level ICI/DCN transport "
                "(dense inside each host slice, compacted across the "
                "host axis); it needs a (hosts, devices) mesh — add "
                "--hosts H > 1")
    if args.coordinator:
        if args.num_processes < 2 or \
                not (0 <= args.process_id < args.num_processes):
            return ("--coordinator needs --num-processes P >= 2 and "
                    "--process-id in [0, P) — one rank per process "
                    "(cluster/launch.py spawns them)")
        if args.hosts != args.num_processes:
            return (f"--hosts {args.hosts} must equal --num-processes "
                    f"{args.num_processes}: the mesh's host axis is one "
                    "row per process")
        if args.rounds <= 0:
            return ("multi-process runs need a fixed --rounds horizon "
                    "(the coverage loop fetches per-process)")
        if args.checkpoint_every > 0 or args.checkpoint:
            return ("checkpointing is single-process for now: the ckpt "
                    "store writes addressable shard files; exercise the "
                    "cross-host restart contract through single-process "
                    "2-D runs (tests/sim/test_cluster.py)")
        if args.profile:
            return "--profile records a single process's trace; drop it"
    elif args.num_processes or args.process_id >= 0:
        return "--num-processes/--process-id need --coordinator"
    return None


def _validate_ckpt(args):
    """Normalize + reject impossible checkpointing configs; returns an
    error string (exit 2) or None — the durability twin of
    :func:`_validate_grow`."""
    if args.checkpoint_every < 0:
        return "--checkpoint-every must be >= 0"
    if args.checkpoint_every == 0:
        set_flags = [
            name for name, dflt in (
                ("--checkpoint-dir", args.checkpoint_dir == ""),
                ("--keep", args.keep == 0),
                ("--checkpoint-shards", args.checkpoint_shards == 0),
            ) if not dflt
        ]
        if set_flags:
            return (f"{set_flags[0]} shapes periodic checkpointing; add "
                    "--checkpoint-every K")
        return None
    if not args.checkpoint_dir:
        return ("--checkpoint-every needs --checkpoint-dir D — the "
                "durable directory the ckpt-<round> checkpoints land in")
    if args.rounds <= 0:
        return ("--checkpoint-every segments a FIXED horizon; a "
                "run-to-coverage loop is a single on-device while_loop "
                "with no deterministic segment grid to cut at — pass "
                "--rounds R")
    if args.keep < 0 or args.checkpoint_shards < 0:
        return "--keep and --checkpoint-shards must be >= 0"
    if args.checkpoint_every >= args.rounds:
        return (f"--checkpoint-every {args.checkpoint_every} must be "
                f"below --rounds {args.rounds}, or no checkpoint would "
                "ever land inside the horizon")
    if args.shard and args.remat_every > 0 \
            and args.checkpoint_every % args.remat_every != 0:
        return ("--checkpoint-every must be a MULTIPLE of --remat-every "
                "under --shard: mid-epoch mesh state cannot be re-placed "
                "without that epoch's partition tables, so checkpoints "
                "land at epoch boundaries (pre-fold) and resume replays "
                "the fold + re-partition deterministically "
                "(docs/checkpointing.md)")
    return None


def _ckpt_policy(args, shards: int, kind: str = "run", extra: dict | None = None):
    """The settled :class:`~tpu_gossip.ckpt.CheckpointPolicy` for this
    run, or None. ``shards`` is the engine path's natural file-shard
    default (mesh size on the mesh, 1 locally); ``extra`` adds
    layout facts (device count) the resume path must re-check."""
    if args.checkpoint_every <= 0:
        return None
    from tpu_gossip.ckpt import CheckpointPolicy

    run_cfg = _manifest_run_config(args)
    if extra:
        run_cfg.update(extra)
    return CheckpointPolicy(
        every=args.checkpoint_every,
        directory=args.checkpoint_dir,
        keep=args.keep,
        shards=args.checkpoint_shards or shards,
        kind=kind,
        run_config=run_cfg,
    )


def _manifest_run_config(args) -> dict:
    """The manifest's ``run`` section: every settled CLI arg (the
    validators' mutations included — grow_rate, slot_ttl, control
    bounds), so ``run_sim resume`` rebuilds the exact run without
    re-deriving anything."""
    return {
        k: v for k, v in vars(args).items()
        if not k.startswith("_")
        and (v is None or isinstance(v, (str, int, float, bool)))
    }


def _layout_summary(args) -> dict:
    """Summary-row layout fields: whether the run carried packed state
    planes (core/packed.py) and which matching builder laid the graph
    out (only meaningful on --shard --graph matching paths)."""
    out = {"packed": bool(getattr(args, "packed", False))}
    if getattr(args, "builder", "local") != "local":
        out["builder"] = args.builder
    return out


def _stderr_log(msg: str) -> None:
    print(msg, file=sys.stderr)


def _split_host_stats(sd: dict):
    """A concatenated driver stats dict back into ``(RoundStats, IciRound
    | None)`` — the transport counters ride the ``ici__`` prefix."""
    from tpu_gossip.sim.engine import RoundStats

    stats = RoundStats(*(sd[f] for f in RoundStats._fields))
    ici = None
    if any(k.startswith("ici__") for k in sd):
        from tpu_gossip.dist.transport import IciRound

        ici = IciRound(*(sd[f"ici__{f}"] for f in IciRound._fields))
    return stats, ici


def _gather_global(tree):
    """Multi-process runs: pull every non-addressable (cross-host
    sharded) array leaf back as its full global value so the summary's
    host-side accounting — digests, coverage, save_swarm — reads the
    whole swarm on every process. Single-process: identity."""
    import jax

    if jax.process_count() == 1:
        return tree
    from jax.experimental import multihost_utils

    def g(x):
        if not (isinstance(x, jax.Array) and not x.is_fully_addressable):
            return x
        if jax.numpy.issubdtype(x.dtype, jax.dtypes.prng_key):
            # key arrays can't cross numpy; gather the raw key data and
            # re-wrap
            data = multihost_utils.process_allgather(
                jax.random.key_data(x), tiled=True
            )
            return jax.random.wrap_key_data(
                jax.numpy.asarray(data), impl=jax.random.key_impl(x)
            )
        return multihost_utils.process_allgather(x, tiled=True)

    return jax.tree_util.tree_map(g, tree)


def _swap_in_resume(resume, state, args):
    """Replace the freshly built initial state with the checkpointed one
    (plans/layouts were rebuilt deterministically from the recorded
    args; the state is the only thing the crash interrupted). Returns
    ``(state, stats_prefix)``; layout mismatches fail with a named
    reason, not a shape error inside jit."""
    if resume is None:
        return state, None
    from tpu_gossip.ckpt import CheckpointError

    loaded, prefix, manifest = resume
    if int(loaded.seen.shape[0]) != int(state.seen.shape[0]) or \
            int(loaded.seen.shape[1]) != int(state.seen.shape[1]):
        raise CheckpointError(
            f"checkpoint state is (N={loaded.seen.shape[0]}, "
            f"M={loaded.seen.shape[1]}) but the rebuilt run layout is "
            f"(N={state.seen.shape[0]}, M={state.seen.shape[1]}) — the "
            "manifest's recorded config no longer reproduces this layout"
        )
    if int(manifest.get("round", 0)) >= args.rounds:
        raise CheckpointError(
            f"checkpoint round {manifest.get('round')} is not inside the "
            f"run's horizon ({args.rounds} rounds) — nothing to resume"
        )
    return loaded, prefix


def _check_resume_devices(resume, mesh_size: int) -> None:
    """A mesh checkpoint re-places onto a mesh of the SAME size (the run
    layout was built for it); a mismatch is a named config error. The
    matching family additionally restores into S'=1 via
    ``run_sim resume D --local`` (the layout-truth contract in
    reverse)."""
    if resume is None:
        return
    from tpu_gossip.ckpt import CheckpointError

    recorded = (resume[2].get("run") or {}).get("devices")
    if recorded is not None and int(recorded) != int(mesh_size):
        raise CheckpointError(
            f"checkpoint was written by a {recorded}-device mesh run but "
            f"this process has {mesh_size} devices — resume on a "
            f"{recorded}-device mesh, or (sharded matching) restore into "
            "the local engine with `run_sim resume D --local`"
        )


def _digest_summary(args, fin, stats, policy=None, resume=None) -> dict:
    """state/stats digests for the summary row — the recovery contract's
    comparison keys (sha256 over every state leaf / every integer stat
    track, the fleet engine's cross-process fingerprints)."""
    if not (args.digest or policy is not None or resume is not None):
        return {}
    from tpu_gossip.fleet.engine import state_digest, stats_digest

    out = {"state_digest": state_digest(fin)}
    if stats is not None:  # a run to coverage keeps no stat trajectory
        out["stats_digest"] = stats_digest(stats)
    return out


def _compile_cli_control(args):
    """Compile the --control policy — layout-blind, so ONE spec serves
    every engine path (and survives epoch re-partitions)."""
    if args.control <= 0:
        return None
    from tpu_gossip.control import compile_control

    return compile_control(
        target_ratio=args.control,
        fanout=args.fanout,
        lo=args.control_lo,
        hi=args.control_hi,
        refresh_every=args.refresh_every,
        ttl=args.slot_ttl if args.stream > 0 else 0,
    )


def _control_summary(args, cfg=None, stats=None) -> dict:
    """Summary-row control fields: the policy config plus, when per-round
    stats exist, the certified reliability contract block
    (sim.metrics.reliability_report)."""
    if args.control <= 0:
        return {}
    out = {"control": {
        "target_ratio": args.control,
        "bounds": [args.control_lo, args.control_hi],
        "refresh_every": args.refresh_every,
    }}
    if stats is not None:
        from tpu_gossip.sim import metrics as M

        out["reliability"] = M.reliability_report(
            stats, target_ratio=args.control, coverage_target=args.target,
            round_seconds=cfg.round_seconds if cfg is not None else 5.0,
        )
    return out


def _compile_cli_stream(args, origin_rows):
    """Compile the --stream workload for one engine's row layout —
    ``origin_rows`` is the id-ordered table of initial-member state rows
    (the same id→row hook the scenario/growth compilers take)."""
    if args.stream <= 0:
        return None
    from tpu_gossip.traffic import compile_stream

    return compile_stream(
        rate=args.stream,
        msg_slots=args.slots,
        ttl=args.slot_ttl,
        origin_rows=origin_rows,
        origins=args.stream_origins,
        k_hashes=args.stream_hashes,
        hot_frac=args.stream_hot_frac,
        hot_weight=args.stream_hot_weight,
        burst_every=args.stream_burst_every,
        burst_mult=args.stream_burst_mult,
    )


def _stream_summary(args, cfg, stats=None) -> dict:
    """Summary-row streaming fields: the workload config plus, when
    per-round stats exist, the steady-state serving report (one TTL of
    warmup dropped so the report reads the loaded window, not the
    ramp)."""
    if args.stream <= 0:
        return {}
    out = {"stream": {
        "rate": args.stream, "origins": args.stream_origins,
        "slot_ttl": args.slot_ttl, "k_hashes": args.stream_hashes,
    }}
    if stats is not None:
        from tpu_gossip.sim import metrics as M

        out["stream"].update(M.steady_state_report(
            stats, target=args.target, round_seconds=cfg.round_seconds,
            warmup_rounds=min(args.slot_ttl, args.rounds // 2),
        ))
    return out


def _rewire_slots(args) -> int:
    """Growth edges ride the re-wiring plane: a growing config needs at
    least --m target slots per row (growth/engine.apply_growth)."""
    return max(args.rewire_slots, args.m) if args.grow else args.rewire_slots


def _compile_cli_growth(args, spec, n_slots, mplan=None, node_map=None):
    """Compile the --grow admission schedule for one engine's layout —
    the growth twin of :func:`_compile_cli_scenario`."""
    if not args.grow:
        return None
    from tpu_gossip.growth import compile_growth, matching_admit_rows

    admit = None
    if mplan is not None:
        admit = matching_admit_rows(mplan, args.grow - args.peers)
    return compile_growth(
        n_initial=args.peers,
        target=args.grow,
        n_slots=n_slots,
        joins_per_round=args.grow_rate,
        attach_m=args.m,
        admit_rows=admit,
        node_map=node_map,
        max_join_burst=spec.max_join_burst if spec is not None else 0,
    )


def _growth_summary(args, fin) -> dict:
    """Final membership + degree-tail fields for a growing run's summary
    (host-side, from the final state — every run shape has one)."""
    if not args.grow:
        return {}
    from tpu_gossip.core.topology import fit_powerlaw_gamma
    from tpu_gossip.growth.engine import realized_degrees

    deg = np.asarray(realized_degrees(
        fin.row_ptr, fin.exists, fin.rewired, fin.rewire_targets,
        fin.degree_credit,
    ))
    live = np.asarray(fin.alive) & ~np.asarray(fin.declared_dead)
    try:
        gamma = round(fit_powerlaw_gamma(deg[live]), 4)
    except ValueError:  # tail too thin (tiny swarms)
        gamma = None
    return {
        "grow_target": args.grow,
        "grow_rate": args.grow_rate,
        "grow_capacity": args.grow_capacity,
        "n_members": int(np.asarray(fin.exists).sum()),
        "degree_gamma": gamma,
    }


def _compile_cli_scenario(
    spec, args, n_slots, node_map=None, shard_ranges=None, n_shards=None
):
    """Compile the parsed --scenario for one engine's slot layout (node
    sets are declared over real peer ids; ``node_map`` carries the
    engine's id→row mapping — the bucketed mesh's load-balance
    permutation, the sharded matching row formula)."""
    if spec is None:
        return None
    from tpu_gossip.faults import compile_scenario

    return compile_scenario(
        spec,
        n_peers=args.peers,
        n_slots=n_slots,
        total_rounds=args.rounds if args.rounds > 0 else args.max_rounds,
        node_map=node_map,
        shard_ranges=shard_ranges,
        n_shards=n_shards,
    )


def _pipeline_summary(args) -> dict:
    """Summary-row pipeline field for a --shard run (absent = serial)."""
    if args.pipeline is None:
        return {}
    return {"pipeline": args.pipeline}


def _compile_cli_pipeline(args):
    if args.pipeline is None:
        return None
    from tpu_gossip.sim.stages import compile_pipeline

    return compile_pipeline(args.pipeline)


def _transport_summary(args, ici=None, rounds=0, graph=None) -> dict:
    """Summary-row transport fields for a --shard run: the configured lane
    plus, when the analytic counter ran, realized occupancy/bytes —
    dense vs shipped vs occupied, bytes/round (dist/transport.IciRound;
    word counters summed in int64 host-side so long runs can't wrap).
    ``graph`` (the ShardedGraph / MatchingPlan) adds ``dense_bool``: the
    retired bool-plane wire's analytic bytes/round — the reference the
    packed-native wire's ~8x reduction is quoted against."""
    if not args.shard:
        return {}
    out = {"transport": args.transport}
    if ici is None:
        return out
    tot = {
        f: int(np.asarray(getattr(ici, f)).astype(np.int64).sum())
        for f in ici._fields
    }
    r = max(rounds, 1)
    out["ici_bytes_per_round"] = {
        "dense": round(4 * tot["dense_words"] / r, 1),
        "shipped": round(4 * tot["shipped_words"] / r, 1),
        "occupied": round(4 * tot["occupied_words"] / r, 1),
        "reduction_vs_dense": round(
            tot["dense_words"] / max(tot["shipped_words"], 1), 3
        ),
    }
    if getattr(args, "hosts", 1) > 1:
        # the per-axis split of the same totals (IciRound's dcn_* columns
        # price the slow host axis; ici = total - dcn is the fast
        # intra-host remainder) — ici_bytes_per_round above stays the
        # TOTAL wire, keys unchanged
        dcn_d, dcn_s = tot["dcn_dense_words"], tot["dcn_shipped_words"]
        ici_d = tot["dense_words"] - dcn_d
        ici_s = tot["shipped_words"] - dcn_s
        out["ici_bytes"] = {
            "dense": round(4 * ici_d / r, 1),
            "shipped": round(4 * ici_s / r, 1),
            "reduction_vs_dense": round(ici_d / max(ici_s, 1), 3),
        }
        out["dcn_bytes"] = {
            "dense": round(4 * dcn_d / r, 1),
            "shipped": round(4 * dcn_s / r, 1),
            "reduction_vs_dense": round(dcn_d / max(dcn_s, 1), 3),
        }
    if graph is not None:
        from tpu_gossip.core.matching_topology import MatchingPlan

        if isinstance(graph, MatchingPlan):
            from tpu_gossip.dist.matching_mesh import dense_wire_words
        else:
            from tpu_gossip.dist.mesh import dense_wire_words
        out["ici_bytes_per_round"]["dense_bool"] = round(4 * dense_wire_words(
            graph, args.slots, args.mode, args.forward_once,
            bool_planes=True,
        ), 1)
    out["sparse_lanes"] = {
        "taken": tot["sparse_lanes"], "gated": tot["total_lanes"],
    }
    return out


def _scenario_summary(spec, stats=None) -> dict:
    """Summary-row fields for an active scenario (+ per-phase report when
    per-round stats exist)."""
    if spec is None:
        return {}
    out = {"scenario": spec.name}
    if stats is not None:
        from tpu_gossip.sim import metrics as M

        out["phases"] = M.phase_report(stats, spec)
    return out


def _run_with_remat(args, cfg, state, scen=None, grow=None, strm=None,
                    ctl=None, lqs=None, policy=None, resume=None):
    """Segmented run: R rounds → fold fresh edges into the CSR → repeat.

    The first re-materialization pads col_idx to the fixed capacity, so the
    timed loop sees TWO segment shapes (the original CSR and the
    capacity-padded one) and two remat input shapes. ALL four compiles are
    warmed outside the timed region on throwaway clones — previously only
    the pre-remat segment was warmed and the first post-remat segment's
    compile landed inside the wall clock, polluting ms_per_round (ADVICE
    leftover / VERDICT r5 item 8). With --staircase, the plan is rebuilt
    from the current CSR per segment (the topology it tiles changed); the
    host plan build is real per-segment work and stays inside."""
    import time as _time

    from tpu_gossip.core.state import clone_state
    from tpu_gossip.sim import metrics as M
    from tpu_gossip.sim.engine import (
        remat_capacity,
        rematerialize_rewired,
        run_until_coverage,
        simulate,
    )

    cap = remat_capacity(state, cfg)
    r = args.remat_every
    total = args.rounds if args.rounds > 0 else args.max_rounds
    remats = 0
    overflow_total = 0
    stats_parts = []

    def seg_plan(st):
        if not args.staircase:
            return None
        from tpu_gossip.kernels.pallas_segment import build_staircase_plan

        return build_staircase_plan(
            np.asarray(st.row_ptr), np.asarray(st.col_idx),
            fanout=None if args.mode == "flood" else args.fanout,
        )

    if policy is not None or resume is not None:
        # the durable path (ckpt/driver.py): cut the horizon at BOTH the
        # remat grid and the checkpoint grid, save between segments,
        # fold at epoch boundaries via the driver's fold hook (a resumed
        # epoch-boundary checkpoint replays its fold first). `cap` above
        # came from the FRESH initial state, exactly what the
        # uninterrupted loop used — so the resumed folds are
        # bit-identical. ms-per-round timing is not a headline here;
        # compiles land in the wall like any cold run.
        from tpu_gossip.ckpt import host_stats, run_checkpointed

        state, prefix = _swap_in_resume(resume, state, args)

        def fold(st):
            nonlocal remats, overflow_total
            st, overflow = rematerialize_rewired(st, cfg, cap)
            remats += 1
            overflow_total += int(overflow)
            return st

        def seg_run(st, seg):
            st, s = simulate(st, cfg, seg, seg_plan(st), args.tail, scen,
                             grow, strm, ctl, None, lqs)
            return st, host_stats(s)

        t0 = _time.perf_counter()
        fin, sd = run_checkpointed(
            state, total, seg_run, policy=policy, stats_prefix=prefix,
            fold_every=r, fold=fold, log=_stderr_log,
        )
        wall = _time.perf_counter() - t0
        stats, _ici = _split_host_stats(sd)
        if not args.quiet:
            M.write_jsonl(stats, sys.stdout)
        summary = _horizon_summary(
            args, stats,
            remat_every=r,
            # folds are a pure function of the round grid — report the
            # whole-horizon count so a resumed summary matches the
            # uninterrupted one (overflow counts this process's folds)
            remats=(total - 1) // r,
            remat_overflow_edges=overflow_total,
            wall_seconds=wall,
            **_stream_summary(args, cfg, stats),
            **_control_summary(args, cfg, stats),
            **_liveness_summary(args, stats),
        )
        summary.update(_digest_summary(args, fin, stats, policy, resume))
        return summary, fin

    def run_segment(st, seg, plan):
        if args.rounds > 0:
            return simulate(st, cfg, seg, plan, args.tail, scen, grow, strm,
                            ctl, None, lqs)
        return run_until_coverage(
            st, cfg, args.target, seg, plan=plan, tail=args.tail,
            scenario=scen, growth=grow, stream=strm, control=ctl,
            liveness=lqs,
        ), None

    # warm EVERY shape the timed loop will see, on throwaway clones:
    # pre-remat segment, the fold at the original CSR shape, the
    # capacity-shaped segment (with its rebuilt plan), the fold at the
    # capacity shape (all later folds), and — when total is not a multiple
    # of remat_every — the TRUNCATED final segment (segment length is a
    # static jit argument, so it is its own compile) — compile-free timed
    # region
    seg0 = min(r, total - int(state.round))
    warm, _ = run_segment(clone_state(state), seg0, seg_plan(state))
    warm, _ = rematerialize_rewired(warm, cfg, cap)
    warm2, _ = run_segment(warm, seg0, seg_plan(warm))
    warm2, _ = rematerialize_rewired(warm2, cfg, cap)
    last_seg = (total - int(state.round)) % r
    if last_seg and total - int(state.round) > r:
        warm2, _ = run_segment(warm2, last_seg, seg_plan(warm2))
    float(warm2.coverage(0))  # fetch = completion barrier
    del warm, warm2

    t0 = _time.perf_counter()
    while int(state.round) < total:
        seg = min(r, total - int(state.round))
        plan = seg_plan(state)
        if args.rounds > 0:
            state, stats = run_segment(state, seg, plan)
            stats_parts.append(stats)
        else:
            state, _ = run_segment(state, seg, plan)
            if float(state.coverage(0)) >= args.target:
                break
        if int(state.round) < total:
            state, overflow = rematerialize_rewired(state, cfg, cap)
            remats += 1
            overflow_total += int(overflow)
    wall = _time.perf_counter() - t0

    extra = {
        "remat_every": r, "remats": remats,
        "remat_overflow_edges": overflow_total,
    }
    if args.rounds > 0:
        stats = type(stats_parts[0])(*(
            np.concatenate([np.asarray(getattr(p, f)) for p in stats_parts])
            for f in stats_parts[0]._fields
        ))
        if not args.quiet:
            M.write_jsonl(stats, sys.stdout)
        summary = _horizon_summary(
            args, stats, **extra, **_stream_summary(args, cfg, stats),
            **_control_summary(args, cfg, stats),
            **_liveness_summary(args, stats),
        )
        summary.update(_digest_summary(args, state, stats))
        return summary, state
    rounds = int(state.round)
    summary = {
        "summary": True, "mode": args.mode, "n_peers": args.peers,
        "rounds": rounds, "target": args.target,
        "wall_seconds": wall,
        "peers_rounds_per_sec": args.peers * rounds / max(wall, 1e-9),
        "coverage": float(state.coverage(0)),
        "ms_per_round": wall / max(rounds, 1) * 1000.0,
        **extra,
        **_liveness_summary(args),
    }
    return summary, state


def _sample_ids(args, rng):
    """Origin peers + silent peers drawn once, identically for both engine
    paths (the sharded path then remaps them through ``position``)."""
    origins = rng.choice(args.peers, size=min(args.origins, args.peers), replace=False)
    silent_ids = None
    if args.silent_frac > 0:
        k = int(args.silent_frac * args.peers)
        silent_ids = rng.choice(args.peers, size=k, replace=False)
    return origins, silent_ids


def _horizon_summary(args, stats, **extra):
    """Fixed-horizon summary row — one schema for local and sharded runs."""
    from tpu_gossip.sim import metrics as M

    return {
        "summary": True,
        "n_peers": args.peers,
        "mode": args.mode,
        "rounds_run": args.rounds,
        "rounds_to_target": M.rounds_to_coverage(stats, args.target),
        "final_coverage": float(np.asarray(stats.coverage)[-1]),
        "total_msgs": int(np.asarray(stats.msgs_sent).sum()),
        **extra,
    }


def _run_shard_with_remat(args, cfg, state, sg, mesh, plans, scen=None,
                          ctl=None, pipe=None, lqs=None, policy=None,
                          resume=None):
    """The mesh epoch loop (SURVEY.md §7.4's full churn lifecycle):

        R churned rounds -> fold fresh edges into the CSR
        (sim.engine.rematerialize_rewired) -> re-partition the LIVE swarm
        onto the mesh (dist.repartition_swarm: fresh bucket tables, state
        remapped through the new load-balance permutation) -> rebuild the
        per-shard staircase plans if --staircase -> continue.

    Between rebuilds every round runs at static-topology cost with a
    bounded rewired set. ms_per_round excludes the first segment's compile
    (warmed below); the per-epoch rebuild cost is reported separately AND
    folded into the amortized figure.
    """
    import time as _time

    import jax

    from tpu_gossip.dist import (
        build_shard_plans, build_transport, repartition_swarm,
        run_until_coverage_dist, shard_swarm, simulate_dist,
    )
    from tpu_gossip.sim import metrics as M
    from tpu_gossip.sim.engine import remat_capacity, rematerialize_rewired

    r = args.remat_every
    total = args.rounds if args.rounds > 0 else args.max_rounds
    remats = 0
    overflow_total = 0
    rebuild_s = 0.0
    stats_parts = []

    def transport_for(sg_now):
        # the compact lane's tables key on the bucket layout, so each
        # epoch re-partition rebuilds them (host-side, like the plans)
        if args.transport == "dense":
            return None
        return build_transport(sg_now, mode=args.transport)

    transport = transport_for(sg)

    if policy is not None or resume is not None:
        # the durable path: checkpoints land at EPOCH boundaries only
        # (parse-enforced: --checkpoint-every is a multiple of
        # --remat-every), holding the PRE-fold state; the fold hook then
        # folds + re-partitions with a seed derived from the fold index
        # (identical to the serial loop's seed sequence), so a resumed
        # run replays the exact partition the uninterrupted run drew.
        from tpu_gossip.ckpt import host_stats, run_checkpointed
        from tpu_gossip.sim import metrics as _M

        nonstate = {"sg": sg, "plans": plans, "transport": transport}
        loaded, prefix = _swap_in_resume(resume, state, args)
        state = shard_swarm(loaded, mesh) if resume is not None else state

        def fold(st):
            k = int(np.asarray(st.round)) // r
            cap = remat_capacity(st, cfg)
            st, _overflow = rematerialize_rewired(st, cfg, cap)
            sg_now, st, _position = repartition_swarm(
                st, mesh.size, seed=args.seed + k
            )
            st = shard_swarm(st, mesh)
            nonstate["sg"] = sg_now
            if nonstate["plans"] is not None:
                nonstate["plans"] = build_shard_plans(sg_now)
            nonstate["transport"] = transport_for(sg_now)
            return st

        def seg_run(st, seg):
            st, s = simulate_dist(
                st, cfg, nonstate["sg"], mesh, seg, nonstate["plans"],
                scen, None, nonstate["transport"], control=ctl,
                pipeline=pipe, liveness=lqs,
            )
            return st, host_stats(s)

        t0 = _time.perf_counter()
        fin, sd = run_checkpointed(
            state, total, seg_run, policy=policy, stats_prefix=prefix,
            fold_every=r, fold=fold, log=_stderr_log,
        )
        wall = _time.perf_counter() - t0
        stats, _ici = _split_host_stats(sd)
        if not args.quiet:
            _M.write_jsonl(stats, sys.stdout)
        summary = _horizon_summary(
            args, stats, devices=mesh.size, remat_every=r,
            remats=(total - 1) // r, wall_seconds=wall,
            **_control_summary(args, cfg, stats),
            **_liveness_summary(args, stats),
        )
        summary.update(_digest_summary(args, fin, stats, policy, resume))
        return summary, fin

    # warm the first segment outside the timed region (same static shapes)
    # on a throwaway clone — the dist engines donate their state
    from tpu_gossip.core.state import clone_state

    seg0 = min(r, total)
    if args.rounds > 0:
        warm = simulate_dist(clone_state(state), cfg, sg, mesh, seg0, plans,
                             scen, None, transport, control=ctl,
                             pipeline=pipe, liveness=lqs)[0]
    else:
        warm = run_until_coverage_dist(
            clone_state(state), cfg, sg, mesh, args.target, seg0,
            shard_plan=plans, scenario=scen, transport=transport,
            control=ctl, pipeline=pipe, liveness=lqs,
        )
    float(warm.coverage(0))
    del warm

    t0 = _time.perf_counter()
    while int(state.round) < total:
        seg = min(r, total - int(state.round))
        if args.rounds > 0:
            state, stats = simulate_dist(state, cfg, sg, mesh, seg, plans,
                                         scen, None, transport, control=ctl,
                                         pipeline=pipe, liveness=lqs)
            stats_parts.append(stats)
        else:
            state = run_until_coverage_dist(
                state, cfg, sg, mesh, args.target, seg, shard_plan=plans,
                scenario=scen, transport=transport, control=ctl,
                pipeline=pipe, liveness=lqs,
            )
            if float(state.coverage(0)) >= args.target:
                break
        if int(state.round) < total:
            tr = _time.perf_counter()
            cap = remat_capacity(state, cfg)
            state, overflow = rematerialize_rewired(state, cfg, cap)
            sg, state, _position = repartition_swarm(
                state, mesh.size, seed=args.seed + remats + 1
            )
            state = shard_swarm(state, mesh)
            if plans is not None:
                plans = build_shard_plans(sg)
            transport = transport_for(sg)
            rebuild_s += _time.perf_counter() - tr
            remats += 1
            overflow_total += int(overflow)
    wall = _time.perf_counter() - t0

    extra = {
        "devices": mesh.size, "remat_every": r, "remats": remats,
        "remat_overflow_edges": overflow_total,
        "epoch_rebuild_seconds_total": round(rebuild_s, 3),
    }
    if args.rounds > 0:
        stats = type(stats_parts[0])(*(
            np.concatenate([np.asarray(getattr(p, f)) for p in stats_parts])
            for f in stats_parts[0]._fields
        ))
        if not args.quiet:
            M.write_jsonl(stats, sys.stdout)
        summary = _horizon_summary(
            args, stats, **extra, **_control_summary(args, cfg, stats),
            **_liveness_summary(args, stats),
        )
        summary.update(_digest_summary(args, state, stats))
        return summary, state
    rounds = int(state.round)
    sim_wall = wall - rebuild_s
    summary = {
        "summary": True, "mode": args.mode, "n_peers": args.peers,
        "rounds": rounds, "target": args.target,
        "wall_seconds": wall,
        "peers_rounds_per_sec": args.peers * rounds / max(wall, 1e-9),
        "coverage": float(state.coverage(0)),
        "ms_per_round": sim_wall / max(rounds, 1) * 1000.0,
        "ms_per_round_amortized": wall / max(rounds, 1) * 1000.0,
        **extra,
    }
    return summary, state


def _main_shard_matching(args, rng, spec=None, resume=None,
                         local=False) -> int:
    """--shard --graph matching: the gather-free pipeline on the mesh.

    The swarm is laid out per shard at build time
    (core.matching_topology.matching_powerlaw_graph_sharded) and the round
    runs expand/shuffle/fold shard-locally with each transpose pass as one
    dense ``all_to_all`` (dist/matching_mesh.py) — bit-identical to the
    local matching round. ``--remat-every`` falls back to the bucketed-CSR
    engine over the exported CSR (``partition_graph``): a re-materialized
    CSR has no pairing pipeline, and the bucket engine owns the epoch
    re-partition lifecycle.

    ``local=True`` (``run_sim resume D --local``) is the resharding
    contract's S'=1 leg: the SAME S-shard layout is rebuilt from the
    manifest's recorded device count, the checkpoint's global state
    drops straight in, and the horizon finishes on the LOCAL engine over
    the un-placed plan — the s=1 layout-truth contract run in reverse,
    bit-identical to finishing on the mesh (tests/sim/test_ckpt.py).
    """
    import jax

    from tpu_gossip.core.state import SwarmConfig, init_swarm, save_swarm
    from tpu_gossip.dist import (
        make_mesh,
        run_until_coverage_dist,
        shard_matching_plan,
        shard_swarm,
        simulate_dist,
    )
    from tpu_gossip.sim import metrics as M
    from tpu_gossip.utils.profiling import trace

    def fallback_to_csr_shard(reason):
        """The ONE bucketed-CSR fallback: classic matching build, exported
        CSR, delegate to the general shard engine."""
        from tpu_gossip.core.matching_topology import matching_powerlaw_graph

        print(f"note: {reason} — falling back to the bucketed-CSR shard "
              "engine on the exported CSR", file=sys.stderr)
        dgraph, _ = matching_powerlaw_graph(
            args.peers, gamma=args.gamma, fanout=None,
            key=jax.random.key(args.seed),
        )
        return _main_shard(args, dgraph.to_host_graph(), rng, spec,
                           resume=resume)

    if args.remat_every > 0:
        return fallback_to_csr_shard(
            "--remat-every re-materializes the CSR, which the matching "
            "pipeline cannot absorb"
        )
    if args.staircase:
        print("note: --staircase is ignored with --graph matching (the "
              "matching pipeline IS the delivery plan)", file=sys.stderr)

    from tpu_gossip.core.matching_topology import (
        matching_powerlaw_graph_sharded,
    )

    if local:
        from tpu_gossip.ckpt import CheckpointError

        run_cfg = (resume[2].get("run") or {}) if resume else {}
        n_build = int(run_cfg.get("devices") or 0)
        if n_build <= 0:
            raise CheckpointError(
                "checkpoint manifest records no device count — cannot "
                "rebuild the sharded matching layout for a local restore"
            )
        mesh = None
        if args.transport != "dense":
            print("note: the recorded --transport compacts MESH "
                  "collectives; the local restore moves no ICI bytes "
                  "(trajectory unchanged — the transport reorders bytes, "
                  "never draws)", file=sys.stderr)
    else:
        if args.hosts > 1:
            from tpu_gossip.cluster import make_cluster_mesh

            mesh = make_cluster_mesh(hosts=args.hosts)
        else:
            mesh = make_mesh()
        if 128 % mesh.size:
            # the transpose all_to_all splits the 128-lane axis; a mesh
            # size that does not divide 128 cannot run the sharded
            # matching layout
            return fallback_to_csr_shard(
                f"mesh size {mesh.size} does not divide 128 (the sharded "
                "matching transpose's lane split)"
            )
        _check_resume_devices(resume, mesh.size)
        n_build = mesh.size
    grow_rows = (
        -(-(args.grow_capacity - args.peers) // n_build)
        if args.grow else 0
    )
    if getattr(args, "builder", "local") == "dist" and not local:
        # born-distributed construction: per-shard blocks derived inside
        # shard_map, per-shard peak build memory, arrays already placed
        # (dist/builder.py; bit-identical to the block-keyed local build)
        from tpu_gossip.dist import matching_powerlaw_graph_dist

        dgraph, plan = matching_powerlaw_graph_dist(
            args.peers, mesh, gamma=args.gamma,
            fanout=None if args.mode == "flood" else args.fanout,
            key=jax.random.key(args.seed),
            growth_rows=grow_rows,
        )
    else:
        dgraph, plan = matching_powerlaw_graph_sharded(
            args.peers, n_build, gamma=args.gamma,
            fanout=None if args.mode == "flood" else args.fanout,
            key=jax.random.key(args.seed),
            growth_rows=grow_rows,
            # a local restore of a --builder dist run rebuilds the SAME
            # layout through the block-keyed derivation (the conformance
            # contract: the two builds are bit-identical)
            block_keys=getattr(args, "builder", "local") == "dist",
        )
    if not local:
        plan = shard_matching_plan(plan, mesh)
    from tpu_gossip.dist import build_transport

    transport = (
        build_transport(plan, mode=args.transport, mesh=mesh,
                        hosts=args.hosts)
        if args.transport != "dense" and not local else None
    )
    cfg = SwarmConfig(
        n_peers=plan.n,  # per-shard blocks incl. born-dead pad rows
        msg_slots=args.slots,
        fanout=args.fanout,
        mode=args.mode,
        forward_once=args.forward_once,
        sir_recover_rounds=args.sir_recover,
        churn_leave_prob=args.churn_leave,
        churn_join_prob=args.churn_join,
        rewire_slots=_rewire_slots(args),
        rewire_compact_cap=args.rewire_compact_cap,
    )
    origins, silent_ids = _sample_ids(args, rng)

    def to_rows(ids):
        """Peer index -> state row (skipping each shard's pad row)."""
        ids = np.asarray(ids)
        return (ids // plan.n_per) * plan.n_blk + (ids % plan.n_per)

    state = init_swarm(
        dgraph.as_padded_graph(), cfg, key=jax.random.key(args.seed),
        origins=to_rows(origins), exists=dgraph.exists,
    )
    if silent_ids is not None:
        state.silent = state.silent.at[to_rows(silent_ids)].set(True)
    if not local:
        state = shard_swarm(state, mesh)

    from tpu_gossip.core.state import shard_ranges

    scen = _compile_cli_scenario(
        spec, args, n_slots=plan.n, node_map=to_rows,
        shard_ranges=shard_ranges(n_build, plan.n_blk, mesh=mesh),
        n_shards=n_build,
    )
    grow = _compile_cli_growth(args, spec, n_slots=plan.n, mplan=plan)
    strm = _compile_cli_stream(args, to_rows(np.arange(args.peers)))
    ctl = _compile_cli_control(args)
    lqs = _compile_cli_liveness(args)
    pipe = _compile_cli_pipeline(args)
    policy = _ckpt_policy(args, shards=n_build, extra={"devices": n_build})
    from tpu_gossip.core.packed import pack_state, unpack_state

    with trace(args.profile):
        if args.rounds > 0:
            if policy is None and resume is None:
                st_in = pack_state(state) if args.packed else state
                if transport is not None:
                    fin, (stats, ici) = simulate_dist(
                        st_in, cfg, plan, mesh, args.rounds, None, scen,
                        grow, transport, True, strm, ctl, pipe, lqs,
                    )
                else:
                    fin, stats = simulate_dist(st_in, cfg, plan, mesh,
                                               args.rounds, None, scen,
                                               grow, stream=strm,
                                               control=ctl, pipeline=pipe,
                                               liveness=lqs)
                    ici = None
                if args.packed:
                    fin = unpack_state(fin)
            else:
                from tpu_gossip.ckpt import host_stats, run_checkpointed
                from tpu_gossip.sim.engine import simulate

                loaded, prefix = _swap_in_resume(resume, state, args)
                if resume is not None:
                    state = loaded if local else shard_swarm(loaded, mesh)
                if local and prefix is not None:
                    # a sparse-transport run's prefix carries ici__*
                    # counters; the local restore ships no ICI bytes, so
                    # the byte accounting ends at the crash (trajectory
                    # stats are unaffected — the transport never draws)
                    prefix = {k: v for k, v in prefix.items()
                              if not k.startswith("ici__")}

                if args.packed:
                    # the segmented carry — and every periodic
                    # checkpoint — is the packed storage ledger
                    state = pack_state(state)

                def seg_run(st, seg):
                    if local:
                        st, s = simulate(st, cfg, seg, plan, "fused", scen,
                                         grow, strm, ctl, pipe, lqs)
                        return st, host_stats(s)
                    if transport is not None:
                        st, (s, seg_ici) = simulate_dist(
                            st, cfg, plan, mesh, seg, None, scen, grow,
                            transport, True, strm, ctl, pipe, lqs,
                        )
                        return st, host_stats(s, seg_ici)
                    st, s = simulate_dist(st, cfg, plan, mesh, seg, None,
                                          scen, grow, stream=strm,
                                          control=ctl, pipeline=pipe,
                                          liveness=lqs)
                    return st, host_stats(s)

                fin, sd = run_checkpointed(
                    state, args.rounds, seg_run, policy=policy,
                    stats_prefix=prefix, log=_stderr_log,
                )
                if args.packed:
                    fin = unpack_state(fin)
                stats, ici = _split_host_stats(sd)
            fin = _gather_global(fin)
            if not args.quiet and jax.process_index() == 0:
                M.write_jsonl(stats, sys.stdout)
            summary = _horizon_summary(
                args, stats, devices=n_build,
                **_scenario_summary(spec, stats),
                **_transport_summary(args, ici, args.rounds, plan),
                **_pipeline_summary(args),
                **_stream_summary(args, cfg, stats),
                **_control_summary(args, cfg, stats),
                **_liveness_summary(args, stats),
            )
            summary.update(_digest_summary(args, fin, stats, policy, resume))
        else:
            # the timed region runs WITHOUT the analytic counter so the
            # sparse-vs-dense ms_per_round A/B measures pure transport;
            # the trajectory comes from an untimed bit-identical replay
            # at the realized horizon, summed in int64 host-side
            def cov_run(st):
                out = run_until_coverage_dist(
                    pack_state(st) if args.packed else st,
                    cfg, plan, mesh, args.target, args.max_rounds,
                    scenario=scen, growth=grow, transport=transport,
                    control=ctl, pipeline=pipe, liveness=lqs,
                )
                return unpack_state(out) if args.packed else out

            r0 = int(state.round)
            result, fin = M.bench_swarm(
                state, cfg, args.target, args.max_rounds, n_peers=args.peers,
                run=cov_run,
            )
            rounds = int(fin.round) - r0
            ici = None
            if transport is not None and rounds > 0:
                from tpu_gossip.core.state import clone_state

                _, (_stats, ici) = simulate_dist(
                    clone_state(state), cfg, plan, mesh, rounds, None, scen,
                    grow, transport, True, control=ctl, pipeline=pipe,
                    liveness=lqs,
                )
            summary = {"summary": True, "mode": args.mode,
                       "devices": mesh.size, "delivery": "matching",
                       **_scenario_summary(spec),
                       **_transport_summary(args, ici, rounds, plan),
                       **_pipeline_summary(args),
                       **_control_summary(args),
                       **_liveness_summary(args),
                       **json.loads(result.to_json())}
            summary.update(_digest_summary(args, fin, None))
    summary.update(_growth_summary(args, fin))
    summary.update(_layout_summary(args))
    if jax.process_index() == 0:
        print(json.dumps(summary))
        if args.checkpoint:
            save_swarm(args.checkpoint, fin)
    return 0


def _main_shard(args, graph, rng, spec=None, resume=None) -> int:
    """The --shard path: identical protocol, peers 1-D sharded over every
    available device with bucketed all_to_all fan-out (dist/mesh.py)."""
    import jax

    from tpu_gossip.core.state import SwarmConfig, save_swarm
    from tpu_gossip.dist import (
        build_shard_plans,
        build_transport,
        init_sharded_swarm,
        make_mesh,
        partition_graph,
        run_until_coverage_dist,
        shard_swarm,
        simulate_dist,
    )
    from tpu_gossip.sim import metrics as M
    from tpu_gossip.utils.profiling import trace

    if args.hosts > 1:
        from tpu_gossip.cluster import make_cluster_mesh

        mesh = make_cluster_mesh(hosts=args.hosts)
    else:
        mesh = make_mesh()
    gexists = None
    if args.grow:
        from tpu_gossip.growth import pad_graph_for_growth

        graph, gexists = pad_graph_for_growth(graph, args.grow_capacity)
    sg, relabeled, position = partition_graph(graph, mesh.size, seed=args.seed)
    transport = (
        build_transport(sg, mode=args.transport, hosts=args.hosts)
        if args.transport != "dense" else None
    )
    cfg = SwarmConfig(
        n_peers=sg.n_pad,  # padded slot space; pads are born dead
        msg_slots=args.slots,
        fanout=args.fanout,
        mode=args.mode,
        forward_once=args.forward_once,
        sir_recover_rounds=args.sir_recover,
        churn_leave_prob=args.churn_leave,
        churn_join_prob=args.churn_join,
        rewire_slots=_rewire_slots(args),
        rewire_compact_cap=args.rewire_compact_cap,
    )
    plans = build_shard_plans(sg) if args.staircase else None
    origins, silent_ids = _sample_ids(args, rng)
    state = init_sharded_swarm(
        sg, relabeled, position, cfg, key=jax.random.key(args.seed),
        origins=origins, exists=gexists,
    )
    if silent_ids is not None:
        state.silent = state.silent.at[position[silent_ids]].set(True)
    state = shard_swarm(state, mesh)
    if jax.process_count() > 1:
        # shard_map operands must be GLOBAL arrays when the mesh spans
        # processes; single-process runs keep the host arrays (jit
        # places them). Placed LAST: build_transport/init consume the
        # host copies above
        from tpu_gossip.dist import shard_graph

        sg = shard_graph(sg, mesh)

    from tpu_gossip.core.state import shard_ranges

    scen = _compile_cli_scenario(
        spec, args, n_slots=sg.n_pad,
        node_map=lambda ids: position[np.asarray(ids)],
        shard_ranges=shard_ranges(mesh.size, sg.per_shard, mesh=mesh),
        n_shards=mesh.size,
    )
    grow = _compile_cli_growth(
        args, spec, n_slots=sg.n_pad,
        node_map=lambda ids: position[np.asarray(ids)],
    )
    strm = _compile_cli_stream(args, position[np.arange(args.peers)])
    ctl = _compile_cli_control(args)
    lqs = _compile_cli_liveness(args)
    pipe = _compile_cli_pipeline(args)
    policy = _ckpt_policy(args, shards=mesh.size,
                          extra={"devices": mesh.size})
    _check_resume_devices(resume, mesh.size)
    from tpu_gossip.core.packed import pack_state, unpack_state

    with trace(args.profile):
        if args.remat_every > 0:
            summary, fin = _run_shard_with_remat(
                args, cfg, state, sg, mesh, plans, scen, ctl, pipe, lqs,
                policy=policy, resume=resume,
            )
            summary.update(_scenario_summary(spec))
            summary.update(_transport_summary(args))
            summary.update(_pipeline_summary(args))
            summary.update(_control_summary(args))
        elif args.rounds > 0:
            if policy is None and resume is None:
                st_in = pack_state(state) if args.packed else state
                if transport is not None:
                    fin, (stats, ici) = simulate_dist(
                        st_in, cfg, sg, mesh, args.rounds, plans, scen, grow,
                        transport, True, strm, ctl, pipe, lqs,
                    )
                else:
                    fin, stats = simulate_dist(st_in, cfg, sg, mesh,
                                               args.rounds, plans, scen,
                                               grow, stream=strm,
                                               control=ctl, pipeline=pipe,
                                               liveness=lqs)
                    ici = None
                if args.packed:
                    fin = unpack_state(fin)
            else:
                from tpu_gossip.ckpt import host_stats, run_checkpointed
                from tpu_gossip.dist import shard_swarm as _reshard

                loaded, prefix = _swap_in_resume(resume, state, args)
                state = _reshard(loaded, mesh) if resume is not None \
                    else state
                if args.packed:
                    state = pack_state(state)

                def seg_run(st, seg):
                    if transport is not None:
                        st, (s, seg_ici) = simulate_dist(
                            st, cfg, sg, mesh, seg, plans, scen, grow,
                            transport, True, strm, ctl, pipe, lqs,
                        )
                        return st, host_stats(s, seg_ici)
                    st, s = simulate_dist(st, cfg, sg, mesh, seg, plans,
                                          scen, grow, stream=strm,
                                          control=ctl, pipeline=pipe,
                                          liveness=lqs)
                    return st, host_stats(s)

                fin, sd = run_checkpointed(
                    state, args.rounds, seg_run, policy=policy,
                    stats_prefix=prefix, log=_stderr_log,
                )
                if args.packed:
                    fin = unpack_state(fin)
                stats, ici = _split_host_stats(sd)
            fin = _gather_global(fin)
            if not args.quiet and jax.process_index() == 0:
                M.write_jsonl(stats, sys.stdout)
            summary = _horizon_summary(
                args, stats, devices=mesh.size,
                **_scenario_summary(spec, stats),
                **_transport_summary(args, ici, args.rounds, sg),
                **_pipeline_summary(args),
                **_stream_summary(args, cfg, stats),
                **_control_summary(args, cfg, stats),
                **_liveness_summary(args, stats),
            )
            summary.update(_digest_summary(args, fin, stats, policy, resume))
        else:
            # the shared timing harness (warmup, fetch barrier) with the
            # dist engine's while_loop swapped in; report the real peer
            # count, not the padded slot count. The timed region runs
            # WITHOUT the analytic counter (pure-transport A/B); the
            # trajectory comes from an untimed bit-identical replay at
            # the realized horizon, summed in int64 host-side
            def cov_run(st):
                out = run_until_coverage_dist(
                    pack_state(st) if args.packed else st,
                    cfg, sg, mesh, args.target, args.max_rounds,
                    shard_plan=plans, scenario=scen, growth=grow,
                    transport=transport, control=ctl, pipeline=pipe,
                    liveness=lqs,
                )
                return unpack_state(out) if args.packed else out

            r0 = int(state.round)
            result, fin = M.bench_swarm(
                state, cfg, args.target, args.max_rounds, n_peers=args.peers,
                run=cov_run,
            )
            rounds = int(fin.round) - r0
            ici = None
            if transport is not None and rounds > 0:
                from tpu_gossip.core.state import clone_state

                _, (_stats, ici) = simulate_dist(
                    clone_state(state), cfg, sg, mesh, rounds, plans, scen,
                    grow, transport, True, control=ctl, pipeline=pipe,
                    liveness=lqs,
                )
            summary = {"summary": True, "mode": args.mode, "devices": mesh.size,
                       **_scenario_summary(spec),
                       **_transport_summary(args, ici, rounds, sg),
                       **_pipeline_summary(args),
                       **_control_summary(args),
                       **_liveness_summary(args),
                       **json.loads(result.to_json())}
    summary.update(_growth_summary(args, fin))
    summary.update(_layout_summary(args))
    if jax.process_index() == 0:
        print(json.dumps(summary))
        if args.checkpoint:
            save_swarm(args.checkpoint, fin)
    return 0


def _add_serve_args(p) -> None:
    g = p.add_argument_group(
        "serving", "run_sim serve: the live ingestion frontend "
        "(tpu_gossip/serve/, docs/serving_frontend.md)"
    )
    g.add_argument("--port", type=int, default=0, metavar="P",
                   help="listen port (0 = ephemeral; the bound port is "
                        "announced on stderr)")
    g.add_argument("--serve-host", type=str, default="127.0.0.1",
                   metavar="H", help="listen address")
    g.add_argument("--rounds-per-sec", type=float, default=0.0, metavar="R",
                   help="pace round windows at R/sec (0 = unpaced: as "
                        "fast as the device steps)")
    g.add_argument("--max-inject", type=int, default=64, metavar="J",
                   help="static per-round injection batch; arrivals past "
                        "it defer to the next window and are counted as "
                        "overflow — never dropped silently")
    g.add_argument("--trace-out", type=str, default="", metavar="F",
                   help="record every accepted arrival as (round, origin, "
                        "payload_hash) to this JSONL — the bit-exact "
                        "replay input (serve/trace.py)")
    g.add_argument("--replay-check", action="store_true",
                   help="after serving, replay the recorded trace through "
                        "the pure-sim injection path and fail (exit 1) "
                        "unless state digest + integer-stat trajectory "
                        "match bit for bit")
    g.add_argument("--serve-target-ratio", type=float, default=0.9,
                   metavar="T", help="delivery-ratio target the "
                        "reliability report certifies against")


def _validate_serve(args):
    """Reject impossible serving configs; returns an error string (exit
    2) or None — the serving twin of :func:`_validate_stream`."""
    if args.rounds <= 0:
        return ("serve runs a fixed horizon of round windows — pass "
                "--rounds R; run-to-coverage has no serving window to "
                "batch arrivals into")
    if not (0 <= args.port <= 65535):
        return f"--port {args.port} outside [0, 65535]"
    if args.rounds_per_sec < 0:
        return f"--rounds-per-sec {args.rounds_per_sec} must be >= 0"
    if args.max_inject < 1:
        return f"--max-inject {args.max_inject} must be >= 1"
    if args.stream <= 0 and args.slot_ttl == 0:
        return ("serve lands live arrivals in the streaming slot plane, "
                "which needs its age-out lease configured: pass "
                "--slot-ttl T (and optionally --stream RATE for "
                "background synthetic load)")
    if args.stream <= 0:
        # rate-0 stream: validate the slot-plane knobs ourselves (the
        # standard validator treats a TTL without a rate as a config
        # error, but serving IS the rate here)
        from tpu_gossip.traffic import min_feasible_ttl

        if not (1 <= args.stream_hashes <= args.slots):
            return (f"--stream-hashes {args.stream_hashes} outside "
                    f"[1, --slots {args.slots}] — the Bloom planes live "
                    "in the slot dimension")
        feasible = min_feasible_ttl(args.peers, args.fanout, args.mode)
        if args.slot_ttl < feasible:
            return (f"--slot-ttl {args.slot_ttl} is below the feasible "
                    f"coverage horizon (~{feasible} rounds for "
                    f"{args.peers} peers at fanout {args.fanout}) — every "
                    "served message would be recycled before it could "
                    "possibly cover")
    else:
        err = _validate_stream(args)
        if err:
            return err
    if args.scenario:
        return ("serve does not compose with --scenario yet: fault "
                "phases would make live delivery attribution ambiguous "
                "(run the fault catalogue through run_sim/fleet instead)")
    if args.grow:
        return ("serve does not compose with --grow yet: grown peers "
                "have no client-addressable identity to map arrivals "
                "onto")
    if args.control > 0:
        return ("serve does not compose with --control yet: the "
                "controller and the live load would chase each other's "
                "delivery ratio — serve certifies the STATIC protocol")
    if args.remat_every > 0:
        return ("serve cannot compose with --remat-every: the epoch "
                "re-partition permutes peers, so the frontend's "
                "client-to-row map would inject at the wrong rows")
    if args.pipeline is not None:
        return ("serve double-buffers the injection window against the "
                "in-flight device round itself (serve/driver.py); "
                "--pipeline's exchange overlap does not compose with it")
    if args.transport != "dense":
        return (f"--transport {args.transport} is not wired through the "
                "serving driver; run the transport A/B offline")
    if getattr(args, "checkpoint_every", 0):
        return ("serve does not checkpoint mid-run (the trace IS the "
                "recovery artifact: replay it); drop --checkpoint-every")
    if args.shard and args.graph != "matching":
        return ("serve's sharded engine is the matching mesh "
                "(dist/matching_mesh.py); add --graph matching or drop "
                "--shard")
    return None


def _main_serve(argv: list[str]) -> int:
    """``run_sim serve``: accept reference-wire clients on a socket and
    disseminate their payloads through the device swarm (tentpole of
    docs/serving_frontend.md).

    The frontend thread batches arrivals per round window; the driver
    double-buffers each window's injection against the in-flight device
    round and records the ``(round, origin, payload_hash)`` trace whose
    replay is bit-identical to the live run (``--replay-check`` proves
    it in-process). The summary row carries the steady-state serving
    report, the certified reliability block, the frontend counters and
    the state/stats digests.
    """
    import jax

    from tpu_gossip.core import topology
    from tpu_gossip.core.state import SwarmConfig, init_swarm, save_swarm
    from tpu_gossip.sim import metrics as M

    p = build_parser()
    _add_serve_args(p)
    args = p.parse_args(argv)
    err = _validate_serve(args)
    if err:
        print(err, file=sys.stderr)
        return 2

    rng = np.random.default_rng(args.seed)
    origins, silent_ids = _sample_ids(args, rng)
    mesh = None
    plan = None

    if args.graph == "matching" and args.shard:
        from tpu_gossip.core.matching_topology import (
            matching_powerlaw_graph_sharded,
        )
        from tpu_gossip.dist import (
            make_mesh, shard_matching_plan, shard_swarm,
        )

        mesh = make_mesh()
        if 128 % mesh.size:
            print(f"serve: mesh size {mesh.size} does not divide 128 "
                  "(the sharded matching transpose's lane split)",
                  file=sys.stderr)
            return 2
        dgraph, plan = matching_powerlaw_graph_sharded(
            args.peers, mesh.size, gamma=args.gamma,
            fanout=None if args.mode == "flood" else args.fanout,
            key=jax.random.key(args.seed),
        )
        plan = shard_matching_plan(plan, mesh)

        def to_rows(ids):
            ids = np.asarray(ids)
            return (ids // plan.n_per) * plan.n_blk + (ids % plan.n_per)

        cfg = SwarmConfig(
            n_peers=plan.n, msg_slots=args.slots, fanout=args.fanout,
            mode=args.mode, forward_once=args.forward_once,
            sir_recover_rounds=args.sir_recover,
            churn_leave_prob=args.churn_leave,
            churn_join_prob=args.churn_join,
            rewire_slots=_rewire_slots(args),
            rewire_compact_cap=args.rewire_compact_cap,
        )
        origin_rows = np.asarray(to_rows(np.arange(args.peers)))

        def make_state():
            st = init_swarm(
                dgraph.as_padded_graph(), cfg,
                key=jax.random.key(args.seed), origins=to_rows(origins),
                exists=dgraph.exists,
            )
            if silent_ids is not None:
                st.silent = st.silent.at[to_rows(silent_ids)].set(True)
            return shard_swarm(st, mesh)
    else:
        exists = None
        if args.graph == "matching":
            from tpu_gossip.core.matching_topology import (
                matching_powerlaw_graph,
            )

            dgraph, plan = matching_powerlaw_graph(
                args.peers, gamma=args.gamma,
                fanout=None if args.mode == "flood" else args.fanout,
                key=jax.random.key(args.seed),
            )
            graph, exists = dgraph.as_padded_graph(), dgraph.exists
        elif args.graph == "pa":
            edges = topology.preferential_attachment(args.peers, m=args.m,
                                                     rng=rng)
            graph = topology.build_csr(args.peers, edges)
        else:
            deg = topology.powerlaw_degree_sequence(args.peers,
                                                    gamma=args.gamma,
                                                    rng=rng)
            edges = topology.configuration_model(deg, rng=rng)
            graph = topology.build_csr(args.peers, edges)
        cfg = SwarmConfig(
            n_peers=graph.n, msg_slots=args.slots, fanout=args.fanout,
            mode=args.mode, forward_once=args.forward_once,
            sir_recover_rounds=args.sir_recover,
            churn_leave_prob=args.churn_leave,
            churn_join_prob=args.churn_join,
            rewire_slots=_rewire_slots(args),
            rewire_compact_cap=args.rewire_compact_cap,
        )
        origin_rows = (np.flatnonzero(np.asarray(exists))
                       if exists is not None else np.arange(graph.n))
        _mk_exists = exists

        def make_state():
            st = init_swarm(graph, cfg, key=jax.random.key(args.seed),
                            origins=origins, exists=_mk_exists)
            if silent_ids is not None:
                st.silent = st.silent.at[silent_ids].set(True)
            return st

    if args.stream > 0:
        strm = _compile_cli_stream(args, origin_rows)
    else:
        # rate-0 stream: a masked no-op injection whose age-out lease and
        # per-slot tracks are exactly what the LIVE arrivals ride
        from tpu_gossip.traffic import compile_stream

        strm = compile_stream(
            rate=0.0, msg_slots=args.slots, ttl=args.slot_ttl,
            origin_rows=origin_rows, k_hashes=args.stream_hashes,
        )
    lqs = _compile_cli_liveness(args)

    from tpu_gossip.core.packed import pack_state, unpack_state
    from tpu_gossip.serve import ServeDriver, ServeFrontend, build_step
    from tpu_gossip.traffic.ingest import IngestPlan

    ingest_plan = IngestPlan(msg_slots=args.slots,
                             max_inject=args.max_inject,
                             k_hashes=args.stream_hashes)

    def fresh_state():
        st = make_state()
        return pack_state(st) if args.packed else st

    def fresh_step():
        return build_step(cfg, plan, mesh=mesh,
                          tail=args.tail if not args.shard else "fused",
                          stream=strm, liveness=lqs)

    driver_box: dict = {}
    frontend = ServeFrontend(
        host=args.serve_host, port=args.port, origin_rows=origin_rows,
        max_inject=args.max_inject,
        query_snapshot=lambda: (
            driver_box["d"].snapshot() if "d" in driver_box else {}
        ),
    )
    try:
        frontend.start()
    except (OSError, TimeoutError) as e:
        print(f"serve: cannot listen on "
              f"{args.serve_host}:{args.port}: {e}", file=sys.stderr)
        return 2

    # announce the bound port BEFORE the first round so scripted clients
    # (loadgen, the CI smoke job) can connect while the run is live
    print(json.dumps({"serving": True, "host": args.serve_host,
                      "port": frontend.port, "rounds": args.rounds,
                      "rounds_per_sec": args.rounds_per_sec,
                      "max_inject": args.max_inject}),
          file=sys.stderr, flush=True)

    driver = ServeDriver(
        fresh_step(), fresh_state(), frontend, ingest_plan,
        rounds=args.rounds, rounds_per_sec=args.rounds_per_sec,
        coverage_target=args.target,
    )
    driver_box["d"] = driver
    try:
        rep = driver.run()
    finally:
        frontend.stop()

    from tpu_gossip.fleet.engine import state_digest, stats_digest

    stats = rep.stats
    live_sd = state_digest(rep.state)
    live_td = stats_digest(stats)
    if not args.quiet:
        M.write_jsonl(stats, sys.stdout)

    round_seconds = (1.0 / args.rounds_per_sec if args.rounds_per_sec > 0
                     else cfg.round_seconds)
    warmup = min(args.slot_ttl, args.rounds // 2)
    summary = _horizon_summary(args, stats)
    summary["serve"] = {
        "host": args.serve_host, "port": frontend.port,
        "rounds_per_sec": args.rounds_per_sec,
        "max_inject": args.max_inject,
        "wall_seconds": round(rep.wall_seconds, 3),
        "ms_per_round": round(1000.0 * rep.wall_seconds / args.rounds, 3),
        "trace_rounds": rep.trace.num_rounds,
        "trace_arrivals": rep.trace.total_arrivals,
        "ingest_offered": int(np.asarray(stats.ingest_offered).sum()),
        "ingest_injected": int(np.asarray(stats.ingest_injected).sum()),
        "ingest_conflated": int(np.asarray(stats.ingest_conflated).sum()),
        "ingest_overflow": int(np.asarray(stats.ingest_overflow).sum()),
        "counters": frontend.counters.as_dict(),
    }
    summary["steady_state"] = M.steady_state_report(
        stats, target=args.target, round_seconds=round_seconds,
        warmup_rounds=warmup,
    )
    summary["reliability"] = M.reliability_report(
        stats, target_ratio=args.serve_target_ratio,
        coverage_target=args.target, round_seconds=round_seconds,
    )
    summary["state_digest"] = live_sd
    summary["stats_digest"] = live_td

    if args.trace_out:
        rep.trace.save(args.trace_out)
        summary["serve"]["trace_path"] = args.trace_out

    rc = 0
    if args.replay_check:
        from tpu_gossip.serve import replay_trace
        from tpu_gossip.serve.driver import stack_round_stats

        fin2, trail = replay_trace(rep.trace, fresh_step(), fresh_state())
        stats2 = stack_round_stats([jax.device_get(s) for s in trail])
        replay_sd, replay_td = state_digest(fin2), stats_digest(stats2)
        identical = (replay_sd == live_sd and replay_td == live_td)
        summary["replay"] = {
            "state_digest": replay_sd, "stats_digest": replay_td,
            "bit_identical": identical,
        }
        if not identical:
            print("serve: trace replay DIVERGED from the live run "
                  f"(state {live_sd[:12]}../{replay_sd[:12]}.., stats "
                  f"{live_td[:12]}../{replay_td[:12]}..)", file=sys.stderr)
            rc = 1

    print(json.dumps(summary))
    if args.checkpoint:
        fin = unpack_state(rep.state) if args.packed else rep.state
        save_swarm(args.checkpoint, fin)
    return rc


if __name__ == "__main__":
    sys.exit(main())
