"""graftmem — the jaxpr memory tier: static residency, width, and wire
audits over the shared traced entry-point matrix.

The ROADMAP's 100M item is "a memory and layout problem, not a kernel
problem", with bytes/peer as the tracked metric and narrow/bit-packed
state planes as the lever. This tier makes those memory contracts
STATICALLY provable the way graftlint's deep tier proves the bit-identity
contracts — same matrix (:mod:`tpu_gossip.analysis.entrypoints`), same
one-trace-per-entry cache, same finding/baseline/CLI machinery:

- :mod:`.ledger` — liveness over eqn order (descending into pjit/scan/
  while/cond/shard_map bodies): per-entry peak live bytes, bytes/peer at
  the entry's n, top-k resident intermediates; donated entries' peak must
  sit under 2x state bytes and a traced ``clone_state`` on the hot path
  is a finding.
- :mod:`.widths` — every state plane materializes exactly its declared
  registry dtype (``core.state.PLANES``); widening casts on (N,)-scale
  operands and any 64-bit promotion are findings (line-pragma escape).
- :mod:`.wire` — shipped words per collective recomputed from the traced
  all_to_all operand shapes x mesh size, cross-checked against both dist
  engines' ``dense_wire_words`` declarations (shared with the analytic
  ``IciRound`` counters) — the hand-written wire model cannot drift.
- :mod:`.budget` — ``memory_budget.toml``: the committed per-entry
  residency budget; >5% regression or an unbudgeted entry fails CI.

Run: ``python -m tpu_gossip.analysis --mem`` (or ``--mem-only``;
``--write-budget`` refreshes the committed budget). Docs:
docs/memory_budget.md.
"""

from __future__ import annotations

from tpu_gossip.analysis.registry import MEM_RULES, Finding  # noqa: F401

__all__ = ["run_mem", "MEM_RULES"]


def run_mem(
    cache: dict | None = None,
    *,
    budget_path=None,
    check_budget: bool = True,
) -> tuple[list, dict]:
    """All memory passes; returns (sorted findings, report).

    ``cache`` (name -> TracedEntry) shares the matrix traces with the
    contract audit and the deep tier in the same invocation.
    ``budget_path`` overrides ``<repo>/memory_budget.toml``;
    ``check_budget=False`` skips the budget gate (the --write-budget
    path prices entries without judging them).

    The report (also the CLI's ``mem`` json block) carries per-entry ledgers, the wire
    cross-check, stale budget lines, and the registry-derived bytes/peer
    at 1M — the ROADMAP metric, computed from declared widths alone.
    """
    from pathlib import Path

    from tpu_gossip.analysis.cli import repo_root
    from tpu_gossip.analysis.entrypoints import entry_points, trace_matrix
    from tpu_gossip.analysis.mem.budget import (
        DEFAULT_BUDGET,
        budget_findings,
        load_budget,
    )
    from tpu_gossip.analysis.mem.ledger import ledger_findings
    from tpu_gossip.analysis.mem.widths import width_findings
    from tpu_gossip.analysis.mem.wire import wire_findings
    from tpu_gossip.core.state import state_bytes_per_peer

    traced = trace_matrix(entry_points(), cache=cache)
    findings, ledgers = ledger_findings(traced)
    findings.extend(width_findings(traced))
    wfindings, wire_report = wire_findings(traced)
    findings.extend(wfindings)

    budget_path = (
        Path(budget_path) if budget_path else repo_root() / DEFAULT_BUDGET
    )
    stale: list = []
    if check_budget:
        bfindings, stale = budget_findings(ledgers, load_budget(budget_path))
        findings.extend(bfindings)
    findings.sort(key=lambda f: f.sort_key)

    report = {
        "entries": {
            name: {
                "n_peers": led.n_peers,
                "state_bytes": led.state_bytes,
                "const_bytes": led.const_bytes,
                "peak_bytes": led.peak_bytes,
                "bytes_per_peer": led.bytes_per_peer,
                "top": led.top,
            }
            for name, led in sorted(ledgers.items())
        },
        "wire": wire_report,
        "stale_budget_entries": stale,
        "budget_path": str(budget_path),
        # the ROADMAP metric at headline scale, from declared widths
        # alone (no arrays built): state-plane bytes per peer slot.
        # Since the packed-plane PR the headline figure prices the PACKED
        # storage ledger (what a PackedSwarm carry keeps resident and
        # what checkpoints write); the unpacked compute materialization
        # rides alongside for the round-transient view.
        "state_bytes_per_peer_1m": round(
            state_bytes_per_peer(1_000_000, 16, packed=True), 3
        ),
        "state_bytes_per_peer_1m_unpacked": round(
            state_bytes_per_peer(1_000_000, 16), 3
        ),
    }
    return findings, (report | {"ledgers": ledgers})
