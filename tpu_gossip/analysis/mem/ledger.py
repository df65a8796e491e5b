"""Pass 1 — plane ledger + live-range residency over traced jaxprs.

A liveness analysis over equation order: every buffer (entry invar, const,
or equation output) is live from its definition to its last use; the
entry's PEAK is the largest sum of live bytes over any program point. The
walk descends into ``pjit``/``scan``/``while``/``cond``/``shard_map``
bodies; a sub-jaxpr contributes its own peak MINUS its boundary (its
invars + outvars alias the outer operands/results, which the outer point
already counts).

Aliasing credit — the part that makes "donation collapses the state copy"
checkable statically:

- a ``pjit`` equation's donated invars (``donated_invars``) share buffers
  with its outputs: their bytes are credited back at that point;
- a ``scan``/``while`` carry aliases in-place across iterations (XLA
  while-loop buffer reuse): the carry's bytes are credited once.

Attribution: entry invars carry their ``SwarmState`` plane names (leaf
order of the traced state pytree); everything else buckets under
``intermediate:<prim>``; closed-over constants under ``const:<prim-free>``
aggregate. Labels follow positional boundary maps into sub-jaxprs, so a
state plane threaded through ``pjit -> scan`` keeps its name and the
report's top-k residents point at planes and primitives, not SSA ids.

The model is deliberately simple enough to hand-compute on micro-jaxprs
(tests/analysis/test_mem.py pins exact byte counts) — it is a LEDGER, not
an XLA buffer assigner: fusion can only shrink what this over-counts, so
a budgeted peak is an upper bound the real allocator sits under.
"""

from __future__ import annotations

import dataclasses

from tpu_gossip.analysis.registry import Finding

__all__ = ["EntryLedger", "entry_ledger", "ledger_findings", "aval_bytes"]

RESIDENCY_RULE = "mem-donation-residency"
CLONE_RULE = "mem-hot-clone"

# a donated entry's CALL-SITE footprint (state in + jit outputs - donated
# bytes) must sit under this multiple of its state bytes: with donation
# working the outputs alias the donated state and the footprint is one
# state + the stats; >= 2x means the in/out copy survived the donation
# declaration. (The GLOBAL peak is gated by memory_budget.toml instead —
# a round's legitimate exchange planes can exceed a tiny fixture state,
# so an absolute peak rail would misfire exactly where the budget file
# is already exact.)
DONATED_PEAK_FACTOR = 2.0

_TOP_K = 8


def aval_bytes(aval) -> int:
    """Materialized bytes of one abstract value (prng keys: 2x uint32)."""
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return 0
    try:
        item = dtype.itemsize
    except Exception:  # noqa: BLE001 — exotic extended dtypes
        item = 4
    return int(aval.size) * int(item)


@dataclasses.dataclass
class EntryLedger:
    """One entry's residency report."""

    name: str
    n_peers: int
    state_bytes: int  # sum of entry invar bytes (the state pytree)
    const_bytes: int  # closed-over constants (plan tables, scenarios, ...)
    peak_bytes: int  # live-range peak over invars + intermediates
    top: list  # [(label, bytes), ...] at the peak point, descending
    bytes_per_peer: float = 0.0

    def __post_init__(self):
        self.bytes_per_peer = round(
            self.peak_bytes / max(self.n_peers, 1), 2
        )


def _boundary_maps(eqn, sub, param_name):
    """Positional outer-operand list matching ``sub.invars``, or None."""
    prim = eqn.primitive.name
    invars = list(eqn.invars)
    n = len(sub.invars)
    if prim == "cond" and len(invars) == n + 1:
        return invars[1:]  # [index, *operands]
    if prim == "while":
        cn = eqn.params.get("cond_nconsts", 0)
        bn = eqn.params.get("body_nconsts", 0)
        if param_name == "cond_jaxpr" and n == cn + (len(invars) - cn - bn):
            return invars[:cn] + invars[cn + bn:]
        if param_name == "body_jaxpr" and n == bn + (len(invars) - cn - bn):
            return invars[cn : cn + bn] + invars[cn + bn:]
    if len(invars) == n:  # pjit / scan / shard_map / same-arity bodies
        return invars
    return None


def _carry_credit(eqn, sizes) -> int:
    """Bytes the eqn's output buffers reuse from its inputs (donation /
    loop-carry aliasing)."""
    prim = eqn.primitive.name
    invars = list(eqn.invars)
    if prim == "jit":
        donated = eqn.params.get("donated_invars")
        if donated:
            return sum(
                sizes(v) for v, d in zip(invars, donated) if d
            )
        return 0
    if prim == "scan":
        nc = eqn.params.get("num_consts", 0)
        ncar = eqn.params.get("num_carry", 0)
        return sum(sizes(v) for v in invars[nc : nc + ncar])
    if prim == "while":
        cn = eqn.params.get("cond_nconsts", 0)
        bn = eqn.params.get("body_nconsts", 0)
        return sum(sizes(v) for v in invars[cn + bn:])
    return 0


def _analyze(jaxpr, labels, label_of=None):
    """(peak_bytes, breakdown{label: bytes}) for one (open) jaxpr.

    ``labels`` maps this jaxpr's vars to attribution labels; vars absent
    from it are labeled from their defining equation — by default the
    ``intermediate:<prim>`` bucket, or through ``label_of(eqn) -> str |
    None`` when a caller supplies one (the deep transient-liveness pass
    labels by source line over the IDENTICAL sweep, so its peaks equal
    this ledger's by construction).
    """
    from jax._src import core

    from tpu_gossip.analysis.deep.jaxpr_tools import subjaxprs

    def is_var(a):
        return isinstance(a, core.Var)

    def size_of(a):
        return aval_bytes(a.aval) if is_var(a) else 0

    eqns = list(jaxpr.eqns)
    k = len(eqns)
    # definition / last-use indices: invars+constvars defined at -1,
    # outvars last used at k
    def_idx, last_use = {}, {}
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        def_idx[v] = -1
        last_use[v] = -1
    for i, eqn in enumerate(eqns):
        for a in eqn.invars:
            if is_var(a) and a in def_idx:
                last_use[a] = i
        for v in eqn.outvars:
            def_idx[v] = i
            last_use[v] = i
            lbl = label_of(eqn) if label_of is not None else None
            labels.setdefault(v, lbl or f"intermediate:{eqn.primitive.name}")
    for a in jaxpr.outvars:
        if is_var(a) and a in def_idx:
            last_use[a] = k

    live_vars = [v for v in def_idx if last_use[v] >= def_idx[v]]

    def breakdown_of(vars_, extra=None):
        out: dict = dict(extra or {})
        for v in vars_:
            lbl = labels.get(v, "intermediate:?")
            out[lbl] = out.get(lbl, 0) + size_of(v)
        return out

    # per-eqn inner extras (sub-jaxpr peaks past their boundary) + credits
    inner_extras = [0] * k
    inner_breaks: list = [None] * k
    credits = [0] * k
    for i, eqn in enumerate(eqns):
        credits[i] = _carry_credit(eqn, size_of)
        for param_name, sub in subjaxprs(eqn):
            sub_labels = {}
            outer = _boundary_maps(eqn, sub, param_name)
            if outer is not None:
                for sv, ov in zip(sub.invars, outer):
                    if is_var(ov) and ov in labels:
                        sub_labels[sv] = labels[ov]
            sub_peak, sub_break = _analyze(sub, sub_labels, label_of)
            boundary = sum(aval_bytes(v.aval) for v in sub.invars)
            boundary += sum(
                aval_bytes(a.aval) for a in sub.outvars if is_var(a)
            )
            extra = max(0, sub_peak - boundary)
            if extra > inner_extras[i]:
                inner_extras[i], inner_breaks[i] = extra, sub_break

    # event sweep: live bytes at point i = live at i-1 + defs(i) -
    # deaths(i-1); one O(V + E) pass finds the argmax, one O(V) pass
    # reconstructs its label breakdown
    births = [0] * (k + 1)  # bytes first live at point i
    deaths = [0] * (k + 1)  # bytes last live at point i
    entry_total = 0
    for v in live_vars:
        if def_idx[v] == -1:
            entry_total += size_of(v)
        else:
            births[def_idx[v]] += size_of(v)
        deaths[last_use[v]] += size_of(v)
    best_i, best_total = -1, entry_total  # point -1: entry binding
    running = entry_total
    for i in range(k):
        running += births[i]
        total = max(0, running - credits[i]) + inner_extras[i]
        if total > best_total:
            best_i, best_total = i, total
        running -= deaths[i]

    if best_i < 0:
        live = [v for v in live_vars if def_idx[v] == -1]
        return entry_total, breakdown_of(live)
    live = [
        v for v in live_vars
        if def_idx[v] <= best_i and last_use[v] >= best_i
    ]
    if inner_breaks[best_i] is not None:
        # the peak sits inside the sub-jaxpr: its breakdown covers the
        # eqn's operands/results (mapped labels), so the outer share is
        # everything live ACROSS the call
        eqn = eqns[best_i]
        operands = {
            a for a in list(eqn.invars) + list(eqn.outvars) if is_var(a)
        }
        across = [v for v in live if v not in operands]
        return best_total, breakdown_of(across, inner_breaks[best_i])
    return best_total, breakdown_of(live)


def entry_ledger(name: str, te) -> "EntryLedger | None":
    """Residency ledger of one TracedEntry (None when it failed to trace)."""
    if te.jaxpr is None:
        return None
    import jax.tree_util as jtu

    closed = te.jaxpr
    labels: dict = {}
    leaves = jtu.tree_flatten_with_path(te.state)[0] if te.state is not None else []
    for var, (path, _) in zip(closed.jaxpr.invars, leaves):
        labels[var] = jtu.keystr(path).lstrip(".")
    const_bytes = 0
    for cv, cval in zip(closed.jaxpr.constvars, closed.consts):
        labels[cv] = "const"
        const_bytes += aval_bytes(cv.aval)
    state_bytes = sum(aval_bytes(v.aval) for v in closed.jaxpr.invars)
    peak, breakdown = _analyze(closed.jaxpr, labels)
    # consts are plan/scenario residency, priced separately from the
    # per-round live-range peak (they do not scale with the round)
    peak -= breakdown.pop("const", 0)
    top = sorted(breakdown.items(), key=lambda kv: (-kv[1], kv[0]))[:_TOP_K]
    return EntryLedger(
        name=name,
        n_peers=te.ep.n_peers if te.ep is not None else 0,
        state_bytes=state_bytes,
        const_bytes=const_bytes,
        peak_bytes=int(peak),
        top=[[lbl, int(b)] for lbl, b in top],
    )


def _donation_footprint(te, jit_name: str, state_bytes: int):
    """Call-site bytes of the entry's named pjit: state in + outputs -
    donated credit. With donation working the outputs alias the donated
    state, so the footprint is ~one state + the stats; a dropped
    donation re-materializes the full copy. None when no matching pjit
    traces (the deep tier's donation pass reports that shape)."""
    from jax._src import core

    for eqn in te.jaxpr.jaxpr.eqns:
        if eqn.primitive.name != "jit" or eqn.params.get("name") != jit_name:
            continue
        donated = eqn.params.get("donated_invars") or ()
        credit = sum(
            aval_bytes(a.aval)
            for a, d in zip(eqn.invars, donated)
            if d and isinstance(a, core.Var)
        )
        out_bytes = sum(aval_bytes(v.aval) for v in eqn.outvars)
        return state_bytes + out_bytes - credit
    return None


def _clone_eqns(te):
    """copy-equations emitted by core.state.clone_state under this trace."""
    from tpu_gossip.analysis.deep.jaxpr_tools import iter_eqns, src_of

    hits = []
    for eqn, _ in iter_eqns(te.jaxpr.jaxpr):
        if eqn.primitive.name != "copy":
            continue
        try:
            from jax._src import source_info_util as siu

            frames = list(siu.user_frames(eqn.source_info.traceback))
        except Exception:  # noqa: BLE001 — source info is best-effort
            frames = []
        if any(fr.function_name == "clone_state" for fr in frames):
            hits.append(src_of(eqn))
    return hits


def ledger_findings(traced) -> tuple[list, dict]:
    """(findings, name -> EntryLedger) over the traced matrix.

    Findings: a donated (jit_name) entry whose peak reaches
    ``DONATED_PEAK_FACTOR``x its state bytes (donation failed to collapse
    the state copy, or round intermediates the size of the state — the
    ledger's top-k names which), and ``clone_state`` traced on ANY
    entry's hot path (the caller-side escape hatch compiled into the
    round itself: one full state copy per round).
    """
    findings: list[Finding] = []
    ledgers: dict = {}
    for name, te in traced.items():
        if te.jaxpr is None:
            if te.error is not None:
                findings.append(Finding(
                    file=f"<mem:{name}>", line=0, col=0,
                    rule="mem-trace-error",
                    message=f"entry point failed to trace: {te.error}",
                    hint="the memory ledger needs a traceable round — fix "
                    "the entry point (audit and deep tiers report the same "
                    "break)",
                    qualname=name,
                ))
            continue
        led = entry_ledger(name, te)
        ledgers[name] = led
        ep = te.ep
        if ep is not None and ep.jit_name is not None and led.state_bytes:
            fp = _donation_footprint(te, ep.jit_name, led.state_bytes)
            if fp is not None and fp >= DONATED_PEAK_FACTOR * led.state_bytes:
                findings.append(Finding(
                    file=f"<mem:{name}>", line=0, col=0,
                    rule=RESIDENCY_RULE,
                    message=(
                        f"donated entry {ep.jit_name}: call-site footprint "
                        f"{fp} B >= {DONATED_PEAK_FACTOR:g}x state "
                        f"({led.state_bytes} B) — donation is not "
                        "collapsing the state copy (the outputs do not "
                        "alias the donated input buffers)"
                    ),
                    hint="check donate_argnames reaches the jit wrapper "
                    "that actually runs (assignment-form re-wraps drop "
                    "it silently)",
                    qualname=name,
                ))
        for src in _clone_eqns(te):
            loc = f"{src.file}:{src.line} ({src.function})" if src else \
                "<unknown>"
            findings.append(Finding(
                file=f"<mem:{name}>", line=0, col=0,
                rule=CLONE_RULE,
                message=(
                    "clone_state traced INSIDE the round path (called "
                    f"from {loc}) — one full state copy every round"
                ),
                hint="clone_state is the CALLER-side escape hatch for "
                "donating entries; hoist it out of the traced region",
                qualname=name,
            ))
            break  # one finding per entry: stable identity
    return findings, ledgers
