"""Deep pass — the collective program + mesh-uniformity audit.

The multi-host failure mode this pass exists for: on a real
``jax.distributed`` mesh a collective is a RENDEZVOUS. Every shard must
post the same collective in the same order; a ``psum`` reachable under a
branch whose predicate differs across shards hangs the fleet (each shard
waits for partners that branched the other way) instead of raising. XLA
cannot diagnose it — the program is valid SPMD — so the gate has to be
static. This pass walks every ``shard_map`` body's jaxpr (recursing
through ``cond``/``while``/``scan``/``pjit`` sub-jaxprs) and does two
things:

1. **Extracts the per-entry collective program** — the ordered sequence
   of wire-moving collective equations (primitive, named mesh axes,
   per-shard operand shape/dtype, byte volume) with their control-flow
   path. Byte volumes are split into per-axis columns priced with
   :func:`tpu_gossip.dist.mesh.axis_kind` ("ici" vs "dcn") — the
   interconnect split the ROADMAP's 2-level multi-host item budgets
   against, derived statically. The program serializes to a committed
   ``collectives.lock`` (same lockfile discipline as
   ``memory_budget.toml``): a PR that changes the wire program ships a
   diff of that file, reviewed explicitly
   (``--check-collectives-lock`` / ``--write-collectives-lock``).

2. **Enforces mesh-uniformity** via an abstract interpretation over the
   body: every var is classified *uniform* (bit-identical on all shards
   of the mesh) or *varying* (per-shard). Sharded body inputs and
   ``all_to_all``/``ppermute``/``axis_index`` outputs vary; replicated
   inputs, consts, and ``psum``/``pmax``/``pmin``/``all_gather`` outputs
   are uniform; everything else is uniform iff all its inputs are.
   Findings (``deep-collective-uniformity``):

   - a ``cond`` with a *varying* predicate whose arms do not issue an
     identical collective sequence (primitive + axes + shape + dtype,
     in order) — the deadlock shape. A cond with a *uniform* predicate
     may diverge freely: the sparse transport's dense/sparse lanes gate
     on psum'd replicated headers for exactly this reason.
   - any collective inside a ``while`` whose predicate is varying — the
     shards disagree on the trip count, so one posts a collective its
     peers never reach.
   - a collective whose operand shape is not static, or whose axis
     order disagrees with the mesh's canonical axis order.

``pbroadcast``/``pvary`` are check_rep replication bookkeeping —
physically no wire moves — and are deliberately excluded from the
program (they propagate uniformity unchanged). ``psum`` traces as
``psum2`` on this jax (same reductions.py note).

Docs: docs/static_analysis.md (deep-tier catalogue + the
``collectives.lock`` workflow). Self-test fixture:
analysis/deep/selftest.py (a deliberately divergent collective).
"""

from __future__ import annotations

import dataclasses
from pathlib import Path

from tpu_gossip.analysis.registry import Finding

__all__ = [
    "RULE",
    "LOCK_RULE",
    "DEFAULT_LOCK",
    "CollectiveOp",
    "entry_program",
    "collective_report",
    "program_summary",
    "write_lock",
    "load_lock",
    "lock_findings",
]

RULE = "deep-collective-uniformity"
LOCK_RULE = "deep-collective-lock-drift"
DEFAULT_LOCK = "collectives.lock"

# wire-moving collective primitives recorded into the program. Under
# check_vma a psum of a varying value traces as ``psum_invariant``; it is
# recorded under its wire name (``_PRIM_NAMES``), like the *2 spellings
_RECORDED = frozenset({
    "psum", "psum2", "psum_invariant", "pmax", "pmax2", "pmin", "pmin2",
    "all_to_all", "all_gather", "ppermute", "pshuffle", "reduce_scatter",
})
_PRIM_NAMES = {"psum_invariant": "psum"}

# collectives whose OUTPUT is bit-identical on every shard of the named
# axis (reductions replicate their result; all_gather hands every shard
# the same concatenation)
_UNIFORM_OUT = frozenset({
    "psum", "psum2", "psum_invariant", "pmax", "pmax2", "pmin", "pmin2",
    "all_gather",
})

# check_rep replication bookkeeping: physically a no-op (no wire), and
# transparent to uniformity — the value on each shard is unchanged
_REP_BOOKKEEPING = frozenset({"pbroadcast", "pvary"})


@dataclasses.dataclass(frozen=True)
class CollectiveOp:
    """One wire-moving collective equation of an entry's trace."""

    prim: str
    axes: tuple  # named mesh axes, in the order the op names them
    shape: tuple  # per-shard operand shape (first payload operand)
    dtype: str
    path: str  # control-flow context, e.g. "simulate_dist/scan/shard_map"
    bytes_per_shard: int  # sum of operand bytes, one shard's block
    per_axis: tuple  # ((axis, global bytes across that axis), ...)

    @property
    def sig(self) -> tuple:
        """The rendezvous identity: what must match across the arms of a
        shard-varying branch for every shard to post the same op."""
        return (self.prim, self.axes, self.shape, self.dtype)

    def render(self) -> str:
        """One deterministic lock-file line (the freshness-check unit)."""
        from tpu_gossip.dist.mesh import axis_kind

        dims = ",".join(str(d) for d in self.shape)
        cols = " ".join(
            f"{axis_kind(ax)}:{ax}={b}B" for ax, b in self.per_axis
        )
        head = (
            f"{self.prim}[{','.join(self.axes)}] {self.dtype}[{dims}] "
            f"@{self.path}"
        )
        return f"{head} {cols}".rstrip()


def _axes_of(eqn) -> tuple:
    """Named mesh axes of a collective eqn (positional vmap axes — ints —
    are batching, not mesh wire, and are dropped)."""
    raw = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if isinstance(raw, (str, int)):
        raw = (raw,)
    return tuple(a for a in raw if isinstance(a, str))


def _join(path: str, seg: str) -> str:
    return f"{path}/{seg}" if path else seg


class _EntryWalk:
    """One entry's walk: collective program + uniformity findings."""

    def __init__(self, name: str):
        self.name = name
        self.ops: list[CollectiveOp] = []
        self.findings: list[Finding] = []

    # ------------------------------------------------------------ helpers
    def _finding(self, eqn, path: str, message: str, hint: str) -> None:
        from tpu_gossip.analysis.deep.jaxpr_tools import src_of

        src = src_of(eqn)
        self.findings.append(Finding(
            file=src.file if src else f"<deep:{self.name}>",
            line=src.line if src else 0,
            col=0,
            rule=RULE,
            message=message,
            hint=hint,
            # path (no line numbers) keeps the identity stable across
            # unrelated edits, and distinguishes multiple sites per entry
            qualname=f"{self.name}:{path}",
        ))

    def _record(self, eqn, axes, axis_sizes, path, record, sink) -> None:
        from tpu_gossip.analysis.mem.ledger import aval_bytes

        avals = [a.aval for a in eqn.invars if hasattr(a, "aval")]
        per_shard = sum(aval_bytes(a) for a in avals)
        first = avals[0] if avals else None
        shape = tuple(getattr(first, "shape", ()))
        dtype = str(getattr(getattr(first, "dtype", None), "name", "?"))
        if record:
            for a in avals:
                if any(not isinstance(d, int) for d in a.shape):
                    self._finding(
                        eqn, path,
                        f"collective {eqn.primitive.name} operand shape "
                        f"{a.shape} depends on a non-static value — shards "
                        "could post different payload sizes to one "
                        "rendezvous",
                        "make the operand shape static (pad to the "
                        "registry width; the packed codec's W is the "
                        "idiom)",
                    )
            canonical = tuple(ax for ax in axis_sizes if ax in axes)
            if len(axes) > 1 and axes != canonical:
                self._finding(
                    eqn, path,
                    f"collective {eqn.primitive.name} names axes "
                    f"{axes} against the mesh's canonical order "
                    f"{canonical} — mixed orders across entries make two "
                    "identical exchanges look different on the wire (and "
                    "to this lock file)",
                    "name multi-axis collectives in mesh order "
                    "(dist.mesh.AXIS_KINDS order)",
                )
        # each shard along `ax` ships its per-shard block across ax-class
        # links (wire.py's census model, split per axis): global bytes on
        # that axis = block x size(ax)
        per_axis = tuple(
            (ax, per_shard * int(axis_sizes.get(ax, 1))) for ax in axes
        )
        prim = eqn.primitive.name
        sink.append(CollectiveOp(
            prim=_PRIM_NAMES.get(prim, prim), axes=axes, shape=shape, dtype=dtype,
            path=path, bytes_per_shard=per_shard, per_axis=per_axis,
        ))

    # --------------------------------------------------------------- walk
    def run(self, closed_jaxpr):
        uni: dict = {}
        jaxpr = closed_jaxpr.jaxpr
        for v in jaxpr.invars:
            uni[v] = True  # outer program: global, trivially uniform
        self._walk(jaxpr, uni, in_sm=False, axis_sizes={}, path="",
                   record=True, sink=self.ops)
        return self.ops, self.findings

    def _walk(self, jaxpr, uni, *, in_sm, axis_sizes, path, record, sink):
        """Abstract interpretation over one (open) jaxpr; ``uni`` maps its
        invars to uniformity (callers seed), constvars are consts (always
        uniform). Returns the outvars' uniformity."""
        from jax._src import core

        from tpu_gossip.analysis.deep.jaxpr_tools import subjaxprs

        for v in jaxpr.constvars:
            uni[v] = True

        def is_u(a):
            return uni.get(a, True) if isinstance(a, core.Var) else True

        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            uin = all(is_u(a) for a in eqn.invars)
            if prim == "shard_map" and not in_sm:
                self._shard_map(eqn, path, record, sink)
                for v in eqn.outvars:
                    uni[v] = True  # back at global shape
            elif prim in _REP_BOOKKEEPING:
                for a, v in zip(eqn.invars, eqn.outvars):
                    uni[v] = is_u(a)
            elif prim in _RECORDED and in_sm:
                axes = _axes_of(eqn)
                if axes:
                    self._record(eqn, axes, axis_sizes, path, record, sink)
                    out_u = prim in _UNIFORM_OUT
                else:  # vmap-axis op: elementwise for mesh purposes
                    out_u = uin
                for v in eqn.outvars:
                    uni[v] = out_u
            elif prim == "axis_index" and in_sm:
                for v in eqn.outvars:
                    uni[v] = False  # the shard id: varying by definition
            elif prim == "cond":
                self._cond(eqn, uni, is_u, in_sm=in_sm,
                           axis_sizes=axis_sizes, path=path,
                           record=record, sink=sink)
            elif prim == "while":
                self._while(eqn, uni, is_u, in_sm=in_sm,
                            axis_sizes=axis_sizes, path=path,
                            record=record, sink=sink)
            elif prim == "scan":
                self._scan(eqn, uni, is_u, in_sm=in_sm,
                           axis_sizes=axis_sizes, path=path,
                           record=record, sink=sink)
            elif prim == "pallas_call":
                # kernel grids hold no mesh collectives; elementwise rule
                for v in eqn.outvars:
                    uni[v] = uin
            else:
                subs = list(subjaxprs(eqn))
                if subs:
                    _, sub = subs[0]
                    seg = eqn.params.get("name") or prim
                    if len(sub.invars) == len(eqn.invars):
                        sub_uni = {
                            sv: is_u(ov)
                            for sv, ov in zip(sub.invars, eqn.invars)
                        }
                    else:  # unknown boundary: assume uniform (collectives
                        # inside still recorded; divergence not guessed)
                        sub_uni = {sv: True for sv in sub.invars}
                    outs = self._walk(
                        sub, sub_uni, in_sm=in_sm, axis_sizes=axis_sizes,
                        path=_join(path, str(seg)), record=record,
                        sink=sink,
                    )
                    if len(outs) == len(eqn.outvars):
                        for v, u in zip(eqn.outvars, outs):
                            uni[v] = u
                    else:
                        for v in eqn.outvars:
                            uni[v] = uin
                else:
                    for v in eqn.outvars:
                        uni[v] = uin
        return [is_u(a) for a in jaxpr.outvars]

    def _shard_map(self, eqn, path, record, sink):
        from tpu_gossip.analysis.deep.jaxpr_tools import subjaxprs

        subs = list(subjaxprs(eqn))
        if not subs:
            return
        _, body = subs[0]
        try:
            axis_sizes = dict(eqn.params["mesh"].shape)
        except Exception:  # noqa: BLE001 — exotic mesh param
            axis_sizes = {}
        in_names = eqn.params.get("in_names") or ()
        uni = {}
        for i, v in enumerate(body.invars):
            names = in_names[i] if i < len(in_names) else {0: ("?",)}
            uni[v] = not names  # empty spec: replicated input -> uniform
        self._walk(body, uni, in_sm=True, axis_sizes=axis_sizes,
                   path=_join(path, "shard_map"), record=record, sink=sink)

    def _cond(self, eqn, uni, is_u, *, in_sm, axis_sizes, path, record,
              sink):
        branches = eqn.params.get("branches") or ()
        pred_u = is_u(eqn.invars[0])
        arm_ops: list[list] = []
        arm_outs: list[list] = []
        for k, br in enumerate(branches):
            sub = br.jaxpr
            sub_uni = {
                sv: is_u(ov) for sv, ov in zip(sub.invars, eqn.invars[1:])
            }
            local: list = []
            outs = self._walk(
                sub, sub_uni, in_sm=in_sm, axis_sizes=axis_sizes,
                path=_join(path, f"cond.arm{k}"), record=record,
                sink=local,
            )
            arm_ops.append(local)
            arm_outs.append(outs)
        if in_sm and not pred_u and record and any(arm_ops):
            sigs = [tuple(op.sig for op in arm) for arm in arm_ops]
            if any(s != sigs[0] for s in sigs[1:]):
                shapes = "; ".join(
                    f"arm{k}=[" + ", ".join(
                        f"{op.prim}[{','.join(op.axes)}]" for op in arm
                    ) + "]"
                    for k, arm in enumerate(arm_ops)
                )
                self._finding(
                    eqn, path,
                    "collective sequence diverges across the arms of a "
                    f"cond whose predicate is shard-varying ({shapes}) — "
                    "shards taking different arms post different "
                    "rendezvous: a deadlock on a real multi-host mesh",
                    "hoist the collective out of the branch, or gate the "
                    "branch on a replicated predicate (psum the header "
                    "first — the sparse transport's dense/sparse lanes "
                    "are the idiom), or make every arm issue the "
                    "identical collective sequence",
                )
        for arm in arm_ops:
            sink.extend(arm)
        for i, v in enumerate(eqn.outvars):
            uni[v] = pred_u and all(outs[i] for outs in arm_outs if outs)

    def _while(self, eqn, uni, is_u, *, in_sm, axis_sizes, path, record,
               sink):
        cn = eqn.params.get("cond_nconsts", 0)
        bn = eqn.params.get("body_nconsts", 0)
        cjx = eqn.params["cond_jaxpr"].jaxpr
        bjx = eqn.params["body_jaxpr"].jaxpr
        invars = list(eqn.invars)
        cconst_u = [is_u(v) for v in invars[:cn]]
        bconst_u = [is_u(v) for v in invars[cn:cn + bn]]
        carry_u = [is_u(v) for v in invars[cn + bn:]]
        # fixpoint: a carry leaf that turns varying inside the body stays
        # varying for every later iteration (monotone, so this terminates)
        for _ in range(len(carry_u) + 1):
            buni = dict(zip(bjx.invars, bconst_u + carry_u))
            outs = self._walk(
                bjx, buni, in_sm=in_sm, axis_sizes=axis_sizes,
                path=_join(path, "while.body"), record=False, sink=[],
            )
            new = [a and b for a, b in zip(carry_u, outs)]
            if new == carry_u:
                break
            carry_u = new
        cond_sink: list = []
        body_sink: list = []
        cuni = dict(zip(cjx.invars, cconst_u + carry_u))
        couts = self._walk(
            cjx, cuni, in_sm=in_sm, axis_sizes=axis_sizes,
            path=_join(path, "while.cond"), record=record, sink=cond_sink,
        )
        buni = dict(zip(bjx.invars, bconst_u + carry_u))
        self._walk(
            bjx, buni, in_sm=in_sm, axis_sizes=axis_sizes,
            path=_join(path, "while.body"), record=record, sink=body_sink,
        )
        pred_u = couts[0] if couts else True
        if in_sm and not pred_u and record and (cond_sink or body_sink):
            self._finding(
                eqn, path,
                "collective inside a while loop whose predicate is "
                "shard-varying — shards disagree on the trip count, so "
                "one posts a collective its peers already exited past "
                "(deadlock on a real multi-host mesh)",
                "make the loop predicate replicated (reduce it with psum "
                "/pmax first — run_until_coverage's psum'd coverage is "
                "the idiom)",
            )
        sink.extend(cond_sink)
        sink.extend(body_sink)
        for v, u in zip(eqn.outvars, carry_u):
            uni[v] = u

    def _scan(self, eqn, uni, is_u, *, in_sm, axis_sizes, path, record,
              sink):
        nc = eqn.params.get("num_consts", 0)
        ncar = eqn.params.get("num_carry", 0)
        sub = eqn.params["jaxpr"].jaxpr
        invars = list(eqn.invars)
        const_u = [is_u(v) for v in invars[:nc]]
        carry_u = [is_u(v) for v in invars[nc:nc + ncar]]
        xs_u = [is_u(v) for v in invars[nc + ncar:]]
        outs: list = []
        for _ in range(len(carry_u) + 1):
            suni = dict(zip(sub.invars, const_u + carry_u + xs_u))
            outs = self._walk(
                sub, suni, in_sm=in_sm, axis_sizes=axis_sizes,
                path=_join(path, "scan"), record=False, sink=[],
            )
            new = [a and b for a, b in zip(carry_u, outs[:ncar])]
            if new == carry_u:
                break
            carry_u = new
        suni = dict(zip(sub.invars, const_u + carry_u + xs_u))
        outs = self._walk(
            sub, suni, in_sm=in_sm, axis_sizes=axis_sizes,
            path=_join(path, "scan"), record=record, sink=sink,
        )
        out_u = carry_u + outs[ncar:]
        for i, v in enumerate(eqn.outvars):
            uni[v] = out_u[i] if i < len(out_u) else True


def entry_program(name: str, te):
    """(ops, findings) of one TracedEntry — the ordered collective
    program plus any mesh-uniformity violations."""
    return _EntryWalk(name).run(te.jaxpr)


def collective_report(traced) -> tuple[list, dict]:
    """(findings, name -> [CollectiveOp]) over the traced matrix.

    Entries with an empty program (the local engines: no shard_map, no
    wire) are omitted from the program dict — the lock file records mesh
    entries only.
    """
    findings: list[Finding] = []
    programs: dict = {}
    for name in sorted(traced):
        te = traced[name]
        if te.jaxpr is None:
            continue
        ops, probs = entry_program(name, te)
        findings.extend(probs)
        if ops:
            programs[name] = ops
    return findings, programs


def program_summary(programs: dict) -> dict:
    """name -> {ops, ici_bytes, dcn_bytes} for the CLI json report."""
    from tpu_gossip.dist.mesh import axis_kind

    out: dict = {}
    for name in sorted(programs):
        totals = {"ici": 0, "dcn": 0}
        for op in programs[name]:
            for ax, b in op.per_axis:
                totals[axis_kind(ax)] += b
        out[name] = {
            "ops": len(programs[name]),
            "ici_bytes": totals["ici"],
            "dcn_bytes": totals["dcn"],
        }
    return out


# ------------------------------------------------------------- lock file
# Same restricted-TOML reader/writer approach as analysis/mem/budget.py
# (Python 3.10 container, no stdlib tomllib): version scalar +
# ``[[entry]]`` tables, with the one extension that the ``op`` key
# repeats — one line per collective, in program order.


def write_lock(path: str | Path, programs: dict) -> None:
    """Write the committed collective lock from name -> [CollectiveOp]."""
    lines = [
        "# tpu-gossip collective lock — the per-entry wire program of the",
        "# shared traced entry-point matrix (analysis/deep/collectives.py):",
        "# every wire-moving collective, in trace order, with per-axis",
        "# byte columns priced by interconnect class (dist.mesh.AXIS_KINDS",
        "# — ici vs dcn). A PR that changes what the mesh engines ship",
        "# shows up as a DIFF OF THIS FILE, reviewed like a lockfile.",
        "# Refresh:",
        "#   python -m tpu_gossip.analysis --write-collectives-lock",
        "version = 1",
    ]
    summary = program_summary(programs)
    for name in sorted(programs):
        s = summary[name]
        lines += [
            "",
            "[[entry]]",
            f'name = "{name}"',
            f"ops = {s['ops']}",
            f"ici_bytes = {s['ici_bytes']}",
            f"dcn_bytes = {s['dcn_bytes']}",
        ]
        lines += [f'op = "{op.render()}"' for op in programs[name]]
    Path(path).write_text("\n".join(lines) + "\n")


def load_lock(path: str | Path) -> dict:
    """name -> {ops, ici_bytes, dcn_bytes, program: [op line, ...]};
    empty when the file is missing (every mesh entry then reports
    unpinned — a fresh checkout cannot silently pass the gate)."""
    from tpu_gossip.analysis.mem.budget import _parse_value

    p = Path(path)
    if not p.is_file():
        return {}
    entries: dict = {}
    cur: dict | None = None

    def flush():
        if cur and "name" in cur:
            entries[cur["name"]] = {
                k: v for k, v in cur.items() if k != "name"
            }

    for raw in p.read_text().splitlines():
        line = raw.strip()
        if not line or line.startswith("#"):
            continue
        if line == "[[entry]]":
            flush()
            cur = {"program": []}
        elif "=" in line and cur is not None:
            key, _, value = line.partition("=")
            key = key.strip()
            if key == "op":
                cur["program"].append(_parse_value(value))
            else:
                cur[key] = _parse_value(value)
    flush()
    return entries


def lock_findings(programs: dict, lock: dict) -> tuple[list, list]:
    """(findings, stale_names): the traced programs vs the committed lock.

    A mesh entry missing from the lock, or whose rendered program
    drifted (op added/dropped/reordered, axes or shapes or byte columns
    changed), is a ``deep-collective-lock-drift`` finding. Lock entries
    naming no current program are returned as ``stale`` but do not fail
    — matrix cells are host-dependent the same way budget entries are.
    """
    findings: list[Finding] = []
    for name in sorted(programs):
        rendered = [op.render() for op in programs[name]]
        pinned = lock.get(name)
        if pinned is None:
            findings.append(Finding(
                file=f"<wire:{name}>", line=0, col=0, rule=LOCK_RULE,
                message=(
                    f"mesh entry has no line in {DEFAULT_LOCK} "
                    f"({len(rendered)} collective(s) unpinned)"
                ),
                hint="pin the new entry's wire program deliberately: "
                "python -m tpu_gossip.analysis --write-collectives-lock, "
                "and review the lock diff",
                qualname=name,
            ))
            continue
        pinned_prog = pinned.get("program") or []
        if pinned_prog == rendered:
            continue
        detail = f"traced {len(rendered)} op(s) vs pinned {len(pinned_prog)}"
        for i, (a, b) in enumerate(zip(rendered, pinned_prog)):
            if a != b:
                detail = f"first divergence at op {i}: traced {a!r} vs pinned {b!r}"
                break
        findings.append(Finding(
            file=f"<wire:{name}>", line=0, col=0, rule=LOCK_RULE,
            message=(
                f"collective program drifted from {DEFAULT_LOCK}: {detail}"
            ),
            hint="if the wire change is deliberate, refresh with "
            "--write-collectives-lock and let the lock diff carry the "
            "review; otherwise the exchange changed by accident",
            qualname=name,
        ))
    stale = sorted(set(lock) - set(programs))
    return findings, stale
