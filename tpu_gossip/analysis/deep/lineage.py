"""deep-rng-lineage: every draw's key must descend from ``state.rng``.

The repo's bit-identity contract (local ↔ sharded, every mode × scenario
× growth × transport) rests on one RNG discipline, until now enforced by
convention plus runtime equality tests:

- every ``random.*`` draw inside a round entry point keys off
  ``state.rng`` through ``split``/``fold_in`` — never a key minted inside
  the trace or baked in as a constant (a constant key replays the same
  randomness every round);
- parallel subsystem streams derive as ``fold_in(state.rng, SALT)`` with
  a salt registered in :mod:`tpu_gossip.core.streams` — an unregistered
  constant salt is a stream nobody audits for collisions, and the same
  (parent, salt) folded twice IS a collision: two subsystems reading one
  stream correlate draws the protocol treats as independent;
- no key value is consumed twice (two draws from one key produce
  identical bits — the correlation no engine-comparison test can see,
  because both engines inherit it);
- draws happen at GLOBAL shape OUTSIDE ``shard_map`` (threefry bits are
  position-deterministic, so a global-shape draw is layout-invariant; a
  draw inside a shard_map body sees per-shard operands and breaks the
  local↔sharded bit-identity — the exact bug class PR 1 engineered out).

This pass checks all four statically, by abstract interpretation over the
traced jaxpr of every entry point in the shared matrix: key-typed values
get structural signatures (root invar / split child index / fold_in salt
chains), signatures flow through pjit/scan/while/cond/shard_map
boundaries, consumption (``random_bits``) and derivation
(``random_split``/``random_fold_in``) are counted per signature.

Known over-approximations (conservative in the safe direction, i.e.
towards NOT flagging): values routed through ``gather``/dynamic indexing
or merged across ``cond`` branches get fresh opaque signatures — reuse
through those is invisible here (the AST-level ``key-linearity`` rule
covers the source-level shapes); loop-carried keys are iteration-fresh by
construction (``split``'s carry refresh), so cross-iteration aliasing is
not modeled. Loop-INVARIANT keys (scan/while consts) ARE modeled: a draw
off one replays identical bits every iteration and is flagged even though
the body traces once.
"""

from __future__ import annotations

import itertools
from typing import Dict, List, Tuple

from tpu_gossip.analysis.deep.jaxpr_tools import src_of, subjaxprs
from tpu_gossip.analysis.registry import Finding

__all__ = ["lineage_findings", "LINEAGE_ALLOWLIST", "RULE"]

RULE = "deep-rng-lineage"

# (repo-relative file, function) -> reason an in-shard_map draw is licensed.
# Same semantics as reductions.REDUCTION_ALLOWLIST: an entry is a written
# justification, not an off switch — it licenses ONLY the
# draw-inside-shard_map check at that source site; lineage-from-root, salt
# registration, and reuse still apply there.
LINEAGE_ALLOWLIST = {
    ("tpu_gossip/dist/mesh.py", "ex"): (
        "the bucketed engine's activation draws run per shard by design, "
        "off an (S,) per-shard key array split OUTSIDE the mesh — its "
        "documented contract is scatter-vs-kernel parity and flood "
        "local↔dist parity, not sampled-mode bit-identity (that is the "
        "matching family's contract, whose draws are all global-shape)"
    ),
}

_DERIVERS = ("random_split", "random_fold_in")
_CONSUMERS = ("random_bits",)
_PASSTHROUGH = ("random_wrap", "random_unwrap", "convert_element_type",
                "reshape", "broadcast_in_dim", "copy")


class _KeyVal:
    """Abstract value for a (possibly unwrapped) PRNG key.

    ``sig`` is a structural signature: two vars with equal comparable sigs
    hold the SAME key value. ``comparable=False`` marks values whose
    identity this pass cannot prove (loop carries, gather results) —
    excluded from reuse accounting, included in root tracking.
    ``loop_const=True`` marks a key that entered a scan/while body at a
    const position — the SAME value on every iteration, so one body-trace
    consumption stands for N identical draws; the flag rides through
    constant-structure derivations (split, constant-salt fold_in) and
    clears only on per-iteration derivations (traced-salt fold_in).
    """

    __slots__ = ("sig", "from_root", "comparable", "loop_const")

    def __init__(self, sig, from_root: bool, comparable: bool = True,
                 loop_const: bool = False):
        self.sig = sig
        self.from_root = from_root
        self.comparable = comparable
        self.loop_const = loop_const


class _Analysis:
    """One entry point's lineage walk: env threading + event accounting."""

    def __init__(self, entry_name: str, registered: Dict[int, str],
                 allowlist=None):
        self.entry = entry_name
        self.registered = registered
        self.allowlist = LINEAGE_ALLOWLIST if allowlist is None else allowlist
        self.allow_used: set = set()
        self.serial = itertools.count()
        # sig -> [(eqn, SrcFrame)] of consumptions (draws)
        self.consumed: Dict[tuple, List] = {}
        # (parent_sig, salt) -> [(eqn, SrcFrame)] of fold_in derivations
        self.folded: Dict[tuple, List] = {}
        self.problems: List[tuple] = []  # (eqn, message, hint)

    # ------------------------------------------------------------ helpers
    def opaque(self, from_root: bool) -> _KeyVal:
        return _KeyVal(("opaque", next(self.serial)), from_root, False)

    def problem(self, eqn, message: str, hint: str) -> None:
        self.problems.append((eqn, message, hint))

    def _is_key(self, aval) -> bool:
        import jax

        try:
            return jax.dtypes.issubdtype(aval.dtype, jax.dtypes.prng_key)
        except Exception:  # noqa: BLE001 — non-array avals
            return False

    def _read(self, env, atom):
        from jax._src import core

        if isinstance(atom, core.Literal):
            return None
        return env.get(atom)

    def _lit_int(self, consts, atom):
        """The operand's trace-time integer value, if provable."""
        from jax._src import core

        if isinstance(atom, core.Literal):
            import numpy as np

            v = atom.val
            if isinstance(v, bool) or (
                hasattr(v, "dtype") and not np.issubdtype(
                    np.asarray(v).dtype, np.integer
                )
            ):
                return None
            if isinstance(v, (int, np.integer)) or (
                hasattr(v, "dtype") and np.ndim(v) == 0
            ):
                try:
                    return int(v)
                except (TypeError, ValueError, OverflowError):
                    return None
            return None
        return consts.get(atom)

    # -------------------------------------------------------- interpreter
    def run(self, closed_jaxpr) -> None:
        jaxpr = closed_jaxpr.jaxpr
        env: dict = {}
        consts: dict = {}
        for cv, cval in zip(jaxpr.constvars, closed_jaxpr.consts):
            if self._is_key(cv.aval):
                # a key baked into the trace as a constant: every draw off
                # it replays identical bits forever — never from_root
                env[cv] = _KeyVal(("const", next(self.serial)), False)
            else:
                try:
                    import numpy as np

                    if np.ndim(cval) == 0 and np.issubdtype(
                        np.asarray(cval).dtype, np.integer
                    ):
                        consts[cv] = int(cval)
                except Exception:  # noqa: BLE001
                    pass
        for i, iv in enumerate(jaxpr.invars):
            if self._is_key(iv.aval):
                env[iv] = _KeyVal(("root", i), True)
        self.interp(jaxpr, env, consts, inside_sm=False)

    def interp(self, jaxpr, env: dict, consts: dict, inside_sm: bool) -> dict:
        """Interpret one (sub-)jaxpr body; returns the final env."""
        for eqn in jaxpr.eqns:
            self.eqn(eqn, env, consts, inside_sm)
        return env

    def _bind_sub(self, sub, outer_atoms, env, consts, *, loop_fresh,
                  sub_consts=()):
        """Env/consts for a sub-jaxpr from the outer operand atoms.

        ``loop_fresh`` marks positions whose binding is per-iteration
        (scan/while carries and xs): their keys keep ``from_root`` but get
        fresh non-comparable signatures — one body trace stands for many
        iterations, each with a distinct refreshed key. The REMAINING
        positions of a loop (the consts) bind the SAME value on every
        iteration, so their keys are tagged ``loop_const``: a draw off one
        replays identical bits per iteration even though the body trace
        shows a single consumption site.
        """
        sub_env: dict = {}
        sub_c: dict = {}
        for cv, cval in zip(sub.constvars, sub_consts):
            if self._is_key(cv.aval):
                sub_env[cv] = _KeyVal(("const", next(self.serial)), False)
        for i, (iv, atom) in enumerate(zip(sub.invars, outer_atoms)):
            if atom is None:
                continue
            val = self._read(env, atom)
            if val is not None:
                if loop_fresh and loop_fresh[i]:
                    sub_env[iv] = self.opaque(val.from_root)
                elif loop_fresh is not None:
                    # loop const position: same key every iteration
                    sub_env[iv] = _KeyVal(
                        val.sig, val.from_root, val.comparable,
                        loop_const=True,
                    )
                else:
                    sub_env[iv] = val
            li = self._lit_int(consts, atom)
            if li is not None:
                sub_c[iv] = li
        return sub_env, sub_c

    def _map_out(self, sub, sub_env, eqn, env, *, exact: bool) -> None:
        """Propagate sub-jaxpr outvar values onto the eqn's outvars."""
        from jax._src import core

        for ov_eqn, ov_sub in zip(eqn.outvars, sub.outvars):
            if isinstance(ov_sub, core.Literal):
                continue
            val = sub_env.get(ov_sub)
            if val is None:
                continue
            env[ov_eqn] = val if exact else self.opaque(val.from_root)

    # --------------------------------------------------------- eqn kinds
    def eqn(self, eqn, env: dict, consts: dict, inside_sm: bool) -> None:
        from jax._src import core

        prim = eqn.primitive.name
        if prim == "random_seed":
            self.problem(
                eqn,
                "root key minted inside a round entry point "
                "(jax.random.key/PRNGKey under the trace) — its draws "
                "replay the same bits every round",
                "derive from state.rng with split/fold_in and thread the "
                "key in as an argument",
            )
            env[eqn.outvars[0]] = _KeyVal(("seeded", next(self.serial)), False)
            return
        if prim in _CONSUMERS:
            self._consume(eqn, env, inside_sm)
            return
        if prim == "random_split":
            val = self._read(env, eqn.invars[0])
            if val is not None:
                env[eqn.outvars[0]] = _KeyVal(
                    ("split", val.sig), val.from_root, val.comparable,
                    val.loop_const,
                )
            return
        if prim == "random_fold_in":
            self._fold(eqn, env, consts)
            return
        if prim in ("jit", "closed_call", "custom_jvp_call",
                    "custom_vjp_call", "remat", "checkpoint"):
            self._call(eqn, env, consts, inside_sm)
            return
        if prim == "scan":
            self._scan(eqn, env, consts, inside_sm)
            return
        if prim == "while":
            self._while(eqn, env, consts, inside_sm)
            return
        if prim == "cond":
            self._cond(eqn, env, consts, inside_sm)
            return
        if prim == "shard_map":
            self._shard_map(eqn, env, consts)
            return
        # structural ops preserve key identity when index-provable
        if prim in _PASSTHROUGH and eqn.invars:
            val = self._read(env, eqn.invars[0])
            if val is not None:
                env[eqn.outvars[0]] = val
            li = self._lit_int(consts, eqn.invars[0])
            if li is not None and prim in ("convert_element_type",
                                           "broadcast_in_dim", "reshape"):
                consts[eqn.outvars[0]] = li
            return
        if prim == "slice":
            val = self._read(env, eqn.invars[0])
            if val is not None:
                start = tuple(eqn.params.get("start_indices", ()))
                limit = tuple(eqn.params.get("limit_indices", ()))
                env[eqn.outvars[0]] = _KeyVal(
                    ("slice", val.sig, start, limit),
                    val.from_root, val.comparable, val.loop_const,
                )
            return
        if prim == "squeeze":
            val = self._read(env, eqn.invars[0])
            if val is not None:
                env[eqn.outvars[0]] = val
            return
        if prim in ("dynamic_slice", "gather", "select_n", "concatenate"):
            vals = [v for v in (self._read(env, a) for a in eqn.invars)
                    if v is not None]
            if vals and any(self._is_key(ov.aval) for ov in eqn.outvars):
                env[eqn.outvars[0]] = self.opaque(
                    all(v.from_root for v in vals)
                )
            return
        # any other primitive taking a key: identity not tracked further;
        # a draw downstream of it will surface as not-comparable (no
        # false reuse) but keeps from_root via opaque propagation
        vals = [v for v in (self._read(env, a) for a in eqn.invars)
                if v is not None]
        if vals:
            for ov in eqn.outvars:
                if self._is_key(ov.aval):
                    env[ov] = self.opaque(all(v.from_root for v in vals))

    def _consume(self, eqn, env: dict, inside_sm: bool) -> None:
        val = self._read(env, eqn.invars[0])
        src = src_of(eqn)
        licensed = src is not None and (
            (src.file, src.function) in self.allowlist
        )
        if inside_sm and licensed:
            self.allow_used.add((src.file, src.function))
        if inside_sm and not licensed:
            self.problem(
                eqn,
                "PRNG draw inside a shard_map body — per-shard shape bits "
                "break the local↔sharded bit-identity contract",
                "draw at GLOBAL shape outside shard_map (threefry bits are "
                "position-deterministic) and pass the bits in",
            )
        if val is None:
            return
        if not val.from_root:
            self.problem(
                eqn,
                "draw keyed off a value that does not derive from the "
                "entry point's state.rng (constant or re-minted key)",
                "every stream must reach state.rng through split/fold_in — "
                "see core/streams.py for the registered parallel streams",
            )
        if val.loop_const:
            self.problem(
                eqn,
                "draw keyed off a loop-invariant key inside a scan/while "
                "body — every iteration redraws IDENTICAL bits (one "
                "body-trace consumption stands for N runtime draws)",
                "thread the key through the loop carry and split it per "
                "iteration, or fold_in the iteration index",
            )
        if val.comparable:
            self.consumed.setdefault(val.sig, []).append((eqn, src))

    def _fold(self, eqn, env: dict, consts: dict) -> None:
        val = self._read(env, eqn.invars[0])
        salt = self._lit_int(consts, eqn.invars[1]) if len(eqn.invars) > 1 \
            else None
        if salt is not None:
            if salt not in self.registered:
                self.problem(
                    eqn,
                    f"fold_in with constant salt {salt:#x} not registered "
                    "in core/streams.py — an unaudited parallel stream",
                    "register it with core.streams.register_stream (the "
                    "registry asserts uniqueness and the split-child "
                    "floor) and fold the registered constant",
                )
            if val is not None and val.comparable:
                self.folded.setdefault((val.sig, salt), []).append(
                    (eqn, src_of(eqn))
                )
            sig = ("fold_in", val.sig if val is not None else None, salt)
            if val is not None:
                # a constant salt derives the SAME child every iteration —
                # loop invariance survives the fold
                env[eqn.outvars[0]] = _KeyVal(
                    sig, val.from_root, val.comparable, val.loop_const
                )
            return
        # traced salt (the sanctioned fold_in(key, i) loop pattern):
        # per-iteration distinct, identity not comparable
        if val is not None:
            env[eqn.outvars[0]] = self.opaque(val.from_root)

    def _call(self, eqn, env, consts, inside_sm) -> None:
        subs = list(subjaxprs(eqn))
        if len(subs) != 1:
            return
        from jax._src import core

        _, sub = subs[0]
        cj = next(
            v for v in eqn.params.values()
            if isinstance(v, (core.ClosedJaxpr, core.Jaxpr))
        )
        sub_consts = cj.consts if isinstance(cj, core.ClosedJaxpr) else ()
        if len(sub.invars) != len(eqn.invars):
            return
        sub_env, sub_c = self._bind_sub(
            sub, eqn.invars, env, consts, loop_fresh=None,
            sub_consts=sub_consts,
        )
        self.interp(sub, sub_env, sub_c, inside_sm)
        self._map_out(sub, sub_env, eqn, env, exact=True)

    def _scan(self, eqn, env, consts, inside_sm) -> None:
        from jax._src import core

        cj = eqn.params["jaxpr"]
        sub = cj.jaxpr if isinstance(cj, core.ClosedJaxpr) else cj
        nc = eqn.params.get("num_consts", 0)
        if len(sub.invars) != len(eqn.invars):
            return
        fresh = [i >= nc for i in range(len(sub.invars))]
        sub_env, sub_c = self._bind_sub(
            sub, eqn.invars, env, consts, loop_fresh=fresh,
            sub_consts=getattr(cj, "consts", ()),
        )
        self.interp(sub, sub_env, sub_c, inside_sm)
        self._map_out(sub, sub_env, eqn, env, exact=False)

    def _while(self, eqn, env, consts, inside_sm) -> None:
        from jax._src import core

        cn = eqn.params.get("cond_nconsts", 0)
        bn = eqn.params.get("body_nconsts", 0)
        atoms = list(eqn.invars)
        cond_atoms = atoms[:cn] + atoms[cn + bn:]
        body_atoms = atoms[cn:cn + bn] + atoms[cn + bn:]
        for cj, op_atoms, nconsts in (
            (eqn.params["cond_jaxpr"], cond_atoms, cn),
            (eqn.params["body_jaxpr"], body_atoms, bn),
        ):
            sub = cj.jaxpr if isinstance(cj, core.ClosedJaxpr) else cj
            if len(sub.invars) != len(op_atoms):
                continue
            fresh = [i >= nconsts for i in range(len(sub.invars))]
            sub_env, sub_c = self._bind_sub(
                sub, op_atoms, env, consts, loop_fresh=fresh,
                sub_consts=getattr(cj, "consts", ()),
            )
            self.interp(sub, sub_env, sub_c, inside_sm)
            if cj is eqn.params["body_jaxpr"]:
                self._map_out(sub, sub_env, eqn, env, exact=False)

    def _cond(self, eqn, env, consts, inside_sm) -> None:
        from jax._src import core

        out_vals: dict = {}
        cid = next(self.serial)
        for bi, cj in enumerate(eqn.params.get("branches", ())):
            sub = cj.jaxpr if isinstance(cj, core.ClosedJaxpr) else cj
            atoms = list(eqn.invars[1:])
            if len(sub.invars) != len(atoms):
                continue
            sub_env, sub_c = self._bind_sub(
                sub, atoms, env, consts, loop_fresh=None,
                sub_consts=getattr(cj, "consts", ()),
            )
            # branches are mutually exclusive at runtime — exactly one
            # executes per round — so a draw in branch 0 and a draw in
            # branch 1 off the same parent key are NOT reuse (and the same
            # salt folded in two branches is not a collision). Re-tag the
            # incoming comparable signatures per (cond, branch); reuse
            # WITHIN one branch keeps a shared sig and is still caught.
            for iv, val in list(sub_env.items()):
                if val.comparable:
                    sub_env[iv] = _KeyVal(
                        ("cond", cid, bi, val.sig), val.from_root, True,
                        val.loop_const,
                    )
            self.interp(sub, sub_env, sub_c, inside_sm)
            for i, ov_sub in enumerate(sub.outvars):
                if isinstance(ov_sub, core.Literal):
                    continue
                val = sub_env.get(ov_sub)
                if val is not None:
                    prev = out_vals.get(i)
                    out_vals[i] = val if prev is None else self.opaque(
                        prev.from_root and val.from_root
                    )
        for i, val in out_vals.items():
            # branch results merge: identity is branch-dependent
            env[eqn.outvars[i]] = self.opaque(val.from_root)

    def _shard_map(self, eqn, env, consts) -> None:
        sub = eqn.params["jaxpr"]
        from jax._src import core

        if isinstance(sub, core.ClosedJaxpr):
            sub = sub.jaxpr
        if len(sub.invars) != len(eqn.invars):
            return
        sub_env, sub_c = self._bind_sub(
            sub, eqn.invars, env, consts, loop_fresh=None,
        )
        self.interp(sub, sub_env, sub_c, inside_sm=True)
        self._map_out(sub, sub_env, eqn, env, exact=False)


def _finding(eqn, message: str, hint: str, entry: str) -> Finding:
    src = src_of(eqn)
    return Finding(
        file=src.file if src else f"<trace:{entry}>",
        line=src.line if src else 0,
        col=0,
        rule=RULE,
        message=message,
        hint=hint + f" (first seen tracing {entry})",
        qualname=src.function if src else entry,
    )


def lineage_findings(traced, allowlist=None) -> list[Finding]:
    """Run the lineage pass over every traced entry; deduped findings.

    A canonical run (``allowlist=None``) also reports DEAD allowlist
    entries — same semantics as the reduction pass: a license matching no
    traced in-shard_map draw is stale and must go (skipped when the
    matrix carries no dist entries, whose traces anchor the licenses)."""
    from tpu_gossip.core.streams import registered_salts

    registered = registered_salts()
    findings: dict = {}
    allow_used: set = set()

    def add(f: Finding):
        findings.setdefault((f.file, f.line, f.rule, f.message), f)

    for name, te in traced.items():
        if te.jaxpr is None:
            continue
        an = _Analysis(name, registered, allowlist)
        an.run(te.jaxpr)
        allow_used |= an.allow_used
        for eqn, msg, hint in an.problems:
            add(_finding(eqn, msg, hint, name))
        for sig, sites in an.consumed.items():
            if len(sites) > 1:
                locs = ", ".join(
                    f"{s.file}:{s.line}" if s else "?" for _, s in sites
                )
                eqn = sites[1][0]
                add(_finding(
                    eqn,
                    f"PRNG key value consumed by {len(sites)} draws "
                    f"({locs}) — identical bits feed draws the protocol "
                    "treats as independent",
                    "split/fold_in a fresh key per draw",
                    name,
                ))
        for (_, salt), sites in an.folded.items():
            if len(sites) > 1:
                locs = ", ".join(
                    f"{s.file}:{s.line}" if s else "?" for _, s in sites
                )
                eqn = sites[1][0]
                sname = registered.get(salt, "unregistered")
                add(_finding(
                    eqn,
                    f"stream salt {salt:#x} ({sname}) folded from the same "
                    f"parent key at {len(sites)} sites ({locs}) — the "
                    "subsystems read ONE stream and correlate their draws",
                    "give each subsystem its own salt in core/streams.py "
                    "(the registry asserts uniqueness)",
                    name,
                ))
    has_dist = any(
        te.ep is not None and te.ep.engine.startswith("dist")
        for te in traced.values()
    )
    if allowlist is None and has_dist:
        for (file, func) in sorted(set(LINEAGE_ALLOWLIST) - allow_used):
            add(Finding(
                file=file, line=0, col=0, rule=RULE,
                message=(
                    f"LINEAGE_ALLOWLIST entry ({file!r}, {func!r}) matches "
                    "no traced in-shard_map draw — a dead license"
                ),
                hint="remove the entry (or fix the anchor): a license that "
                "matches nothing documents a draw that no longer exists",
                qualname=func,
            ))
    return sorted(findings.values(), key=lambda f: f.sort_key)
