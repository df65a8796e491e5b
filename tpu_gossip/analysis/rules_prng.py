"""key-linearity: every PRNG key is consumed at most once.

The invariant this protects: the local engine and the mesh engines share
one RNG stream contract — ``tests/sim/test_dist.py`` asserts bit-identical
trajectories — and that contract holds only if every key is used linearly:
derive with ``split``/``fold_in``, consume exactly once. A key consumed
twice (two samplers, sampler-then-split, double split) silently correlates
draws that the protocol treats as independent, which breaks the
local↔sharded bit-identity *statistically* — no test that compares the two
engines can catch it, because both engines inherit the same correlated
stream. PeerSwap (arXiv:2408.03829) makes the same point for protocol-level
randomness: uniformity claims need provable draw discipline.

Mechanics — a small per-function abstract interpreter over statement order:

- Key variables: parameters named like keys (``key``, ``k_*``, ``key_*``,
  ``*_key`` — NOT bare ``rng``, which names stateful numpy Generators in
  this codebase) and variables assigned from
  ``jax.random.split/key/PRNGKey/fold_in/clone/wrap_key_data``.
- Consumption: passing a key variable to any ``jax.random.*`` function
  except the non-consuming constructors (``key``, ``PRNGKey``,
  ``key_data``, ``wrap_key_data``, ``clone``) and ``fold_in`` (a
  derivation operator: ``fold_in(key, i)`` with varying ``i`` is the
  sanctioned loop pattern) — or passing it to ANY other callable
  (ownership transfers to the callee, which consumes it).
- Reassignment refreshes: ``key, sub = jax.random.split(key)`` consumes
  the old key and binds a fresh one, so later uses are of the new key.
- Branches: ``if``/``elif``/``else`` arms are analyzed independently and
  merged as a union of consumptions from arms that fall through
  (``return``/``raise`` arms don't merge — the early-return kernel-path
  idiom in ``sim/engine.py`` stays clean). Mutually-exclusive sibling
  ``if`` statements (trace-time mode dispatch) are beyond static reach —
  deliberate cases carry pragmas with reasons.
- Loops: the body is interpreted twice so a key consumed across
  iterations without re-derivation is caught.
- Subscripted keys (``keys[i]``) and attribute keys (``state.rng``) are
  not tracked (index- and field-sensitive tracking is out of scope).

Also flagged: a root key constructed inline inside a sampler call
(``jax.random.uniform(jax.random.key(0), ...)``) — library code must
thread keys, not mint constant streams.
"""

from __future__ import annotations

import ast
import re

from tpu_gossip.analysis.registry import Finding, rule
from tpu_gossip.analysis.walker import ModuleInfo

__all__ = ["check_key_linearity"]

# bare `rng` is deliberately NOT assumed to be a jax key: this codebase
# threads numpy Generators under that name (cli/run_sim.py,
# core/topology.py), and those are stateful — reuse is their contract.
# Anything ASSIGNED from jax.random.* is tracked regardless of its name.
_KEY_PARAM_RE = re.compile(r"^(key|k_\w+|key_\w+|\w+_key)$")

_NON_CONSUMING = {"key", "PRNGKey", "key_data", "wrap_key_data", "clone"}
_DERIVING = {"fold_in"}
_PRODUCERS = {"split", "key", "PRNGKey", "fold_in", "clone", "wrap_key_data"}

_TERMINATORS = (ast.Return, ast.Raise, ast.Break, ast.Continue)


def _is_key_param(name: str) -> bool:
    return bool(_KEY_PARAM_RE.match(name))


class _Env:
    """var -> consumption site line, or None when fresh."""

    def __init__(self, data=None):
        self.data: dict[str, int | None] = dict(data or {})

    def copy(self) -> "_Env":
        return _Env(self.data)

    def merge(self, branches: list["_Env"]) -> None:
        for b in branches:
            for var, site in b.data.items():
                if site is not None or var not in self.data:
                    if self.data.get(var) is None:
                        self.data[var] = site


_LOOP_TRACERS = (
    "jax.lax.scan", "jax.lax.while_loop", "jax.lax.fori_loop",
    "jax.lax.map", "jax.vmap",
)


class _FnChecker:
    def __init__(self, module: ModuleInfo, fn: ast.AST):
        self.module = module
        self.fn = fn
        self.findings: list[Finding] = []
        self._reported: set[tuple[int, str]] = set()
        # nested function names handed to lax.scan/while_loop/fori_loop (or
        # vmapped): their bodies trace once per ITERATION, so a captured key
        # consumed there is consumed many times with one value
        self._loop_traced = self._collect_loop_traced()

    def _collect_loop_traced(self) -> set[str]:
        names: set[str] = set()
        for node in ast.walk(self.fn):
            if isinstance(node, ast.Call):
                dotted = self.module.dotted(node.func) or ""
                if dotted in _LOOP_TRACERS:
                    for a in list(node.args) + [k.value for k in node.keywords]:
                        if isinstance(a, ast.Name):
                            names.add(a.id)
        return names

    def run(self) -> list[Finding]:
        env = _Env()
        args = self.fn.args
        for a in (
            list(args.posonlyargs) + list(args.args) + list(args.kwonlyargs)
        ):
            if _is_key_param(a.arg):
                env.data[a.arg] = None
        self._block(self.fn.body, env)
        return self.findings

    # ----------------------------------------------------------- reporting
    def _reuse(self, name: str, node: ast.AST, first_line: int) -> None:
        if (node.lineno, name) in self._reported:
            return
        self._reported.add((node.lineno, name))
        self.findings.append(
            Finding(
                file=self.module.rel,
                line=node.lineno,
                col=node.col_offset + 1,
                rule="key-linearity",
                message=(
                    f"PRNG key {name!r} consumed again (first consumed at "
                    f"line {first_line}) in {self._fname()}"
                ),
                hint="derive fresh keys with jax.random.split/fold_in before "
                "each consumer; reuse silently correlates draws and voids "
                "the local<->sharded bit-identity contract",
                qualname=self._fname(),
            )
        )

    def _fname(self) -> str:
        return getattr(self.fn, "name", "<lambda>")

    # ------------------------------------------------------ expression walk
    def _consume_in_expr(self, expr: ast.AST, env: _Env) -> None:
        """Find key consumptions in an expression (call-order approximate)."""
        for node in ast.walk(expr):
            if not isinstance(node, ast.Call):
                continue
            dotted = self.module.dotted(node.func) or ""
            argv = list(node.args) + [kw.value for kw in node.keywords]
            if dotted.startswith("jax.random."):
                fn = dotted.rsplit(".", 1)[1]
                if fn in _NON_CONSUMING or fn in _DERIVING:
                    consuming = False
                else:
                    consuming = True  # samplers AND split both consume
                if consuming:
                    for a in argv:
                        self._consume_name(a, env, node)
                    # inline root key minted inside a sampler
                    for a in argv:
                        if isinstance(a, ast.Call):
                            ad = self.module.dotted(a.func) or ""
                            if ad in ("jax.random.key", "jax.random.PRNGKey"):
                                self.findings.append(
                                    Finding(
                                        file=self.module.rel,
                                        line=a.lineno,
                                        col=a.col_offset + 1,
                                        rule="key-linearity",
                                        message=(
                                            f"root key minted inline inside "
                                            f"{dotted} in {self._fname()}"
                                        ),
                                        hint="thread a split product of the "
                                        "caller's key instead of a constant "
                                        "stream",
                                        qualname=self._fname(),
                                    )
                                )
            else:
                # transfer: handing a key to any callable consumes it there
                for a in argv:
                    self._consume_name(a, env, node)

    def _consume_name(self, a: ast.AST, env: _Env, site: ast.AST) -> None:
        if isinstance(a, ast.Name) and a.id in env.data:
            prior = env.data[a.id]
            if prior is not None:
                self._reuse(a.id, site, prior)
            else:
                env.data[a.id] = site.lineno

    # ------------------------------------------------------- statement walk
    def _block(self, stmts, env: _Env) -> bool:
        """Interpret a statement list; True when it always terminates."""
        for stmt in stmts:
            if isinstance(stmt, _TERMINATORS):
                for child in ast.iter_child_nodes(stmt):
                    self._consume_in_expr(child, env)
                return True
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # a nested def's OWN keys are checked as its own scope, but
                # keys it CAPTURES from this scope are consumed here: the
                # closure is traced by whatever it's handed to (lax.scan
                # bodies, shard_map closures), so a captured-key use counts
                # against the outer budget — and a loop-traced body consumes
                # per iteration, which is reuse by itself
                self._consume_captured(stmt, env)
                continue
            if isinstance(stmt, ast.ClassDef):
                continue  # methods are checked as their own scope
            if isinstance(stmt, ast.If):
                self._consume_in_expr(stmt.test, env)
                arms, n_arms, n_term = [], 0, 0
                for body in (stmt.body, stmt.orelse):
                    if not body:
                        continue
                    n_arms += 1
                    arm = env.copy()
                    if self._block(body, arm):
                        n_term += 1
                    else:
                        arms.append(arm)
                env.merge(arms)
                if stmt.orelse and n_term == n_arms:
                    return True  # both arms terminate
                continue
            if isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
                if isinstance(stmt, ast.While):
                    self._consume_in_expr(stmt.test, env)
                else:
                    self._consume_in_expr(stmt.iter, env)
                # two passes catch cross-iteration reuse; re-derivation at
                # the loop top (key, sub = split(key)) stays clean
                self._block(stmt.body, env)
                self._block(stmt.body, env)
                self._block(stmt.orelse, env)
                continue
            if isinstance(stmt, ast.Try):
                arms = []
                for body in [stmt.body] + [h.body for h in stmt.handlers] + [
                    stmt.orelse, stmt.finalbody,
                ]:
                    if body:
                        arm = env.copy()
                        self._block(body, arm)
                        arms.append(arm)
                env.merge(arms)
                continue
            if isinstance(stmt, (ast.With, ast.AsyncWith)):
                for item in stmt.items:
                    self._consume_in_expr(item.context_expr, env)
                if self._block(stmt.body, env):
                    return True
                continue
            if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                value = stmt.value
                if value is not None:
                    self._consume_in_expr(value, env)
                targets = (
                    stmt.targets if isinstance(stmt, ast.Assign) else [stmt.target]
                )
                produces = self._produces_key(value)
                for tgt in targets:
                    for name in _target_names(tgt):
                        if produces or name in env.data:
                            env.data[name] = None  # (re)bound fresh
                continue
            # plain expression / assert / anything else: just scan it
            for child in ast.iter_child_nodes(stmt):
                self._consume_in_expr(child, env)
        return False

    def _consume_captured(self, nested: ast.AST, env: _Env) -> None:
        """Consumptions of OUTER-scope keys inside a nested def (free
        variables: used as call args but neither a parameter of the nested
        function nor bound inside it)."""
        bound = {
            a.arg
            for a in (
                list(nested.args.posonlyargs)
                + list(nested.args.args)
                + list(nested.args.kwonlyargs)
            )
        }
        if nested.args.vararg:
            bound.add(nested.args.vararg.arg)
        if nested.args.kwarg:
            bound.add(nested.args.kwarg.arg)
        for sub in ast.walk(nested):
            if isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Store):
                bound.add(sub.id)
        loop_traced = nested.name in self._loop_traced
        for sub in ast.walk(nested):
            if not isinstance(sub, ast.Call):
                continue
            dotted = self.module.dotted(sub.func) or ""
            if dotted.startswith("jax.random.") and (
                dotted.rsplit(".", 1)[1] in _NON_CONSUMING
                or dotted.rsplit(".", 1)[1] in _DERIVING
            ):
                continue
            for a in list(sub.args) + [kw.value for kw in sub.keywords]:
                if (
                    isinstance(a, ast.Name)
                    and a.id not in bound
                    and a.id in env.data
                ):
                    self._consume_name(a, env, sub)
                    if loop_traced:
                        # second bite: per-iteration tracing makes one
                        # lexical consumption many runtime consumptions
                        self._consume_name(a, env, sub)

    def _produces_key(self, value: ast.AST | None) -> bool:
        if isinstance(value, ast.Call):
            dotted = self.module.dotted(value.func) or ""
            if dotted.startswith("jax.random."):
                return dotted.rsplit(".", 1)[1] in _PRODUCERS
        return False


def _target_names(tgt: ast.AST):
    if isinstance(tgt, ast.Name):
        yield tgt.id
    elif isinstance(tgt, (ast.Tuple, ast.List)):
        for el in tgt.elts:
            yield from _target_names(el)
    elif isinstance(tgt, ast.Starred):
        yield from _target_names(tgt.value)


@rule("key-linearity")
def check_key_linearity(module: ModuleInfo):
    for fi in module.functions:
        yield from _FnChecker(module, fi.node).run()
