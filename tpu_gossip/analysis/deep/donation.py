"""deep-use-after-donate: a donated state is GONE — nobody may read it.

The donation contract (PR 3, ``core.state.clone_state`` docstring): every
jitted round entry point donates its ``state`` pytree, so the caller's
buffers alias the output and the caller's handles are DELETED by the
call. Reading a donated argument afterwards raises "array has been
deleted" at runtime — but only on the code path that reads it, which is
exactly how the bug ships (an error branch, a stats line, a benchmark
variant). This pass closes the loop from both sides:

- **jaxpr side** — for every jitted loop entry in the shared matrix
  (``simulate``/``run_until_coverage`` and the dist twins) the traced
  ``pjit`` equation's ``donated_invars`` must cover EVERY state leaf: the
  AST rule ``jit-state-donation`` checks the *declaration*, this checks
  what the trace actually carries (a refactor that re-wraps the function
  and drops the kwarg passes the AST rule's assignment-form blind spots;
  it cannot pass here).
- **AST side** — in every scoped module, a name passed as the ``state``
  argument to a known donating entry point must not be READ after the
  call until rebound. ``clone_state(state)`` as the argument is the
  sanctioned escape hatch (the clone is donated, the name survives);
  rebinding the name from the call's result (``state, stats =
  simulate(state, ...)``) is the threading idiom and stays clean.

AST-side over-approximation boundaries (documented, deliberate):
aliases (``s2 = state``) and attribute/subscript state holders are not
tracked; reads inside nested function definitions are that function's
own-scope concern; a second read in the donating statement itself is out
of scope. The runtime error covers what the static pass cannot see —
this pass exists to catch the common shapes before they need a run.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Tuple

from tpu_gossip.analysis.registry import Finding
from tpu_gossip.analysis.rules_donation import _declares_donation
from tpu_gossip.analysis.rules_staticargs import _jit_call_kwargs, _param_names
from tpu_gossip.analysis.walker import ModuleInfo

__all__ = [
    "RULE",
    "donation_jaxpr_findings",
    "donation_ast_findings",
    "donating_entry_points",
]

RULE = "deep-use-after-donate"

_CLONE = "clone_state"

_TERMINATORS = (ast.Return, ast.Raise, ast.Break, ast.Continue)


def _terminates(stmts) -> bool:
    """True when the statement list never falls through (the key-linearity
    rule's early-return discipline: a branch ending in return/raise does
    not merge its donations into the fall-through path)."""
    for s in stmts:
        if isinstance(s, _TERMINATORS):
            return True
        if isinstance(s, ast.If) and s.orelse and _terminates(s.body) and (
            _terminates(s.orelse)
        ):
            return True
    return False


# ------------------------------------------------------------- jaxpr side
def donation_jaxpr_findings(traced) -> list[Finding]:
    """Verify the traced pjit of every jitted matrix entry donates every
    state leaf."""
    findings: list[Finding] = []
    for name, te in traced.items():
        ep = te.ep
        if ep is None or ep.jit_name is None or te.jaxpr is None:
            continue
        state_leaves = set(te.jaxpr.jaxpr.invars)
        pjits = [
            e for e in te.jaxpr.jaxpr.eqns
            if e.primitive.name == "jit"
            and e.params.get("name") == ep.jit_name
        ]
        if not pjits:
            findings.append(Finding(
                file=f"<trace:{name}>", line=0, col=0, rule=RULE,
                message=(
                    f"entry {ep.jit_name} did not trace as a jit call — "
                    "the donation contract cannot be verified"
                ),
                hint="keep the loop entries @jax.jit-wrapped with "
                "donate_argnames=('state',)",
                qualname=ep.jit_name,
            ))
            continue
        for eqn in pjits:
            donated = eqn.params.get("donated_invars")
            if donated is None:
                continue
            from jax._src import core

            missing = sum(
                1 for atom, d in zip(eqn.invars, donated)
                if not d and isinstance(atom, core.Var)
                and atom in state_leaves
            )
            if missing:
                findings.append(Finding(
                    file=f"<trace:{name}>", line=0, col=0, rule=RULE,
                    message=(
                        f"jitted entry {ep.jit_name}: {missing} of "
                        f"{len(state_leaves)} state leaves NOT donated — "
                        "every call copies those buffers"
                    ),
                    hint="donate_argnames=('state',) must reach the jit "
                    "wrapper that actually runs (check assignment-form "
                    "re-wraps)",
                    qualname=ep.jit_name,
                ))
    return findings


# --------------------------------------------------------------- AST side
def donating_entry_points(
    modules: List[ModuleInfo],
) -> Dict[str, int]:
    """absolute dotted name -> positional index of the donated ``state``
    parameter, for every jit entry point that declares state donation."""
    out: Dict[str, int] = {}

    def state_index(fn: ast.AST) -> int | None:
        a = fn.args
        pos = [p.arg for p in a.posonlyargs] + [p.arg for p in a.args]
        return pos.index("state") if "state" in pos else None

    for module in modules:
        top = {
            fi.qualname: fi.node
            for fi in module.functions
            if "." not in fi.qualname
        }
        for fi in module.functions:
            if "." in fi.qualname:
                continue
            idx = state_index(fi.node)
            if idx is None:
                continue
            for dec in fi.node.decorator_list:
                kwargs = _jit_call_kwargs(module, dec)
                if kwargs is None:
                    continue
                if "state" in _param_names(fi.node) and _declares_donation(
                    fi.node, kwargs
                ):
                    out[f"{module.module_dotted}.{fi.qualname}"] = idx
        # assignment form: f = jax.jit(g, donate_argnames=("state",))
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Assign) or not isinstance(
                node.value, ast.Call
            ):
                continue
            kwargs = _jit_call_kwargs(module, node.value)
            if kwargs is None or not node.value.args:
                continue
            wrapped = node.value.args[0]
            if not (isinstance(wrapped, ast.Name) and wrapped.id in top):
                continue
            fn = top[wrapped.id]
            idx = state_index(fn)
            if idx is None or not _declares_donation(fn, kwargs):
                continue
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    out[f"{module.module_dotted}.{tgt.id}"] = idx
    return out


def _resolve_call(module: ModuleInfo, call: ast.Call) -> str | None:
    dotted = module.dotted(call.func)
    if dotted is None:
        return None
    if "." not in dotted:
        return f"{module.module_dotted}.{dotted}"
    return dotted


def _donated_name(module: ModuleInfo, call: ast.Call, idx: int) -> str | None:
    """The caller-side name a donating call consumes, if trackable."""
    arg: ast.AST | None = None
    for kw in call.keywords:
        if kw.arg == "state":
            arg = kw.value
    if arg is None and len(call.args) > idx:
        arg = call.args[idx]
    if arg is None:
        return None
    if isinstance(arg, ast.Call):
        d = module.dotted(arg.func)
        if d is not None and d.split(".")[-1] == _CLONE:
            return None  # the sanctioned escape hatch: the clone dies
    if isinstance(arg, ast.Name):
        return arg.id
    return None  # attribute/subscript holders: out of scope (docstring)


class _BodyScan:
    """Statement-order read-after-donate over one function body."""

    def __init__(self, module: ModuleInfo, donating: Dict[str, int],
                 qualname: str, findings: list):
        self.module = module
        self.donating = donating
        self.qualname = qualname
        self.findings = findings

    # expression-level helpers -------------------------------------------
    def _own_nodes(self, node: ast.AST):
        """Walk a statement, stopping at nested scope boundaries."""
        stack = [node]
        while stack:
            n = stack.pop()
            yield n
            for child in ast.iter_child_nodes(n):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.Lambda, ast.ClassDef)):
                    continue
                stack.append(child)

    def _check_reads(self, node: ast.AST, donated: set) -> None:
        if not donated:
            return
        for n in self._own_nodes(node):
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load) and (
                n.id in donated
            ):
                prag = self.module.pragmas.get(n.lineno)
                if prag is not None and (
                    "*" in prag.rules or RULE in prag.rules
                ):
                    continue
                self.findings.append(Finding(
                    file=self.module.rel,
                    line=n.lineno,
                    col=n.col_offset + 1,
                    rule=RULE,
                    message=(
                        f"`{n.id}` read after being donated to a jitted "
                        "entry point — its buffers were deleted by that "
                        "call"
                    ),
                    hint="read what you need BEFORE the call, pass "
                    "clone_state(state) to keep the input alive, or "
                    "rebind the name from the call's result",
                    qualname=self.qualname,
                ))

    def _donations(self, node: ast.AST, donated: set) -> None:
        for n in self._own_nodes(node):
            if not isinstance(n, ast.Call):
                continue
            target = _resolve_call(self.module, n)
            if target is None or target not in self.donating:
                continue
            nm = _donated_name(self.module, n, self.donating[target])
            if nm is not None:
                donated.add(nm)

    def _bound_names(self, target: ast.AST) -> set:
        names = set()
        for n in ast.walk(target):
            if isinstance(n, ast.Name) and isinstance(
                n.ctx, (ast.Store, ast.Del)
            ):
                names.add(n.id)
        return names

    # statement-level walk -----------------------------------------------
    def block(self, stmts, donated: set) -> set:
        for stmt in stmts:
            donated = self.stmt(stmt, donated)
        return donated

    def stmt(self, stmt: ast.stmt, donated: set) -> set:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            # nested scopes are scanned as their own scope entries
            return donated
        if isinstance(stmt, ast.If):
            self._check_reads(stmt.test, donated)
            self._donations(stmt.test, donated)
            d1 = self.block(stmt.body, set(donated))
            d2 = self.block(stmt.orelse, set(donated))
            # an arm that never falls through (return/raise) keeps its
            # donations to itself — `if cond: return simulate(st, ...)`
            # followed by a fall-through read of `st` is the sanctioned
            # early-return dispatch idiom, not a use-after-donate
            merged = set()
            if not _terminates(stmt.body):
                merged |= d1
            if not _terminates(stmt.orelse):
                merged |= d2
            return merged
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            self._check_reads(stmt.iter, donated)
            self._donations(stmt.iter, donated)
            # two passes: a donation on iteration k is read at the top of
            # iteration k+1 (the same cross-iteration trick key-linearity
            # uses); the loop target rebinds each pass. A body that never
            # falls through has no iteration k+1 — one pass only.
            for _ in range(1 if _terminates(stmt.body) else 2):
                donated = donated - self._bound_names(stmt.target)
                donated = self.block(stmt.body, donated)
            return self.block(stmt.orelse, donated)
        if isinstance(stmt, ast.While):
            for _ in range(1 if _terminates(stmt.body) else 2):
                self._check_reads(stmt.test, donated)
                self._donations(stmt.test, donated)
                donated = self.block(stmt.body, donated)
            return self.block(stmt.orelse, donated)
        if isinstance(stmt, ast.Try):
            donated = self.block(stmt.body, donated)
            merged = set(donated)
            for h in stmt.handlers:
                merged |= self.block(h.body, set(donated))
            merged = self.block(stmt.orelse, merged)
            return self.block(stmt.finalbody, merged)
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            bound = set()
            for item in stmt.items:
                self._check_reads(item.context_expr, donated)
                self._donations(item.context_expr, donated)
                if item.optional_vars is not None:
                    bound |= self._bound_names(item.optional_vars)
            return self.block(stmt.body, donated - bound)
        # simple statements: reads against the PRE-statement set, then
        # this statement's donations, then its (re)bindings
        self._check_reads(stmt, donated)
        donated = set(donated)
        self._donations(stmt, donated)
        if isinstance(stmt, ast.Assign):
            for tgt in stmt.targets:
                donated -= self._bound_names(tgt)
        elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
            donated -= self._bound_names(stmt.target)
        elif isinstance(stmt, ast.Delete):
            for tgt in stmt.targets:
                donated -= self._bound_names(tgt)
        return donated


def donation_ast_findings(modules: List[ModuleInfo]) -> list[Finding]:
    """Read-after-donate over every function body (and module body) of
    ``modules``, against the donating entry points declared anywhere in
    them."""
    donating = donating_entry_points(modules)
    findings: list[Finding] = []
    for module in modules:
        scopes: List[Tuple[str, list]] = [("<module>", module.tree.body)]
        for fi in module.functions:
            scopes.append((fi.qualname, fi.node.body))
        for qualname, body in scopes:
            scan = _BodyScan(module, donating, qualname, findings)
            scan.block(body, set())
    # the two-pass loop scan re-checks a body's reads on pass 2 (the
    # cross-iteration trick): the same violating read must not surface as
    # two identical findings
    uniq: dict = {}
    for f in findings:
        uniq.setdefault((f.file, f.line, f.col, f.message), f)
    return sorted(uniq.values(), key=lambda f: f.sort_key)
