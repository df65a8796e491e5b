"""graftlint CLI: ``python -m tpu_gossip.analysis`` / ``tpu-gossip-lint``.

Exit codes: 0 = clean (no findings beyond the baseline), 1 = new findings,
2 = usage error. ``--fail-on-new`` is the default semantics and accepted
explicitly for CI-invocation clarity.

Default scope is the package (tests are exempt — they
deliberately construct pathological inputs); passing explicit paths lints
just those files and SKIPS the contract audit (fixture linting must not
import the fixtures' runtime). The contract audit needs a multi-device
host to verify the mesh engines — run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CLI sets it
when jax is not yet imported and no device-count flag is present).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

from tpu_gossip.analysis.baseline import (
    DEFAULT_BASELINE,
    load_baseline,
    split_new,
    write_baseline,
)
from tpu_gossip.analysis.registry import RULES, Finding, run_rules
from tpu_gossip.analysis.walker import ModuleInfo, Project

__all__ = ["main", "lint_paths", "modules_for", "repo_root", "run_repo_lint"]

_DEFAULT_SCOPE = ("tpu_gossip",)
_EXCLUDE_PARTS = ("tests", ".git", "__pycache__", ".jax_cache")


def repo_root() -> Path:
    """The repo checkout containing this package (pyproject.toml anchor)."""
    here = Path(__file__).resolve()
    for parent in here.parents:
        if (parent / "pyproject.toml").is_file():
            return parent
    return here.parents[2]


def _collect_files(root: Path, paths: list[str]) -> list[Path]:
    files: list[Path] = []
    for p in paths:
        pt = Path(p)
        if not pt.is_absolute():
            pt = root / pt
        if pt.is_dir():
            files.extend(
                f
                for f in sorted(pt.rglob("*.py"))
                if not set(f.relative_to(root).parts) & set(_EXCLUDE_PARTS)
            )
        elif pt.is_file():
            files.append(pt)
        else:
            raise FileNotFoundError(f"no such file or directory: {p}")
    return files


def modules_for(root: Path, paths: list[str]) -> list[ModuleInfo]:
    """ModuleInfos for ``paths`` under the repo-relative identity every
    consumer (AST rules, deep tier, baseline keys) must share — finding
    files must not depend on how a path was spelled on the command line."""
    modules = []
    for f in _collect_files(root, paths):
        try:
            rel = str(f.relative_to(root))
        except ValueError:
            rel = str(f)
        modules.append(ModuleInfo(f, rel))
    return modules


def lint_paths(
    paths: list[str],
    *,
    root: Path | None = None,
    rules=None,
    project_wide: bool = True,
) -> list[Finding]:
    """AST rules over ``paths`` (files or directories), sorted findings.

    ``project_wide`` builds the cross-module jit-reachability fixpoint
    over everything collected (the trace-purity rule needs it); fixture
    runs on single files can disable it to get module-local semantics.
    """
    from tpu_gossip.analysis import rules_purity

    root = repo_root() if root is None else root
    modules = modules_for(root, paths)
    rules_purity.set_project(Project(modules) if project_wide else None)
    try:
        findings: list[Finding] = []
        for m in modules:
            findings.extend(run_rules(m, only=rules))
    finally:
        rules_purity.set_project(None)
    return sorted(findings, key=lambda f: (f.file, f.line, f.col, f.rule))


def run_repo_lint(
    with_contracts: bool = False, with_deep: bool = False
) -> dict:
    """Programmatic entry: returns
    ``{"clean": bool, "new": [...], "baselined": n}`` over the default
    scope + baseline. ``with_deep`` adds the jaxpr deep tier IN-PROCESS
    (``deep_seconds`` records its wall time; the entry-point traces are
    shared with the contract audit through one per-invocation cache) —
    note it forces an 8-device XLA_FLAGS if none is set, so callers that
    must keep their own device layout run the CLI in a subprocess
    instead."""
    root = repo_root()
    findings = lint_paths(list(_DEFAULT_SCOPE), root=root)
    out: dict = {}
    cache: dict = {}
    if with_contracts or with_deep:
        _ensure_multi_device_env()
    if with_contracts:
        from tpu_gossip.analysis.contracts import audit_contracts

        findings = findings + audit_contracts(cache=cache)
    if with_deep:
        from tpu_gossip.analysis.deep import run_deep

        t0 = time.perf_counter()
        findings = findings + run_deep(cache=cache)
        out["deep_seconds"] = round(time.perf_counter() - t0, 2)
    baseline = load_baseline(root / DEFAULT_BASELINE)
    new, old = split_new(findings, baseline)
    out.update({
        "clean": not new,
        "new": [f.to_dict() for f in new],
        "baselined": len(old),
    })
    return out


def _print_planes(args) -> int:
    """The --planes table: every PLANES entry priced at the given (n, m)
    — compute dtype, info bits, packed storage encoding, unpacked and
    packed B/peer — plus the matching family's declared plan-table
    widths. Pure registry/host arithmetic: no arrays are built, so the
    packing headroom is inspectable at 100M without reading state.py."""
    try:
        n, m = (int(x) for x in args.planes_shape.split(","))
    except ValueError:
        print(f"--planes-shape wants N,M; got {args.planes_shape!r}",
              file=sys.stderr)
        return 2
    from tpu_gossip.core.matching_topology import plan_table_widths
    from tpu_gossip.core.state import (
        PLANES, state_plane_bytes, state_bytes_per_peer,
    )

    unpacked = state_plane_bytes(n, m)
    packed = state_plane_bytes(n, m, packed=True)
    print(f"PLANES registry priced at N={n:,} M={m} "
          f"(core/state.py; storage codec core/packed.py)")
    hdr = (f"{'plane':<16} {'dtype':<6} {'shape':<8} {'bits':>4} "
           f"{'storage':<7} {'B/peer':>9} {'packed':>9} {'saved':>8}")
    print(hdr)
    print("-" * len(hdr))
    for p in PLANES:
        u = unpacked[p.name] / n
        q = packed[p.name] / n
        print(f"{p.name:<16} {p.dtype:<6} {p.shape:<8} {p.info_bits:>4} "
              f"{(p.packed or '-'):<7} {u:>9.3f} {q:>9.3f} {u - q:>8.3f}")
    tot_u = state_bytes_per_peer(n, m)
    tot_p = state_bytes_per_peer(n, m, packed=True)
    print("-" * len(hdr))
    print(f"{'TOTAL':<16} {'':<6} {'':<8} {'':>4} {'':<7} "
          f"{tot_u:>9.3f} {tot_p:>9.3f} {tot_u - tot_p:>8.3f}")
    print(f"\nmatching plan tables at N={n:,}, "
          f"{args.planes_shards} shards (declared widths, saturating at "
          f"DEG_TABLE_CAP; core/matching_topology.py):")
    for name, row in plan_table_widths(
        n, n_shards=args.planes_shards
    ).items():
        print(f"  {name:<10} {row['dtype']:<6} {row['shape']:<18} "
              f"{row['bytes'] / 1e6:>10.2f} MB  {row['why']}")
    return 0


def _ensure_multi_device_env() -> None:
    """Give the contract audit its 8-CPU mesh: XLA reads XLA_FLAGS at
    backend CREATION, which is lazy — so setting it here works even though
    jax was imported with the package, as long as no computation ran yet
    (same trick as tests/conftest.py). A user-provided device-count flag
    is respected."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="tpu-gossip-lint",
        description="graftlint: JAX-invariant static analysis for tpu-gossip "
        "(key linearity, shard_map hygiene, trace purity, static_argnames "
        "drift) plus an eval_shape contract audit.",
    )
    ap.add_argument(
        "paths", nargs="*",
        help="files/directories to lint (default: tpu_gossip/ + contract "
        "audit)",
    )
    ap.add_argument(
        "--format", choices=("text", "json"), default="text",
        help="json emits {findings, new, baselined, clean} for tooling diffs",
    )
    ap.add_argument(
        "--fail-on-new", action="store_true",
        help="exit 1 when findings beyond the baseline exist (the default "
        "semantics; accepted explicitly for CI invocations)",
    )
    ap.add_argument(
        "--rules", default=None,
        help="comma-separated rule subset (default: all AST rules)",
    )
    ap.add_argument(
        "--no-contracts", action="store_true",
        help="skip the eval_shape contract audit (AST rules only)",
    )
    ap.add_argument(
        "--contracts-only", action="store_true",
        help="run only the contract audit",
    )
    ap.add_argument(
        "--deep", action="store_true",
        help="add the jaxpr deep tier (RNG lineage, float-reduction "
        "order, use-after-donate) — traces the shared entry-point matrix "
        "once, reusing the contract audit's traces",
    )
    ap.add_argument(
        "--deep-only", action="store_true",
        help="run only the deep tier",
    )
    ap.add_argument(
        "--mem", action="store_true",
        help="add the graftmem memory tier (plane ledger + live-range "
        "residency, declared-width audit, static wire cross-check, "
        "memory_budget.toml gate) — shares the entry-point traces with "
        "the audit and deep tiers",
    )
    ap.add_argument(
        "--mem-only", action="store_true",
        help="run only the memory tier",
    )
    ap.add_argument(
        "--budget", default=None,
        help="memory budget file (default: <repo>/memory_budget.toml)",
    )
    ap.add_argument(
        "--write-budget", action="store_true",
        help="write the current per-entry residency ledgers to the "
        "memory budget file and exit 0 (the committed diff is the "
        "review surface)",
    )
    ap.add_argument(
        "--collectives-lock", default=None,
        help="collective lock file (default: <repo>/collectives.lock)",
    )
    ap.add_argument(
        "--write-collectives-lock", action="store_true",
        help="trace the matrix, write every mesh entry's collective "
        "program (ordered ops + per-axis ici/dcn byte columns) to the "
        "lock file and exit 0 (the committed diff is the review surface)",
    )
    ap.add_argument(
        "--check-collectives-lock", action="store_true",
        help="fail when any mesh entry's traced collective program "
        "drifted from the committed lock file (deep-collective-lock-"
        "drift findings; stale lock entries report but do not fail)",
    )
    ap.add_argument(
        "--deep-selftest", action="store_true",
        help="run the deep tier's adversarial self-test fixtures (a "
        "deliberately divergent collective, a deliberate out-of-codec "
        "unpack) and exit 0 iff both rules fire — the gate that keeps "
        "the gate honest",
    )
    ap.add_argument(
        "--baseline", default=None,
        help=f"baseline file (default: <repo>/{DEFAULT_BASELINE})",
    )
    ap.add_argument(
        "--write-baseline", action="store_true",
        help="write current findings to the baseline file and exit 0",
    )
    ap.add_argument(
        "--list-rules", action="store_true", help="print rule ids and exit"
    )
    ap.add_argument(
        "--planes", action="store_true",
        help="print the priced PLANES registry table (dtype, info bits, "
        "packed storage, B/peer at --planes-shape) plus the matching "
        "family's declared plan-table widths, then exit — the packing "
        "headroom without reading state.py",
    )
    ap.add_argument(
        "--planes-shape", default="1000000,16", metavar="N,M",
        help="swarm shape the --planes table prices (default 1000000,16)",
    )
    ap.add_argument(
        "--planes-shards", type=int, default=8, metavar="S",
        help="mesh size for the --planes matching-table ledger (default 8)",
    )
    args = ap.parse_args(argv)

    if args.list_rules:
        for rid in sorted(RULES):
            print(rid)
        return 0

    if args.planes:
        return _print_planes(args)

    if args.deep_selftest:
        # the gate that keeps the gate honest: the adversarial fixtures
        # (divergent collective, out-of-codec unpack) must still FIRE and
        # the sanctioned word-kernel fixture must stay clean
        _ensure_multi_device_env()
        from tpu_gossip.analysis.deep.selftest import run_selftest

        failures = run_selftest()
        for msg in failures:
            print(f"deep-selftest FAIL: {msg}", file=sys.stderr)
        print(
            "deep-selftest: "
            + ("adversarial fixtures fired, word-kernel fixture clean"
               if not failures else f"{len(failures)} dead rail(s)"),
            file=sys.stderr,
        )
        return 1 if failures else 0

    root = repo_root()
    only = (
        [r.strip() for r in args.rules.split(",") if r.strip()]
        if args.rules
        else None
    )
    if only:
        unknown = set(only) - set(RULES)
        if unknown:
            print(
                f"unknown rule(s): {', '.join(sorted(unknown))} "
                f"(known: {', '.join(sorted(RULES))})",
                file=sys.stderr,
            )
            return 2

    explicit_paths = bool(args.paths)
    # the memory tier is trace-only (no AST side): explicit-path runs
    # lint sources without importing the fixtures' runtime, so the
    # mem-only modes cannot run there — a silent no-op would exit 0
    # having analyzed NOTHING, which is worse than refusing
    if (args.write_budget or args.mem_only or args.write_collectives_lock
            or args.check_collectives_lock) and explicit_paths:
        print(
            "--mem-only/--write-budget/--write-collectives-lock/"
            "--check-collectives-lock trace the full entry-point matrix; "
            "they cannot run with explicit paths",
            file=sys.stderr,
        )
        return 2
    # --write-collectives-lock is a dedicated mode (pattern of
    # --write-budget): only the trace + program extraction run, nothing
    # the early exit could swallow
    if args.write_collectives_lock:
        _ensure_multi_device_env()
        from tpu_gossip.analysis.deep.collectives import (
            collective_report,
            write_lock,
        )
        from tpu_gossip.analysis.entrypoints import entry_points, trace_matrix

        traced = trace_matrix(entry_points(), cache={})
        _, programs = collective_report(traced)
        lock_path = (
            Path(args.collectives_lock) if args.collectives_lock
            else root / "collectives.lock"
        )
        write_lock(lock_path, programs)
        print(
            f"wrote {len(programs)} collective program(s) to {lock_path}",
            file=sys.stderr,
        )
        return 0
    run_contracts = (
        (not args.no_contracts and not explicit_paths and only is None)
        or args.contracts_only
    ) and not (args.deep_only or args.mem_only or args.write_budget)
    run_deep_tier = (
        (args.deep or args.deep_only)
        and not (args.mem_only or args.write_budget)
    )
    run_mem_tier = (
        args.mem or args.mem_only or args.write_budget
    ) and not explicit_paths
    t0 = time.perf_counter()
    findings: list[Finding] = []
    # --write-budget is a dedicated mode: only the mem trace runs (an AST
    # lint or contract audit whose findings the early exit would swallow
    # must not run at all)
    if not (args.contracts_only or args.deep_only or args.mem_only
            or args.write_budget):
        try:
            findings = lint_paths(
                args.paths or list(_DEFAULT_SCOPE), root=root, rules=only
            )
        except (FileNotFoundError, SyntaxError) as e:
            print(str(e), file=sys.stderr)
            return 2
    # one per-invocation trace cache: the audit and the deep tier walk the
    # SAME entry-point matrix (analysis/entrypoints.py) and must pay the
    # make_jaxpr cost once between them
    trace_cache: dict = {}
    if run_contracts:
        _ensure_multi_device_env()
        from tpu_gossip.analysis.contracts import audit_contracts

        findings = findings + audit_contracts(cache=trace_cache)
    if run_deep_tier:
        from tpu_gossip.analysis.deep import run_deep

        if explicit_paths:
            # explicit-path runs lint sources only (fixture linting must
            # not import the fixtures' runtime): AST-side pass only
            try:
                mods = modules_for(root, args.paths)
            except (FileNotFoundError, SyntaxError) as e:
                print(str(e), file=sys.stderr)
                return 2
            findings = findings + run_deep(modules=mods, trace=False)
        else:
            _ensure_multi_device_env()
            findings = findings + run_deep(cache=trace_cache)
    mem_report = None
    mem_seconds = None
    if run_mem_tier:
        _ensure_multi_device_env()
        from tpu_gossip.analysis.mem import run_mem

        t_mem = time.perf_counter()
        mem_findings, mem_report = run_mem(
            cache=trace_cache,
            budget_path=args.budget,
            check_budget=not args.write_budget,
        )
        mem_seconds = round(time.perf_counter() - t_mem, 2)
        ledgers = mem_report.pop("ledgers")
        if args.write_budget:
            from tpu_gossip.analysis.mem.budget import write_budget

            budget_path = (
                Path(args.budget) if args.budget
                else root / "memory_budget.toml"
            )
            write_budget(budget_path, ledgers)
            print(
                f"wrote {len(ledgers)} entry budget(s) to {budget_path}",
                file=sys.stderr,
            )
            return 0
        findings = findings + mem_findings

    coll_report = None
    if args.check_collectives_lock:
        # lock freshness only: uniformity findings come from the deep
        # tier itself (running both must not double-report), and stale
        # lock entries (committed on a host where more of the matrix
        # traced, e.g. the dist cells) report without failing
        _ensure_multi_device_env()
        from tpu_gossip.analysis.deep.collectives import (
            collective_report,
            load_lock,
            lock_findings,
        )
        from tpu_gossip.analysis.entrypoints import entry_points, trace_matrix

        traced = trace_matrix(entry_points(), cache=trace_cache)
        _, programs = collective_report(traced)
        lock_path = (
            Path(args.collectives_lock) if args.collectives_lock
            else root / "collectives.lock"
        )
        drift, stale = lock_findings(programs, load_lock(lock_path))
        findings = findings + drift
        if stale:
            print(
                f"collectives.lock: {len(stale)} stale entr"
                f"{'y' if len(stale) == 1 else 'ies'} (locked but not "
                f"traced on this host): {', '.join(stale)}",
                file=sys.stderr,
            )
        coll_report = {
            "lock": str(lock_path),
            "entries": sorted(programs),
            "drift": len(drift),
            "stale": stale,
        }

    baseline_path = Path(args.baseline) if args.baseline else root / DEFAULT_BASELINE
    if args.write_baseline:
        write_baseline(baseline_path, findings)
        print(
            f"wrote {len(findings)} finding(s) to {baseline_path}",
            file=sys.stderr,
        )
        return 0
    baseline = load_baseline(baseline_path)
    new, old = split_new(findings, baseline)
    elapsed = time.perf_counter() - t0

    if args.format == "json":
        # identity-stable order (file, rule, qualname, message) — NOT line
        # numbers, so unrelated edits above a finding don't churn diffs of
        # the machine-readable output (the same reason baseline keys drop
        # line numbers)
        print(
            json.dumps(
                {
                    "clean": not new,
                    "new": [
                        f.to_dict() for f in sorted(
                            new, key=lambda f: f.sort_key
                        )
                    ],
                    "baselined": [
                        f.to_dict() for f in sorted(
                            old, key=lambda f: f.sort_key
                        )
                    ],
                    "rules": sorted(RULES),
                    "contract_audit": run_contracts,
                    "deep": run_deep_tier,
                    "mem": run_mem_tier,
                    # entries are name-sorted (run_mem) — the same
                    # identity-stable-diff property as the findings order
                    "mem_report": mem_report,
                    "mem_seconds": mem_seconds,
                    "collectives": coll_report,
                    "elapsed_seconds": round(elapsed, 2),
                },
                indent=1,
                sort_keys=True,
            )
        )
    else:
        for f in new:
            print(f.render())
        tail = (
            f"graftlint: {len(new)} new finding(s), {len(old)} baselined, "
            f"{len(RULES)} rules"
            + (", contract audit on" if run_contracts else "")
            + (", deep tier on" if run_deep_tier else "")
            + (", mem tier on" if run_mem_tier else "")
            + f", {elapsed:.1f}s"
        )
        print(tail, file=sys.stderr)
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())
