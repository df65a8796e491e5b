"""CLI contract: exit codes, JSON output, baseline interplay — the exact
interface CI depends on."""

import json
from pathlib import Path

import pytest

from tpu_gossip.analysis.cli import main, run_repo_lint

FIXTURES = Path(__file__).parent / "fixtures"


def test_bad_fixture_exits_nonzero(capsys):
    for bad in sorted(FIXTURES.glob("bad_*.py")):
        rc = main([str(bad)])
        out = capsys.readouterr()
        assert rc == 1, f"{bad.name} should fail: {out.out}\n{out.err}"


def test_good_fixtures_exit_zero(capsys):
    for good in sorted(FIXTURES.glob("good_*.py")):
        rc = main([str(good)])
        out = capsys.readouterr()
        assert rc == 0, f"{good.name} should pass: {out.out}"


def test_repo_ast_lint_clean(capsys):
    rc = main(["--no-contracts"])
    capsys.readouterr()
    assert rc == 0


def test_json_format(capsys):
    rc = main([str(FIXTURES / "bad_shard_map.py"), "--format=json"])
    data = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert data["clean"] is False
    assert data["new"], "json output must carry the findings"
    f = data["new"][0]
    assert {"file", "line", "col", "rule", "message", "hint"} <= set(f)
    assert "rules" in data and "elapsed_seconds" in data


def test_json_output_identity_sorted(capsys):
    """--format=json orders findings by (file, rule, qualname, message),
    NOT by line number — unrelated edits that shift lines must not churn
    diffs of the machine-readable output (same reason baseline keys drop
    line numbers)."""
    rc = main([
        str(FIXTURES / "bad_key_reuse.py"),
        str(FIXTURES / "bad_shard_map.py"),
        "--format=json",
    ])
    data = json.loads(capsys.readouterr().out)
    assert rc == 1
    keys = [
        (f["file"], f["rule"], f.get("qualname", ""), f["message"])
        for f in data["new"]
    ]
    assert keys == sorted(keys), keys
    # findings from multiple rules/files present: the sort is exercised
    assert len({k[1] for k in keys}) >= 2


def test_deep_flag_on_explicit_paths_lints_ast_side(capsys):
    """--deep with explicit paths runs the AST-side donation pass (no
    tracing — fixture linting must not import the fixtures' runtime):
    the deep_bad fixture fails, the deep_good twin stays clean."""
    rc = main([str(FIXTURES / "deep_bad_use_after_donate.py"), "--deep"])
    out = capsys.readouterr()
    assert rc == 1, out.out
    assert "deep-use-after-donate" in out.out
    rc = main([str(FIXTURES / "deep_good_use_after_donate.py"), "--deep"])
    capsys.readouterr()
    assert rc == 0
    # without --deep the read-after-donate is invisible to the AST rules
    rc = main([str(FIXTURES / "deep_bad_use_after_donate.py")])
    capsys.readouterr()
    assert rc == 0


@pytest.mark.slow  # whole-tree AST walk just to accept a flag; CI's lint
# job passes --fail-on-new on every push
def test_fail_on_new_flag_accepted(capsys):
    rc = main(["--no-contracts", "--fail-on-new"])
    capsys.readouterr()
    assert rc == 0


def test_rules_subset(capsys):
    rc = main([str(FIXTURES / "bad_shard_map.py"), "--rules=key-linearity"])
    capsys.readouterr()
    assert rc == 0  # shard_map fixture is clean under the key rule alone
    rc = main([str(FIXTURES / "bad_shard_map.py"), "--rules=raw-shard-map"])
    capsys.readouterr()
    assert rc == 1


def test_unknown_rule_usage_error(capsys):
    rc = main(["--rules=no-such-rule"])
    capsys.readouterr()
    assert rc == 2


def test_write_and_respect_baseline(tmp_path, capsys):
    bad = str(FIXTURES / "bad_shard_map.py")
    bl = tmp_path / "baseline.toml"
    assert main([bad, "--write-baseline", f"--baseline={bl}"]) == 0
    capsys.readouterr()
    # baselined findings no longer fail...
    assert main([bad, f"--baseline={bl}"]) == 0
    capsys.readouterr()
    # ...but a different bad fixture still does
    assert main([str(FIXTURES / "bad_key_reuse.py"), f"--baseline={bl}"]) == 1
    capsys.readouterr()


@pytest.mark.slow  # whole-tree walk; the API shape is pinned here, the
# clean-tree claim is CI's lint job every push
def test_run_repo_lint_programmatic():
    out = run_repo_lint()
    assert out["clean"] is True, out["new"]
    assert out["new"] == []
    assert isinstance(out["baselined"], int)


def test_list_rules(capsys):
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out.split()
    assert "key-linearity" in out and "trace-purity" in out
