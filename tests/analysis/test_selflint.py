"""Self-lint: the repo's own tree is clean modulo the committed baseline.

This is the enforcement test — a new violation anywhere in tpu_gossip/ fails
HERE (and in CI via `python -m tpu_gossip.analysis`)
before it can land. Pragma hygiene is asserted alongside: every pragma in
the tree carries a reason.
"""

from tpu_gossip.analysis import lint_paths
from tpu_gossip.analysis.baseline import (
    DEFAULT_BASELINE, load_baseline, split_new,
)
from tpu_gossip.analysis.cli import _DEFAULT_SCOPE, repo_root


def test_repo_lints_clean_modulo_baseline():
    root = repo_root()
    findings = lint_paths(list(_DEFAULT_SCOPE), root=root)
    baseline = load_baseline(root / DEFAULT_BASELINE)
    new, _ = split_new(findings, baseline)
    assert new == [], "new lint findings:\n" + "\n".join(
        f.render() for f in new
    )


def test_baseline_is_empty():
    """The committed baseline carries NO suppressed debt: deliberate
    exceptions live as inline pragmas with reasons (ISSUE 2 satellite 1).
    If you are adding an entry here, prefer a pragma — or say why not in
    lint_baseline.toml."""
    root = repo_root()
    assert load_baseline(root / DEFAULT_BASELINE) == set()


def test_all_rules_registered():
    from tpu_gossip.analysis import RULES

    assert set(RULES) == {
        "key-linearity",
        "raw-shard-map",
        "trace-purity",
        "static-argnames-drift",
        "jit-state-donation",
    }
