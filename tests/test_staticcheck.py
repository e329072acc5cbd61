"""Tier-1 enforcement of the project-native static analyzer (ISSUE 11).

Three layers:

* the REPO ITSELF must lint clean — `staticcheck.run_default()` walks
  butterfly_tpu/, tools/, tests/ (minus the fixture snippets, which
  violate rules by design) and must return zero unsuppressed findings;
  every inline suppression must carry a reason;
* each rule must FIRE on its positive fixture and stay SILENT on its
  negative one (tests/staticcheck_fixtures/) — the contract
  tools/mutcheck.py's analyzer mutants verify stays sharp: weakening
  any one rule predicate makes its positive-count assertion fail;
* the driver surfaces behave: CLI exit codes, suppression mechanics,
  and the `butterfly lint` subcommand.

Stdlib-only (AST analysis): fast tier.
"""
from pathlib import Path
import subprocess
import sys

import pytest

REPO = Path(__file__).parent.parent
TOOLS = REPO / "tools"
FIXTURES = Path(__file__).parent / "staticcheck_fixtures"

sys.path.insert(0, str(TOOLS))
import staticcheck  # noqa: E402
import staticrules  # noqa: E402


def lint_fixture(name: str, rule_id: str):
    """Run exactly one rule over one fixture file (force=True: fixtures
    live outside the rule's deployment scope on purpose)."""
    rule = staticrules.RULES[rule_id]
    return staticrules.check_file(FIXTURES / name, rules=[rule],
                                  force=True)


# -- the rule catalog ---------------------------------------------------------

EXPECTED_RULES = {
    "BTF001": "outbound-http-timeout",
    "BTF002": "use-after-donation",
    "BTF003": "host-sync-in-hot-path",
    "BTF004": "lock-discipline",
    "BTF005": "workload-determinism",
    "BTF006": "prng-key-discipline",
}

#: rule -> expected finding count on its positive fixture. Pinned as
#: exact counts (not >= 1) so a weakened predicate that still catches
#: SOME sites — the mutcheck analyzer mutants — fails loudly.
POSITIVE_COUNTS = {
    "BTF001": 4,
    "BTF002": 8,
    "BTF003": 10,
    "BTF004": 7,
    "BTF005": 7,
    "BTF006": 3,
}


def test_all_rules_registered():
    assert set(EXPECTED_RULES) <= set(staticrules.RULES)
    for rid, name in EXPECTED_RULES.items():
        rule = staticrules.RULES[rid]
        assert rule.name == name
        assert rule.invariant, f"{rid} must state its invariant"
        assert rule.scope, f"{rid} must declare a scope"


@pytest.mark.parametrize("rid", sorted(EXPECTED_RULES))
def test_rule_fires_on_positive_fixture(rid):
    found = [f for f in lint_fixture(f"btf{rid[3:]}_pos.py", rid)
             if f.rule == rid]
    assert len(found) == POSITIVE_COUNTS[rid], \
        f"{rid} expected {POSITIVE_COUNTS[rid]} findings, got:\n" \
        + "\n".join(f.render() for f in found)
    assert all(not f.suppressed for f in found)


@pytest.mark.parametrize("rid", sorted(EXPECTED_RULES))
def test_rule_silent_on_negative_fixture(rid):
    found = [f for f in lint_fixture(f"btf{rid[3:]}_neg.py", rid)
             if f.rule == rid]
    assert not found, "false positives on the negative fixture:\n" \
        + "\n".join(f.render() for f in found)


# -- the repo itself ----------------------------------------------------------

def test_repo_tree_lints_clean():
    """THE acceptance gate: butterfly_tpu/ + tools/ + tests/ carry zero
    unsuppressed findings. A new violation anywhere in the walked trees
    fails tier-1 — the machine check the last ten PRs did by hand."""
    findings = staticcheck.run_default()
    assert not findings, "unsuppressed staticcheck findings:\n" \
        + "\n".join(f.render() for f in findings)


def test_no_bare_suppressions_in_repo():
    """Every `# btf: disable=` in the walked trees must carry a reason
    (a bare one would also surface as BTF000 in the clean-tree test;
    this pins the contract directly and readably)."""
    bare = []
    for f in staticcheck.iter_py_files(
            [REPO / t for t in staticcheck.DEFAULT_TREES]):
        for s in staticrules.parse_suppressions(f.read_text()):
            if not s.reason:
                bare.append(f"{f.relative_to(REPO)}:{s.line}")
    assert not bare, f"reason-less suppressions: {bare}"


def test_repo_suppressions_are_used_and_scarce():
    """Suppressions must point at real findings (a stale disable hides
    nothing and rots) and stay rare — the analyzer encodes contracts,
    not preferences."""
    findings = staticcheck.run_paths(
        [REPO / t for t in staticcheck.DEFAULT_TREES])
    suppressed = [f for f in findings if f.suppressed]
    assert suppressed, "expected the documented intentional exceptions"
    assert len(suppressed) < 20, \
        "suppression creep: fix the code or retune the rule"
    for f in suppressed:
        assert f.reason


# -- suppression mechanics ----------------------------------------------------

def test_suppression_mechanics():
    rule = staticrules.RULES["BTF001"]
    found = staticrules.check_file(FIXTURES / "suppression.py",
                                   rules=[rule], force=True)
    by_rule = {}
    for f in found:
        by_rule.setdefault(f.rule, []).append(f)
    btf1 = sorted(by_rule["BTF001"], key=lambda f: f.line)
    assert len(btf1) == 3
    reasoned, bare, multiline = btf1
    assert reasoned.suppressed and "reasoned suppression" in reasoned.reason
    assert not bare.suppressed, \
        "a reason-less disable must NOT suppress"
    assert multiline.suppressed, \
        "a standalone comment must cover the whole next statement"
    assert len(by_rule.get("BTF000", [])) == 1, \
        "the bare disable must itself be a BTF000 finding"


# -- driver surfaces ----------------------------------------------------------

def test_cli_clean_tree_exits_zero():
    r = subprocess.run([sys.executable, str(TOOLS / "staticcheck.py")],
                       cwd=REPO, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_violation_exits_one():
    r = subprocess.run(
        [sys.executable, str(TOOLS / "staticcheck.py"), "--force",
         str(FIXTURES / "btf001_pos.py")],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "BTF001" in r.stdout


def test_cli_list_rules():
    r = subprocess.run(
        [sys.executable, str(TOOLS / "staticcheck.py"), "--list-rules"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0
    for rid in EXPECTED_RULES:
        assert rid in r.stdout


def test_butterfly_lint_subcommand():
    """`butterfly lint` goes through serve/cli.py and must agree with
    the direct driver on the clean tree."""
    from butterfly_tpu.serve.cli import main
    assert main(["lint"]) == 0
