"""syndeo-lint's own tests: the fixture corpus (every rule proven to
fire at exact lines, and to stay quiet on the repaired twin), the
baseline machinery, and the real-tree regression pinning
``src/repro/core`` to zero unsuppressed findings."""
import textwrap
from pathlib import Path

import pytest

from repro.analysis import run_analysis
from repro.analysis.__main__ import main as lint_main
from repro.analysis.baseline import apply_baseline, load_baseline
from repro.analysis.model import Finding

REPO = Path(__file__).resolve().parents[1]
FIXTURES = Path(__file__).resolve().parent / "analysis_fixtures"


def _findings(name):
    return run_analysis([str(FIXTURES / name)])


# -- fixture corpus: known-bad fires exactly, known-good stays quiet ----

KNOWN_BAD = {
    "lock_bad.py": [("SYN-L001", 14), ("SYN-L001", 19)],
    "lock_order_bad.py": [("SYN-L002", 13)],
    "taint_bad.py": [("SYN-A001", 11)],
    "verify_bad.py": [("SYN-A002", 14)],
    "nonce_bad.py": [("SYN-A003", 6)],
    "wire_bad.py": [("SYN-W001", 28), ("SYN-W002", 12),
                    ("SYN-W003", 13)],
    "wire_batch_bad.py": [("SYN-W001", 28), ("SYN-W002", 13)],
    "wire_blobs_bad.py": [("SYN-W001", 35), ("SYN-W002", 18)],
    "wire_actor_bad.py": [("SYN-W001", 28), ("SYN-W002", 17),
                          ("SYN-W003", 15)],
    # metric-delta pass: W001 fires once per send site of the unfolded
    # "hists" payload (exit flush AND queued batch sub-op)
    "wire_metrics_bad.py": [("SYN-W001", 44), ("SYN-W001", 50),
                            ("SYN-W002", 27)],
}

KNOWN_GOOD = ["lock_good.py", "lock_order_good.py", "taint_good.py",
              "verify_good.py", "nonce_good.py", "wire_good.py",
              "wire_batch_good.py", "wire_blobs_good.py",
              "wire_actor_good.py", "wire_metrics_good.py"]


@pytest.mark.parametrize("name,expected", sorted(KNOWN_BAD.items()))
def test_known_bad_fires_exact_rules_and_lines(name, expected):
    got = sorted((f.rule, f.line) for f in _findings(name))
    assert got == sorted(expected)


@pytest.mark.parametrize("name", KNOWN_GOOD)
def test_known_good_is_clean(name):
    assert _findings(name) == []


def test_findings_carry_function_and_message():
    by_line = {f.line: f for f in _findings("lock_bad.py")}
    direct = by_line[14]
    assert direct.function == "Cache.refresh"
    assert "Cache._lock" in direct.message
    transitive = [f for f in _findings("lock_bad.py")
                  if f.function == "Cache.tick"]
    assert transitive and "time.sleep" in transitive[0].message


def test_transitive_chain_in_message():
    (f,) = [x for x in _findings("lock_bad.py") if x.line == 19]
    assert "via" in f.message  # witness chain, not a bare verdict


def test_lock_order_cycle_names_both_locks():
    (f,) = _findings("lock_order_bad.py")
    assert "Ledger._lock" in f.message and "Mirror._lock" in f.message


def test_render_format_is_clickable():
    (f,) = _findings("nonce_bad.py")
    assert f.render().startswith(f"{f.file}:{f.line}: SYN-A003 ")


# -- baseline machinery -------------------------------------------------


def _finding(rule="SYN-L001", file="src/repro/core/worker.py", line=1,
             function="HeadServer.dispatch", message="call x() blocks"):
    return Finding(rule, file, line, function, message)


def test_baseline_matches_on_rule_file_function_and_match():
    entries = [{"rule": "SYN-L001", "file": "worker.py",
                "function": "HeadServer.dispatch", "match": "x()",
                "reason": "documented"}]
    unsup, sup, unused = apply_baseline([_finding()], entries)
    assert not unsup and len(sup) == 1 and not unused


def test_baseline_does_not_match_other_function_or_rule():
    entries = [{"rule": "SYN-L001", "file": "worker.py",
                "function": "BlobServer._handle", "reason": "r"}]
    unsup, _, unused = apply_baseline([_finding()], entries)
    assert len(unsup) == 1 and len(unused) == 1


def test_baseline_loader_rejects_missing_reason(tmp_path):
    p = tmp_path / "b.toml"
    p.write_text('[[suppress]]\nrule = "SYN-L001"\nfile = "x.py"\n')
    with pytest.raises(ValueError, match="reason"):
        load_baseline(str(p))


def test_baseline_loader_round_trips_the_shape(tmp_path):
    p = tmp_path / "b.toml"
    p.write_text(textwrap.dedent('''
        # comment
        [[suppress]]
        rule = "SYN-A002"
        file = "worker.py"
        reason = "verified in _handle() before the \\"blob\\" frame"

        [[suppress]]
        rule = "SYN-L001"
        file = "cluster.py"
        reason = "bounded"
    '''))
    entries = load_baseline(str(p))
    assert [e["rule"] for e in entries] == ["SYN-A002", "SYN-L001"]
    assert '"blob"' in entries[0]["reason"]


def test_repo_baseline_loads():
    entries = load_baseline(str(REPO / "analysis" / "baseline.toml"))
    assert entries and all(e.get("reason") for e in entries)


# -- CLI ----------------------------------------------------------------


def test_cli_exit_codes(capsys):
    assert lint_main([str(FIXTURES / "lock_bad.py"),
                      "--no-baseline"]) == 1
    assert "SYN-L001" in capsys.readouterr().out
    assert lint_main([str(FIXTURES / "lock_good.py"),
                      "--no-baseline"]) == 0


# -- real-tree regression ----------------------------------------------


def test_real_tree_has_zero_unsuppressed_findings():
    """The CI gate: src/repro/core is clean modulo the reviewed
    baseline, and the baseline carries no stale entries."""
    findings = run_analysis([str(REPO / "src" / "repro" / "core")])
    entries = load_baseline(str(REPO / "analysis" / "baseline.toml"))
    unsuppressed, suppressed, unused = apply_baseline(findings, entries)
    assert unsuppressed == [], "\n".join(f.render()
                                         for f in unsuppressed)
    assert unused == [], f"stale baseline entries: {unused}"
    assert suppressed, "baseline expected to cover documented exceptions"


def test_real_tree_wire_protocol_is_symmetric():
    """No unsuppressed W-rule findings: every op sent in-tree has a
    handler and every required field is sent."""
    findings = run_analysis([str(REPO / "src" / "repro" / "core")])
    assert [f for f in findings if f.rule.startswith("SYN-W")] == []
