"""ozlint tier-1 gate + analyzer unit tests (docs/LINT.md).

Three contracts:
1. ZERO unsuppressed findings over ozone_tpu/ — the committed baseline.
   Seeding any fixed violation back (a literal socket timeout in
   client/native_dn.py, an unfenced background DeleteKey, a jit keyed
   on an erasure pattern) fails this suite.
2. Each of the eight rules demonstrably trips on its known-bad fixture
   and stays quiet on the known-good one (tests/lint_fixtures/).
3. The CLI is fast and import-light: `python -m ozone_tpu.tools.lint
   --check` must run WITHOUT importing jax, so the gate costs seconds,
   not a jax cold start.
"""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

from ozone_tpu.tools.lint import (
    RULES,
    format_findings,
    lint_paths,
    lint_source,
    rewrite_legacy_suppressions,
)

ROOT = Path(__file__).resolve().parent.parent
FIXTURES = Path(__file__).resolve().parent / "lint_fixtures"

RULE_IDS = [
    "deadline-propagation",
    "blocking-under-lock",
    "fence-carrying-commit",
    "dispatch-shape-stability",
    "error-swallowing",
    "span-on-dispatch",
    "datapath-no-copy",
    "bounded-queue",
]


# ------------------------------------------------------------ the gate
def test_zero_findings_on_tree():
    """The committed baseline: every violation in ozone_tpu/ is either
    fixed or carries an in-line `# ozlint: allow[...] -- reason`."""
    findings = lint_paths([str(ROOT / "ozone_tpu")], root=str(ROOT))
    assert not findings, format_findings(findings)


def test_dispatch_shape_stability_covers_lrc_math(tmp_path):
    """The LRC repair planner is dispatch-adjacent code: its recovery
    matrices feed the fused decode as TRACED arguments, so the shipped
    codec/lrc_math.py must stay clean under dispatch-shape-stability —
    and an lrc-flavored plan factory that jits per erasure pattern must
    still trip the rule (the scope covers the new module, not just the
    rs-era ones)."""
    findings = lint_paths(
        [str(ROOT / "ozone_tpu" / "codec" / "lrc_math.py")],
        root=str(ROOT))
    assert not [f for f in findings
                if f.rule == "dispatch-shape-stability"], \
        format_findings(findings)

    bad = tmp_path / "bad_lrc_plan.py"
    bad.write_text(
        "# ozlint: path ozone_tpu/codec/lrc_plan.py\n"
        "from functools import lru_cache\n"
        "import jax\n\n\n"
        "@lru_cache(maxsize=512)\n"
        "def lrc_repair_plan(options, erased):\n"
        "    @jax.jit\n"
        "    def fn(units):\n"
        "        return units\n\n"
        "    return fn\n")
    findings = lint_paths([str(bad)])
    assert any(f.rule == "dispatch-shape-stability" for f in findings), \
        "per-pattern jitted LRC plan factory must trip the rule"


def test_all_eight_rules_registered():
    for rid in RULE_IDS:
        assert rid in RULES, f"rule {rid} not registered"
        assert RULES[rid].summary and RULES[rid].rationale


def test_cli_check_exits_zero_without_importing_jax():
    """`--check` is the CI surface: exit 0 on the clean tree, and the
    whole run must not import jax (the <5 s budget is only possible
    import-light) — under the same JAX_PLATFORMS=cpu environment every
    other test child gets, since the package __init__ pins nothing."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys\n"
         "from ozone_tpu.tools.lint.__main__ import main\n"
         "rc = main(['--check', 'ozone_tpu'])\n"
         "assert 'jax' not in sys.modules, 'lint imported jax'\n"
         "sys.exit(rc)"],
        cwd=str(ROOT), capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert proc.stdout.strip().endswith("0 findings")


def test_cli_nonzero_on_findings_and_list_rules(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(FIXTURES.joinpath(
        "bad_error_swallowing.py").read_text())
    proc = subprocess.run(
        [sys.executable, "-m", "ozone_tpu.tools.lint", str(bad)],
        cwd=str(ROOT), capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1
    assert "error-swallowing" in proc.stdout
    proc = subprocess.run(
        [sys.executable, "-m", "ozone_tpu.tools.lint", "--list-rules"],
        cwd=str(ROOT), capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0
    for rid in RULE_IDS:
        assert rid in proc.stdout


# ------------------------------------------------- fixture corpus: bad
@pytest.mark.parametrize("rule", RULE_IDS)
def test_bad_fixture_trips_its_rule(rule):
    path = FIXTURES / f"bad_{rule.replace('-', '_')}.py"
    findings = lint_paths([str(path)])
    assert findings, f"{path.name} tripped nothing"
    assert {f.rule for f in findings} == {rule}, format_findings(findings)
    # each fixture packs several distinct violation shapes of its rule
    assert len(findings) >= 2, format_findings(findings)


@pytest.mark.parametrize("rule", RULE_IDS)
def test_good_fixture_is_clean(rule):
    path = FIXTURES / f"good_{rule.replace('-', '_')}.py"
    findings = lint_paths([str(path)])
    assert not findings, format_findings(findings)


# --------------------------------------------------- golden output pin
def test_finding_output_format_golden():
    """Pin the rendered finding format: `path:line: rule-id: message`.
    Tooling (editors, CI annotations) parses this shape."""
    src = (
        "# ozlint: path ozone_tpu/client/_fx.py\n"
        "import socket\n"
        "s = socket.create_connection(('h', 1), timeout=9.5)\n"
    )
    findings = lint_source(src, path="ozone_tpu/client/_fx.py")
    assert len(findings) == 1
    assert findings[0].render() == (
        "ozone_tpu/client/_fx.py:3: deadline-propagation: socket "
        "connect timeout is a numeric literal — derive it from "
        "resilience.op_timeout()/Deadline.timeout() or a documented "
        "env knob")
    assert format_findings(findings).endswith("\nozlint: 1 finding")
    assert format_findings([]).strip() == "ozlint: 0 findings"


# ----------------------------------------------- suppression semantics
def test_suppression_same_line_with_reason():
    src = ("# ozlint: path ozone_tpu/client/_fx.py\n"
           "s.settimeout(5)  # ozlint: allow[deadline-propagation]"
           " -- fixture reason\n")
    assert not lint_source(src, path="x.py")


def test_suppression_own_line_covers_next_statement():
    src = ("# ozlint: path ozone_tpu/client/_fx.py\n"
           "# ozlint: allow[deadline-propagation] -- fixture reason\n"
           "s.settimeout(\n    5)\n")
    assert not lint_source(src, path="x.py")


def test_suppression_requires_reason():
    src = ("# ozlint: path ozone_tpu/client/_fx.py\n"
           "s.settimeout(5)  # ozlint: allow[deadline-propagation]\n")
    findings = lint_source(src, path="x.py")
    assert [f.rule for f in findings] == ["suppression-format"]
    assert "missing `-- reason`" in findings[0].message


def test_suppression_unknown_rule_is_flagged():
    src = ("s = 1  # ozlint: allow[no-such-rule] -- whatever\n")
    findings = lint_source(src, path="x.py")
    assert [f.rule for f in findings] == ["suppression-format"]


def test_suppression_for_other_rule_does_not_mask():
    src = ("# ozlint: path ozone_tpu/client/_fx.py\n"
           "s.settimeout(5)  # ozlint: allow[error-swallowing]"
           " -- wrong rule\n")
    findings = lint_source(src, path="x.py")
    assert "deadline-propagation" in {f.rule for f in findings}


# ------------------------------------------ seeded-violation detection
def test_seeding_fixed_violation_back_fails(tmp_path):
    """The acceptance drill: re-introduce the PR 2 class of bug (a
    literal socket timeout in client/native_dn.py) and the analyzer
    must catch it — proving the committed baseline actually guards."""
    real = (ROOT / "ozone_tpu" / "client" / "native_dn.py").read_text()
    fenced = "timeout = resilience.op_timeout(_connect_timeout_s(), " \
             "\"connect\")"
    assert fenced in real, "native_dn connect no longer fenced?"
    seeded = real.replace(fenced, "timeout = 120.0")
    findings = lint_source(seeded, path="ozone_tpu/client/native_dn.py")
    assert any(f.rule == "deadline-propagation" for f in findings), \
        format_findings(findings)

    # and an unfenced background DeleteKey in re_encode (the PR 7 fix)
    re_enc = (ROOT / "ozone_tpu" / "client" / "re_encode.py").read_text()
    seeded = re_enc.replace(
        "om.commit_key(session, groups, writer.bytes_written)",
        "om.submit(rq.DeleteKey(volume, bucket, key))\n"
        "    om.commit_key(session, groups, writer.bytes_written)")
    findings = lint_source(seeded, path="ozone_tpu/client/re_encode.py")
    assert any(f.rule == "fence-carrying-commit" for f in findings)


# --------------------------------------------- legacy marker migration
def test_fix_suppressions_rewrites_legacy_marker(tmp_path):
    f = tmp_path / "legacy.py"
    f.write_text("# ozlint: path ozone_tpu/client/_fx.py\n"
                 "import time\n"
                 "time.sleep(d)  # resilience-lint: allow\n")
    changed = rewrite_legacy_suppressions([str(f)])
    assert changed == [str(f)]
    text = f.read_text()
    assert "resilience-lint" not in text
    assert "# ozlint: allow[deadline-propagation] -- " in text
    # the rewritten marker now suppresses the finding it used to
    assert not lint_paths([str(f)])


# ------------------------------------------------------- perf envelope
def test_analysis_is_fast_in_process():
    """The AST pass itself (imports excluded) stays comfortably inside
    the tier-1 budget: a second run over the whole tree must be cheap
    even on a loaded one-core rig."""
    import time

    t0 = time.monotonic()
    lint_paths([str(ROOT / "ozone_tpu")], root=str(ROOT))
    took = time.monotonic() - t0
    # generous load-aware ceiling: ~2.5 s quiet; scale by load like
    # test_acceptance._budget so contention doesn't flake the gate
    try:
        load = os.getloadavg()[0]
    except OSError:
        load = 1.0
    scale = min(4.0, max(1.0, load / max(1, os.cpu_count() or 1)))
    assert took < 10.0 * scale, f"lint pass took {took:.1f}s"
