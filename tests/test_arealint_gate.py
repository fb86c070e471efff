"""Tier-1 gate: the whole package must be arealint-clean against the
checked-in baseline (ISSUE 2: zero-new-findings CI gate).

Any new finding fails this test. The fix is one of, in order of
preference: fix the code; suppress at the site with
``# arealint: disable=<rule> <why>``; or add a baseline entry with a
written reason (``python -m areal_tpu.tools.arealint --write-baseline``
then fill in the reason field).
"""

import functools

import pytest

from areal_tpu.analysis import (
    default_baseline_path,
    default_package_root,
    run_analysis,
)
from areal_tpu.analysis.core import load_baseline


@pytest.fixture(scope="module")
def package_result():
    """One whole-package scan shared by the gate assertions."""
    return run_analysis(
        [default_package_root()], baseline_path=default_baseline_path()
    )


@functools.lru_cache(maxsize=None)
def _scoped_result(*rules: str):
    """One whole-package scan a rule scope, shared by the scope's two tests."""
    return run_analysis([default_package_root()], rules=list(rules), baseline_path=default_baseline_path())


def test_package_is_clean_against_baseline(package_result):
    res = package_result
    assert res.files_checked > 100  # sanity: we really scanned the package
    assert not res.findings, "new arealint findings:\n" + "\n".join(
        f.render() for f in res.findings
    )


def test_baseline_entries_have_written_reasons():
    doc = load_baseline(default_baseline_path())
    missing = [e["key"] for e in doc["findings"] if not e.get("reason", "").strip()]
    assert not missing, (
        "baseline entries need a written reason (why the finding is "
        f"acceptable): {missing}"
    )


def test_baseline_has_no_stale_entries(package_result):
    """Every baseline entry must still match a live finding — otherwise the
    underlying issue was fixed and the entry should be deleted so it cannot
    mask a future regression at the same site."""
    res = package_result
    assert not res.stale_baseline, (
        "stale baseline entries (regenerate with --write-baseline): "
        + ", ".join(e["key"] for e in res.stale_baseline)
    )


def test_every_rule_family_is_loaded():
    from areal_tpu.analysis import Analyzer

    table = Analyzer().rule_table()
    families = {r.rstrip("0123456789") for r in table}
    assert {
        "ASY", "JAX", "THR", "CFG", "OBS", "EXC", "SIG",
        "PRF", "DON", "SHD", "RCP", "WIRE", "LCK",
        "KRN", "PVT", "MSH",
    } <= families


def test_wire_lck_enforced_repo_wide():
    """ISSUE 15: the distributed control plane's wire contract and lock
    ordering are tier-1-clean — a scoped run so a WIRE/LCK regression
    names the family even if another family also broke."""
    res = _scoped_result("WIRE", "LCK")
    assert res.files_checked > 100
    assert not res.findings, "WIRE/LCK findings:\n" + "\n".join(
        f.render() for f in res.findings
    )


def test_wire_lck_suppressions_carry_written_reasons():
    """No blanket burn-down: every inline WIRE/LCK suppression in the
    package must say WHY the finding is acceptable (e.g. the etcd /v3/*
    routes belong to an external server)."""
    res = _scoped_result("WIRE", "LCK")
    from areal_tpu.analysis.core import SourceFile

    bare = []
    for f in res.suppressed:
        sf = SourceFile.load(default_package_root() / ".." / f.path, default_package_root().parent)
        sup = sf.suppressions.get(f.line) or sf.file_suppression
        if sup is None or not sup.reason.strip():
            bare.append(f.key)
    assert not bare, f"reason-less WIRE/LCK suppressions: {bare}"


def test_wire_lck_baseline_entries_would_need_reasons(package_result):
    """The new families ride the same baseline machinery: any WIRE/LCK
    entry that ever lands in baseline.json is caught reason-less by
    test_baseline_entries_have_written_reasons and stale by
    test_baseline_has_no_stale_entries. Pin that the CURRENT burn-down
    ended clean — no WIRE/LCK entries hide in the baseline at all."""
    doc = load_baseline(default_baseline_path())
    wire_lck = [
        e["key"]
        for e in doc["findings"]
        if e["rule"].startswith(("WIRE", "LCK"))
    ]
    assert not wire_lck, (
        "WIRE/LCK must stay fixed-or-inline-suppressed, not baselined: "
        f"{wire_lck}"
    )


def test_krn_pvt_msh_enforced_repo_wide():
    """ISSUE 17: the Pallas-kernel and SPMD-collective families are
    tier-1-clean — the scoped run that guards the kernel arc (ROADMAP
    items 2-3). PVT here re-verifies every pinned private-API signature
    against the INSTALLED jax, so this test is also the early-warning
    trip-wire for the next jax bump."""
    res = _scoped_result("KRN", "PVT", "MSH")
    assert res.files_checked > 100
    assert not res.findings, "KRN/PVT/MSH findings:\n" + "\n".join(
        f.render() for f in res.findings
    )


def test_krn_pvt_msh_suppressions_carry_written_reasons():
    """No blanket burn-down: every inline KRN/PVT/MSH suppression in the
    package must say WHY (e.g. jax_compat's raw constraint IS the shim
    the MSH003 rule tells everyone else to route through)."""
    res = _scoped_result("KRN", "PVT", "MSH")
    from areal_tpu.analysis.core import SourceFile

    bare = []
    for f in res.suppressed:
        sf = SourceFile.load(
            default_package_root() / ".." / f.path,
            default_package_root().parent,
        )
        sup = sf.suppressions.get(f.line) or sf.file_suppression
        if sup is None or not sup.reason.strip():
            bare.append(f.key)
    assert not bare, f"reason-less KRN/PVT/MSH suppressions: {bare}"


def test_krn_pvt_msh_never_baselined(package_result):
    """The kernel-arc families stay fixed-or-inline-suppressed: a
    baselined KRN/PVT/MSH entry would let signature drift or a manual-axes
    regression ride silently through the next jax bump."""
    doc = load_baseline(default_baseline_path())
    entries = [
        e["key"]
        for e in doc["findings"]
        if e["rule"].startswith(("KRN", "PVT", "MSH"))
    ]
    assert not entries, (
        "KRN/PVT/MSH must never be baselined, only fixed or "
        f"inline-suppressed with a reason: {entries}"
    )


def test_repo_scripts_are_clean():
    """Entry scripts outside the package (bench, profiling, examples) ride
    the same gate — they drive the same APIs."""
    repo = default_package_root().parent
    paths = [p for p in repo.glob("*.py")] + [repo / "examples"]
    paths = [p for p in paths if p.exists()]
    res = run_analysis(paths, baseline_path=default_baseline_path())
    assert not res.findings, "\n".join(f.render() for f in res.findings)
