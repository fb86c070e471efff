"""kernelcheck differential-harness smoke (ISSUE 17): registered kernels
enumerate, the parity grid covers the int8-scales and stacked-cache
variants, and a seeded wrong-output kernel is caught loudly."""

import numpy as np
import pytest

from areal_tpu.tools import kernelcheck


def test_registered_kernels_enumerate():
    """Every Pallas kernel family in ops/ is registered, and enumeration
    (the --list path) walks each grid without executing kernels."""
    assert {
        "paged_attention_q8",
        "paged_attention_stacked",
        "flash_fwd",
        "flash_train",
        "tree_attention",
        "paged_suffix_attention",
    } <= set(kernelcheck.REGISTRY)
    for name, cases_fn in kernelcheck.REGISTRY.items():
        labels = [c["case"] for c in cases_fn()]
        assert labels, name
        assert len(labels) == len(set(labels)), f"duplicate case labels in {name}"


def test_grid_covers_int8_scales_and_stacked_variants():
    labels = [
        c["case"] for c in kernelcheck.REGISTRY["paged_attention_stacked"]()
    ]
    assert any("int8" in label for label in labels)
    assert any("bf16" in label for label in labels)
    assert any(
        "int8" in c["case"] for c in kernelcheck.REGISTRY["paged_attention_q8"]()
    )
    # multiple layer indices of the stacked cache are exercised
    layers = {label.rsplit("layer", 1)[-1] for label in labels}
    assert len(layers) >= 2


def test_flash_fwd_parity_runs_clean():
    """One real grid end-to-end (the cheapest): interpret-mode flash
    forward against the XLA sdpa reference."""
    results = kernelcheck.run_kernel("flash_fwd")
    assert results and all(r["ok"] for r in results), results


def test_seeded_wrong_output_kernel_is_caught(monkeypatch):
    """A kernel that silently returns wrong numbers must FAIL its case —
    the harness's whole reason to exist."""

    def bad_cases():
        yield {
            "case": "seeded-divergence",
            "kernel": lambda: np.ones((4, 4), np.float32),
            "reference": lambda: np.zeros((4, 4), np.float32),
            "tol": 1e-3,
        }

    monkeypatch.setitem(kernelcheck.REGISTRY, "bad_kernel", bad_cases)
    results = kernelcheck.run_kernel("bad_kernel")
    assert len(results) == 1
    assert not results[0]["ok"]
    assert results[0]["max_abs_diff"] == pytest.approx(1.0)
    # and the CLI surfaces it as a nonzero exit
    assert kernelcheck.main(["--kernel", "bad_kernel"]) == 1


def test_crashing_kernel_is_a_failure_not_a_crash(monkeypatch):
    def crash_cases():
        yield {
            "case": "raises",
            "kernel": lambda: (_ for _ in ()).throw(RuntimeError("boom")),
            "reference": lambda: np.zeros(1, np.float32),
            "tol": 1e-3,
        }

    monkeypatch.setitem(kernelcheck.REGISTRY, "crash_kernel", crash_cases)
    results = kernelcheck.run_kernel("crash_kernel")
    assert not results[0]["ok"]
    assert "RuntimeError" in results[0]["error"]


def test_unknown_kernel_is_a_usage_error():
    assert kernelcheck.main(["--kernel", "nope"]) == 2


def test_suffix_attention_grid_covers_masks_dtypes_gqa():
    """The suffix-attention family's case grid spans both launch variants
    (chain mask = suffix prefill, tree mask = spec verify), the full
    quantization ladder, and GQA ratios — the coverage the single-kernel-
    body design claim stands on."""
    cases = list(kernelcheck.REGISTRY["paged_suffix_attention"]())
    labels = [c["case"] for c in cases]
    assert any(label.startswith("chain") for label in labels)
    assert any(label.startswith("tree") for label in labels)
    for dtype in ("bf16", "int8", "fp8"):
        assert any(dtype in label for label in labels), dtype
    # every case carries its params dict (the FAIL-repro payload)
    assert all("params" in c for c in cases)
    gqa = {c["params"]["G"] for c in cases}
    assert len(gqa) >= 2, f"one GQA ratio only: {gqa}"
    # ragged (non-page-aligned) prefix lengths are present
    assert any("ragged" in label or "straddle" in label for label in labels)


def test_case_filter_selects_one_grid_point():
    """run_kernel(case=...) filters by index or label; the CLI rejects
    --case without --kernel and unknown case labels (usage errors, not
    silent empty runs)."""
    cases = list(kernelcheck.REGISTRY["flash_fwd"]())
    by_idx = kernelcheck.run_kernel("flash_fwd", case=0)
    assert len(by_idx) == 1 and by_idx[0]["index"] == 0
    by_label = kernelcheck.run_kernel("flash_fwd", case=cases[-1]["case"])
    assert len(by_label) == 1
    assert by_label[0]["case"] == cases[-1]["case"]
    assert kernelcheck.main(["--case", "0"]) == 2  # no --kernel
    assert kernelcheck.main(["--kernel", "flash_fwd", "--case", "nope"]) == 2


def test_failing_case_prints_params_and_repro(monkeypatch, capsys):
    """A parity failure prints the full case-params dict plus the --case
    incantation that re-runs just that grid point."""

    def bad_cases():
        yield {
            "case": "diverges",
            "params": {"S": 3, "dtype": "int8", "mask": "tree"},
            "kernel": lambda: np.ones((2, 2), np.float32),
            "reference": lambda: np.zeros((2, 2), np.float32),
            "tol": 1e-3,
        }

    monkeypatch.setitem(kernelcheck.REGISTRY, "bad_kernel", bad_cases)
    assert kernelcheck.main(["--kernel", "bad_kernel"]) == 1
    out = capsys.readouterr().out
    assert "params={'S': 3, 'dtype': 'int8', 'mask': 'tree'}" in out
    assert "--kernel bad_kernel --case 0" in out
