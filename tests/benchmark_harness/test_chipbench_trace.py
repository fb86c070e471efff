"""The trace reduction against a recorded device trace: 1,200 events a line
of the first traced chip run of PR 23 (rollout-1.5b-grpo on one TPU v5 lite),
cut by ``benchmarks/chip/tools/cut_xplane.py``."""

import os

import pytest
from chipbench_util import CHIP, load_run

FIXTURE = os.path.join(CHIP, "testdata", "rollout-1.5b-decode.xplane.pb")
KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def trace():
    load_run()
    from benchlib import trace_reduce

    assert os.path.getsize(FIXTURE) < 1_000_000
    return trace_reduce.load(FIXTURE)


def test_planes_lines_and_window(trace):
    assert [d.name for d in trace.devices] == ["/device:TPU:0"]
    d = trace.devices[0]
    assert len(d.ops) == 1200 and len(d.modules) == 2
    assert {n for n, _, _ in d.modules} == {"jit_chunk(7908224568374651669)"}
    assert trace.host and 1.0 < trace.window_s < 1.1


def test_busy_is_the_union_of_leaf_ops(trace):
    from benchlib import trace_reduce as tr

    d = trace.devices[0]
    leaves = tr.leaf_ops(d)
    assert len(leaves) < len(d.ops) and not any(n.startswith("%while") for n, _, _ in leaves)
    busy = tr.busy_seconds(trace)
    assert busy == pytest.approx(sum(e - s for s, e in tr.busy_intervals(d)))
    # ops never overlap on one core, so the union is the sum of the leaves
    assert busy == pytest.approx(sum(du for _, _, du in leaves), rel=1e-6)
    assert 0.014 < busy < 0.018
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]


def test_time_by_program_and_by_kernel(trace):
    from benchlib import trace_reduce as tr

    secs, n = tr.matched(trace, "modules", r"^jit_chunk\(")
    assert n == 2 and secs == pytest.approx(1.0546, abs=1e-3)
    assert tr.matched(trace, "modules", r"^jit_prefill\(") == (0.0, 0)
    k_secs, k_n = tr.matched(trace, "ops", KERNEL, within=r"^jit_chunk\(")
    assert k_n == 26 and k_secs == pytest.approx(0.0095, abs=5e-4)  # 26 layers of the first decode step
    assert tr.matched(trace, "ops", KERNEL, within=r"^jit_prefill\(") == (0.0, 0)
    top = tr.top_ops(trace, 3)
    assert top[0][0].startswith("%closed_call.13 custom-call:tpu_custom_call") and top[0][1] == pytest.approx(k_secs)
    assert len(top) == 3 and top[0][1] >= top[1][1] >= top[2][1]


def test_idle_gaps_name_the_host(trace):
    from benchlib import trace_reduce as tr

    gaps = tr.idle_gaps(trace, 10)
    assert 1 <= len(gaps) <= 10 and gaps[0][1] >= gaps[-1][1] > 0
    assert all(isinstance(name, str) and ":" in name for name, _ in gaps)
    s = tr.summary(trace)
    assert set(s) == {"busy_s", "window_s", "breakdown"} and set(s["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < s["busy_s"] < s["window_s"]


def test_cutter_keeps_a_readable_trace(tmp_path):
    import importlib.util

    spec = importlib.util.spec_from_file_location("chipbench_cut", os.path.join(CHIP, "tools", "cut_xplane.py"))
    cut = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cut)
    out = tmp_path / "small.xplane.pb"
    assert cut.main(["cut", FIXTURE, str(out), "100"]) == 0
    from benchlib import trace_reduce as tr

    small = tr.load(str(out))
    assert len(small.devices[0].ops) == 100 and os.path.getsize(out) < os.path.getsize(FIXTURE)
    assert cut.varint(cut.enc_varint(300), 0) == (300, 2)
