"""Scopes, spans and request events read from a recorded chip trace of PR 24
(``rollout-1.5b-grpo``, seed 3000024101, one TPU v5 lite): every line cut to
its first 1,200 events by ``benchmarks/chip/tools/cut_xplane.py``, so all 15
program runs and all 190 ``areal.*`` host events of the 8 s are there, and
the device ops of the first 1.3 decode steps. The expected numbers were
summed by hand from ``jax.profiler.ProfileData`` and the raw ``tf_op``
strings. PR 23's fixture, recorded before the program named anything, is
the scope-less case."""

import os

import pytest
from chipbench_util import CHIP, bench, load_run

SCOPED = os.path.join(CHIP, "testdata", "rollout-1.5b-scoped.xplane.pb")
UNSCOPED = os.path.join(CHIP, "testdata", "rollout-1.5b-decode.xplane.pb")


def _facts(path):
    load_run()
    from benchlib import trace_reduce

    return {"trace": trace_reduce.load(path), "xplane": path, "traced_steps": 0}


@pytest.fixture(scope="module")
def scoped():
    assert os.path.getsize(SCOPED) < 400_000
    return _facts(SCOPED)


def _read(name, facts):
    metric = bench().layer_metric(name)
    return bench().reader(metric["reader"]).read(metric, facts)


def test_device_ops_carry_the_programs_scopes(scoped):
    from benchlib import trace_scopes as ts

    sc = ts.for_run(scoped)
    paths = sc.op_paths["/device:TPU:0"]
    kernel = next(p for op, p in paths.items() if op.startswith("%paged_decode_attn"))
    assert kernel == "jit(chunk)/while/body/closed_call/while/body/closed_call/attn/paged_decode_attn/pallas_call"
    assert ts.scopes_of(kernel) == {"attn"}
    assert ts.scopes_of("jit(step)/transpose(jvp(loss))/while/body/closed_call/checkpoint/rematted_computation/td,vd->tv/dot_general") == {"loss"}
    assert ts.scopes_of("jit(chunk)/while/body/closed_call/reshape") == set()
    found = set().union(*(ts.scopes_of(p) for p in paths.values()))
    assert found == {"attn_proj", "kv_write", "attn", "mlp", "lm_head", "sampler"}  # the embed runs later in the step
    ops = ts.scoped_ops(scoped, within=r"^jit_chunk\(")
    assert len(ops) == 1200 and sum(1 for _, p in ops if ts.scopes_of(p) == {"attn"}) == 108


def test_spans_and_request_events_are_read_with_their_stats(scoped):
    from benchlib import trace_scopes as ts

    sc = ts.for_run(scoped)
    by = {}
    for s in sc.spans:
        by.setdefault(s.name, []).append(s)
    assert {k: len(v) for k, v in by.items()} == {
        "areal.decode.pass": 13, "areal.decode.admission": 14, "areal.decode.radix_match": 14, "areal.decode.prefill": 28,
        "areal.decode.dispatch": 14, "areal.decode.device_wait": 13, "areal.decode.bookkeeping": 14,
        "areal.request.admitted": 32, "areal.request.first_token": 48,
    }
    assert len({s.thread for s in sc.spans}) == 1  # all on the decode thread
    first = by["areal.decode.pass"][0]
    assert first.stats == {"active": 44, "tokens": 1444} and first.dur_s == pytest.approx(0.61352, abs=1e-4)
    kids = {s.name.rsplit(".", 1)[-1] for s in ts.children(sc.spans, first)}
    assert kids >= {"admission", "radix_match", "prefill", "dispatch", "device_wait", "bookkeeping"}
    ev = by["areal.request.first_token"][0].stats
    assert (ev["queue_wait_us"], ev["prefill_us"], ev["since_prefill_end_us"]) == (590180, 3210, 1212500)


@pytest.mark.parametrize(
    "name,want",
    [
        ("decode_attn_pct", 100 * 0.011650675 / 8.078780966),
        ("decode_kv_write_pct", 100 * 0.001359478 / 8.078780966),
        ("decode_head_sampler_pct", 100 * (0.00075065 + 0.000488797) / 8.078780966),
        ("scope_coverage_pct.rollout", 99.4281),
    ],
)
def test_scope_shares_of_the_recorded_trace(scoped, name, want, capsys):
    assert _read(name, scoped) == pytest.approx(want, rel=1e-4)
    if name == "decode_head_sampler_pct":  # each scope is logged alone
        out = capsys.readouterr().out
        assert "lm_head 0.0008 s" in out and "sampler 0.0005 s" in out


def test_span_metrics_of_the_recorded_trace(scoped, capsys):
    assert _read("decode_host_ms_per_pass", scoped) == pytest.approx(6.26531, rel=1e-4)
    assert _read("queue_wait_p50_ms", scoped) == pytest.approx(588.456)
    assert _read("first_token_drain_p50_ms", scoped) == pytest.approx(1197.6345)
    # the cut keeps 18 ms of device ops, so nearly all of the 8.13 s is idle
    assert _read("idle_attributed_pct.rollout", scoped) == pytest.approx(96.0179, rel=1e-4)
    out = capsys.readouterr().out
    assert "13 x areal.decode.pass: mean 599.08 ms" in out and "48 x areal.request.first_token" in out
    assert "areal.decode.device_wait 7706.556" in out


def test_a_trace_from_before_the_names_reads_as_nothing(capsys):
    facts = _facts(UNSCOPED)
    for name in ("decode_attn_pct", "scope_coverage_pct.rollout", "decode_host_ms_per_pass", "queue_wait_p50_ms", "idle_attributed_pct.rollout"):
        assert _read(name, facts) is None, name
    out = capsys.readouterr().out
    assert "no device op carries a scope of the program's vocabulary" in out
    assert "no areal.decode.pass span in the trace" in out and "no areal.* span in the trace" in out


def test_another_runs_file_is_refused(scoped, capsys):
    from benchlib import trace_scopes as ts

    assert ts.for_run({"trace": None}) is None  # not a traced run
    assert ts.for_run({**scoped, "xplane": UNSCOPED}) is None
    assert "is not this run's trace" in capsys.readouterr().out


def test_train_shares_unwrap_autodiff_and_find_the_recompute(monkeypatch, capsys):
    load_run()
    from benchlib import trace_reduce as tr
    from benchlib import trace_scopes as ts

    layer = "jit(step)/transpose(jvp())/while/body/closed_call/checkpoint"
    ops = [
        (0.7, "jit(step)/jvp()/while/body/closed_call/attn/jit(flash_attention)/pallas_call"),
        (0.7, layer + "/rematted_computation/attn/jit(flash_attention)/pallas_call"),
        (1.7, layer + "/attn/jit(flash_attention)/flash_mha_bwd_dkv/pallas_call"),
        (0.9, layer + "/mlp/dot_general"),
        (0.3, layer + "/rematted_computation/mlp/dot_general"),
        (0.3, "jit(step)/transpose(jvp(loss))/while/body/closed_call/checkpoint/td,vd->tv/dot_general"),
        (0.3, "jit(step)/optimizer/add"),
        (0.1, "jit(step)/transpose(jvp())/while"),
    ]
    trace = tr.Trace([tr.DeviceTrace("/device:TPU:0", ops=[("%op = f32[] add()", 0.0, 5.0)], modules=[("jit_step(1)", 0.0, 5.0)])], [], 0.0, 5.0)
    monkeypatch.setattr(ts, "scoped_ops", lambda facts, within=None: ops)
    facts = {"trace": trace}
    assert _read("train_attn_pct", facts) == pytest.approx(100 * 3.1 / 5.0)
    assert _read("train_recompute_pct", facts) == pytest.approx(100 * 1.0 / 5.0)
    assert _read("scope_coverage_pct.train", facts) == pytest.approx(100 * 4.9 / 5.0)
    assert "rematted_computation 1.0000 s (20.00%)" in capsys.readouterr().out
