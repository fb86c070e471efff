"""The reader of the decode passes' row ledger (``layer_metrics/readers/
pass_ledger.py``) on hand-built records, where every value can be worked out
on paper, and the three metric files it serves. Nothing here asserts a time."""

import pytest
from chipbench_util import bench, load_run

from areal_tpu.utils.perf_tracer import RecordEntry, SpanRecord

NEW = {
    "slot_live_pct": ("%", "higher", "rollout_tok_s", "live_pct"),
    "slot_spent_pct": ("%", "lower", "rollout_tok_s", "spent_pct"),
    "decode_wall_ms_per_step": ("ms", "lower", "tpot_p95_ms", "wall_ms_per_step"),
}
S = 1_000_000_000
OFF = 5_000.0  # the record's clock is 5,000 s ahead of the trace's
SLOTS, STEPS = 4, 8


@pytest.fixture()
def lib():
    load_run()
    from benchlib import trace_reduce, trace_scopes

    return trace_reduce, trace_scopes


def _pass(end_s, dur_s=0.25, **args):
    """One pass that ends at ``end_s``; ``steps`` absent: a parent's pass."""
    base = {"active": 2, "tokens": 0, "held_us": 0, "cpu_us": 900}
    if "steps" in args:
        base.update(rows=0, spent=0, dropped=0, admitted=0, prompt_tokens=0, queued=0)
    return RecordEntry("areal.decode.pass", int((end_s - dur_s) * S), int(end_s * S), 1, {**base, **args}, None, "X")


def _facts(lib, monkeypatch, entries, window_at, window_s, modules=None):
    """A traced run whose profiler session began at ``window_at`` (record's
    clock); the trace holds thread 1's spans from there on, on its own clock."""
    tr, ts = lib
    seen = [e for e in entries if e.start_ns >= window_at * S]
    spans = sorted((ts.Span("python3#4", e.name, e.start_ns / S - OFF, (e.end_ns - e.start_ns) / S, {}) for e in seen), key=lambda s: s.start_s)
    monkeypatch.setattr(ts, "for_run", lambda facts: ts.Scoped("hand-made", {}, spans, None))
    devices = [tr.DeviceTrace("/device:TPU:0", [], modules)] if modules else []
    return {
        "trace": tr.Trace(devices, [], window_at - OFF, window_at - OFF + 8.0),
        "record": SpanRecord(int(5_000.0 * S), sorted(entries, key=lambda e: e.end_ns), {1: "loop"}),
        "window_s": window_s,
        "server": {"slots": SLOTS, "decode_steps": STEPS},
        "values": {"rollout_tok_s": 10.0},
    }


def _read(name, facts):
    metric = bench().layer_metric(name)
    return bench().reader(metric["reader"]).read(metric, facts)


def test_the_three_values_over_the_passes_that_end_inside_the_window(lib, monkeypatch, capsys):
    # the window is (5,100, 5,103]; a pass credits at its drain, so it counts where it ENDS
    chunk = lambda end, rows, tokens, spent, dropped=0, **kw: _pass(end, steps=STEPS, rows=rows, tokens=tokens, spent=spent, dropped=dropped, **kw)  # noqa: E731
    entries = [
        chunk(5_099.9, 4, 32, 0),  # ended before the window: not counted
        chunk(5_100.2, 3, 19, 5),  # began before the window, ended inside it: counted
        chunk(5_100.7, 3, 5, 19, admitted=2, prompt_tokens=600, queued=3, dur_s=0.5, held_us=100_000),
        chunk(5_101.0, 2, 8, 0, dropped=8),
        _pass(5_101.5, steps=1, rows=2, tokens=7, spec=1),  # a speculative round: apart, in no share
        _pass(5_102.0, steps=0, tokens=0, queued=1),  # admitted nothing, drained nothing
        chunk(5_103.0, 1, 0, 8),  # ends on the window's last instant: counted
        chunk(5_103.2, 4, 32, 0),  # ends after it: not counted
    ]
    modules = [("jit_chunk(123)", 100.0 + 0.3 * i, 0.25) for i in range(3)] + [("jit_prefill(4)", 101.5, 0.1)]
    facts = _facts(lib, monkeypatch, entries, 5_100.0, 3.0, modules)
    facts["trace_span"] = (5_100.0, 5_101.2)
    got = {name: _read(name, facts) for name in NEW}
    steps = 4 * STEPS  # the four chunks that ended inside
    assert got["slot_live_pct"] == pytest.approx(100 * (19 + 5 + 8 + 0) / (SLOTS * steps))
    assert got["slot_spent_pct"] == pytest.approx(100 * (5 + 19 + 0 + 8) / (SLOTS * steps))
    assert got["decode_wall_ms_per_step"] == pytest.approx(1e3 * 3.0 / steps)
    out = capsys.readouterr().out
    assert out.count("ended inside the window") == 1  # logged once a run, not once a metric
    assert f"5 x areal.decode.pass ended inside the window of 3.000 s: {steps} steps ({steps / 3.0:.2f} a second) x 4 slots = 128 row-steps: live 32 (25.00%), spent 32 (25.00%), dropped 8 (6.25%), empty 56 (43.75%); tokens + spent + dropped - rows x steps = 0" in out
    by_ledger = steps / 3.0 * SLOTS * 0.25
    assert f"= {by_ledger:.1f} tokens/s; the run's rollout_tok_s 10.0 ({100 * (by_ledger / 10.0 - 1):+.2f}%" in out
    assert "1 passes handed 600 prompt tokens to prefill programs (2 requests given a slot): 0.500 s, 16.67% of the window; mean queued after admission 0.800" in out
    assert "1 speculative passes apart: 1 rounds, 7 tokens, 0.250 s" in out
    # C2's check: three passes ended inside the traced span, the trace holds three chunk programs
    assert f"in the traced span the program says it ran {3 * STEPS} steps; the trace holds 3 chunk programs x the file's decode_steps {STEPS} = {3 * STEPS} (+0:" in out


def test_none_and_never_an_error_where_there_is_nothing_to_read(lib, monkeypatch, capsys):
    parents = [_pass(5_100.0 + 0.3 * i, tokens=64) for i in range(1, 12)]  # the parent's passes: active, tokens, held_us
    facts = _facts(lib, monkeypatch, parents, 5_100.0, 3.0)
    assert all(_read(n, dict(facts)) is None for n in NEW)
    with_steps = [_pass(5_100.0 + 0.3 * i, steps=STEPS, rows=2, tokens=16) for i in range(1, 12)]
    facts = _facts(lib, monkeypatch, with_steps, 5_100.0, 3.0)
    assert _read("slot_live_pct", dict(facts)) == pytest.approx(50.0)
    assert all(_read(n, {**facts, "trace": None}) is None for n in NEW)  # an untraced run
    assert all(_read(n, {**facts, "record": SpanRecord(0, [], {})}) is None for n in NEW)
    assert all(_read(n, {k: v for k, v in facts.items() if k != "window_s"}) is None for n in NEW)
    # every pass of the window speculative, or none ended inside it: no step to divide by
    spec = [_pass(5_100.0 + 0.3 * i, steps=1, rows=2, tokens=5, spec=1) for i in range(1, 12)]
    assert all(_read(n, dict(_facts(lib, monkeypatch, spec, 5_100.0, 3.0))) is None for n in NEW)
    assert all(_read(n, dict(_facts(lib, monkeypatch, with_steps, 5_100.0, 0.2))) is None for n in NEW)
    out = capsys.readouterr().out
    for why in ("carry no steps: a program without the row ledger", "the span record is empty", "no decode step ended inside the window"):
        assert why in out, why


def test_the_new_files_resolve_and_agree_with_their_entries():
    """Held so that a later PR's appended entries and cells leave it green:
    the three follow everything PR 48 had, together and in this order, and
    list the nine rollout cells of this PR first, in the benchmark's order."""
    b = bench()
    assert b.problems() == []
    rollout = next(m for m in b.doc["end_to_end"] if m["name"] == "rollout_tok_s")["workloads"]
    assert rollout == [w["name"] for w in b.doc["workloads"] if w["name"] in rollout]  # the benchmark's order
    nine = rollout[:9]
    assert nine[0] == "rollout-1.5b-grpo" and nine[-1] == "rollout-solar-open2-ep16-d8-longctx-grpo"
    names = [m["name"] for m in b.doc["per_layer"]]
    at = names.index("slot_live_pct")
    assert at > names.index("scope_coverage_pct.rollout-kda") and names[at : at + 3] == list(NEW)
    entries = {m["name"]: m for m in b.doc["per_layer"]}
    for name, (unit, better, moves, value) in NEW.items():
        entry, lm = entries[name], b.layer_metric(name)
        assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert entry["workloads"][:9] == nine and set(entry["workloads"]) <= set(rollout)
        assert lm == {"reader": "pass_ledger", "layer": "decode engine", "unit": unit, "better": better, "source": "program_span", "moves": moves, "value": value}
        assert {k: entry[k] for k in ("unit", "better", "source", "layer", "moves")} == {k: lm[k] for k in ("unit", "better", "source", "layer", "moves")}
        assert all(any(m["name"] == name for m in b.cell(c)["per_layer"]) for c in ("rollout-1.5b-grpo", "rollout-7b-d14-grpo", nine[-1]))
    assert hasattr(b.reader("pass_ledger"), "read")
    assert "decode engine" in {m["layer"] for m in b.doc["per_layer"][:at]}  # a layer the benchmark already names


def test_a_traced_rehearsal_reads_the_three_from_the_engines_own_record(lib, monkeypatch, tmp_path, capsys):
    """The reader over the record a real engine leaves (tiny, on the CPU: the
    numbers are counts, no measurement): the ledger closes over the window and
    steps/s x slots x live share is the run's tokens a second."""
    import glob
    import os
    import re

    from chipbench_util import check_rehearsal, rehearse

    from areal_tpu.api.config import PerfTracerConfig
    from areal_tpu.utils import perf_tracer

    _, ts = lib
    monkeypatch.setattr(perf_tracer, "_TRACER", perf_tracer.PerfTracer(PerfTracerConfig()))
    monkeypatch.setattr(ts, "newest_xplane", lambda root=None: max(glob.glob(str(tmp_path / "trace/plugins/profile/*/*.xplane.pb")), key=os.path.getmtime, default=None))
    res = rehearse("rollout-1.5b-grpo", 1, tmp_path)
    if not res["correct"] and res["rehearsal"]["check"]["radix_hits"]:
        pytest.xfail("logprobs off after a radix hit inside a group (test_chipbench_rehearse_rollout.py)")
    check_rehearsal("rollout-1.5b-grpo", 1, res)
    got = {n: res["metrics"][n]["value"] for n in NEW}
    assert 0 < got["slot_live_pct"] <= 100 and 0 <= got["slot_spent_pct"] < 100 and got["slot_live_pct"] + got["slot_spent_pct"] <= 100
    assert got["decode_wall_ms_per_step"] > 0
    out = capsys.readouterr().out
    assert "tokens + spent + dropped - rows x steps = 0" in out
    off = float(re.search(r"the run's rollout_tok_s [\d.]+ \(([+-][\d.]+)%", out).group(1))
    assert abs(off) < 50, off  # two windows of 2 s whose edges lie a pass (or a compilation) apart: on the chip, 30 s and under 1.5%
