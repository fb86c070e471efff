"""The readers of the program's host spans and request events
(``layer_metrics/readers/host_span.py``, ``request_stage.py``) on hand-made
spans, where every value can be worked out on paper; the recorded chip
trace is in ``test_chipbench_scopes.py``."""

import pytest
from chipbench_util import bench, load_run


@pytest.fixture()
def lib():
    load_run()
    from benchlib import trace_reduce, trace_scopes

    return trace_reduce, trace_scopes


def _scoped(ts, spans):
    return ts.Scoped("hand-made", {}, sorted(spans, key=lambda s: (s.start_s, -s.dur_s)), None)


def _pass(ts, t0, wait, stats):
    """A 100 ms pass: admission 10 (radix_match 2 inside it), dispatch 5,
    device_wait ``wait``, bookkeeping 3 ms, the rest the pass's own."""
    ms = 1e-3
    S = lambda name, a, d, st=None: ts.Span("python3#4", name, t0 + a * ms, d * ms, st or {})  # noqa: E731
    return [
        S("areal.decode.pass", 0, 100, stats),
        S("areal.decode.admission", 1, 10),
        S("areal.decode.radix_match", 2, 2),
        S("areal.decode.dispatch", 12, 5),
        S("areal.decode.device_wait", 18, wait),
        S("areal.decode.bookkeeping", 18 + wait, 3),
    ]


def test_innermost_segments_pause_the_outer_span(lib):
    _, ts = lib
    segs = ts.innermost_segments(_pass(ts, 0.0, 70, {}))
    got = [(round(1e3 * s), round(1e3 * e), n.rsplit(".", 1)[-1]) for s, e, n in segs]
    assert got == [
        (0, 1, "pass"), (1, 2, "admission"), (2, 4, "radix_match"), (4, 11, "admission"), (11, 12, "pass"),
        (12, 17, "dispatch"), (17, 18, "pass"), (18, 88, "device_wait"), (88, 91, "bookkeeping"), (91, 100, "pass"),
    ]


def test_host_ms_per_pass_is_the_pass_less_its_device_wait(lib, monkeypatch, capsys):
    _, ts = lib
    spans = _pass(ts, 0.0, 70, {"active": 40, "tokens": 1280}) + _pass(ts, 0.2, 60, {"active": 41, "tokens": 1300})
    spans[6] = ts.Span("python3#4", "areal.decode.pass", 0.2, 0.110, spans[6].stats)  # the second pass: 110 ms
    monkeypatch.setattr(ts, "for_run", lambda facts: _scoped(ts, spans))
    metric = bench().layer_metric("decode_host_ms_per_pass")
    v = bench().reader(metric["reader"]).read(metric, {"trace": object()})
    assert v == pytest.approx(((100 - 70) + (110 - 60)) / 2)
    out = capsys.readouterr().out
    assert "2 x areal.decode.pass: mean 105.00 ms" in out and "device_wait 65.000" in out and "admission 8.000" in out
    assert "longest areal.decode.pass: 110.00 ms {'active': 41, 'tokens': 1300}" in out


def test_host_span_readers_find_nothing_in_a_trace_without_spans(lib, monkeypatch, capsys):
    tr, ts = lib
    monkeypatch.setattr(ts, "for_run", lambda facts: _scoped(ts, []))
    trace = tr.Trace([tr.DeviceTrace("/device:TPU:0", ops=[("%fusion.1 = f32[8] fusion(x)", 0.0, 1.0)])], [], 0.0, 2.0)
    for name in ("decode_host_ms_per_pass", "train_host_prep_ms", "idle_attributed_pct.rollout", "queue_wait_p50_ms"):
        metric = bench().layer_metric(name)
        assert bench().reader(metric["reader"]).read(metric, {"trace": trace, "traced_steps": 2}) is None, name
    assert "no areal.decode.pass span in the trace" in capsys.readouterr().out


def test_train_host_prep_is_per_traced_step(lib, monkeypatch):
    _, ts = lib
    spans = [ts.Span("python3#0", "areal.train.host_prep", t, 0.004, {}) for t in (0.0, 0.1, 2.5, 2.6)]
    spans.append(ts.Span("python3#0", "areal.train.forward_backward", 0.2, 2.2, {}))
    monkeypatch.setattr(ts, "for_run", lambda facts: _scoped(ts, spans))
    metric = bench().layer_metric("train_host_prep_ms")
    assert bench().reader(metric["reader"]).read(metric, {"trace": object(), "traced_steps": 2}) == pytest.approx(8.0)


def test_idle_seconds_are_attributed_to_the_innermost_span(lib, monkeypatch, capsys):
    tr, ts = lib
    op = "%fusion.1 = f32[8] fusion(x)"
    # busy 0-1 s, 1.2-2 s, 2.5-3 s over a span of 0-4 s: idle 0.2 + 0.5 + 1.0
    dev = tr.DeviceTrace("/device:TPU:0", ops=[(op, 0.0, 1.0), (op, 1.2, 0.8), (op, 2.5, 0.5)])
    trace = tr.Trace([dev], [], 0.0, 4.0)
    spans = [
        ts.Span("python3#4", "areal.decode.pass", 0.9, 1.5, {}),  # 0.9-2.4
        ts.Span("python3#4", "areal.decode.admission", 1.0, 0.1, {}),  # 1.0-1.1
        ts.Span("python3#4", "areal.decode.device_wait", 2.0, 0.3, {}),  # 2.0-2.3
        ts.Span("python3#9", "areal.request.admitted", 3.5, 1e-6, {}),  # another thread: not the loop
    ]
    monkeypatch.setattr(ts, "for_run", lambda facts: _scoped(ts, spans))
    metric = bench().layer_metric("idle_attributed_pct.rollout")
    v = bench().reader(metric["reader"]).read(metric, {"trace": trace})
    # gap 1.0-1.2: admission 0.1 + pass 0.1; gap 2.0-2.5: device_wait 0.3 + pass 0.1; gap 3.0-4.0: outside
    assert v == pytest.approx(100 * 0.6 / 1.7)
    out = capsys.readouterr().out
    assert "areal.decode.device_wait 300.000" in out and "areal.decode.pass 200.000" in out and "outside every span 1100.000" in out


def test_request_stage_medians(lib, monkeypatch, capsys):
    _, ts = lib
    ev = lambda q, p, d: ts.Span("python3#4", "areal.request.first_token", 1.0, 1e-6, {"queue_wait_us": q, "prefill_us": p, "since_prefill_end_us": d})  # noqa: E731
    spans = [ev(400_000, 5_000, 1_100_000), ev(500_000, 7_000, 1_300_000), ev(540_000, 9_000, 1_200_000)]
    spans.append(ts.Span("python3#4", "areal.request.admitted", 0.5, 1e-6, {"queue_wait_us": 1}))
    monkeypatch.setattr(ts, "for_run", lambda facts: _scoped(ts, spans))
    got = {}
    for name in ("queue_wait_p50_ms", "first_token_drain_p50_ms"):
        metric = bench().layer_metric(name)
        got[name] = bench().reader(metric["reader"]).read(metric, {"trace": object()})
    assert got == {"queue_wait_p50_ms": pytest.approx(500.0), "first_token_drain_p50_ms": pytest.approx(1200.0)}
    assert "3 x areal.request.first_token in the traced span" in capsys.readouterr().out
