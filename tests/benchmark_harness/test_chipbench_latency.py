"""The arithmetic between the load generator's records and the latency metrics,
and the cached tokens the decode kernel's roofline share is counted from."""

import pytest
from chipbench_util import bench


def _rec(t_send, t_done, ttft, n_out, ok=True, cut=False, prompt_len=100):
    return {"t_send": t_send, "t_done": t_done, "ttft": ttft, "n_out": n_out, "ok": ok, "cut": cut, "prompt_len": prompt_len}


def test_ttft_counts_every_request_sent_inside_the_window():
    rollout = bench().cell_kind("rollout")
    recs = [
        _rec(9.0, 12.0, 0.5, 11),  # sent before the window, ends inside: tpot only
        _rec(10.5, 14.5, 1.0, 36),  # inside: both
        _rec(18.0, 24.0, 2.0, 7, ok=False, cut=True),  # sent inside, cut after the window: ttft only
        _rec(19.5, 24.0, 4.5, 0, ok=False, cut=True),  # sent inside, no token before the cut: counted as missing
        _rec(21.0, 24.0, 0.7, 3, ok=False, cut=True),  # sent after the window
        _rec(11.0, 11.2, None, 0, ok=False),  # failed inside
    ]
    s = rollout._summarise(recs, 10.0, 20.0)
    assert s["ttft"] == [1000.0, 2000.0] and s["ttft_missing"] == 2
    assert s["tpot"] == pytest.approx([1e3 * 2.5 / 10, 1e3 * 3.0 / 35])
    assert s["failed"] == 1 and s["attempted"] == 5 and s["tokens_of_finished"] == 47
    assert rollout.decoding_spans(recs) == [(9.5, 12.0, 100, 11), (11.5, 14.5, 100, 36), (20.0, 24.0, 100, 7), (21.7, 24.0, 100, 3)]


def test_cached_tokens_of_decoding_requests():
    reader = bench().reader("kernel_roofline")
    # one request decodes over the whole span and grows from 100 to 200 tokens: 150 on average;
    # a second holds 50..150 over the first half only: 100 x 1/2
    spans = [(0.0, 10.0, 100, 100), (0.0, 5.0, 50, 100)]
    assert reader.live_tokens(spans, 0.0, 10.0) == pytest.approx(150.0 + 50.0, rel=1e-3)
    assert reader.live_tokens(spans, 6.0, 8.0) == pytest.approx(170.0, rel=1e-3)
    assert reader.live_tokens([], 0.0, 1.0) == 0.0
