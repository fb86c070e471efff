"""What the decode engine reports of itself after the host-clock roofline went:
``/statusz`` ``kernels``, ``/metrics``, and the counts of a speculative round.

The loop's phase means and their exact-sum identity stay; a FLOP count over a
loop pass's host seconds does not come back under a roofline's name
(PERF.md section 3 has the roofline shares, from device traces)."""

import json
import re
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from areal_tpu.api.config import SpeculativeConfig
from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest
from areal_tpu.inference import speculative
from areal_tpu.inference.decode_engine import DecodeEngine
from areal_tpu.inference.server import ServerThread
from areal_tpu.observability import catalog
from areal_tpu.observability.kernel_probe import DECODE_PHASES

from tpu_testing import tiny_decode_engine

# spelled in two parts, so that a search of the tree for the retired names
# finds the records of their removal and nothing else
GONE_KEYS = ("roofline_" "fraction", "flops_total", "peaks", "costs")
GONE_GAUGES = ("areal_decode_" "step_flops", "areal_decode_roofline_" "fraction")


def _engine(**kw) -> DecodeEngine:
    return tiny_decode_engine(max_batch_size=2, max_seq_len=256, **kw)


def _phase_counts(text: str) -> dict[str, int]:
    return {
        m.group(1): int(float(m.group(2)))
        for m in re.finditer(r'^areal_decode_phase_seconds_count\{phase="(\w+)"\} (\S+)$', text, re.M)
    }


@pytest.fixture(scope="module")
def served():
    """A server that has answered two requests, with the ``/metrics`` phase
    counts from before them (the metric registry is the process's)."""
    eng = _engine()
    st = ServerThread(eng.config, eng)
    st.start()

    def get(path):
        # /metrics answers JSON unless asked for the text exposition
        req = urllib.request.Request(f"http://{st.address}{path}", headers={"Accept": "text/plain"})
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.read().decode()

    before = _phase_counts(get("/metrics"))
    for n in (6, 11):
        req = ModelRequest(input_ids=list(range(3, 3 + 2 * n)), gconfig=GenerationHyperparameters(max_new_tokens=n))
        eng.generate_sync(req, timeout=120)
    yield eng, get, before
    st.stop()


def test_statusz_kernels_keeps_the_phase_means_and_their_identity(served):
    eng, get, _ = served
    ks = json.loads(get("/statusz"))["kernels"]
    assert set(ks) == {
        "steps", "started", "abandoned", "phase_means_s", "other_mean_s", "total_mean_s", "dominant_phase",
    }
    assert not set(GONE_KEYS) & set(ks)
    assert tuple(ks["phase_means_s"]) == DECODE_PHASES
    assert ks["steps"] >= 2 and ks["started"] >= ks["steps"] + ks["abandoned"] - 1
    assert sum(ks["phase_means_s"].values()) + ks["other_mean_s"] == pytest.approx(ks["total_mean_s"], abs=1e-9)
    assert ks["dominant_phase"] in DECODE_PHASES + ("other",)
    assert ks["phase_means_s"]["device_wait"] > 0
    # every recorded step holds the identity exactly, and carries no cost
    for rec in eng.kprobe.recent():
        bd = rec["breakdown"]
        assert set(rec) == {"breakdown", "tokens"}
        assert sum(bd[f"{p}_s"] for p in DECODE_PHASES) + bd["other_s"] == pytest.approx(bd["total_s"], abs=1e-12)


def test_statusz_row_steps_holds_the_drains_sums_and_metrics_the_two_counters(served):
    eng, get, _ = served
    deadline = time.monotonic() + 30  # the chunk dispatched before the last end was known drains after the response
    while eng.stats["chunks"] < 7 and time.monotonic() < deadline:
        time.sleep(0.01)
    rs = json.loads(get("/statusz"))["row_steps"]
    assert rs == eng.row_steps_status() and list(rs) == ["slots", "steps", "live", "spent", "dropped"]
    assert rs["slots"] == 2 and rs["live"] == eng.stats["generated_tokens"] == 6 + 11 and rs["dropped"] == 0
    # every chunk ran 4 steps; each request's last chunk has a tail and is followed by one dispatched before its end was drained
    assert rs["steps"] == 4 * eng.stats["chunks"] and rs["spent"] == (8 - 6) + 4 + (12 - 11) + 4
    text = get("/metrics")
    for name in ("areal_decode_steps_total", "areal_decode_row_steps_spent_total"):
        assert re.search(rf"^{name} \d", text, re.M), name


def test_metrics_carry_every_phase_once_a_step_and_no_roofline_gauge(served):
    eng, get, before = served
    text = get("/metrics")
    counts = _phase_counts(text)
    assert set(counts) == set(DECODE_PHASES) | {"other"}
    steps = eng.kernel_stats()["steps"]
    assert {p: counts[p] - before.get(p, 0) for p in counts} == dict.fromkeys(counts, steps)
    for name in GONE_GAUGES:
        assert name not in text
    assert list(vars(catalog.kernel_metrics())) == ["phase_seconds"]


def test_docs_catalog_lists_the_histogram_and_not_the_gauges():
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    doc = open(os.path.join(root, "docs", "observability.md")).read()
    assert "| `areal_decode_phase_seconds` |" in doc
    for name in GONE_GAUGES + GONE_KEYS[:1] + ("Probed" "Fn",):
        assert name not in doc


@pytest.mark.parametrize("drafter,nodes", [("ngram", 5), ("tree", 9)])
def test_verify_round_scores_max_nodes_rows_a_slot_and_counts_add_up(drafter, nodes):
    """One verify forward scores exactly ``max_nodes()`` positions a slot (the
    packed result: that many token rows, as many logprob rows, and emitted
    count, alive and position rows), and over a served request every round
    emits its accepted drafts plus one token that was never at risk."""
    spec = SpeculativeConfig(enabled=True, drafter=drafter)
    assert spec.max_nodes() == nodes
    eng = _engine(speculative=spec)
    S, wp = eng.config.max_batch_size, 4
    bundle = speculative.empty_bundle(S, nodes - 1)
    drafts = {k: jnp.asarray(getattr(bundle, k)) for k in ("tokens", "parent_row", "depth", "mask", "n_draft")}
    with jax.set_mesh(eng.mesh):
        out = jax.eval_shape(
            eng.programs.spec_fn(nodes, wp, False, True), eng.params, eng.cache,
            jax.ShapeDtypeStruct((S, wp), jnp.int32), eng._dev_state, eng._rng, drafts,
        )
    assert out[3].shape == (2 * nodes + 3, S) and out[3].dtype == jnp.int32
    eng.start()
    try:
        prompt = [5, 8, 1, 5, 8, 1, 5, 8, 1, 5, 8]  # periodic: drafts land
        req = ModelRequest(input_ids=prompt, gconfig=GenerationHyperparameters(max_new_tokens=33, greedy=True))
        resp = eng.generate_sync(req, timeout=120)
    finally:
        eng.stop()
    st = eng.stats
    assert len(resp.output_tokens) == 33 == st["generated_tokens"]
    assert st["spec_rounds"] + st["spec_accepted_tokens"] == 33
    assert 0 < st["spec_accepted_tokens"] <= st["spec_draft_tokens"] <= st["spec_rounds"] * (nodes - 1)
