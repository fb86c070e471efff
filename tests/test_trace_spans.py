"""The program's spans on the profiler's clock, and the names its device
programs carry (docs/observability.md "Spans and scopes").

A ``jax.profiler`` session around a tiny DecodeEngine run and a tiny
``train_batch`` must hold the ``areal.*`` spans the chip benchmark's readers
take their per-layer metrics from; the lowered programs must hold every
``jax.named_scope`` of the vocabulary they use.
"""

import glob
import re
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.api.config import (
    MeshConfig,
    MicroBatchSpec,
    OptimizerConfig,
    PerfTracerConfig,
    ServerConfig,
    TrainEngineConfig,
)
from areal_tpu.api.io_struct import (
    FinetuneSpec,
    GenerationHyperparameters,
    ModelRequest,
)
from areal_tpu.engine.train_engine import JaxTrainEngine
from areal_tpu.inference.decode_engine import DecodeEngine
from areal_tpu.models import qwen
from areal_tpu.observability import step_timeline
from areal_tpu.utils import perf_tracer

from tpu_testing import TINY_QWEN2, random_batch



@pytest.fixture(scope="module")
def decode_engine():
    cfg = ServerConfig(
        max_batch_size=4,
        max_seq_len=256,
        decode_steps_per_call=8,
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=2),
    )
    params = qwen.init_params(jax.random.PRNGKey(0), TINY_QWEN2)
    eng = DecodeEngine(cfg, params=params, model_cfg=TINY_QWEN2)
    eng.initialize()
    eng.start()
    yield eng
    eng.stop()


@pytest.fixture(scope="module")
def train_engine():
    cfg = TrainEngineConfig(
        init_from_scratch=True,
        dtype="float32",
        param_dtype="float32",
        mesh=MeshConfig(data=2, fsdp=2, seq=1, model=2),
        optimizer=OptimizerConfig(lr=1e-2, lr_scheduler_type="constant"),
        mb_spec=MicroBatchSpec(max_tokens_per_mb=1024),
        bucket_step=64,
    )
    eng = JaxTrainEngine(cfg, model_config=TINY_QWEN2)
    eng.initialize(FinetuneSpec(1, 128, 16))
    return eng


def sft_loss(outputs, b):
    lm = (b["label_valid"] & (b["loss_mask"] > 0)).astype(jnp.float32)
    loss = -(outputs["logprobs"] * lm).sum() / jnp.maximum(lm.sum(), 1)
    return loss, {"ppl_loss": jax.lax.stop_gradient(loss)}


def weight_fn(d):
    return float((np.asarray(d["loss_mask"]) > 0).sum())


class _Profile:
    """A profiler session as the benchmark's tracer opens it; ``events`` are
    the ``areal.*`` host events: (thread line, name, start ns, end ns, stats)."""

    def __init__(self, out_dir):
        self.dir = str(out_dir)
        self.events = []

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        (path,) = glob.glob(self.dir + "/plugins/profile/*/*.xplane.pb")
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name.startswith("areal."):
                        self.events.append(
                            (i, ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
                        )

    def named(self, name):
        return [e for e in self.events if e[1] == name]


def test_decode_pass_spans_and_request_events(decode_engine, tmp_path):
    req = ModelRequest(
        input_ids=list(range(3, 15)),
        gconfig=GenerationHyperparameters(max_new_tokens=20, greedy=True),
    )
    perf_tracer.set_task_context(task_id="task-7")
    try:
        with _Profile(tmp_path) as prof:
            resp = decode_engine.generate_sync(req, timeout=120)
            # the response is sent from inside the last pass: let that pass
            # end (the loop then only polls) before the session does
            time.sleep(0.3)
    finally:
        perf_tracer.clear_task_context()
    assert len(resp.output_tokens) == 20
    passes = prof.named("areal.decode.pass")
    assert passes, sorted({e[1] for e in prof.events})
    # every pass is a productive one and says so in its args
    assert sum(p[4]["tokens"] for p in passes) >= 20
    assert all("active" in p[4] for p in passes)
    for phase in ("admission", "radix_match", "prefill", "dispatch", "device_wait", "bookkeeping"):
        kids = prof.named("areal.decode." + phase)
        assert kids, phase
        for line, _, s, e, _ in kids:
            assert any(p[0] == line and p[2] <= s and e <= p[3] for p in passes), (
                f"areal.decode.{phase} outside every areal.decode.pass"
            )
    (adm,) = prof.named("areal.request.admitted")
    assert adm[4]["queue_wait_us"] >= 0
    (first,) = prof.named("areal.request.first_token")
    st = first[4]
    assert st["queue_wait_us"] == adm[4]["queue_wait_us"]
    assert st["prefill_us"] > 0 and st["since_prefill_end_us"] > 0
    assert st["task_id"] == "task-7"
    # the three terms are the request's time to its first token
    assert st["queue_wait_us"] + st["prefill_us"] + st["since_prefill_end_us"] <= resp.ttft * 1e6 * 1.5 + 5e3


def test_train_spans_without_step_timeline(train_engine, tmp_path):
    assert step_timeline.current_step_timeline() is None
    batch = random_batch(seed=1)
    train_engine.train_batch(batch, sft_loss, weight_fn)  # compile outside the session
    with _Profile(tmp_path) as prof:
        train_engine.train_batch(batch, sft_loss, weight_fn)
    assert prof.named("areal.train.host_prep")
    assert prof.named("areal.train.forward_backward")


def test_engine_phase_accumulates_only_under_a_timeline(tmp_path):
    tracer = perf_tracer.PerfTracer(PerfTracerConfig(enabled=True, output_dir=str(tmp_path)))
    old = perf_tracer._TRACER
    perf_tracer._TRACER = tracer
    try:
        with step_timeline.engine_phase("host_prep"):
            pass
        rec = step_timeline.StepTimelineRecorder()
        tl = rec.start(0)
        with step_timeline.engine_phase("forward_backward"):
            pass
        with tl.phase("ckpt_eval", perf_tracer.Category.IO, {"global_step": 0}) as _:
            with step_timeline.engine_phase("forward_backward"):
                pass  # suppressed in the accumulation, still a span
        bd = rec.complete(tl)
    finally:
        perf_tracer._TRACER = old
    events = tracer.chrome_events()
    names = [(e["name"], e["cat"]) for e in events]
    assert names == [
        ("areal.train.host_prep", "compute"),
        ("areal.train.forward_backward", "compute"),
        ("areal.train.forward_backward", "compute"),
        ("areal.train.ckpt_eval", "io"),
    ]
    assert events[-1]["args"] == {"global_step": 0}
    assert bd["host_prep_s"] == 0.0 and bd["ckpt_eval_s"] >= bd["forward_backward_s"] > 0.0


def test_span_set_reaches_the_chrome_event(tmp_path):
    tracer = perf_tracer.PerfTracer(PerfTracerConfig(enabled=True, output_dir=str(tmp_path)))
    with tracer.trace_scope("areal.decode.pass") as span:
        span.set(active=3, tokens=96)
    tracer.instant("areal.request.admitted", args={"queue_wait_us": 5})
    (ev, inst) = tracer.chrome_events()
    assert ev["ph"] == "X" and ev["args"] == {"active": 3, "tokens": 96}
    assert inst["ph"] == "i" and inst["args"] == {"queue_wait_us": 5}


def test_span_in_a_process_without_jax_emits_nothing_and_imports_nothing():
    code = textwrap.dedent(
        """
        import sys
        from areal_tpu.utils import perf_tracer
        from areal_tpu.observability import step_timeline
        assert "jax" not in sys.modules, "importing the tracer pulled jax in"
        with perf_tracer.trace_scope("areal.train.rollout_wait", args={"global_step": 1}) as span:
            span.set(done=1)
            assert span._ann is None
        with step_timeline.engine_phase("host_prep"):
            pass
        perf_tracer.instant("areal.request.admitted", args={"queue_wait_us": 1})
        # the record holds them all the same, tracer not enabled
        names = [e.name for e in perf_tracer.get_tracer().record().entries]
        assert names == ["areal.train.rollout_wait", "areal.train.host_prep", "areal.request.admitted"], names
        assert "jax" not in sys.modules, "a span imported jax"
        print("OK")
        """
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=120)
    assert out.returncode == 0 and out.stdout.strip() == "OK", out.stderr[-2000:]


def _scopes_in(lowered) -> set[str]:
    """Every path component of every op name in a lowered program's text;
    autodiff wraps a scope's name (``transpose(jvp(attn))``)."""
    text = lowered.as_text(debug_info=True)
    return {part for name in re.findall(r'loc\("([^"]+)"', text) for part in re.split(r"[/()]+", name)}


def _lower_chunk(eng):
    wp = 2
    fn = eng.programs.chunk_fn(8, wp, False, True, False)
    with jax.set_mesh(eng.mesh):
        return fn.lower(eng.params, eng.cache, jnp.asarray(eng.slots.page_table(wp)), eng._dev_state, eng._rng)


def _lower_prefill(eng):
    bucket = eng.config.page_size
    fn = eng.programs.prefill_fn(1, bucket)
    ids = jnp.zeros((1, bucket), jnp.int32)
    with jax.set_mesh(eng.mesh):
        return fn.lower(
            eng.params, eng.cache, ids, jnp.array([5], jnp.int32), jnp.array([1], jnp.int32), jnp.array([0], jnp.int32)
        )


def _lower_train_step(eng, monkeypatch):
    got = {}
    orig = eng._get_fused_step_fn

    def spy(*a, **k):
        fn = orig(*a, **k)

        def call(*args):
            got["lowered"] = fn.lower(*args)
            return fn(*args)

        return call

    monkeypatch.setattr(eng, "_get_fused_step_fn", spy)
    eng.train_batch(random_batch(seed=2), sft_loss, weight_fn)
    return got["lowered"]


@pytest.mark.parametrize(
    "program,scopes",
    [
        ("chunk", qwen.SCOPES + ("sampler",)),
        ("prefill", ("embed", "attn_proj", "attn", "mlp", "kv_write")),
        ("train_step", ("embed", "attn_proj", "attn", "mlp", "lm_head", "loss", "optimizer")),
    ],
)
def test_lowered_programs_hold_their_scopes(program, scopes, request, monkeypatch):
    if program == "train_step":
        lowered = _lower_train_step(request.getfixturevalue("train_engine"), monkeypatch)
    else:
        eng = request.getfixturevalue("decode_engine")
        lowered = (_lower_chunk if program == "chunk" else _lower_prefill)(eng)
    missing = set(scopes) - _scopes_in(lowered)
    assert not missing, f"{program} lost the scopes {sorted(missing)}"
    # the module name is what a device trace shows and the benchmark's readers match
    module = {"train_step": "jit_step"}.get(program, "jit_" + program)
    assert f"module @{module} " in lowered.as_text()
