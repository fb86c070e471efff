"""Cell kind ``rollout``: a DecodeEngine behind a ServerThread in this
process, closed-loop GRPO group clients in a child process.

The engine is built as ``python -m areal_tpu.inference.server`` builds it
(the few lines of ``chip_smoke._start_server``), on weights the benchmark
makes from the seed. Set-up warms exactly the programs the cell's traffic
uses, by sending them: held admission waves that touch every batched-prefill
size at every prompt bucket, group sizes 1..slots for the slot-scatter and
page-copy programs, then ``warm_seconds`` of the traffic itself.

From the program this takes: the served system, ``/generate`` responses
(``ttft``, ``output_tokens``, ``output_logprobs``), ``/metrics`` counters
and ``/statusz`` gauges, and (traced runs) the device trace.
"""

from __future__ import annotations

import gc
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np

from benchlib import harness, reference, traffic, weights

COUNTERS = (
    "areal_decode_generated_tokens_total",
    "areal_decode_prefill_tokens_total",
    "areal_decode_prefills_total",
    "areal_decode_chunks_total",
    "areal_decode_completed_total",
    "areal_prefix_cache_hit_tokens_total",
)


def _get(addr: str, path: str) -> bytes:
    # /metrics answers JSON unless asked for the Prometheus text
    req = urllib.request.Request(f"http://{addr}{path}", headers={"Accept": "text/plain"})
    with urllib.request.urlopen(req, timeout=30) as r:
        return r.read()


def read_counters(addr: str) -> dict:
    """The catalogued counters of COUNTERS from the server's /metrics page."""
    out = dict.fromkeys(COUNTERS, 0.0)
    for line in _get(addr, "/metrics").decode().splitlines():
        if line.startswith("#"):
            continue
        name, _, val = line.partition(" ")
        base = name.split("{", 1)[0]
        if base in out:
            out[base] += float(val)
    return out


def counters_at_next_drain(addr: str, patience: float = 3.0) -> tuple[dict, float]:
    """(counters, time) at the instant the token counter next moves.

    The engine credits a decode chunk's tokens when it drains the chunk
    (32 steps x the active slots at once, about 3% of a 30 s window), so a
    window cut at an arbitrary instant reads one chunk more or less from run
    to run. Both edges of the window are therefore put on a drain: the
    window then holds a whole number of chunks and all of their time."""
    key = "areal_decode_generated_tokens_total"
    first = read_counters(addr)
    deadline = time.monotonic() + patience
    while time.monotonic() < deadline:
        time.sleep(0.01)
        now = read_counters(addr)
        if now[key] != first[key]:
            return now, time.monotonic()
    return first, time.monotonic()


def read_gauges(addr: str) -> dict:
    lc = json.loads(_get(addr, "/statusz"))["lifecycle"]
    return {"t": time.monotonic(), "active_slots": lc["active_slots"]}


class GaugeSampler(threading.Thread):
    """Samples the /statusz gauges a few times a second (traced runs only)."""

    def __init__(self, addr: str, period: float = 0.25):
        super().__init__(daemon=True)
        self.addr, self.period = addr, period
        self.samples: list[dict] = []
        self._halt = threading.Event()

    def run(self):
        while not self._halt.is_set():
            try:
                self.samples.append(read_gauges(self.addr))
            except (OSError, ValueError, KeyError):
                pass
            self._halt.wait(self.period)

    def stop(self):
        self._halt.set()
        self.join(5)


def build_server(cfg: dict, sp: dict, dtype: str, seed: int, dev, control: bool):
    """(engine, server thread, a function that returns the seeded weights for
    the reference, to be called once the engine is gone)."""
    import jax

    from areal_tpu.api.config import MeshConfig, PrefixCacheConfig, ServerConfig
    from areal_tpu.inference.decode_engine import DecodeEngine
    from areal_tpu.inference.server import ServerThread
    from areal_tpu.models import qwen
    from areal_tpu.parallel import mesh as mesh_lib

    mcfg = harness.model_config(cfg, dtype)
    scfg = ServerConfig(
        dtype=dtype,
        max_batch_size=int(sp["slots"]),
        max_seq_len=int(sp["max_seq_len"]),
        page_size=int(sp["page_size"]),
        kv_hbm_gb=sp.get("kv_hbm_gb"),
        decode_steps_per_call=int(sp["decode_steps"]),
        attn_window_step=int(sp["attn_window_step"]),
        seed=seed & 0x7FFFFFFF,
        host="127.0.0.1",
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
        prefix_cache=PrefixCacheConfig(enabled=True),
        # the control of the output check: the program's own int8 paths,
        # weights and KV cache, in place of everything it keeps in bfloat16
        quantization="int8" if control else "none",
        kv_quantization="int8" if control else "none",
    )
    mesh = mesh_lib.make_mesh(scfg.mesh, devices=dev)
    shardings = mesh_lib.param_sharding(mesh, qwen.param_partition_specs(mcfg))
    def make():
        with jax.set_mesh(mesh):
            return weights.make_params(cfg, seed, mcfg.jax_dtype, shardings)

    if control:
        # the engine keeps only its int8 copy (no second reference here, or
        # both would have to fit); the reference's weights come back from the seed
        eng = DecodeEngine(scfg, params=make(), model_cfg=mcfg, mesh=mesh)
        eng.initialize()
        return eng, ServerThread(scfg, eng), make
    params = make()  # the engine serves these very arrays: the reference reads them too
    eng = DecodeEngine(scfg, params=params, model_cfg=mcfg, mesh=mesh)
    eng.initialize()
    return eng, ServerThread(scfg, eng), lambda: params


def sweep_plan(mix: dict, sp: dict) -> list[dict]:
    """Held waves that run every program the traffic can reach: one wave of
    15 distinct prompts at the top of each prompt bucket (batched-prefill
    sizes 8, 4, 2, 1), then one prompt repeated 1, 2, 4, ... slots times
    (slot-scatter and page-copy sizes)."""
    from areal_tpu.utils.data import round_up_to_bucket  # the engine's own bucket rule

    lo, hi = int(mix["prompt_len"]["lo"]), int(mix["prompt_len"]["hi"])
    cap = int(sp["max_seq_len"])
    buckets = sorted({min(cap, round_up_to_bucket(n, 256)) for n in range(lo, hi + 1)})
    per = min(15, int(sp["slots"]) // max(1, len(buckets)))
    waves = [{"prompt_lens": [min(b, hi) for b in buckets], "per_bucket": per, "new_tokens": 2}]
    n = 1
    while n <= int(sp["slots"]):
        waves.append({"prompt_lens": [lo], "per_bucket": 1, "new_tokens": 2, "repeat": n})
        n *= 2
    return waves


def _summarise(records: list[dict], t0: float, t1: float) -> dict:
    """End-to-end latency numbers from the child's records.

    Time to first token is taken over every request sent inside the window,
    also those the cut after the window ended early; time per output token
    and the failures over those that ended inside it."""
    sent_in = [r for r in records if t0 <= r["t_send"] < t1]
    done_in = [r for r in records if t0 <= r["t_done"] < t1]
    first = lambda r: (r.get("ok") or r.get("cut")) and r.get("ttft") is not None and r["n_out"] > 0  # noqa: E731
    ttft = [1e3 * r["ttft"] for r in sent_in if first(r)]
    tpot = [
        1e3 * ((r["t_done"] - r["t_send"]) - r["ttft"]) / (r["n_out"] - 1)
        for r in done_in
        if r.get("ok") and r.get("ttft") is not None and r["n_out"] > 1
    ]
    tok_done = sum(r.get("n_out", 0) for r in done_in if r.get("ok"))
    return {
        "ttft": ttft,
        "ttft_missing": len(sent_in) - len(ttft),
        "tpot": tpot,
        "tokens_of_finished": tok_done,
        "attempted": len({id(r) for r in sent_in} | {id(r) for r in done_in}),
        "failed": sum(1 for r in done_in if not r.get("ok")),
    }


def decoding_spans(records: list[dict]) -> list[tuple]:
    """(first token, end, prompt tokens, output tokens) of every request that
    produced a token: what the decode kernel had to read for it, and when."""
    return [
        (r["t_send"] + r["ttft"], r["t_done"], r["prompt_len"], r["n_out"])
        for r in records
        if (r.get("ok") or r.get("cut")) and r.get("ttft") is not None and r["n_out"] > 0
    ]


def check_outputs(records, params, cfg, sp, chk, seed) -> dict:
    """Server logprobs of the sampled tokens against the reference's full
    forward over prompt+output, on a seeded sample of finished requests."""
    prompts = {(r["client"], r["group"]): r["prompt"] for r in records if "prompt" in r}
    ok = [r for r in records if r.get("ok") and (r["client"], r["group"]) in prompts and r["n_out"] > 0]
    rng = traffic.rng_for(seed, 7)
    k = min(int(chk["sample"]), len(ok))
    if k == 0:
        return {"n": 0, "mean_abs": None, "max_abs": None}
    ok.sort(key=lambda r: -(r["prompt_len"] + r["n_out"]))
    picked = ok[: max(1, k // 4)]  # the longest contexts are always in
    rest = ok[len(picked):]
    picked += [rest[i] for i in rng.permutation(len(rest))[: k - len(picked)]]
    errs = []
    for r in picked:
        p = prompts[(r["client"], r["group"])]
        ids = p + r["tokens"]
        ref = reference.token_logprobs(params, cfg, ids, pad_to=int(sp["max_seq_len"]))
        got = np.asarray(r["logprobs"], np.float64)
        errs.append(np.abs(got - ref[len(p) - 1 :].astype(np.float64)))
    e = np.concatenate(errs)
    return {
        "n": int(len(picked)),
        "tokens": int(e.size),
        "mean_abs": float(e.mean()),
        "max_abs": float(e.max()),
        "radix_hits": sum(1 for r in picked if r.get("cached_prefix_tokens")),
    }


def run(ctx: dict) -> dict:
    cell, seed, seconds = ctx["cell"], ctx["seed"], ctx["seconds"]
    p = dict(cell["params"])
    cfg, mix = dict(cell["model"]), cell["traffic"]
    reh = ctx["rehearsal"]
    if reh:
        cfg, mix = reh["model"], reh.get("traffic", mix)
        p = {**p, **reh["params"]}
    sp, chk = p["server"], p["check"]
    devs = harness.devices_for(cell["chips"], bool(reh))
    cache = harness.setup_compile_cache(ctx["bench"].root)
    harness.log(f"cell {cell['name']} seed {seed} device {devs[0].device_kind} compile cache {cache}")
    c_start = harness.compile_counts()
    eng, server, make_params = build_server(cfg, sp, p["dtype"], seed, devs, ctx["control"])
    impl = eng.attention_impl()
    harness.log(f"attention paths: {impl}")
    server.start()
    addr = server.address
    plan = {
        "addr": addr,
        "mix": mix,
        "seed": seed,
        "vocab": int(cfg["vocab_size"]),
        "clients": int(p["clients"]),
        "sweep": sweep_plan(mix, sp),
        "out": os.path.join(ctx["tmp"], "records.json"),
    }
    plan_path = os.path.join(ctx["tmp"], "plan.json")
    with open(plan_path, "w") as f:
        json.dump(plan, f)
    loadgen = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "loadgen.py")
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env["JAX_PLATFORMS"] = "cpu"  # belt and braces: the child never imports jax
    child = subprocess.Popen(
        [sys.executable, loadgen, plan_path], stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env
    )
    sampler = tracer = trace = None
    trace_counters = None
    try:
        line = child.stdout.readline().split()
        if not line or line[0] != "SWEPT":
            raise RuntimeError(f"load generator said {line!r} instead of SWEPT")
        harness.log(f"warm-up waves: {line[1]} requests ok, {line[2]} failed")
        if int(line[2]):
            raise RuntimeError("a warm-up request failed")
        if child.stdout.readline().strip() != "RUNNING":
            raise RuntimeError("load generator did not start its clients")
        time.sleep(float(p["warm_seconds"]))
        # ---- the window --------------------------------------------------
        c_warm = harness.compile_counts()
        if ctx["trace"]:
            sampler = GaugeSampler(addr)
            sampler.start()
            tracer = harness.Tracer(ctx["tmp"])
            tracer.start()
        c0, t0 = counters_at_next_drain(addr)
        setup_s = t0 - ctx["t0"]
        if tracer is not None:
            time.sleep(min(seconds, float(p["trace_seconds"])))
            ct = read_counters(addr)
            trace = tracer.stop()
            trace_counters = {k: ct[k] - c0[k] for k in ct}
        time.sleep(max(0.0, t0 + seconds - time.monotonic()))
        c1, t1 = counters_at_next_drain(addr)
        c_end = harness.compile_counts()
        if sampler is not None:
            sampler.stop()
        # the traffic runs on until the requests sent inside the window have
        # their first token; then no client starts another group and the
        # server ends what is in flight and queued (a drain with no budget),
        # so every call returns with the tokens it has and its ttft
        time.sleep(float(p["ttft_grace_seconds"]))
        child.stdin.write("STOP\n")
        child.stdin.flush()
        if child.stdout.readline().strip() != "STOPPING":
            raise RuntimeError("load generator did not stop")
        drain = urllib.request.Request(f"http://{addr}/drain", data=b'{"budget_s": 0}', method="POST")
        urllib.request.urlopen(drain, timeout=60).read()
        if not child.stdout.readline().startswith("WROTE"):
            raise RuntimeError("load generator wrote no records")
        child.wait(30)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait(30)
        t_stop = time.monotonic()
        server.stop()
        harness.log(f"server stopped in {time.monotonic() - t_stop:.1f}s")
    with open(plan["out"]) as f:
        records = json.load(f)["records"]
    device = harness.device_report(devs)
    window = t1 - t0
    delta = {k: c1[k] - c0[k] for k in c1}
    s = _summarise(records, t0, t1)
    n_comp = c_end["compiles"] - c_warm["compiles"]
    n_hit = c_end["cache_hits"] - c_warm["cache_hits"]
    harness.log(
        f"set-up compiled {c_warm['compiles'] - c_start['compiles']} programs "
        f"({c_warm['cache_hits'] - c_start['cache_hits']} from the persistent cache); "
        f"inside the window: {n_comp} compilations ({n_hit} cache hits)"
    )
    values = {"setup_s": setup_s, "rollout_tok_s": delta["areal_decode_generated_tokens_total"] / window}
    harness.log(
        f"window {window:.3f}s: counter says {delta['areal_decode_generated_tokens_total']:.0f} tokens; "
        f"responses finished inside it carry {s['tokens_of_finished']} tokens "
        f"({s['tokens_of_finished'] / window:.1f} tok/s, a check on the counter: it misses requests that cross the end)"
    )
    if s["ttft"]:
        # groups of 8 wait whole chunks: one group more or less beyond the 95th
        # percentile moves it by a chunk time, so the median carries the bound
        values["ttft_p50_ms"] = harness.percentile(s["ttft"], 50)
        values["ttft_p95_ms"] = harness.percentile(s["ttft"], 95)
        harness.log(
            f"ttft ms: n={len(s['ttft'])} of the requests sent inside the window ({s['ttft_missing']} more had no token "
            f"{p['ttft_grace_seconds']}s after it, or failed) median={values['ttft_p50_ms']:.1f} p95={values['ttft_p95_ms']:.1f}"
        )
    if s["tpot"]:
        values["tpot_p95_ms"] = harness.percentile(s["tpot"], 95)
        harness.log(f"tpot ms: n={len(s['tpot'])} median={harness.percentile(s['tpot'], 50):.2f} p95={values['tpot_p95_ms']:.2f}")
    # ---- outputs against the reference, outside the window ---------------
    del eng, server  # the KV pool makes room for the reference
    gc.collect()
    res = check_outputs(records, make_params(), cfg, sp, chk, seed)
    limit = float(chk["limit_mean_abs_logprob"])
    correct = res["n"] > 0 and res["mean_abs"] <= limit
    harness.log(
        f"output check: {res['n']} requests ({res.get('radix_hits', 0)} after a radix hit), {res.get('tokens', 0)} tokens: mean |logprob - reference| = "
        f"{res['mean_abs']} (limit {limit}), max {res['max_abs']}"
        + (" [CONTROL: program with int8 weights and int8 KV]" if ctx["control"] else "")
    )
    facts = {
        "kind": "rollout",
        "cfg": cfg,
        "server": sp,
        "window_s": window,
        "counters": delta,
        "trace": trace,
        "trace_counters": trace_counters,
        "trace_span": (tracer.t_start, tracer.t_stop) if tracer else None,
        "gauges": sampler.samples if sampler else [],
        "decoding": decoding_spans(records),
        "attention_impl": impl,
        "device_kind": devs[0].device_kind,
        "check": res,
    }
    out = {
        "correct": bool(correct),
        "attempted": s["attempted"],
        "failed": s["failed"],
        "values": values,
        "facts": facts,
        "device": device,
    }
    return harness.attach_trace(out, trace)
