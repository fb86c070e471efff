"""Cell kind ``rollout_family``: the ``rollout`` kind for a model of ANY family
the decode engine serves, the family's pieces named by the cell's ``params``
and not bound here by import:

    "family": {
      "weights":   benchlib module with make_params(cfg, seed, dtype, shardings)
      "reference": benchlib module with token_logprobs(params, cfg, ids, pad_to)
      "control":   {"server": ServerConfig fields, "round_int8": weight
                    leaves, "note": what it is} : the
                    step below what the configuration states, switched on by
                    control.py. ``round_int8`` hands the engine those
                    matrices rounded to the int8 grid of each output channel
                    (held in their own type: what a weight-only int8 path
                    computes), for a family whose program has no such path;
                    the reference then reads weights made from the seed anew
      "not_the_programs": keys under the file's ``assumed`` that are the
                    benchmark's (the weights' std), not the program's
      "counters":  catalogued counters read at the window's and the trace's
                    edges (beside the rollout kind's own), into facts["extra"]
      "status":    top-level /statusz fields read at the same instants
    }

The served system, the warm-up waves, the load generator, the window with
both edges on a chunk drain, the grace period, the ``/drain`` and the
arithmetic of every end-to-end metric are ``cells/rollout.py``'s, whose
helpers this imports unchanged and whose ``run`` this copies line for line
(``tests/benchmark_harness/test_chipbench_family_files.py`` diffs them), so
that the numbers mean what they mean in the other rollout cells.
``rollout_hybrid`` binds one family's weights and reference by import; this
kind is meant to be the last copy: a later ``benchmark`` PR can move the
other rollout cells onto it and delete theirs.

The output check is the rollout kind's (server logprobs of a seeded sample of
finished requests against the reference's full forward): the mean of
|logprob - reference| is held to ``check.limit``; the percentiles beside it
are log lines.
"""

from __future__ import annotations

import gc
import importlib
import json
import os
import subprocess
import sys
import time
import urllib.request

import numpy as np

from benchlib import harness, traffic
from benchlib.cells.rollout import (
    GaugeSampler,
    _get,
    _summarise,
    counters_at_next_drain,
    decoding_spans,
    read_counters,
    sweep_plan,
)

NOTES = ("source", "reduced", "reduced_from", "assumed", "assumed_notes", "stands_for")


def piece(fam: dict, which: str):
    """The benchlib module the cell names for ``which`` (weights, reference)."""
    return importlib.import_module(f"benchlib.{fam[which]}")


def model_config(cfg: dict, fam: dict, dtype: str):
    """The program's model configuration for a configuration file: its
    published keys plus what the file lists under ``assumed``."""
    from areal_tpu import models

    hf = {k: v for k, v in cfg.items() if k not in NOTES}
    hf.update(cfg.get("assumed", {}))
    for k in fam.get("not_the_programs", ()):
        hf.pop(k, None)
    hf["dtype"] = dtype
    return models.config_from_hf_dict(hf)


def round_int8(params: dict, names) -> dict:
    """``params`` with every leaf called one of ``names`` rounded to the int8
    grid of its output channel (symmetric; the scale is the largest |w| over
    the contracted axis, the last but one, over 127) and cast back to its
    type. A leaf at a time, donated, one layer of a stack at a time: the
    weights fill most of the chip."""
    import jax
    import jax.numpy as jnp

    def one(w):
        w32 = w.astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(w32), axis=-2, keepdims=True), 1e-30) / 127.0
        return (jnp.round(w32 / scale) * scale).astype(w.dtype)

    rounded = jax.jit(lambda w: jax.lax.map(one, w), donate_argnums=0)
    return {
        k: round_int8(v, names) if isinstance(v, dict) else (rounded(v) if k in names else v)
        for k, v in params.items()
    }


def build_server(cfg: dict, sp: dict, fam: dict, dtype: str, seed: int, dev, control: bool):
    """(engine, server thread, a function that returns the seeded weights for
    the reference, to be called once the engine is gone: the very arrays the
    engine serves, unless the control rounded those)."""
    import jax

    from areal_tpu import models
    from areal_tpu.api.config import MeshConfig, PrefixCacheConfig, ServerConfig
    from areal_tpu.inference.decode_engine import DecodeEngine
    from areal_tpu.inference.server import ServerThread
    from areal_tpu.parallel import mesh as mesh_lib

    mcfg = model_config(cfg, fam, dtype)
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
        # on as in the Qwen rollout cells: the engine itself keeps the radix
        # cache away from a model it cannot serve a prefix of
        prefix_cache=PrefixCacheConfig(enabled=True),
        **(fam["control"].get("server", {}) if control else {}),
    )
    mesh = mesh_lib.make_mesh(scfg.mesh, devices=dev)
    shardings = mesh_lib.param_sharding(mesh, models.family_of(mcfg).param_partition_specs(mcfg))
    def make():
        with jax.set_mesh(mesh):
            return piece(fam, "weights").make_params(cfg, seed, mcfg.jax_dtype, shardings)

    params = make()
    rounds = fam["control"].get("round_int8") if control else None
    eng = DecodeEngine(scfg, params=round_int8(params, rounds) if rounds else params, model_cfg=mcfg, mesh=mesh)
    eng.initialize()
    return eng, ServerThread(scfg, eng), (make if rounds else lambda: params)


def read_extra(addr: str, fam: dict) -> dict:
    """{"t", "counters": {name: value}, "status": {field: value or None}} of
    what the cell names beside the rollout kind's counters; a counter or a
    field the program lacks reads 0 or None."""
    want = dict.fromkeys(fam.get("counters", ()), 0.0)
    if want:
        for line in _get(addr, "/metrics").decode().splitlines():
            name, _, val = line.partition(" ")
            if not line.startswith("#") and name.split("{", 1)[0] in want:
                want[name.split("{", 1)[0]] += float(val)
    status = {}
    if fam.get("status"):
        page = json.loads(_get(addr, "/statusz"))
        status = {k: page.get(k) for k in fam["status"]}
    return {"t": time.monotonic(), "counters": want, "status": status}


def extra_facts(x0: dict, xt: dict | None, x1: dict) -> dict:
    """The extra readings as the readers take them: counter deltas over the
    window and over the traced span, the status fields at the window's edges."""
    def delta(a, b):
        return {k: b["counters"][k] - a["counters"][k] for k in b["counters"]}

    return {
        "window_counters": delta(x0, x1),
        "trace_counters": delta(x0, xt) if xt else None,
        "status": (x0["status"], x1["status"]),
    }


def check_outputs(records, params, cfg, sp, chk, seed, fam) -> dict:
    """Server logprobs of the sampled tokens against the reference's full
    forward over prompt+output, on a seeded sample of finished requests (the
    longest quarter of the sample always in), as ``rollout.check_outputs``;
    beside the mean, what says how its tail lies."""
    reference = piece(fam, "reference")
    prompts = {(r["client"], r["group"]): r["prompt"] for r in records if "prompt" in r}
    ok = [r for r in records if r.get("ok") and (r["client"], r["group"]) in prompts and r["n_out"] > 0]
    rng = traffic.rng_for(seed, 7)
    k = min(int(chk["sample"]), len(ok))
    if k == 0:
        return {"n": 0, "mean_abs": None, "max_abs": None}
    ok.sort(key=lambda r: -(r["prompt_len"] + r["n_out"]))
    picked = ok[: max(1, k // 4)]
    rest = ok[len(picked):]
    picked += [rest[i] for i in rng.permutation(len(rest))[: k - len(picked)]]
    errs = []
    for r in picked:
        p = prompts[(r["client"], r["group"])]
        ref = reference.token_logprobs(params, cfg, p + r["tokens"], pad_to=int(sp["max_seq_len"]))
        got = np.asarray(r["logprobs"], np.float64)
        errs.append(np.abs(got - ref[len(p) - 1 :].astype(np.float64)))
    e = np.concatenate(errs)
    return {
        "n": int(len(picked)),
        "tokens": int(e.size),
        "mean_abs": float(e.mean()),
        "median_abs": float(np.median(e)),
        "p90_abs": float(np.percentile(e, 90)),
        "p99_abs": float(np.percentile(e, 99)),
        "share_over_0.1": float((e > 0.1).mean()),
        "max_abs": float(e.max()),
        "radix_hits": sum(1 for r in picked if r.get("cached_prefix_tokens")),
    }


def describe(res: dict) -> str:
    keys = ("median_abs", "p90_abs", "p99_abs", "share_over_0.1", "max_abs")
    return ", ".join(f"{k} {res.get(k)}" for k in keys)


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
    fam = p["family"]
    eng, server, make_params = build_server(cfg, sp, fam, p["dtype"], seed, devs, ctx["control"])
    impl = eng.attention_impl()
    harness.log(f"attention paths: {impl}")
    ledger = eng.hbm_ledger()["components"]
    harness.log("device memory by component: " + ", ".join(f"{k} {v / 1e9:.3f} GB" for k, v in ledger.items()))
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
        x0 = read_extra(addr, fam)
        setup_s = t0 - ctx["t0"]
        if tracer is not None:
            time.sleep(min(seconds, float(p["trace_seconds"])))
            ct = read_counters(addr)
            xt = read_extra(addr, fam)
            trace = tracer.stop()
            trace_counters = {k: ct[k] - c0[k] for k in ct}
        time.sleep(max(0.0, t0 + seconds - time.monotonic()))
        c1, t1 = counters_at_next_drain(addr)
        x1 = read_extra(addr, fam)
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
    del eng, server  # the cache (pages and state) makes room for the reference
    gc.collect()
    res = check_outputs(records, make_params(), cfg, sp, chk, seed, fam)
    limit = float(chk["limit"])
    correct = res["n"] > 0 and res["mean_abs"] <= limit
    harness.log(
        f"output check: {res['n']} requests ({res.get('radix_hits', 0)} after a radix hit), {res.get('tokens', 0)} tokens: mean |logprob - reference| = "
        f"{res['mean_abs']} (limit {limit}); {describe(res)}"
        + (f" [CONTROL: {fam['control']['note']}]" if ctx["control"] else "")
    )
    facts = {
        "kind": "rollout_family",
        "extra": extra_facts(x0, xt if tracer else None, x1),
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
