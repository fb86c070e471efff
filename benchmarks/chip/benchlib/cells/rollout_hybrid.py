"""Cell kind ``rollout_hybrid``: the ``rollout`` kind for a model of the
hybrid family (state-space layers beside attention layers,
``areal_tpu/models/hybrid.py``).

The served system, the warm-up waves, the load generator, the window with
both edges on a chunk drain, the grace period, the ``/drain`` and the
arithmetic of every end-to-end metric are ``cells/rollout.py``'s, whose
helpers this imports unchanged, so that the numbers mean what they mean in
the Qwen rollout cells. What differs is what names the family: the program's
model configuration, the seeded weights (``hybrid_weights.py``), the
reference of the output check (``hybrid_reference.py``) and the control.

The output check has two numbers, each under a limit of its own. The Qwen
cells' mean |logprob - reference| finds a wrong token in a state or a page,
but at this model's widths it cannot see the state's precision: what a
bfloat16 state adds to a logprob is a twentieth of what the bfloat16
arithmetic of 40 layers leaves there anyway (PERF.md section 4). So the state
itself is compared too (``state_probe``): once the traffic has ended, a few
requests decode some hundreds of tokens on the idle engine, and each one's
slot must hold, in the first state-space layer, the state the reference
reaches token by token after exactly the tokens the request consumed (every
token but the last sampled one, which nothing was fed). The slots are found from
outside: those whose state changed during the probe, which must be as many as
the probe's requests, since a step may change a live slot's state only.

Control (``control.py``): the step below what the configuration states, the
recurrent state kept in bfloat16 instead of float32 and the attention
layers' KV pages in int8 (the program has no int8 weights for this family).
It has to fail one of the two limits, and fails the state's.

Each run also logs where its set-up went and, from the program's
``areal_decode_phase_seconds`` histograms, how the engine loop's wall time in
the window splits into phases, so that a run that stalls says whether the
loop was waiting for the device or busy on the host.
"""

from __future__ import annotations

import gc
import json
import os
import subprocess
import sys
import time
import urllib.request

import numpy as np

from benchlib import harness, hybrid_reference, hybrid_weights, loadgen, traffic
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
PHASES = "areal_decode_phase_seconds"
CONTROL_NOTE = " [CONTROL: recurrent state in bfloat16, int8 KV pages]"


def model_config(cfg: dict, dtype: str, control: bool):
    """The program's model configuration for a configuration file: its
    published keys plus what the file lists under ``assumed``."""
    from areal_tpu import models

    hf = {k: v for k, v in cfg.items() if k not in NOTES}
    hf.update(cfg.get("assumed", {}))
    for k in ("time_step_limit", "initializer_range"):  # (0, inf) is what the program computes; the weights are the benchmark's
        hf.pop(k, None)
    hf["dtype"] = dtype
    if control:
        hf["ssm_state_dtype"] = "bfloat16"
    return models.config_from_hf_dict(hf)


def build_server(cfg: dict, sp: dict, dtype: str, seed: int, dev, control: bool):
    """(engine, server thread, the seeded weights: the engine serves these
    very arrays and the reference reads them too)."""
    import jax

    from areal_tpu import models
    from areal_tpu.api.config import MeshConfig, PrefixCacheConfig, ServerConfig
    from areal_tpu.inference.decode_engine import DecodeEngine
    from areal_tpu.inference.server import ServerThread
    from areal_tpu.parallel import mesh as mesh_lib

    mcfg = model_config(cfg, dtype, control)
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
        # cache away from a model with recurrent layers
        prefix_cache=PrefixCacheConfig(enabled=True),
        kv_quantization="int8" if control else "none",
    )
    mesh = mesh_lib.make_mesh(scfg.mesh, devices=dev)
    shardings = mesh_lib.param_sharding(mesh, models.family_of(mcfg).param_partition_specs(mcfg))
    with jax.set_mesh(mesh):
        params = hybrid_weights.make_params(cfg, seed, mcfg.jax_dtype, shardings)
    eng = DecodeEngine(scfg, params=params, model_cfg=mcfg, mesh=mesh)
    eng.initialize()
    return eng, ServerThread(scfg, eng), params


def check_outputs(records, params, cfg, sp, chk, seed) -> dict:
    """Server logprobs of the sampled tokens against the reference's full
    forward over prompt+output, on a seeded sample of finished requests (the
    longest quarter of the sample always in), as ``rollout.check_outputs``."""
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
        ref = hybrid_reference.token_logprobs(params, cfg, p + r["tokens"], pad_to=int(sp["max_seq_len"]))
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


def read_phases(addr: str) -> dict:
    """{phase: (seconds, passes, {upper edge: passes at or under it})} of the
    engine loop's phase histograms on the server's /metrics page."""
    out: dict = {}
    for line in _get(addr, "/metrics").decode().splitlines():
        if not line.startswith(PHASES + "_"):
            continue
        name, _, val = line.rpartition(" ")
        labels = dict(kv.split("=", 1) for kv in name[name.index("{") + 1 : -1].split(","))
        ph = out.setdefault(labels["phase"].strip('"'), [0.0, 0.0, {}])
        if name.startswith(PHASES + "_sum"):
            ph[0] = float(val)
        elif name.startswith(PHASES + "_count"):
            ph[1] = float(val)
        elif "le" in labels:
            ph[2][float(labels["le"].strip('"'))] = float(val)
    return out


def phase_line(p0: dict, p1: dict) -> str:
    """The loop's wall seconds by phase between two readings, and the
    histogram bucket of the slowest ``device_wait`` among them."""
    parts = []
    for ph in sorted(p1, key=lambda k: -(p1[k][0] - p0.get(k, [0.0])[0])):
        s = p1[ph][0] - p0.get(ph, [0.0])[0]
        if s >= 0.0005:
            parts.append(f"{ph} {s:.3f}s")
    line = ", ".join(parts) or "no pass"
    dw1, dw0 = p1.get("device_wait"), p0.get("device_wait", [0.0, 0.0, {}])
    if dw1 and dw1[1] > dw0[1]:
        edges = sorted(dw1[2])
        new = [dw1[2][e] - dw0[2].get(e, 0.0) for e in edges]
        top = next((e for e, below in zip(edges, new) if below >= dw1[1] - dw0[1]), float("inf"))
        line += f"; {dw1[1] - dw0[1]:.0f} passes, the slowest device_wait at or under {top}s"
    return line


def state_marks(eng) -> np.ndarray:
    """One number a slot: the sum of |state| over every state-space layer.
    It changes when the slot's state does. One fused reduction over the
    state where it lies, no copy of it; read while the engine is idle or
    stopped (a running loop donates the array to its next chunk)."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda s: jnp.sum(jnp.abs(s.astype(jnp.float32)), axis=(0, 2, 3, 4)))
    return np.asarray(fn(eng.cache["ssm"]))


def probe_states(addr: str, eng, cfg: dict, mix: dict, pp: dict, seed: int):
    """Send the state probe to the idle server (the traffic has ended and the
    drain is lifted here). Returns (its records, the slot marks before it)."""
    rng = traffic.rng_for(seed, 11)
    reqs = [
        (rng.integers(0, int(cfg["vocab_size"]), int(pp["prompt_len"])).tolist(), int(pp["new_tokens"]))
        for _ in range(int(pp["requests"]))
    ]
    loadgen.post(addr, "/undrain", {})
    before = state_marks(eng)
    recs = loadgen.held_wave(addr, reqs, mix["sampling"])
    for r, (prompt, _) in zip(recs, reqs):
        r["prompt"] = prompt
    return recs, before


def pull_changed_states(eng, before: np.ndarray, limit: int):
    """(slots of a stopped engine whose state changed since ``before``, their
    state in the first state-space layer as float32 [slots, heads, head size, state size])."""
    import jax.numpy as jnp

    changed = np.flatnonzero(state_marks(eng) != before)
    return changed, np.asarray(eng.cache["ssm"][0][jnp.asarray(changed[:limit], jnp.int32)].astype(jnp.float32))


def check_states(recs, changed, states, params, cfg, sp) -> dict:
    """The probe's slots against the reference, in the first state-space
    layer. There the recurrence's inputs are one bfloat16 matmul away from the
    reference's; further down, the error the hidden state has gathered (the
    deep layers' states stand 2-3% off the reference's for that alone, PERF.md
    section 4) hides what the state's own precision adds. A head's error is
    norm(slot's state - reference's) / norm(reference's); ``state_rel`` is the
    mean over the probe's requests of the worst head's, the head that
    remembers longest and so gathers the most rounding of its state. A
    request's slot is the changed one nearest its reference."""
    ok = [r for r in recs if r.get("ok")]
    out = {"probe_requests": len(recs), "probe_failed": len(recs) - len(ok), "slots_changed": int(len(changed))}
    if len(ok) < len(recs) or len(changed) != len(recs):
        return {**out, "state_rel": None}
    per_head = []
    for r in ok:
        consumed = r["prompt"] + r["tokens"][:-1]  # the last sampled token was fed to nothing
        ref = hybrid_reference.first_layer_state(params, cfg, consumed, pad_to=int(sp["max_seq_len"])).astype(np.float64)
        err = ((states - ref) ** 2).sum(axis=(2, 3))  # [slots, heads]
        per_head.append(np.sqrt(err[int(err.sum(axis=1).argmin())] / (ref * ref).sum(axis=(1, 2))))
    e = np.stack(per_head)  # [requests, heads]
    return {
        **out,
        "state_rel": float(e.max(axis=1).mean()),
        "state_rel_head_mean": float(e.mean()),
        "state_rel_head_p90": float(np.percentile(e, 90, axis=1).mean()),
        "state_tokens": int(sum(len(r["prompt"]) + len(r["tokens"]) - 1 for r in ok)),
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
    eng, server, params = build_server(cfg, sp, p["dtype"], seed, devs, ctx["control"])
    t_engine = time.monotonic()
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
        t_swept = time.monotonic()
        if int(line[2]):
            raise RuntimeError("a warm-up request failed")
        if child.stdout.readline().strip() != "RUNNING":
            raise RuntimeError("load generator did not start its clients")
        time.sleep(float(p["warm_seconds"]))
        # ---- the window (as in cells/rollout.py) -------------------------
        c_warm = harness.compile_counts()
        if ctx["trace"]:
            sampler = GaugeSampler(addr)
            sampler.start()
            tracer = harness.Tracer(ctx["tmp"])
            tracer.start()
        c0, t0 = counters_at_next_drain(addr)
        ph0 = read_phases(addr)
        setup_s = t0 - ctx["t0"]
        if tracer is not None:
            time.sleep(min(seconds, float(p["trace_seconds"])))
            ct = read_counters(addr)
            trace = tracer.stop()
            trace_counters = {k: ct[k] - c0[k] for k in ct}
        time.sleep(max(0.0, t0 + seconds - time.monotonic()))
        c1, t1 = counters_at_next_drain(addr)
        ph1 = read_phases(addr)
        c_end = harness.compile_counts()
        if sampler is not None:
            sampler.stop()
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
        # ---- the state probe, on the idle engine (this kind only) --------
        t_probe = time.monotonic()
        probe, marks = probe_states(addr, eng, cfg, mix, chk["state_probe"], seed)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait(30)
        t_stop = time.monotonic()
        server.stop()
        harness.log(f"server stopped in {time.monotonic() - t_stop:.1f}s")
    changed, states = pull_changed_states(eng, marks, 2 * len(probe))
    harness.log(
        f"state probe: {len(probe)} requests of {chk['state_probe']['prompt_len']}+{chk['state_probe']['new_tokens']} tokens "
        f"in {t_stop - t_probe:.1f}s; the state of slots {changed.tolist()} changed"
    )
    harness.log(
        f"set-up {setup_s:.1f}s: {t_engine - ctx['t0']:.1f}s to the engine (imports, weights, cache, programs), "
        f"{t_swept - t_engine:.1f}s of warm-up waves, {t0 - t_swept:.1f}s of warm traffic up to a drain; "
        f"{c_warm['compile_seconds'] - c_start['compile_seconds']:.1f}s of it compiling or loading programs"
    )
    harness.log(f"engine loop by phase inside the window: {phase_line(ph0, ph1)}")
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
    res = check_outputs(records, params, cfg, sp, chk, seed)
    limit = float(chk["limit_mean_abs_logprob"])
    correct = res["n"] > 0 and res["mean_abs"] <= limit
    harness.log(
        f"output check: {res['n']} requests ({res.get('radix_hits', 0)} after a radix hit), {res.get('tokens', 0)} tokens: mean |logprob - reference| = "
        f"{res['mean_abs']} (limit {limit}), max {res['max_abs']}"
        + (CONTROL_NOTE if ctx["control"] else "")
    )
    res.update(check_states(probe, changed, states, params, cfg, sp))
    limit_state = float(chk["limit_state_rel"])
    correct = correct and res["state_rel"] is not None and res["state_rel"] <= limit_state
    harness.log(
        f"state check: {res['probe_requests']} requests ({res['probe_failed']} failed, the state of {res['slots_changed']} slots "
        f"changed), {res.get('state_tokens', 0)} tokens consumed, first state-space layer: worst head's |slot's state - reference| / "
        f"|reference|, mean over the requests = {res['state_rel']} (limit {limit_state}); the heads' mean "
        f"{res.get('state_rel_head_mean')}, their 90th percentile {res.get('state_rel_head_p90')}"
        + (CONTROL_NOTE if ctx["control"] else "")
    )
    facts = {
        "kind": "rollout_hybrid",
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
