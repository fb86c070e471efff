#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process holds one TPU chip from start to end and drives the system's
main path once, through the entry points a user would call, at the
published widths of Qwen2.5-1.5B (examples/smoke/qwen2p5_1p5b/config.json)
with seeded random weights:

  device   what jax sees, the peaks from the chip table, the compile cache
  kernels  every Pallas kernel of the main path, compiled (interpret=False),
           against its XLA reference (tools/kernelcheck.py --compiled)
  serve    DecodeEngine + ServerThread at full depth: /health, then 16
           POST /generate requests over HTTP
  train    JaxTrainEngine at full depth: three GRPO train_batch steps
  rl_loop  examples/math/gsm8k_rl.py main(): three trainer steps with a
           versioned weight update into the in-process server after each
           (depth cut: trainer and server share the chip)

Each phase prints one JSON line; a phase that fails raises, and the run
ends non-zero. The last line of a complete run on a TPU is exactly
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Off a TPU the script fails at once. ``--size tiny`` is the rehearsal: the
same phases at toy widths on whatever backend jax finds — off a TPU it
still never prints "ok": true and never exits 0.

``--chips 4`` (run by the builder, never by the driver) runs ONLY the
four-chip path and what it is compared with: one train step on an fsdp=4
and on an fsdp=2 x model=2 mesh against the same step on one chip, and a
model=2 server against the one-chip server.

Nothing here spawns a process that imports jax, and nothing touches the
network beyond loopback HTTP to the in-process server.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import gc
import json
import math
import os
import sys
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
FULL_MODEL_DIR = "examples/smoke/qwen2p5_1p5b"  # the published config.json
RL_FULL_CONFIG = "examples/smoke/chip_grpo.yaml"
RL_TINY_CONFIG = "examples/smoke/synthetic_grpo.yaml"
PHASES = ("device", "kernels", "serve", "train", "rl_loop")
LOGPROB_TOL = 5e-2  # kernel-vs-gather / sharded-vs-one-chip, bf16 weights
LOSS_RTOL = 2e-2  # sharded-vs-one-chip loss and grad norm


def emit(**kv) -> None:
    print(json.dumps(kv), flush=True)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


class Meter:
    """Wall seconds and XLA compile activity of one phase."""

    def __init__(self):
        from areal_tpu.utils.compile_cache import compile_stats

        self._stats = compile_stats
        self.t0 = time.monotonic()
        self.c0 = compile_stats()

    def compiles(self) -> int:
        return self._stats()["compiles"] - self.c0["compiles"]

    def report(self) -> dict:
        c1 = self._stats()
        return {
            "secs": round(time.monotonic() - self.t0, 1),
            "compiles": c1["compiles"] - self.c0["compiles"],
            "compile_secs": round(c1["compile_seconds"] - self.c0["compile_seconds"], 1),
            "cache_hits": c1["cache_hits"] - self.c0["cache_hits"],
        }


def hbm(device) -> dict:
    """bytes_in_use now and the process-lifetime peak (cumulative across
    phases: the backend keeps no per-phase peak)."""
    s = device.memory_stats() or {}
    return {
        "hbm_in_use_gb": round(s.get("bytes_in_use", 0) / 1e9, 2),
        "hbm_peak_gb": round(s.get("peak_bytes_in_use", 0) / 1e9, 2),
    }


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def sizes(size: str) -> dict:
    """Everything that differs between the real run and the rehearsal."""
    if size == "full":
        return dict(
            model_dir=FULL_MODEL_DIR,
            dtype="bfloat16",
            slots=128,
            max_seq_len=2048,
            page_size=128,
            kv_hbm_gb=4.0,
            decode_steps=32,
            # (prompt tokens, new tokens): 128-1024 / 64-256, two buckets
            lens=[(128, 64), (200, 128), (256, 256), (1000, 96), (1024, 64), (960, 160)],
            prefix=768,  # shared prefix of the radix pair (6 pages)
            pair_len=900,
            buckets=[256, 1024],
            precompile_budget_s=420.0,
            train_rows=6,
            train_len=(1500, 2048),
            rl_config=RL_FULL_CONFIG,
            rl_overrides=[],
        )
    return dict(
        model_dir="examples/smoke/tiny_model",
        dtype="float32",
        slots=8,
        max_seq_len=256,
        page_size=16,
        kv_hbm_gb=None,
        decode_steps=8,
        lens=[(20, 8), (40, 12), (64, 16), (200, 8), (256 - 20, 8), (180, 12)],
        prefix=96,
        pair_len=120,
        buckets=[256],
        precompile_budget_s=120.0,
        train_rows=6,
        train_len=(60, 128),
        rl_config=RL_TINY_CONFIG,
        # the learning smoke's config, re-sized like chip_grpo.yaml: one
        # batch ahead, one rollout at a time, long rollouts
        rl_overrides=[
            "train_dataset.batch_size=2",
            "rollout.consumer_batch_size=2",
            "rollout.max_concurrent_rollouts=1",
            "rollout.max_head_offpolicyness=1",
            "gconfig.max_new_tokens=200",
            "server.max_seq_len=256",
            "server.attn_window_step=256",
        ],
    )


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_device(args, on_tpu: bool) -> dict:
    import jax

    from areal_tpu import native
    from areal_tpu.observability import hw_accounting as hw
    from areal_tpu.utils.compile_cache import (
        enable_persistent_cache,
        install_compile_counters,
    )

    check(install_compile_counters(), "jax monitoring hook unavailable")
    cache_dir = enable_persistent_cache()
    devs = jax.devices()
    d = devs[0]
    if on_tpu:
        check(
            len(devs) == args.chips,
            f"--chips {args.chips} but jax reports {len(devs)} devices",
        )
    flops, membw, source = hw.resolve_chip_peaks(d)  # unknown TPU kind raises; a CPU has none
    info = {
        "platform": d.platform,
        "kind": d.device_kind,
        "count": len(devs),
    }
    emit(
        phase="device",
        **info,
        jax=jax.__version__,
        hbm_limit_gb=round((d.memory_stats() or {}).get("bytes_limit", 0) / 1e9, 2),
        peak_tflops=flops and round(flops / 1e12, 1),
        peak_membw_gbps=membw and round(membw / 1e9, 1),
        peaks_source=source,
        compile_cache_dir=cache_dir,
        datapack=native.implementation(),
        size=args.size,
        seed=args.seed,
    )
    return info


def phase_kernels(args, on_tpu: bool) -> None:
    from areal_tpu.tools import kernelcheck

    m = Meter()
    results = kernelcheck.run_all(compiled=on_tpu)
    failed = [r for r in results if not r["ok"]]
    worst: dict[str, float] = {}
    for r in results:
        if "max_abs_diff" in r:
            worst[r["kernel"]] = max(worst.get(r["kernel"], 0.0), r["max_abs_diff"])
    emit(
        phase="kernels",
        mode="compiled" if on_tpu else "interpret",
        cases=len(results),
        failed=[
            {k: r.get(k) for k in ("kernel", "case", "max_abs_diff", "tol", "error")}
            for r in failed
        ],
        max_abs_diff={k: float(f"{v:.3g}") for k, v in sorted(worst.items())},
        chip_tol=kernelcheck.CHIP_TOL,
        **m.report(),
    )
    check(not failed, f"{len(failed)} kernel case(s) diverged from the XLA reference")


def _load_model_cfg(model_dir: str, dtype: str, num_layers: int | None = None):
    from areal_tpu.models import qwen

    cfg = qwen.ModelConfig.from_hf_path(os.path.join(ROOT, model_dir))
    kw = {**cfg.__dict__, "dtype": dtype}
    if num_layers is not None:
        kw["num_layers"] = num_layers
    return qwen.ModelConfig(**kw)


def _post(addr: str, path: str, body: dict, timeout: float = 900.0) -> dict:
    req = urllib.request.Request(
        f"http://{addr}{path}",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _generate(addr: str, ids: list[int], new: int, greedy: bool) -> dict:
    out = _post(
        addr,
        "/generate",
        {
            "input_ids": ids,
            "sampling_params": {
                "max_new_tokens": new,
                "greedy": greedy,
                "temperature": 1.0,
                "ignore_eos": True,
            },
        },
    )
    toks, lps = out["output_tokens"], out["output_logprobs"]
    check(len(toks) == new, f"asked {new} tokens, got {len(toks)} ({out['stop_reason']})")
    check(
        len(lps) == new and all(math.isfinite(x) for x in lps),
        "non-finite or missing logprobs",
    )
    return out


def _wave(addr: str, reqs: list[tuple[list[int], int, bool]]) -> list[dict]:
    with concurrent.futures.ThreadPoolExecutor(len(reqs)) as pool:
        futs = [pool.submit(_generate, addr, *r) for r in reqs]
        return [f.result() for f in futs]


def _agree(a: dict, b: dict, what: str) -> dict:
    """Greedy twins: the first token must match, and logprobs must agree
    within LOGPROB_TOL for as long as the tokens do (after a near-tie flips
    one argmax the two sequences are different questions)."""
    ta, tb = a["output_tokens"], b["output_tokens"]
    n = next((i for i, (x, y) in enumerate(zip(ta, tb)) if x != y), len(ta))
    check(n >= 1, f"{what}: first greedy token differs ({ta[0]} vs {tb[0]})")
    diff = max(
        abs(x - y) for x, y in zip(a["output_logprobs"][:n], b["output_logprobs"][:n])
    )
    check(diff <= LOGPROB_TOL, f"{what}: logprobs differ by {diff:.4f} > {LOGPROB_TOL}")
    return {"tokens_compared": n, "of": len(ta), "max_logprob_diff": float(f"{diff:.3g}")}


def _server_config(sz: dict, seed: int, mesh=None):
    from areal_tpu.api.config import MeshConfig, ServerConfig

    return ServerConfig(
        dtype=sz["dtype"],
        max_batch_size=sz["slots"],
        max_seq_len=sz["max_seq_len"],
        page_size=sz["page_size"],
        kv_hbm_gb=sz["kv_hbm_gb"],
        decode_steps_per_call=sz["decode_steps"],
        # one attention window: every decode chunk is the same program
        attn_window_step=sz["max_seq_len"],
        seed=seed,
        host="127.0.0.1",
        mesh=mesh or MeshConfig(data=-1, fsdp=1, seq=1, model=1),
    )


def _start_server(scfg, mcfg, seed: int, devices=None):
    """The server as ``python -m areal_tpu.inference.server`` builds it
    (ServerThread over a DecodeEngine), on seeded random weights."""
    import jax

    from areal_tpu.inference.decode_engine import DecodeEngine
    from areal_tpu.inference.server import ServerThread
    from areal_tpu.models import qwen
    from areal_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.make_mesh(scfg.mesh, devices=devices)
    shardings = mesh_lib.param_sharding(mesh, qwen.param_partition_specs(mcfg))
    with jax.set_mesh(mesh):
        params = jax.jit(
            lambda k: qwen.init_params(k, mcfg), out_shardings=shardings
        )(jax.random.PRNGKey(seed))
    eng = DecodeEngine(scfg, params=params, model_cfg=mcfg, mesh=mesh)
    eng.initialize()
    return eng, ServerThread(scfg, eng)


def phase_serve(args, on_tpu: bool) -> None:
    import jax
    import numpy as np

    sz = sizes(args.size)
    mcfg = _load_model_cfg(sz["model_dir"], sz["dtype"])
    m = Meter()
    eng, server = _start_server(
        _server_config(sz, args.seed), mcfg, args.seed, devices=jax.devices()[:1]
    )
    if on_tpu:
        impl = eng.attention_impl()
        check(
            impl["decode"] == "pallas" and impl["suffix_prefill"] == "pallas",
            f"engine does not report the Pallas kernels: {impl}",
        )
    else:
        eng.programs.set_suffix_kernel(True)  # rehearse the kernel body (interpreter)
        impl = eng.attention_impl()
    eng.precompile(prompt_buckets=sz["buckets"], budget_s=sz["precompile_budget_s"])
    warm = m.report()
    server.start()
    addr = server.address
    try:
        with urllib.request.urlopen(f"http://{addr}/health", timeout=30) as r:
            check(json.loads(r.read())["status"] == "ok", "/health not ok")
        rng = np.random.default_rng(args.seed)
        V = mcfg.vocab_size

        def prompt(n):
            return [int(t) for t in rng.integers(0, V, n)]

        def traffic():
            return [
                (prompt(p), new, i % 2 == 0) for i, (p, new) in enumerate(sz["lens"])
            ]

        prefix = prompt(sz["prefix"])
        tail = sz["pair_len"] - sz["prefix"]
        first, second, third = (prefix + prompt(tail) for _ in range(3))
        run = Meter()
        n_new = 64 if args.size == "full" else 8
        # 1. publish the shared prefix (cold prefill)
        a = _generate(addr, first, n_new, True)
        check(a["cached_prefix_tokens"] == 0, "first request hit a cold cache")
        # 2. a wave: mixed lengths, greedy and sampled, one radix hit riding
        #    the Pallas suffix-prefill kernel
        w1 = _wave(addr, traffic() + [(second, n_new, True)])
        b_kernel = w1[-1]
        check(
            b_kernel["cached_prefix_tokens"] >= sz["prefix"],
            f"no radix hit: {b_kernel['cached_prefix_tokens']} cached tokens",
        )
        # 3. the same pair over the gather path
        _post(addr, "/flush_prefix_cache", {})
        eng.programs.set_suffix_kernel(False)
        _generate(addr, first, n_new, True)
        b_xla = _generate(addr, second, n_new, True)
        check(b_xla["cached_prefix_tokens"] >= sz["prefix"], "no radix hit (gather)")
        eng.programs.set_suffix_kernel(None if on_tpu else True)
        agree = _agree(b_kernel, b_xla, "suffix-prefill kernel vs gather")
        # 4. the last requests: same shapes, new tokens — nothing compiles
        last = Meter()
        w2 = _wave(addr, traffic() + [(third, n_new, True)])
        check(
            w2[-1]["cached_prefix_tokens"] >= sz["prefix"], "no radix hit (last wave)"
        )
        check(
            last.compiles() == 0,
            f"{last.compiles()} compile(s) during the last requests",
        )
        n_req = 1 + len(w1) + 2 + len(w2)
        st = eng.stats
        emit(
            phase="serve",
            depth=mcfg.num_layers,
            slots=sz["slots"],
            max_seq_len=sz["max_seq_len"],
            attention=impl,
            requests=n_req,
            generated_tokens=st["generated_tokens"],
            prefix_hit_tokens=st["prefix_hit_tokens"],
            kernel_vs_gather=agree,
            logprob_tol=LOGPROB_TOL,
            precompile=warm,
            run={**run.report(), "last_wave_secs": last.report()["secs"]},
            **hbm(jax.devices()[0]),
        )
    finally:
        server.stop()
    del eng, server
    gc.collect()


def _grpo_batch(mcfg, sz: dict, seed: int):
    """Seeded packed GRPO batch."""
    import numpy as np

    from areal_tpu.utils.data import pad_sequences_to_tensors

    rng = np.random.default_rng(seed)
    lo, hi = sz["train_len"]
    trajs = []
    for _ in range(sz["train_rows"]):
        n = int(rng.integers(lo, hi))
        p = max(1, n // 12)  # prompt tokens: no loss
        old = rng.normal(-1.5, 0.1, n).astype(np.float32)
        trajs.append(
            {
                "input_ids": rng.integers(0, min(mcfg.vocab_size, 32000), n).astype(np.int32),
                "loss_mask": np.concatenate(
                    [np.zeros(p, np.float32), np.ones(n - p, np.float32)]
                ),
                "old_logprobs": old,
                "prox_logprobs": old + rng.normal(0, 0.02, n).astype(np.float32),
                "advantages": rng.normal(0, 1, n).astype(np.float32),
            }
        )
    return pad_sequences_to_tensors(trajs)


def _grpo_loss(outputs, b):
    import jax.numpy as jnp

    from areal_tpu.ops import functional as F

    lm = (b["label_valid"] & (b["loss_mask"] > 0)).astype(jnp.float32)
    loss, stats = F.ppo_actor_loss_fn(
        logprobs=outputs["logprobs"],
        proximal_logprobs=b["prox_logprobs"],
        old_logprobs=b["old_logprobs"],
        advantages=b["advantages"],
        loss_mask=lm,
        behave_imp_weight_cap=5.0,
    )
    return loss, {
        "clip_ratio": stats["clip_mask"].astype(jnp.float32).sum()
        / jnp.maximum(lm.sum(), 1.0)
    }


def _loss_weight(d) -> float:
    import numpy as np

    return float((np.asarray(d["loss_mask"]) > 0).sum())


def _train_engine(mcfg, sz: dict, seed: int, mesh_cfg=None, devices=None):
    """JaxTrainEngine with the train cell's settings: bf16 params
    and AdamW state, remat, one microbatch."""
    from areal_tpu.api.config import (
        MeshConfig,
        MicroBatchSpec,
        OptimizerConfig,
        TrainEngineConfig,
    )
    from areal_tpu.api.io_struct import FinetuneSpec
    from areal_tpu.engine.train_engine import JaxTrainEngine
    from areal_tpu.parallel import mesh as mesh_lib

    mesh_cfg = mesh_cfg or MeshConfig(data=-1, fsdp=1, seq=1, model=1)
    cfg = TrainEngineConfig(
        init_from_scratch=True,
        dtype=sz["dtype"],
        param_dtype=sz["dtype"],
        gradient_checkpointing=True,
        mesh=mesh_cfg,
        optimizer=OptimizerConfig(lr=1e-5, lr_scheduler_type="constant"),
        mb_spec=MicroBatchSpec(max_tokens_per_mb=100_000),
        bucket_step=512,
        logprob_chunk_size=256,
    )
    eng = JaxTrainEngine(cfg, model_config=mcfg)
    eng.initialize(
        FinetuneSpec(1, 1000, 8),
        seed=seed,
        mesh=mesh_lib.make_mesh(mesh_cfg, devices=devices),
    )
    return eng


def _leaf_sums(tree) -> list[float]:
    import jax
    import jax.numpy as jnp

    return [
        float(x)
        for x in jax.device_get(
            jax.jit(
                lambda t: [jnp.abs(v.astype(jnp.float32)).sum() for v in jax.tree.leaves(t)]
            )(tree)
        )
    ]


def phase_train(args, on_tpu: bool) -> None:
    import jax
    import numpy as np

    sz = sizes(args.size)
    mcfg = _load_model_cfg(sz["model_dir"], sz["dtype"])
    m = Meter()
    eng = _train_engine(mcfg, sz, args.seed, devices=jax.devices()[:1])
    batch = _grpo_batch(mcfg, sz, args.seed)
    n_tokens = int(np.asarray(batch["attention_mask"]).sum())
    before = _leaf_sums(eng.params)
    steps = []
    for i in range(3):
        s = Meter()
        out = eng.train_batch(batch, _grpo_loss, _loss_weight)
        check(
            math.isfinite(out["loss"]) and math.isfinite(out["grad_norm"]),
            f"step {i}: loss {out['loss']} grad_norm {out['grad_norm']}",
        )
        check(out["grad_norm"] > 0, f"step {i}: zero gradient")
        steps.append(
            {
                "loss": float(f"{out['loss']:.5g}"),
                "grad_norm": float(f"{out['grad_norm']:.5g}"),
                **s.report(),
            }
        )
        if i > 0:
            check(steps[-1]["compiles"] == 0, f"step {i} compiled {steps[-1]['compiles']} program(s)")
    after = _leaf_sums(eng.params)
    changed = sum(a != b for a, b in zip(before, after))
    check(changed > 0, "no parameter leaf changed after three steps")
    emit(
        phase="train",
        depth=mcfg.num_layers,
        tokens=n_tokens,
        rows=sz["train_rows"],
        steps=steps,
        leaves_changed=f"{changed}/{len(after)}",
        **m.report(),
        **hbm(jax.devices()[0]),
    )
    eng.destroy()
    del eng
    gc.collect()


def phase_rl_loop(args, on_tpu: bool) -> None:
    import jax

    from areal_tpu.api.config import GRPOConfig, load_expr_config
    from areal_tpu.models import qwen
    from areal_tpu.observability import catalog, lineage, timeline

    sz = sizes(args.size)
    argv = [
        "--config",
        os.path.join(ROOT, sz["rl_config"]),
        "total_train_steps=3",
        *sz["rl_overrides"],
    ]
    cfg, _ = load_expr_config(list(argv), GRPOConfig)
    rl_model = qwen.ModelConfig.from_hf_path(os.path.join(ROOT, cfg.actor.path))
    if args.size == "full":
        # the committed depth-cut config differs from the published one in
        # depth alone
        pub = _load_model_cfg(FULL_MODEL_DIR, "bfloat16", rl_model.num_layers)
        check(
            qwen.ModelConfig(**{**rl_model.__dict__, "dtype": "bfloat16"}) == pub,
            f"{cfg.actor.path} departs from the published widths",
        )
    sys.path.insert(0, os.path.join(ROOT, "examples", "math"))
    import gsm8k_rl

    stale, eng_m = catalog.staleness_metrics(), catalog.engine_metrics()

    def counts() -> dict:  # process-wide counters: the phase reads deltas
        return {
            "submitted": stale.submitted.get(),
            "accepted": stale.accepted.get(),
            "rejected": stale.rejected.get(),
            "aborted": eng_m.aborted.get(),
        }

    base = counts()
    flight = timeline.get_flight_recorder()
    seq0 = max((e["seq"] for e in flight.snapshot()["events"]), default=0)
    n_lineage0 = len(lineage.get_lineage().recent())
    m = Meter()
    gsm8k_rl.main(argv)  # the RL entry itself
    # -- the server's policy version rises with every update ---------------
    commits = [
        e["data"]["version"]
        for e in flight.snapshot()["events"]
        if e["kind"] == "weight_commit" and e["seq"] > seq0
    ]
    check(commits == [1, 2, 3], f"weight commits {commits}, wanted [1, 2, 3]")
    # -- a consumed batch carries tokens of more than one version ----------
    by_batch: dict[int, set[int]] = {}
    for r in lineage.get_lineage().recent()[n_lineage0:]:
        if r.consumed_version is not None and r.head_version >= 0:
            by_batch.setdefault(r.consumed_version, set()).update(
                range(r.head_version, r.tail_version + 1)
            )
    check(len(by_batch) == 3, f"trainer consumed {len(by_batch)} batches, wanted 3")
    mixed = {v: sorted(s) for v, s in by_batch.items() if len(s) > 1}
    check(
        bool(mixed),
        f"every consumed batch is single-version: { {v: sorted(s) for v, s in by_batch.items()} }",
    )
    # -- no request is lost across an update --------------------------------
    d = {k: int(v - base[k]) for k, v in counts().items()}
    want = 3 * cfg.train_dataset.batch_size
    check(d["rejected"] == 0, f"{d['rejected']} rollout(s) rejected")
    check(d["accepted"] >= want, f"{d['accepted']} rollouts accepted, wanted >= {want}")
    emit(
        phase="rl_loop",
        config=sz["rl_config"],
        depth=rl_model.num_layers,
        published_depth=_load_model_cfg(sz["model_dir"], sz["dtype"]).num_layers,
        trainer_steps=3,
        weight_commits=commits,
        versions_in_consumed_batches={str(v): sorted(s) for v, s in sorted(by_batch.items())},
        rollouts=d,
        **m.report(),
        **hbm(jax.devices()[0]),
    )
    gc.collect()


# ---------------------------------------------------------------------------
# --chips 4: what exists only across chips, and what it is compared with
# ---------------------------------------------------------------------------


def _per_device_gb(tree) -> dict[str, float]:
    import jax

    out: dict[str, float] = {}
    for leaf in jax.tree.leaves(tree):
        for sh in leaf.addressable_shards:
            out[str(sh.device.id)] = out.get(str(sh.device.id), 0.0) + sh.data.nbytes
    return {k: round(v / 1e9, 3) for k, v in sorted(out.items())}


def phase_sharded_train(args, on_tpu: bool) -> None:
    import jax

    from areal_tpu.api.config import MeshConfig
    from areal_tpu.observability import hw_accounting as hw

    sz = sizes(args.size)
    mcfg = _load_model_cfg(sz["model_dir"], sz["dtype"])
    devs = jax.devices()[:4]
    batch = _grpo_batch(mcfg, sz, args.seed)
    runs = {}
    for name, mesh_cfg, devices in (
        ("one_chip", MeshConfig(data=-1, fsdp=1, seq=1, model=1), devs[:1]),
        ("fsdp4", MeshConfig(data=1, fsdp=4, seq=1, model=1), devs),
        ("fsdp2_model2", MeshConfig(data=1, fsdp=2, seq=1, model=2), devs),
    ):
        m = Meter()
        eng = _train_engine(mcfg, sz, args.seed, mesh_cfg, devices)
        state = {"params": eng.params, "opt_state": eng.opt_state}
        total = hw.tree_bytes(state)
        per_dev = _per_device_gb(state)
        del state
        out = eng.train_batch(batch, _grpo_loss, _loss_weight)
        runs[name] = {
            "loss": out["loss"],
            "grad_norm": out["grad_norm"],
            "state_gb": round(total / 1e9, 3),
            "state_gb_per_device": per_dev,
            **m.report(),
            **hbm(devices[-1]),
        }
        eng.destroy()
        del eng
        gc.collect()
        if name != "one_chip":
            shares = [v * 1e9 / total for v in per_dev.values()]
            check(len(per_dev) == 4, f"{name}: state lives on {len(per_dev)} devices")
            check(
                max(shares) <= 0.30,
                f"{name}: a device holds {max(shares):.0%} of the parameter and "
                "optimizer bytes (about a quarter expected)",
            )
            ref = runs["one_chip"]
            for k in ("loss", "grad_norm"):
                rel = abs(runs[name][k] - ref[k]) / max(abs(ref[k]), 1e-9)
                check(
                    rel <= LOSS_RTOL,
                    f"{name}: {k} {runs[name][k]:.6g} vs one chip {ref[k]:.6g}",
                )
    emit(phase="sharded_train", depth=mcfg.num_layers, rtol=LOSS_RTOL, **runs)


def phase_tp_serve(args, on_tpu: bool) -> None:
    import jax
    import numpy as np

    from areal_tpu.api.config import MeshConfig

    sz = sizes(args.size)
    mcfg = _load_model_cfg(sz["model_dir"], sz["dtype"])
    devs = jax.devices()[:4]
    rng = np.random.default_rng(args.seed)
    n_new = 32 if args.size == "full" else 8
    prompts = [
        [int(t) for t in rng.integers(0, mcfg.vocab_size, p)] for p, _ in sz["lens"][:4]
    ]
    answers, info = {}, {}
    for name, mesh_cfg, devices in (
        ("one_chip", MeshConfig(data=-1, fsdp=1, seq=1, model=1), devs[:1]),
        ("model2", MeshConfig(data=-1, fsdp=1, seq=1, model=2), devs),
    ):
        m = Meter()
        eng, server = _start_server(
            _server_config(sz, args.seed, mesh_cfg), mcfg, args.seed, devices
        )
        impl = eng.attention_impl()
        if name == "model2":
            # kernels are single-device: tensor-parallel serving gathers
            check(impl["decode"] == "xla", f"TP server reports {impl}")
        server.start()
        try:
            answers[name] = _wave(server.address, [(p, n_new, True) for p in prompts])
        finally:
            server.stop()
        info[name] = {
            "mesh": {k: v for k, v in eng.mesh.shape.items() if v > 1},
            "attention": impl,
            "params_gb_per_device": _per_device_gb(eng.params),
            **m.report(),
        }
        del eng, server
        gc.collect()
    agree = [
        _agree(a, b, f"model=2 vs one chip, request {i}")
        for i, (a, b) in enumerate(zip(answers["model2"], answers["one_chip"]))
    ]
    emit(
        phase="tp_serve",
        depth=mcfg.num_layers,
        requests=len(prompts),
        logprob_tol=LOGPROB_TOL,
        agreement=agree,
        **info,
    )


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--size", choices=("full", "tiny"), default="full")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--phases",
        default="",
        help="comma-separated subset (builder's iteration aid; a subset "
        "never prints \"ok\": true)",
    )
    args = ap.parse_args()
    os.chdir(ROOT)
    sys.path.insert(0, ROOT)

    import jax

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and args.size != "tiny":
        log(
            f"jax found no accelerator (backend {jax.default_backend()!r}): "
            "nothing to prove. --size tiny rehearses the phases on this backend."
        )
        return 2

    table = {
        "kernels": phase_kernels,
        "serve": phase_serve,
        "train": phase_train,
        "rl_loop": phase_rl_loop,
        "sharded_train": phase_sharded_train,
        "tp_serve": phase_tp_serve,
    }
    default = ["sharded_train", "tp_serve"] if args.chips == 4 else list(PHASES[1:])
    wanted = [p for p in args.phases.split(",") if p] or default
    unknown = [p for p in wanted if p != "device" and p not in table]
    if unknown:
        log(f"unknown phase(s) {unknown}; known: device,{','.join(table)}")
        return 2

    device = phase_device(args, on_tpu)
    for name in wanted:
        if name == "device":
            continue
        log(f"phase {name}")
        table[name](args, on_tpu)  # a failed phase raises: non-zero exit

    complete = [p for p in wanted if p != "device"] == default
    if not on_tpu:
        emit(ok=False, rehearsal=True, device=device, phases=wanted)
        return 3
    if not complete:
        emit(ok=False, partial=wanted, device=device)
        return 0
    emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
