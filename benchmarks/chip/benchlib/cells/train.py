"""Cell kind ``train``: JaxTrainEngine GRPO steps on one packed batch.

The trainer of ``chip_smoke._train_engine`` (bf16 parameters and AdamW
state, remat, one microbatch, the program's own PPO/GRPO loss), on weights
the benchmark makes from the seed and a batch from the cell's traffic mix.
A step ends in the engine's blocking pull of its loss, so the host clock
around ``train_batch`` spans the device work.

``correct`` looks at the step the cell times: the loss of the first call of
``train_batch``, the gradient it left in AdamW's first moment, and what the
first step with a learning rate did to the parameters, against a float32
reference of the same step (``reference.grpo_grads``, ``adamw_delta``).
"""

from __future__ import annotations

import gc
import math
import time

import numpy as np

from benchlib import harness, reference, traffic, weights


def _loss_fns(lp: dict):
    import jax.numpy as jnp

    from areal_tpu.ops import functional as F

    def grpo_loss(outputs, b):
        lm = (b["label_valid"] & (b["loss_mask"] > 0)).astype(jnp.float32)
        loss, stats = F.ppo_actor_loss_fn(
            logprobs=outputs["logprobs"],
            proximal_logprobs=b["prox_logprobs"],
            old_logprobs=b["old_logprobs"],
            advantages=b["advantages"],
            loss_mask=lm,
            eps_clip=float(lp["eps_clip"]),
            behave_imp_weight_cap=float(lp["behave_imp_weight_cap"]),
        )
        return loss, {"clip_ratio": stats["clip_mask"].astype(jnp.float32).sum() / jnp.maximum(lm.sum(), 1.0)}

    def loss_weight(d) -> float:
        return float((np.asarray(d["loss_mask"]) > 0).sum())

    return grpo_loss, loss_weight


def build_engine(cfg: dict, ep: dict, dtype: str, seed: int, devs):
    from areal_tpu.api.config import MeshConfig, MicroBatchSpec, OptimizerConfig, TrainEngineConfig
    from areal_tpu.api.io_struct import FinetuneSpec
    from areal_tpu.engine.train_engine import JaxTrainEngine
    from areal_tpu.parallel import mesh as mesh_lib

    mcfg = harness.model_config(cfg, dtype)
    mesh_cfg = MeshConfig(data=-1, fsdp=1, seq=1, model=1)
    tcfg = TrainEngineConfig(
        init_from_scratch=True,
        dtype=dtype,
        param_dtype=dtype,
        gradient_checkpointing=True,
        mesh=mesh_cfg,
        optimizer=OptimizerConfig(
            lr=float(ep["lr"]),
            lr_scheduler_type="constant",
            weight_decay=float(ep["weight_decay"]),
            beta1=float(ep["beta1"]),
            beta2=float(ep["beta2"]),
            eps=float(ep["eps"]),
            gradient_clipping=float(ep["gradient_clipping"]),
        ),
        mb_spec=MicroBatchSpec(max_tokens_per_mb=int(ep["max_tokens_per_mb"])),
        bucket_step=int(ep["bucket_step"]),
        logprob_chunk_size=int(ep["logprob_chunk_size"]),
    )
    eng = JaxTrainEngine(tcfg, model_config=mcfg)
    # 125 steps in all: the engine's warm-up is then max(1, int(0.001 * 125)) = 1 step
    eng.initialize(FinetuneSpec(1, 1000, 8), seed=seed & 0x7FFFFFFF, mesh=mesh_lib.make_mesh(mesh_cfg, devices=devs))
    return eng, mcfg, tcfg


def seeded_params(eng, cfg, mcfg, seed):
    """The benchmark's weights in the engine's own placement."""
    import jax

    sh = {k: v for k, v in eng.param_shardings.items() if k != "value_head"}
    with jax.set_mesh(eng.mesh):
        return weights.make_params(cfg, seed, mcfg.jax_dtype, sh)


def first_moment(opt_state):
    """AdamW's first moment, a tree shaped like the parameters, from the
    trainer's optimizer state (optax ``ScaleByAdamState.mu``)."""
    import jax

    has = lambda x: hasattr(x, "mu") and hasattr(x, "nu")  # noqa: E731
    for node in jax.tree.leaves(opt_state, is_leaf=has):
        if has(node):
            return node.mu
    raise RuntimeError("no AdamW moments in the trainer's optimizer state")


def snapshot(tree, keep: dict) -> dict:
    """Device copies of the compared leaves (the next step donates the originals)."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(jnp.copy, weights.kept_leaves(tree, keep))


def _rel(got: dict, ref: dict, names) -> float | None:
    num = sum(float(np.sum(np.square(got[k].astype(np.float64) - ref[k]))) for k in names)
    den = sum(float(np.sum(np.square(ref[k].astype(np.float64)))) for k in names)
    return math.sqrt(num / den) if den > 0 else None


def check_step(got: dict, params, cfg, seqs, p: dict, dtype) -> dict:
    """The trainer's first steps against the reference's, three numbers:

    ``loss_abs``    |first step's loss - reference loss|
    ``grad_rel``    the largest, over groups of leaves, of
                    |gradient - reference gradient| / |reference gradient|;
                    the trainer's gradient is its first step's AdamW first
                    moment over (1 - beta1)
    ``update_rel``  |parameter change - reference change| / |reference change|
                    over all compared leaves, after the first step whose
                    learning rate is above 0

    ``got`` = {"loss", "grads", "delta"} of the program, or of the control
    in its place."""
    ep, chk = p["engine"], p["check"]
    keep = chk["keep"]
    ref = reference.grpo_grads(params, cfg, seqs, p["loss"], keep, int(chk["pad_multiple"]))
    clip = float(ep["gradient_clipping"])
    if ref["norm_bound"] >= clip:
        raise RuntimeError(
            f"the reference bounds the gradient norm by {ref['norm_bound']:.4g}, not under the clip of {clip}: "
            "it cannot tell whether the trainer clips"
        )
    if got is None:  # the control: the reference in int8 in the program's place
        ctl = reference.grpo_grads(params, cfg, seqs, p["loss"], keep, int(chk["pad_multiple"]), int8=True)
        got = {"loss": ctl["loss"], "grads": ctl["grads"], "delta": None}
    p0 = {k: np.asarray(v) for k, v in weights.kept_leaves(params, keep).items()}
    steps = int(ep["lr_warmup_steps"]) + 1
    ref_delta = {k: reference.adamw_delta(p0[k], ref["grads"][k], ep, steps, dtype) for k in p0}
    if got["delta"] is None:
        got["delta"] = {k: reference.adamw_delta(p0[k], got["grads"][k], ep, steps, dtype) for k in p0}
    groups = {"embedding rows": [k for k in ("embed", "lm_head") if k in p0], "final norm": ["final_norm"]}
    groups["layer vectors"] = list(keep["vectors"])
    for i in keep["layers"]:
        groups[f"matrices of layer {i}"] = [f"{k}.{i}" for k in keep["matrices"]]
    per_group = {g: _rel(got["grads"], ref["grads"], names) for g, names in groups.items()}
    moved = sum(int(np.count_nonzero(v)) for v in ref_delta.values())
    return {
        "loss_abs": abs(got["loss"] - ref["loss"]),
        "grad_rel": max(v for v in per_group.values() if v is not None),
        "update_rel": _rel(got["delta"], ref_delta, list(p0)),
        "per_group": per_group,
        "ref_loss": ref["loss"],
        "norm_bound": ref["norm_bound"],
        "moved": moved,
        "compared": sum(v.size for v in p0.values()),
    }


def run(ctx: dict) -> dict:
    import jax.numpy as jnp

    from areal_tpu.utils.data import pad_sequences_to_tensors

    cell, seed, seconds = ctx["cell"], ctx["seed"], ctx["seconds"]
    p = dict(cell["params"])
    cfg, mix = dict(cell["model"]), dict(cell["traffic"])
    reh = ctx["rehearsal"]
    if reh:
        cfg = reh["model"]
        mix = {**mix, **reh.get("traffic", {})}
        p = {**p, **reh["params"]}
    ep, chk = p["engine"], p["check"]
    keep = chk["keep"]
    devs = harness.devices_for(cell["chips"], bool(reh))
    cache = harness.setup_compile_cache(ctx["bench"].root)
    harness.log(f"cell {cell['name']} seed {seed} device {devs[0].device_kind} compile cache {cache}")
    c_start = harness.compile_counts()
    lengths_mix = ctx["bench"].traffic(mix["lengths_from"])
    if reh and "lengths_mix" in reh:
        lengths_mix = reh["lengths_mix"]
    seqs = traffic.packed_batch(mix, lengths_mix, seed, int(cfg["vocab_size"]))
    seq_lens = [len(s["input_ids"]) for s in seqs]
    n_tokens = int(sum(seq_lens))
    values, attempted, failed, got = {}, 0, 0, None
    trace, traced_steps = None, 0
    device = harness.device_report(devs)
    if not ctx["control"]:
        batch = pad_sequences_to_tensors(seqs)
        eng, mcfg, tcfg = build_engine(cfg, ep, p["dtype"], seed, devs)
        from areal_tpu.ops.attention import resolve_impl

        harness.log(
            f"batch: {len(seqs)} sequences, {n_tokens} real tokens, rows of {mix['row_len']}; "
            f"attention asked {tcfg.attn_impl!r}, resolved {resolve_impl(tcfg.attn_impl, int(mix['row_len']), mcfg.head_dim_)!r}"
        )
        eng.params = None
        gc.collect()
        eng.params = seeded_params(eng, cfg, mcfg, seed)
        loss_fn, weight_fn = _loss_fns(p["loss"])

        def step():
            nonlocal attempted, failed
            t = time.monotonic()
            out = eng.train_batch(batch, loss_fn, weight_fn)
            dt = time.monotonic() - t
            attempted += 1
            if not (math.isfinite(out["loss"]) and math.isfinite(out["grad_norm"])):
                failed += 1
            return dt, out

        # ---- warm-up; the first steps are the ones the output check reads ----
        warmup = int(ep["lr_warmup_steps"])
        if int(p["warm_steps"]) < warmup + 1:
            raise ValueError("warm_steps must reach the first step with a learning rate above 0")
        for i in range(int(p["warm_steps"])):
            dt, out = step()
            harness.log(f"warm step {i}: {dt:.3f}s loss {out['loss']:.6g} grad_norm {out['grad_norm']:.4g} lr {out['lr']:.3g}")
            lr_i = float(ep["lr"]) * min(1.0, i / warmup)
            if not math.isclose(out["lr"], lr_i, rel_tol=1e-6, abs_tol=1e-12):
                raise RuntimeError(f"step {i} ran at lr {out['lr']}, the cell states {lr_i}")
            if i == 0:
                got = {"loss": out["loss"], "grad_norm": out["grad_norm"], "mu": snapshot(first_moment(eng.opt_state), keep)}
            if i == warmup:
                got["params"] = snapshot(eng.params, keep)
        attempted = failed = 0
        c_warm = harness.compile_counts()
        tracer = None
        if ctx["trace"]:
            tracer = harness.Tracer(ctx["tmp"])
            tracer.start()
        t0 = time.monotonic()
        values["setup_s"] = t0 - ctx["t0"]
        durs = []
        while time.monotonic() - t0 < seconds:
            dt, out = step()
            durs.append(dt)
            if tracer is not None and trace is None:
                traced_steps += 1
                if time.monotonic() - t0 >= min(seconds, float(p["trace_seconds"])):
                    trace = tracer.stop()
        if tracer is not None and trace is None:
            trace = tracer.stop()
        c_end = harness.compile_counts()
        device = harness.device_report(devs)
        harness.log(
            f"set-up compiled {c_warm['compiles'] - c_start['compiles']} programs "
            f"({c_warm['cache_hits'] - c_start['cache_hits']} from the persistent cache); "
            f"inside the window: {c_end['compiles'] - c_warm['compiles']} compilations"
        )
        values["train_tok_s"] = n_tokens * len(durs) / sum(durs)
        harness.log(f"{len(durs)} steps, median {sorted(durs)[len(durs) // 2]:.4f}s, first loss {got['loss']:.6g}, last {out['loss']:.6g}")
        eng.destroy()
        del eng, batch
        gc.collect()
    # ---- the first steps against the reference, outside the window --------
    dtype = jnp.dtype(p["dtype"])
    params = weights.make_params(cfg, seed, dtype)
    if got is not None:
        p0 = weights.kept_leaves(params, keep)
        f32 = lambda t: {k: np.asarray(v, np.float32) for k, v in t.items()}  # noqa: E731
        mu, after = f32(got.pop("mu")), f32(got.pop("params"))
        got["grads"] = {k: v / (1.0 - float(ep["beta1"])) for k, v in mu.items()}
        got["delta"] = {k: after[k] - np.asarray(p0[k], np.float32) for k in after}
    t_chk = time.monotonic()
    res = check_step(got, params, cfg, seqs, p, dtype)
    limits = chk["limits"]
    correct = all(res[k] <= float(limits[k]) for k in limits)
    harness.log(
        "output check" + (" [CONTROL: the reference with int8 matmuls, forward and backward, in the trainer's place]" if ctx["control"] else "")
        + ": " + "; ".join(f"{k} = {res[k]} (limit {limits[k]})" for k in limits)
    )
    harness.log(
        f"output check: loss against the reference's {res['ref_loss']:.6g}: |difference| = {res['loss_abs']:.3g} (compared only where "
        f"the cell sets a limit: on this batch the PPO ratio is near 0 and the loss does not follow the model); "
        f"gradient norm bounded by {res['norm_bound']:.4g} "
        + (f"(trainer says {got['grad_norm']:.4g}); " if got else "; ")
        + "gradient by group " + ", ".join(f"{g} {v:.3g}" for g, v in res["per_group"].items() if v is not None) + "; "
        f"{res['moved']} of {res['compared']} compared parameters move in the reference; {time.monotonic() - t_chk:.1f}s"
    )
    facts = {
        "kind": "train",
        "cfg": cfg,
        "seq_lens": seq_lens,
        "tokens_per_step": n_tokens,
        "traced_steps": traced_steps,
        "trace": trace,
        "device_kind": devs[0].device_kind,
        "chips": len(devs),
        "check": res,
    }
    out = {
        "correct": bool(correct),
        "attempted": attempted,
        "failed": failed,
        "values": values,
        "facts": facts,
        "device": device,
    }
    return harness.attach_trace(out, trace)
