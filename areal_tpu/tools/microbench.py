"""Standing microbench registry + noise-aware regression gate.

The kernel observatory's second half (docs/perf.md "Kernel observatory"):
where observability/kernel_probe.py attributes *production* decode steps,
this module pins each hot-path kernel in isolation so a regression shows
up as one number moving, not as a 3%% end-to-end drift nobody can bisect.

Registered benches (fast set — the committed CPU baseline under
benchmarks/):

    paged_decode_step    one forward_decode_paged step over all slots
    paged_attention_interpret  interpret-mode stacked paged kernel alone
    suffix_prefill       radix-suffix prefill over a cached prefix
    int8_kv_dequant      KV quantize->dequantize round trip
    tree_verify_forward  ancestor-masked forest forward (no_grad)
    spec_decode_step     oracle-draft speculative verify + accept walk
    radix_match          host-side radix prefix walk (no device work)
    weight_stage_encode  weight-bucket wire encoding (server push path)

Heavy benches (``--heavy`` / named via ``--benches``; engine- or
trainer-level, minutes not seconds — these subsume the retired root
prof_* scripts, see docs/perf.md "Reproduction"):

    decode_engine_steady  live DecodeEngine steady-state tok/s + the
                          probe's achieved roofline   (was prof_decode /
                          prof_r3 phase_decode; BENCH_QUANT=int8 covers
                          prof_r4 phase_int8)
    train_step            fwd+bwd+CE optimizer-shaped step (prof_r3
                          phase_train)
    tree_train            grad through the ancestor-mask forward
                          (prof_r5 phase_tree)
    weight_update         paused LoRA-delta fold + one full mem-path
                          push on a live engine (prof_r4 phase_wu)

Every bench emits ``{wall_s, tok_s, flops, bytes, roofline_frac,
noise_frac}`` measured with warm-up + median-of-N (the PR 12 lesson:
first-call compile and cache replay must never land in the measured
window); every timed call ends in ``block_until_ready``.

``--compare BASELINE.json`` applies a noise-aware relative threshold per
bench — regression iff ``cur > base * (1 + max(threshold, 2*noise)) +
floor`` — and exits nonzero iff any bench regresses; new/missing entries
are warnings, not failures, so adding a bench never breaks CI.

Modes:

    --learn-gate  on-chip RL learning gate through the full stack
                  (was prof_learn.py); excluded from --compare

Dims default tiny (CPU-runnable, the committed baseline);
``MICROBENCH_FULL=1`` switches to bench.py's MODEL_KW (Qwen2.5-1.5B).
One process holds the chip: run one invocation at a time.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from typing import Any, Callable

import numpy as np

DEFAULT_ITERS = 7
DEFAULT_WARMUP = 2
# relative slack below which a move is never a regression. Measured on
# this image: identical back-to-back suites differ up to ~45% on ms-scale
# kernels — the variance is CROSS-PROCESS (container CPU contention slows
# a whole run), so neither median-of-N nor min-of-N inside one process can
# average it away. The gate therefore targets kernel-scale regressions
# (a 2x is always flagged: 2.0 > 1.6 + floor) and stays silent on drift
# smaller than the machine's own run-to-run wobble; the per-bench measured
# noise_frac widens the margin further for intrinsically jumpy benches.
DEFAULT_THRESHOLD = 0.6
# absolute floor: sub-millisecond medians can move tens of µs on one
# scheduler hiccup regardless of the kernel under test
NOISE_FLOOR_S = 5e-5

REGISTRY: dict[str, dict[str, Any]] = {}


def register(name: str, *, heavy: bool = False) -> Callable:
    """Class-of-one decorator: the registered fn is a SETUP fn returning
    ``{"run": closure, "tokens"?, "flops"?, "bytes"?}`` (the harness times
    ``run``), or ``{"entry": {...}}`` for benches that self-measure (the
    engine-level heavies, where one "iteration" is a multi-second run)."""

    def deco(fn: Callable) -> Callable:
        REGISTRY[name] = {"fn": fn, "heavy": heavy, "doc": (fn.__doc__ or "").strip().splitlines()[0] if fn.__doc__ else ""}
        return fn

    return deco


def _sync(x: Any) -> Any:
    """Wait for the device: a timing that does not end here measures the
    enqueue."""
    import jax

    return jax.block_until_ready(x)


def model_cfg():
    """Tiny CPU-runnable dims by default; MICROBENCH_FULL=1 uses bench.py's
    MODEL_KW (Qwen2.5-1.5B) so a chip run measures the real model."""
    from areal_tpu.models import qwen

    if os.environ.get("MICROBENCH_FULL"):
        from bench import MODEL_KW  # bench.py owns the 1.5B dims

        return qwen.ModelConfig(**MODEL_KW)
    return qwen.ModelConfig(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        dtype="float32",
        tie_word_embeddings=True,
        attention_bias=True,
        rope_theta=10000.0,
    )


_CTX: dict[str, Any] = {}


def _ctx() -> dict[str, Any]:
    """Shared per-process setup (params init + jit are the expensive part;
    every bench reuses one tree)."""
    if _CTX:
        return _CTX
    import jax

    from areal_tpu.models import qwen

    cfg = model_cfg()
    params = jax.jit(lambda k: qwen.init_params(k, cfg))(jax.random.PRNGKey(0))
    _sync(params)
    full = bool(os.environ.get("MICROBENCH_FULL"))
    _CTX.update(
        cfg=cfg,
        params=params,
        full=full,
        page_size=128 if full else 16,
        n_slots=32 if full else 8,
    )
    return _CTX


# ---------------------------------------------------------------------------
# fast benches (the committed CPU baseline)
# ---------------------------------------------------------------------------


@register("paged_decode_step")
def bench_paged_decode_step() -> dict:
    """One forward_decode_paged step for all slots over a warm paged KV."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.inference.paged_kv import init_paged_cache
    from areal_tpu.models import qwen
    from areal_tpu.observability import hw_accounting as hw

    c = _ctx()
    cfg, psz, S = c["cfg"], c["page_size"], 4 * c["n_slots"]
    ctx_len = 7 * psz  # seven warm pages per slot
    wp = ctx_len // psz + 1
    n_pages = S * wp + 1
    cache = init_paged_cache(cfg, n_pages, psz)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(1, cfg.vocab_size, S), jnp.int32)
    pos = jnp.full((S,), ctx_len, jnp.int32)
    table = jnp.asarray(
        1 + np.arange(S * wp, dtype=np.int32).reshape(S, wp)
    )
    use_kernel = jax.default_backend() == "tpu"
    step = jax.jit(
        lambda i, p, kv, t: qwen.forward_decode_paged(
            c["params"], cfg, i, p, kv, t, page_size=psz, use_kernel=use_kernel
        )[0]
    )
    costs = hw.decode_step_costs(cfg, 1, S, float(ctx_len))
    return {
        "run": lambda: _sync(step(ids, pos, cache, table)),
        "tokens": S,
        "flops": costs["flops"],
        "bytes": costs["bytes"],
    }


@register("paged_attention_interpret")
def bench_paged_attention_interpret() -> dict:
    """Revived interpret-mode stacked paged-attention kernel in isolation
    (ISSUE 17 burn-down): the same Pallas body the TPU runs, executed via
    the interpreter so the CPU baseline pins the kernel's own cost — a
    signature or index-map regression shows up here before any TPU job."""
    import jax
    import jax.numpy as jnp

    c = _ctx()
    from areal_tpu.ops.paged_attention_q8 import paged_attention_stacked

    S, KH, G, hd, psz, wp, L = 4, 2, 6, 128, c["page_size"], 4, 2
    H = KH * G
    N = S * wp + 1
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(0, 1, (S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (L, KH, N, psz, hd)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(0, 1, (L, KH, N, psz, hd)), jnp.bfloat16)
    pt = jnp.asarray(1 + np.arange(S * wp, dtype=np.int32).reshape(S, wp))
    ctx = wp * psz  # every slot fully warm
    lengths = jnp.full((S,), ctx, jnp.int32)
    fn = jax.jit(
        lambda q, k, v, le, t: paged_attention_stacked(
            q, k, v, jnp.int32(0), le, t,
            pages_per_compute_block=2,
            interpret=True,
        )
    )
    # QK^T + AV over the warm context, one query row per slot
    flops = 4.0 * S * H * hd * ctx
    bytes_ = 2.0 * KH * S * ctx * hd * k.dtype.itemsize + q.nbytes * 2
    return {
        "run": lambda: _sync(fn(q, k, v, lengths, pt)),
        "tokens": S,
        "flops": flops,
        "bytes": bytes_,
    }


def _suffix_prefill_bench(use_kernel: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from areal_tpu.inference.paged_kv import init_paged_cache
    from areal_tpu.models import qwen
    from areal_tpu.observability import hw_accounting as hw

    c = _ctx()
    cfg, psz = c["cfg"], c["page_size"]
    A, B = 4, 2 * psz  # suffix bucket: two pages of new tokens per row
    wp = 4
    cache = init_paged_cache(cfg, A * wp + 1, psz)
    rng = np.random.default_rng(1)
    ids = jnp.asarray(rng.integers(1, cfg.vocab_size, (A, B)), jnp.int32)
    offs = np.full((A,), psz, np.int32)  # one page already cached
    positions = jnp.asarray(offs[:, None] + np.arange(B, dtype=np.int32))
    seg = jnp.ones((A, B), jnp.int32)
    table = jnp.asarray(1 + np.arange(A * wp, dtype=np.int32).reshape(A, wp))
    fn = jax.jit(
        lambda i, p, s, kv, t, o: qwen.forward_prefill_paged(
            c["params"], cfg, i, p, s, kv, t, o, use_kernel=use_kernel
        )[1]
    )
    offs_d = jnp.asarray(offs)
    costs = hw.prefill_costs(cfg, float(A * B))
    return {
        "run": lambda: _sync(fn(ids, positions, seg, cache, table, offs_d)),
        "tokens": A * B,
        "flops": costs["flops"],
        "bytes": costs["bytes"],
    }


@register("suffix_prefill")
def bench_suffix_prefill() -> dict:
    """Radix-suffix prefill: queries attend over one cached prefix page."""
    return _suffix_prefill_bench(False)


@register("suffix_prefill_kernel")
def bench_suffix_prefill_kernel() -> dict:
    """Radix-suffix prefill through the Pallas suffix-prefill kernel
    (ops/paged_suffix_attention.py, chain-mask launch). On CPU this runs
    the kernel body in interpret mode — the honest bar is parity-not-perf;
    the HBM-bound win is measured on TPU (docs/perf.md)."""
    return _suffix_prefill_bench(True)


@register("int8_kv_dequant")
def bench_int8_kv_dequant() -> dict:
    """KV int8 quantize -> dequantize round trip (the serving KV-cache
    compression path; decode reads pay the dequant side every step)."""
    import jax

    from areal_tpu.inference.paged_kv import dequantize_kv, quantize_kv

    c = _ctx()
    cfg = c["cfg"]
    n_tok = 16384 if c["full"] else 8192
    x = jax.numpy.asarray(
        np.random.default_rng(2).normal(
            0, 1, (cfg.num_layers, cfg.num_kv_heads, n_tok, cfg.head_dim_)
        ).astype(np.float32)
    )
    rt = jax.jit(lambda t: dequantize_kv(*quantize_kv(t), t.dtype))
    nelem = float(x.size)
    return {
        "run": lambda: _sync(rt(x)),
        "tokens": None,
        # abs/max/scale/rint/clip on the way down, one fma on the way up
        "flops": 8.0 * nelem,
        # f32 read + int8 write + int8 read + f32 write (+ scales, small)
        "bytes": 10.0 * nelem,
    }


@register("tree_verify_forward")
def bench_tree_verify_forward() -> dict:
    """Ancestor-masked forest forward (no_grad): the tree-verify step of
    speculative/tree decoding — shared prefixes scored once."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import qwen
    from areal_tpu.models.tree import build_tree
    from areal_tpu.observability import hw_accounting as hw

    c = _ctx()
    cfg = c["cfg"]
    rng = np.random.default_rng(3)
    base = 48 if c["full"] else 16
    seqs = [list(rng.integers(1, cfg.vocab_size, base + int(rng.integers(0, 8)))) for _ in range(8)]
    for i in range(4, 8):  # force shared prefixes: real GRPO-group shape
        seqs[i] = seqs[i - 4][: base // 2] + seqs[i]
    pack = build_tree(seqs)
    N = pack.n_nodes
    ids = jnp.asarray(pack.tokens, jnp.int32)[None]
    pos = jnp.asarray(pack.depth, jnp.int32)[None]
    seg = jnp.ones((1, N), jnp.int32)
    mask = jnp.asarray(pack.ancestor_mask())[None, None]
    fn = jax.jit(
        lambda i, s, p, m: qwen.forward(
            c["params"], cfg, i, s, p, attn_mask=m, no_grad=True
        )
    )
    costs = hw.prefill_costs(cfg, float(N))
    return {
        "run": lambda: _sync(fn(ids, seg, pos, mask)),
        "tokens": N,
        "flops": costs["flops"],
        "bytes": costs["bytes"],
    }


def _spec_decode_step_bench(use_kernel: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from areal_tpu.inference.paged_kv import init_paged_cache
    from areal_tpu.models import qwen
    from areal_tpu.observability import hw_accounting as hw

    c = _ctx()
    cfg, psz, S = c["cfg"], c["page_size"], 4 * c["n_slots"]
    K = 4  # SpeculativeConfig.spec_depth default
    B = K + 1
    ctx_len = 7 * psz  # seven warm pages per slot (= paged_decode_step)
    wp = (ctx_len + B) // psz + 1
    n_pages = S * wp + 1
    cache = init_paged_cache(cfg, n_pages, psz)
    rng = np.random.default_rng(0)
    pending = jnp.asarray(rng.integers(1, cfg.vocab_size, S), jnp.int32)
    table = jnp.asarray(1 + np.arange(S * wp, dtype=np.int32).reshape(S, wp))
    prefix_lens = jnp.full((S,), ctx_len, jnp.int32)
    positions = jnp.broadcast_to(
        ctx_len + jnp.arange(B, dtype=jnp.int32)[None], (S, B)
    )
    # chain tree: row j attends rows 0..j (lower-triangular ancestor mask)
    mask = jnp.broadcast_to(
        jnp.asarray(np.tril(np.ones((B, B), bool)))[None], (S, B, B)
    )

    def verify(drafts):
        ids_nodes = jnp.concatenate([pending[:, None], drafts], 1)
        hidden, _ks, _vs = qwen.forward_verify_paged(
            c["params"], cfg, ids_nodes, positions, mask, cache, table,
            prefix_lens, use_kernel=use_kernel,
        )
        logits = qwen.compute_logits(c["params"], cfg, hidden)
        targets = jnp.argmax(logits, -1).astype(jnp.int32)  # [S, B]
        hit = (targets[:, :-1] == drafts).astype(jnp.int32)
        accepted = jnp.cumprod(
            jnp.concatenate([jnp.ones((S, 1), jnp.int32), hit], 1), axis=1
        )
        return targets, accepted.sum(1)  # emitted tokens per slot

    fn = jax.jit(verify)
    # oracle: each pass fixes one more chain position (target at depth d
    # depends only on draft rows < d), so K+1 passes reach a fixed point
    drafts = jnp.asarray(rng.integers(1, cfg.vocab_size, (S, K)), jnp.int32)
    for _ in range(K + 1):
        targets, _em = fn(drafts)
        drafts = targets[:, :K]
    _targets, emitted = fn(drafts)
    assert int(np.asarray(emitted).min()) == B, "oracle draft did not converge"
    costs = hw.decode_step_costs(cfg, 1, S * B, float(ctx_len))
    return {
        "run": lambda: _sync(fn(drafts)),
        "tokens": S * B,
        "flops": costs["flops"],
        "bytes": costs["bytes"],
    }


@register("spec_decode_step")
def bench_spec_decode_step() -> dict:
    """Speculative verify step at full acceptance: forward_verify_paged
    over K+1 chain rows per slot plus the greedy accept walk. Setup
    iterates the verify fn to the model's own self-consistent greedy
    chain (an oracle draft), so every row lands and one timed call emits
    (K+1) x slots tokens — divide this bench's tok/s by
    paged_decode_step's for the raw speculation multiplier."""
    return _spec_decode_step_bench(False)


@register("spec_decode_step_kernel")
def bench_spec_decode_step_kernel() -> dict:
    """Oracle-draft speculative verify through the Pallas tree-verify
    launch (ops/paged_suffix_attention.py, ancestor-mask operand). On CPU
    the kernel runs in interpret mode — parity is the bar here, the
    HBM-bound win is a TPU measurement (docs/perf.md)."""
    return _spec_decode_step_bench(True)


@register("radix_match")
def bench_radix_match() -> dict:
    """Host-side radix prefix walk: the admission-time lookup kernel_probe
    times as the radix_match phase. Pure host — no device work."""
    from areal_tpu.inference.paged_kv import PagePool, RadixPrefixCache

    c = _ctx()
    psz = c["page_size"]
    depth = 8  # pages per published prompt
    n_pub, n_probe = 64, 32
    pool = PagePool(n_pub * depth + 64)
    cache = RadixPrefixCache(pool, psz, max_pages=n_pub * depth)
    rng = np.random.default_rng(4)
    shared = rng.integers(1, 200, 4 * psz)
    pubs = []
    for _ in range(n_pub):
        tail = rng.integers(1, 200, (depth - 4) * psz)
        pubs.append(np.concatenate([shared, tail]))
    for ids in pubs:
        pages = pool.alloc(depth)
        assert pages is not None
        cache.insert(ids, pages, [0] * depth)
    probes = [pubs[i % n_pub][: (depth - 1) * psz] for i in range(n_probe)]

    def run() -> int:
        hits = 0
        for p in probes:
            pages, _v = cache.match(p)
            hits += len(pages)
        return hits

    return {
        "run": run,
        "tokens": sum(len(p) for p in probes),
        "flops": None,
        "bytes": None,
    }


@register("weight_stage_encode")
def bench_weight_stage_encode() -> dict:
    """Weight-bucket wire encoding: the per-bucket host cost of a staged
    mem-mode weight push (server.encode_weight_bucket)."""
    from areal_tpu.inference.server import encode_weight_bucket

    c = _ctx()
    mb = 64 if c["full"] else 4
    arr = np.random.default_rng(5).normal(0, 1, (mb * 256 * 1024,)).astype(np.float32)
    entries = [("layers/wq", arr), ("layers/wo", arr[: arr.size // 2])]
    nbytes = float(sum(a.nbytes for _n, a in entries))
    return {
        "run": lambda: len(encode_weight_bucket(entries)),
        "tokens": None,
        "flops": None,
        "bytes": 2.0 * nbytes,  # one read + one write of the payload
    }


# ---------------------------------------------------------------------------
# heavy benches (engine/trainer level; subsume the retired prof_* scripts)
# ---------------------------------------------------------------------------


def _make_engine():
    import jax

    from areal_tpu.api.config import MeshConfig, ServerConfig
    from areal_tpu.inference.decode_engine import DecodeEngine

    c = _ctx()
    full = c["full"]
    scfg = ServerConfig(
        max_batch_size=128 if full else 8,
        max_seq_len=512 if full else 128,
        decode_steps_per_call=32 if full else 8,
        quantization=os.environ.get("BENCH_QUANT", "none"),
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
    )
    # the engine's weight-update paths DONATE the served buffers (the LoRA
    # fold frees the fold base) — hand it a private host copy so the shared
    # _ctx() tree survives for later benches in the same process
    host = jax.tree.map(np.asarray, c["params"])
    eng = DecodeEngine(scfg, params=host, model_cfg=c["cfg"])
    eng.initialize()
    return eng, scfg, host


@register("decode_engine_steady", heavy=True)
def bench_decode_engine_steady() -> dict:
    """Live DecodeEngine steady state: continuous-batched tok/s plus the
    kernel probe's achieved roofline over the same window (was
    prof_decode / prof_r3 phase_decode; BENCH_QUANT=int8 gives the
    prof_r4 phase_int8 comparison)."""
    import threading

    from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest

    c = _ctx()
    eng, scfg, _host = _make_engine()
    eng.start()
    try:
        rng = np.random.default_rng(0)
        n_req = 128 if c["full"] else 32
        new_tokens = 128 if c["full"] else 32
        plen = scfg.max_seq_len // 4
        done = threading.Event()
        results: list = []
        lock = threading.Lock()

        def cb(resp):
            with lock:
                results.append(resp)
                if len(results) == n_req:
                    done.set()

        warm = ModelRequest(
            input_ids=rng.integers(1, c["cfg"].vocab_size, plen).tolist(),
            gconfig=GenerationHyperparameters(max_new_tokens=8, greedy=True),
        )
        eng.generate_sync(warm, timeout=600.0)
        t0 = time.monotonic()
        for _ in range(n_req):
            eng.submit(
                ModelRequest(
                    input_ids=rng.integers(1, c["cfg"].vocab_size, plen).tolist(),
                    gconfig=GenerationHyperparameters(
                        max_new_tokens=new_tokens, temperature=1.0
                    ),
                ),
                cb,
            )
        done.wait(timeout=1200.0)
        dt = max(1e-9, time.monotonic() - t0)
        with lock:
            gen = sum(len(r.output_tokens) for r in results)
        ks = eng.kernel_stats()
        return {
            "entry": {
                "wall_s": dt,
                "tok_s": gen / dt,
                "flops": ks.get("flops_total"),
                "bytes": None,
                "roofline_frac": ks.get("roofline_fraction"),
                "noise_frac": 0.0,
                "dominant_phase": ks.get("dominant_phase"),
                "requests_done": len(results),
            }
        }
    finally:
        eng.stop()


@register("train_step", heavy=True)
def bench_train_step() -> dict:
    """Fwd+bwd cross-entropy step — the optimizer-shaped FLOPs path (was
    prof_r3 phase_train)."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import qwen
    from areal_tpu.observability import hw_accounting as hw

    c = _ctx()
    cfg = c["cfg"]
    B, T = (8, 512) if c["full"] else (4, 64)
    rng = np.random.default_rng(6)
    ids = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, T)), jnp.int32)
    labels = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, T)), jnp.int32)
    seg = jnp.ones((B, T), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

    def loss_fn(p, i, l):
        logits = qwen.forward(p, cfg, i, seg, pos)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        return -jnp.take_along_axis(logp, l[..., None], -1).mean()

    grad = jax.jit(jax.grad(loss_fn))
    return {
        "run": lambda: _sync(grad(c["params"], ids, labels)),
        "tokens": B * T,
        "flops": hw.train_step_flops(cfg, float(B * T)),
        "bytes": None,
        "warmup": 1,
    }


@register("tree_train", heavy=True)
def bench_tree_train() -> dict:
    """Grad through the ancestor-mask forest forward — the tree-training
    FLOP-reduction path (was prof_r5 phase_tree)."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import qwen
    from areal_tpu.models.tree import build_tree
    from areal_tpu.observability import hw_accounting as hw

    c = _ctx()
    cfg = c["cfg"]
    rng = np.random.default_rng(7)
    base = 64 if c["full"] else 20
    seqs = [list(rng.integers(1, cfg.vocab_size, base)) for _ in range(8)]
    for i in range(4, 8):
        seqs[i] = seqs[i - 4][: base // 2] + seqs[i]
    pack = build_tree(seqs)
    N = pack.n_nodes
    ids = jnp.asarray(pack.tokens, jnp.int32)[None]
    pos = jnp.asarray(pack.depth, jnp.int32)[None]
    seg = jnp.ones((1, N), jnp.int32)
    mask = jnp.asarray(pack.ancestor_mask())[None, None]
    labels = jnp.asarray(np.roll(pack.tokens, -1), jnp.int32)[None]

    def loss_fn(p):
        logits = qwen.forward(p, cfg, ids, seg, pos, attn_mask=mask)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        return -jnp.take_along_axis(logp, labels[..., None], -1).mean()

    grad = jax.jit(jax.grad(loss_fn))
    return {
        "run": lambda: _sync(grad(c["params"])),
        "tokens": N,
        "flops": hw.train_step_flops(cfg, float(N)),
        "bytes": None,
        "warmup": 1,
    }


@register("weight_update", heavy=True)
def bench_weight_update() -> dict:
    """Paused weight-update latency on a live engine: the LoRA-delta fold
    (measured, LoRA FIRST — any full update invalidates the engine's
    delta-fold base by design) plus one full mem-path push reported as
    ``full_update_s`` (was prof_r4 phase_wu)."""
    import jax

    c = _ctx()
    eng, _scfg, host = _make_engine()
    eng.start()
    try:
        rng = np.random.default_rng(8)
        lora = {}
        for t in ("wq", "wk", "wv", "wo"):
            L, d_in, d_out = c["params"]["layers"][t].shape
            lora[f"layers/{t}_lora_a"] = rng.normal(0, 0.01, (L, d_in, 32)).astype(np.float32)
            # b == 0: repeated folds leave the served weights unchanged
            lora[f"layers/{t}_lora_b"] = np.zeros((L, 32, d_out), np.float32)
        version = [1]

        def fold():
            version[0] += 1
            eng.pause_generation()
            eng.update_weights_lora(lora, scale=0.5, version=version[0])
            eng.continue_generation()
            _sync(eng.params["layers"]["wq"])

        fold()  # warm the fold-fn compile outside the measured window
        wall, noise, _s = _measure(fold, iters=3, warmup=0)
        # one full mem-path push, measured once (it invalidates the LoRA
        # base, so it must come LAST)
        t0 = time.monotonic()
        eng.pause_generation()
        eng.update_weights_from_params(host, version=version[0] + 1)
        eng.continue_generation()
        _sync(eng.params["layers"]["wq"])
        full_s = time.monotonic() - t0
        return {
            "entry": {
                "wall_s": wall,
                "tok_s": None,
                "flops": None,
                "bytes": float(sum(a.nbytes for a in lora.values())),
                "roofline_frac": None,
                "noise_frac": noise,
                "full_update_s": full_s,
            }
        }
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------


def _measure(fn: Callable, *, iters: int, warmup: int) -> tuple[float, float, list[float]]:
    """Warm-up + median-of-N; noise_frac = MAD/median of the measured
    samples (robust against single outliers — a max-based spread on
    sub-ms benches reads one scheduler hiccup as 50-80%% "noise" and
    would widen the compare margin past a genuine 2x regression)."""
    for _ in range(warmup):
        fn()
    samples = []
    for _ in range(max(1, iters)):
        t0 = time.monotonic()
        fn()
        samples.append(time.monotonic() - t0)
    med = statistics.median(samples)
    mad = statistics.median([abs(s - med) for s in samples])
    noise = mad / med if med > 0 else 0.0
    return med, noise, samples


def _peaks() -> dict[str, Any]:
    import jax

    from areal_tpu.observability import hw_accounting as hw

    # a TPU resolves from the chip table or raises; only the CPU backend
    # measures its host
    pf, pb, source = hw.resolve_chip_peaks(jax.devices()[0])
    return {"flops": pf, "membw": pb, "source": source}


def run_bench(name: str, *, iters: int, warmup: int, peaks: dict) -> dict:
    from areal_tpu.observability import kernel_probe

    spec = REGISTRY[name]
    b = spec["fn"]()
    if "entry" in b:
        return b["entry"]
    wall, noise, _samples = _measure(
        b["run"], iters=iters, warmup=b.get("warmup", warmup)
    )
    tokens = b.get("tokens")
    flops = b.get("flops")
    nbytes = b.get("bytes")
    return {
        "wall_s": wall,
        "tok_s": (tokens / wall) if tokens else None,
        "flops": flops,
        "bytes": nbytes,
        "roofline_frac": kernel_probe.roofline_fraction(
            flops or 0.0, nbytes or 0.0, wall, peaks["flops"], peaks["membw"]
        ),
        "noise_frac": noise,
    }


def run_suite(
    names: list[str], *, iters: int = DEFAULT_ITERS, warmup: int = DEFAULT_WARMUP
) -> dict:
    import jax

    peaks = _peaks()
    out = {
        "schema": 1,
        "backend": jax.default_backend(),
        "full": bool(os.environ.get("MICROBENCH_FULL")),
        "peaks": peaks,
        "benches": {},
    }
    for name in names:
        t0 = time.monotonic()
        entry = run_bench(name, iters=iters, warmup=warmup, peaks=peaks)
        out["benches"][name] = entry
        rf = entry.get("roofline_frac")
        print(
            f"[microbench] {name}: wall={entry['wall_s']:.6f}s"
            + (f" tok/s={entry['tok_s']:.1f}" if entry.get("tok_s") else "")
            + (f" roofline={rf:.4f}" if rf is not None else "")
            + f" (setup+run {time.monotonic()-t0:.1f}s)",
            flush=True,
        )
    return out


# ---------------------------------------------------------------------------
# compare gate
# ---------------------------------------------------------------------------


def compare(
    current: dict, baseline: dict, threshold: float = DEFAULT_THRESHOLD
) -> dict:
    """Noise-aware regression check of ``current`` against ``baseline``.

    Per shared bench: regression iff
    ``cur.wall_s > base.wall_s * (1 + max(threshold, 2*noise)) + floor``
    where noise is the larger of the two runs' measured noise_frac.
    Entries only in current are "new", only in baseline "missing" — both
    are warnings (a renamed bench must not hard-fail the gate; the
    baseline refresh is the reviewed fix)."""
    cur = current.get("benches", {})
    base = baseline.get("benches", {})
    out: dict[str, list] = {"regressions": [], "ok": [], "new": [], "missing": []}
    for name, c in cur.items():
        b = base.get(name)
        if b is None:
            out["new"].append(name)
            continue
        noise = max(
            float(c.get("noise_frac") or 0.0), float(b.get("noise_frac") or 0.0)
        )
        margin = max(threshold, 2.0 * noise)
        limit = float(b["wall_s"]) * (1.0 + margin) + NOISE_FLOOR_S
        if float(c["wall_s"]) > limit:
            out["regressions"].append(
                {
                    "bench": name,
                    "wall_s": float(c["wall_s"]),
                    "baseline_s": float(b["wall_s"]),
                    "limit_s": limit,
                    "margin": margin,
                }
            )
        else:
            out["ok"].append(name)
    out["missing"] = sorted(set(base) - set(cur))
    return out


def _print_compare(result: dict) -> None:
    for r in result["regressions"]:
        print(
            f"[microbench] REGRESSION {r['bench']}: {r['wall_s']:.6f}s vs"
            f" baseline {r['baseline_s']:.6f}s (limit {r['limit_s']:.6f}s,"
            f" margin {r['margin']:.0%})",
            flush=True,
        )
    for n in result["new"]:
        print(f"[microbench] WARN new bench not in baseline: {n}", flush=True)
    for n in result["missing"]:
        print(f"[microbench] WARN baseline bench not run: {n}", flush=True)
    print(
        f"[microbench] compare: {len(result['ok'])} ok,"
        f" {len(result['regressions'])} regression(s),"
        f" {len(result['new'])} new, {len(result['missing'])} missing",
        flush=True,
    )


# ---------------------------------------------------------------------------
# --learn-gate: on-chip RL learning gate (was prof_learn.py)
# ---------------------------------------------------------------------------

LEARN_TARGET = 7
LEARN_GROUP = 4


def _learn_reward(prompt, completions, prompt_ids, completion_ids, **kw):
    return 1.0 if LEARN_TARGET in completion_ids else 0.0


def learn_gate() -> int:
    """Full-stack learning smoke on the REAL backend: a tiny from-scratch
    policy must learn to emit LEARN_TARGET through DecodeEngine-over-HTTP,
    staleness-gated async rollout, GRPO advantages, and mem-mode weight
    updates. Prints ``LEARN_RESULT {json}``; exit 0 iff it learned.
    (No pretrained weights exist in the zero-egress image, so this is the
    hardware-validated stand-in for a benchmark reward curve.)"""
    import tempfile

    import jax

    from areal_tpu.api.config import (
        DatasetConfig,
        EvaluatorConfig,
        InferenceEngineConfig,
        MeshConfig,
        MicroBatchSpec,
        NormConfig,
        OptimizerConfig,
        PPOActorConfig,
        PPOConfig,
        RecoverConfig,
        SaverConfig,
        ServerConfig,
        StatsLoggerConfig,
    )
    from areal_tpu.api.io_struct import (
        FinetuneSpec,
        GenerationHyperparameters,
        ModelRequest,
    )
    from areal_tpu.engine.train_engine import JaxTrainEngine
    from areal_tpu.inference.client import RemoteJaxEngine
    from areal_tpu.inference.decode_engine import DecodeEngine
    from areal_tpu.inference.server import ServerThread
    from areal_tpu.models import qwen
    from areal_tpu.trainer.rl_trainer import PPOTrainer
    from areal_tpu.workflow.rlvr import RLVRWorkflow

    platform = jax.default_backend()
    print(f"[learn] backend={platform}", flush=True)
    model_cfg_ = qwen.ModelConfig(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        dtype="float32",
        tie_word_embeddings=True,
        attention_bias=True,
        rope_theta=10000.0,
    )
    root = tempfile.mkdtemp(prefix="learn_gate_")
    actor_cfg = PPOActorConfig(
        init_from_scratch=True,
        dtype="float32",
        param_dtype="float32",
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
        optimizer=OptimizerConfig(lr=2e-2, lr_scheduler_type="constant"),
        mb_spec=MicroBatchSpec(max_tokens_per_mb=100_000),
        bucket_step=64,
        group_size=LEARN_GROUP,
        ppo_n_minibatches=1,
        adv_norm=NormConfig(
            mean_level="group", std_level="group", group_size=LEARN_GROUP
        ),
        kl_ctl=0.0,
        use_decoupled_loss=True,
        prox_logp_mode="recompute",
        eps_clip=0.4,
        temperature=1.0,
    )
    engine = JaxTrainEngine(actor_cfg, model_config=model_cfg_)
    engine.initialize(FinetuneSpec(1, 32, 8))
    scfg = ServerConfig(
        max_batch_size=8,
        max_seq_len=64,
        decode_steps_per_call=4,
        seed=0,
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
    )
    dec = DecodeEngine(
        scfg, params=jax.tree.map(np.asarray, engine.params), model_cfg=model_cfg_
    )
    dec.initialize()
    server = ServerThread(scfg, dec)
    server.start()
    rollout = RemoteJaxEngine(
        InferenceEngineConfig(
            max_concurrent_rollouts=8,
            consumer_batch_size=4,
            max_head_offpolicyness=2,
            request_timeout=300,
        ),
        addresses=[server.address],
    )
    rollout.initialize()
    cfg = PPOConfig(
        experiment_name="learn_onchip",
        trial_name="t0",
        total_train_epochs=12,
        weight_update_mode="mem",
        gconfig=GenerationHyperparameters(
            n_samples=LEARN_GROUP, max_new_tokens=4, temperature=1.0
        ),
        train_dataset=DatasetConfig(batch_size=4, shuffle=True),
        actor=actor_cfg,
        saver=SaverConfig(fileroot=root),
        checkpointer=SaverConfig(fileroot=root),
        evaluator=EvaluatorConfig(fileroot=root),
        recover=RecoverConfig(mode="disabled", fileroot=root),
        stats_logger=StatsLoggerConfig(fileroot=root),
    )
    cfg.cluster.fileroot = root
    rng = np.random.default_rng(0)
    dataset = [{"prompt_ids": rng.integers(20, 200, 4).tolist()} for _ in range(32)]
    trainer = PPOTrainer(cfg, dataset, rollout=rollout, actor_engine=engine)

    def hit_rate(n=16):
        import asyncio

        async def probe_fn():
            reqs = [
                ModelRequest(
                    input_ids=row["prompt_ids"],
                    gconfig=GenerationHyperparameters(
                        n_samples=1, max_new_tokens=4, greedy=True
                    ),
                )
                for row in dataset[:n]
            ]
            resps = await asyncio.gather(*[rollout.agenerate(r) for r in reqs])
            return float(np.mean([LEARN_TARGET in r.output_tokens for r in resps]))

        return asyncio.run(probe_fn())

    t0 = time.monotonic()
    before = hit_rate()
    trainer.train(workflow=RLVRWorkflow(_learn_reward, cfg.gconfig))
    after = hit_rate()
    dt = time.monotonic() - t0
    ok = after > max(0.5, before + 0.3)
    print(
        "LEARN_RESULT "
        + json.dumps(
            {
                "backend": platform,
                "before": before,
                "after": after,
                "learned": ok,
                "secs": round(dt, 1),
                "versions": engine.get_version(),
            }
        ),
        flush=True,
    )
    server.stop()
    return 0 if ok else 1


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def fast_names() -> list[str]:
    return [n for n, s in REGISTRY.items() if not s["heavy"]]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="microbench", description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    ap.add_argument("--list", action="store_true", help="list registered benches")
    ap.add_argument(
        "--benches", help="comma-separated bench names (default: all fast benches)"
    )
    ap.add_argument(
        "--heavy", action="store_true", help="include the heavy engine-level benches"
    )
    ap.add_argument("--iters", type=int, default=DEFAULT_ITERS)
    ap.add_argument("--warmup", type=int, default=DEFAULT_WARMUP)
    ap.add_argument("--out", help="write results JSON here")
    ap.add_argument("--compare", help="baseline JSON; exit 1 on regression")
    ap.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD)
    ap.add_argument(
        "--learn-gate", action="store_true", help="run the on-chip RL learning gate"
    )
    args = ap.parse_args(argv)

    if args.list:
        for n, s in REGISTRY.items():
            kind = "heavy" if s["heavy"] else "fast"
            print(f"{n:22s} [{kind}] {s['doc']}")
        return 0
    if args.learn_gate:
        return learn_gate()

    if args.benches:
        names = [n.strip() for n in args.benches.split(",") if n.strip()]
        unknown = [n for n in names if n not in REGISTRY]
        if unknown:
            print(f"[microbench] unknown bench(es): {unknown}", file=sys.stderr)
            return 2
    else:
        names = [
            n for n, s in REGISTRY.items() if args.heavy or not s["heavy"]
        ]

    result = run_suite(names, iters=args.iters, warmup=args.warmup)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
            f.write("\n")
        print(f"[microbench] wrote {args.out}", flush=True)

    if args.compare:
        with open(args.compare) as f:
            baseline = json.load(f)
        cmp_res = compare(result, baseline, threshold=args.threshold)
        _print_compare(cmp_res)
        return 1 if cmp_res["regressions"] else 0
    print(json.dumps({"benches": result["benches"]}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
