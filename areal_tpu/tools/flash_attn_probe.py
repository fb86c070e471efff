"""Time the train step's flash attention alone on the chip, by tile edge.

    chiprun -- python -m areal_tpu.tools.flash_attn_probe

``flash_train`` (jax's library kernel, forward and both backward kernels) on
12,288 tokens of bf16 q/k/v at 12 heads of 128 with KV already repeated, as
``train-1.5b-packed4k`` calls it a layer: 3 rows of 4096, and the same
tokens in rows of 2048 down to 256 (where ``FLASH_MIN_LEN`` is decided
against ``sdpa_xla``). Rows are packed like ``grpo-packed-4k``: sequences of
``grpo-reasoning``'s lengths back to back, the last cut to fill the row.

One line for the program's own tiles, then (rows of 1024 and up) one a
candidate: device ms a layer of each kernel, read from a profiler trace by
the kernels' own names, the causal FLOPs a layer and their share of the
MXU's peak; then host-clock ms of forward and forward+backward against XLA,
and the repo's forward-only kernel. The sweep lives here: the program ships
``ops.attention.flash_tiles``. TPU only: a CPU time is no speed.
"""

from __future__ import annotations

import argparse
import glob
import json
import tempfile
import time

import numpy as np

H, HD, TOKENS = 12, 128, 12288
ROW_LENS = (4096, 2048, 1024, 512, 256)
SWEEP_MIN_LEN = 1024
# matmuls of [L, L, d] each kernel makes: S and PV; S, dV, dP, dK; S, dP, dQ
MATMULS = {"fwd": 2, "dkv": 4, "dq": 3}
# candidates, each kernel's in the library's own field order:
# fwd (block_q, block_k_major, block_k); dkv (block_q_major, block_q,
# block_k_major, block_k); dq (block_q, block_k_major, block_k). Row i of
# the three lists makes one program; the kernels do not see each other.
FWD = [(128,) * 3, (256,) * 3, (512,) * 3, (1024,) * 3, (1024, 1024, 512), (512, 1024, 512),
       (1024, 512, 512), (512, 2048, 512), (2048, 1024, 1024), (1024, 2048, 1024)]
DKV = [(128,) * 4, (256,) * 4, (512,) * 4, (1024,) * 4, (1024, 512, 1024, 512), (512, 512, 1024, 512),
       (1024, 512, 512, 512), (1024, 256, 1024, 512), (2048, 512, 2048, 512), (1024, 1024, 512, 512)]
DQ = [(128,) * 3, (256,) * 3, (512,) * 3, (1024,) * 3, (1024, 1024, 512), (512, 1024, 512),
      (1024, 512, 512), (1024, 2048, 512), (2048, 2048, 512), (512, 1024, 1024)]


def causal_flops(kernel: str, G: int, L: int) -> float:
    """FLOPs a layer of one kernel over the causal half of [L, L]."""
    return MATMULS[kernel] * 2 * L * L * HD * H * G / 2


def draw_segments(G: int, L: int, seed: int) -> np.ndarray:
    """[G, L] segment ids, 1-based, every row full: boundaries fall where
    the traffic's lengths put them, not on tile edges."""
    rng = np.random.default_rng(seed)
    seg = np.zeros((G, L), np.int32)
    for g in range(G):
        at, sid = 0, 1
        while at < L:
            prompt = np.exp(rng.uniform(np.log(128), np.log(1024)))
            out = np.clip(rng.lognormal(np.log(384), 1.0), 16, 3072)
            n = int(min(prompt + out, 4000, L - at))
            seg[g, at:at + n] = sid
            at, sid = at + n, sid + 1
    return seg


def block_sizes(fwd, dkv, dq):
    from areal_tpu.ops.attention import pinned_block_sizes

    return pinned_block_sizes()(
        block_q=fwd[0], block_k_major=fwd[1], block_k=fwd[2], block_b=1,
        block_q_major_dkv=dkv[0], block_q_dkv=dkv[1], block_k_major_dkv=dkv[2], block_k_dkv=dkv[3],
        block_q_dq=dq[0], block_k_major_dq=dq[1], block_k_dq=dq[2],
    )


def kernel_ms(step, args, reps: int) -> dict:
    """Device ms a call of the three flash kernels and of everything else
    (transposes, the row sums of dO*O, the dq wrapper's broadcast) over
    ``reps`` traced calls, by the names the library gives its kernels."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        for _ in range(reps):
            out = step(*args)
        out[0].block_until_ready()
        jax.profiler.stop_trace()
        planes = ProfileData.from_file(sorted(glob.glob(f"{d}/plugins/profile/*/*.xplane.pb"))[-1]).planes
    ms = {"fwd": 0.0, "dkv": 0.0, "dq": 0.0, "other": 0.0}
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for ev in (ev for line in plane.lines if line.name == "XLA Ops" for ev in line.events):
            kind = ("dkv" if "flash_mha_bwd_dkv" in ev.name else "dq" if "flash_mha_bwd_dq" in ev.name
                    else "fwd" if "flash_attention" in ev.name else "other")
            ms[kind] += ev.duration_ns * 1e-6 / reps
    return ms


def host_ms(fn, args, reps: int) -> float:
    fn(*args)[0].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    out[0].block_until_ready()
    return (time.perf_counter() - t0) / reps * 1e3


def report(rec: dict, measure) -> None:
    """Print ``rec`` with what ``measure()`` adds to it, or with the
    compiler's refusal: a tile Mosaic refuses is a row of the table."""
    try:
        rec.update(measure())
    except Exception as e:  # noqa: BLE001
        rec["refused"] = f"{type(e).__name__}: {str(e)[:200]}"
    print(json.dumps(rec), flush=True)


def probe(L: int, *, seed: int, reps: int, sweep: bool) -> None:
    import jax
    import jax.numpy as jnp

    from areal_tpu.observability.hw_accounting import resolve_chip_peaks
    from areal_tpu.ops import attention
    from areal_tpu.tools.kernelcheck import _packed_mask

    G = TOKENS // L
    peak = resolve_chip_peaks()[0]  # published bf16 FLOP/s; an unknown chip is an error
    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(seed), 4)
    qkv = tuple(jax.random.normal(x, (G, L, H, HD), jnp.bfloat16) for x in (kq, kk, kv))
    w = jax.random.normal(kw, (G, L, H, HD), jnp.float32)
    seg_np = draw_segments(G, L, seed)
    seg = jnp.asarray(seg_np)
    sweep = sweep and L >= SWEEP_MIN_LEN

    def peak_pct(kernel: str, ms: float) -> float:
        return ms and 100 * causal_flops(kernel, G, L) / (ms * 1e-3) / peak

    def fwd_of(attn):
        return jax.jit(lambda q, k, v: (attn(q, k, v),))

    def grad_of(attn):
        return jax.jit(jax.grad(lambda q, k, v: (attn(q, k, v).astype(jnp.float32) * w).sum(), argnums=(0, 1, 2)))

    head = {"G": G, "L": L, "segments_a_row": float(np.mean(seg_np.max(axis=1)))}
    for row in [None, *zip(FWD, DKV, DQ)] if sweep else [None]:  # None: what flash_tiles gives this row length
        if row and any(L % e for kernel in row for e in kernel):
            continue
        bs = block_sizes(*row) if row else None
        step = grad_of(lambda q, k, v, bs=bs: attention.flash_train(q, k, v, seg, block_sizes=bs))
        fwd, dkv, dq = row or ((e,) for e in attention.flash_tiles(L, HD))

        def measure(step=step):
            out = {"host_fwd_bwd_ms": host_ms(step, qkv, reps)}
            ms = kernel_ms(step, qkv, reps)
            for kern in MATMULS:
                out[f"{kern}_ms"], out[f"{kern}_peak_pct"] = ms[kern], peak_pct(kern, ms[kern])
            return {**out, "other_ms": ms["other"]}

        report({**head, "tiles": "swept" if row else "flash_tiles", "fwd": fwd, "dkv": dkv, "dq": dq}, measure)

    # the program's own choice against XLA on the host's clock
    mask = jnp.asarray(_packed_mask(seg_np))
    impls = {"flash": lambda q, k, v: attention.flash_train(q, k, v, seg)}
    if L < 4096:  # [G, H, L, L] float32 logits and their gradient: 5 GB and no contest
        impls["xla"] = lambda q, k, v: attention.sdpa_xla(q, k, v, mask, HD)
    rec = dict(head)
    for name, attn in impls.items():
        rec[f"{name}_fwd_ms"] = host_ms(fwd_of(attn), qkv, reps)
        rec[f"{name}_fwd_bwd_ms"] = host_ms(grad_of(attn), qkv, reps)
    print(json.dumps(rec), flush=True)
    # the repo's forward-only kernel (no cell runs it): (blk_q, blk_k), None = its default
    for blk in [None, *((e, e) for e in (128, 256, 512, 1024)), (1024, 512), (512, 1024)] if sweep else [None]:
        if blk and any(L % e for e in blk):
            continue
        bq, bk = blk or (None, None)
        fwd_only = fwd_of(lambda q, k, v, bq=bq, bk=bk: attention.flash_fwd_pallas(q, k, v, seg, bq, bk))

        def measure(fwd_only=fwd_only):
            ms = host_ms(fwd_only, qkv, reps)
            return {"host_ms": ms, "peak_pct": peak_pct("fwd", ms)}

        report({**head, "flash_fwd_pallas": blk or "default"}, measure)


def main() -> int:
    import jax

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--no-sweep", action="store_true", help="only the program's own tiles against XLA")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("flash_attn_probe: needs a TPU (a CPU time is no speed)")
        return 2
    print(json.dumps({"causal_gflop_a_layer_3x4096": {k: causal_flops(k, 3, 4096) / 1e9 for k in MATMULS}}))
    for L in ROW_LENS:
        probe(L, seed=args.seed, reps=args.reps, sweep=not args.no_sweep)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
