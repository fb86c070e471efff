"""Time the train step's flash attention alone on the chip, by tile edge.

    chiprun -- python -m areal_tpu.tools.flash_attn_probe
    chiprun -- python -m areal_tpu.tools.flash_attn_probe --row-lens 4096,8192 --segments one

``flash_train`` (``ops/flash_kernels.py``: forward and both backward
kernels) on 12,288 tokens of bf16 q/k/v at 12 heads of 128 with KV already
repeated, as ``train-1.5b-packed4k`` calls it a layer: 3 rows of 4096, and
the same tokens in rows of 2048 down to 256 (where ``FLASH_MIN_LEN`` is
decided against ``sdpa_xla``). ``--segments`` lays the rows out: ``cell``
(the default) packs ``grpo-packed-4k``'s 13 sequences, in an order the seed
draws, first-fit-decreasing as the trainer does; ``one`` makes every row
one sequence (nothing for the segment skip to find); a comma list packs
those lengths. Lengths repeat until the tokens are reached, a sequence
longer than the row is cut to it, a row's free tail is padding (id 0).

One line for the program's own tiles, then (rows of 1024 and up) one a
candidate: device ms a layer of each kernel, read from a profiler trace by
the kernels' own names, beside the tiles it ran of its causal tiles and the
share of the MXU's peak that the causal FLOPs of those tiles make; then host-clock ms
of forward and forward+backward against XLA, and the repo's forward-only
kernel. The sweep lives here: the program ships
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
# candidate (query edge, key edge) tiles, the same list for each kernel: row
# i makes one program; the kernels do not see each other
EDGES = [(128, 128), (256, 256), (512, 512), (1024, 1024), (1024, 512), (512, 1024), (512, 256), (256, 512), (2048, 1024)]
# grpo-packed-4k's sequence lengths (benchmarks/chip/benchlib/traffic.py
# packed_shapes: a function of the mix alone)
CELL_LENGTHS = (718, 960, 539, 804, 1129, 691, 562, 1946, 1018, 663, 989, 1611, 658)


def causal_flops(kernel: str, G: int, L: int) -> float:
    """FLOPs a layer of one kernel over the causal half of [L, L]."""
    return MATMULS[kernel] * 2 * L * L * HD * H * G / 2


def lay_out(segments: str, L: int, seed: int) -> np.ndarray:
    """[G, L] segment ids for ``--segments``: 1-based within a row as
    ``utils/grid.py pack_grid`` numbers them, 0 the padded tail."""
    from areal_tpu.utils import datapack

    G = max(1, TOKENS // L)
    if segments == "one":
        return np.ones((G, L), np.int32)
    lengths = CELL_LENGTHS if segments == "cell" else tuple(int(n) for n in segments.split(","))
    order = np.random.default_rng(seed).permutation(len(lengths))
    seqs: list[int] = []
    while sum(seqs) < G * L:
        seqs.append(min(lengths[order[len(seqs) % len(order)]], L, G * L - sum(seqs)))
    rows = datapack.ffd_allocate(seqs, L, min_groups=1)
    seg = np.zeros((len(rows), L), np.int32)
    for r, row in enumerate(rows):
        at = 0
        for sid, i in enumerate(row, start=1):
            seg[r, at:at + seqs[i]] = sid
            at += seqs[i]
    return seg


def kernel_ms(step, args, reps: int) -> dict:
    """Device ms a call of the three flash kernels and of everything else
    (transposes, the row sums of dO*O, the dq wrapper's broadcast) over
    ``reps`` traced calls, by the kernels' names."""
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
            kind = next((k for k in ("dkv", "dq") if f"flash_mha_bwd_{k}" in ev.name), "fwd" if "flash_mha_fwd" in ev.name else "other")
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


def probe(L: int, *, segments: str, seed: int, reps: int, sweep: bool) -> None:
    import jax
    import jax.numpy as jnp

    from areal_tpu.observability.hw_accounting import resolve_chip_peaks
    from areal_tpu.ops import attention
    from areal_tpu.tools.kernelcheck import _packed_mask

    seg_np = lay_out(segments, L, seed)
    G = seg_np.shape[0]
    peak = resolve_chip_peaks()[0]  # published bf16 FLOP/s; an unknown chip is an error
    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(seed), 4)
    qkv = tuple(jax.random.normal(x, (G, L, H, HD), jnp.bfloat16) for x in (kq, kk, kv))
    w = jax.random.normal(kw, (G, L, H, HD), jnp.float32)
    seg = jnp.asarray(seg_np)
    sweep = sweep and L >= SWEEP_MIN_LEN

    def peak_pct(kernel: str, ms: float, run: int, causal: int) -> float:
        # of the causal FLOPs the share the tiles run hold at most: a skipped tile does none
        return ms and 100 * causal_flops(kernel, G, L) * run / causal / (ms * 1e-3) / peak

    def fwd_of(attn):
        return jax.jit(lambda q, k, v: (attn(q, k, v),))

    def grad_of(attn):
        return jax.jit(jax.grad(lambda q, k, v: (attn(q, k, v).astype(jnp.float32) * w).sum(), argnums=(0, 1, 2)))

    head = {"G": G, "L": L, "segments": segments, "segments_a_row": float(np.mean(seg_np.max(axis=1)))}
    own = attention.flash_block_sizes(attention.flash_tiles(L, HD))
    for row in [None, *EDGES] if sweep else [None]:  # None: what flash_tiles gives this row length
        if row and any(L % e for e in row):
            continue
        blocks = attention.FlashBlocks(row, row, row) if row else own
        # the mask is built outside the timed program, as a model builds it outside its layers
        mask = attention.flash_mask(seg, HD, blocks)
        step = grad_of(lambda q, k, v, mask=mask: attention.flash_train(q, k, v, mask))
        counts = attention.flash_tile_counts(seg_np, blocks)

        def measure(step=step, counts=counts):
            out = {"host_fwd_bwd_ms": host_ms(step, qkv, reps)}
            ms = kernel_ms(step, qkv, reps)
            for kern in MATMULS:
                out[f"{kern}_ms"], out[f"{kern}_peak_pct"] = ms[kern], peak_pct(kern, ms[kern], *counts[kern])
            return {**out, "other_ms": ms["other"]}

        rec = {**head, "tiles": "swept" if row else "flash_tiles", **blocks._asdict()}
        rec.update({f"{kern}_tiles_run": "%d/%d" % counts[kern] for kern in MATMULS})
        report(rec, measure)

    # the program's own choice against XLA on the host's clock
    mask = jnp.asarray(_packed_mask(seg_np))
    own_mask = attention.flash_mask(seg, HD)
    impls = {"flash": lambda q, k, v: attention.flash_train(q, k, v, own_mask)}
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
        bq, bk = blk or own.fwd
        fwd_only = fwd_of(lambda q, k, v, bq=bq, bk=bk: attention.flash_fwd_pallas(q, k, v, seg, bq, bk))
        counts = attention.flash_tile_counts(seg_np, attention.FlashBlocks((bq, bk), (bq, bk), (bq, bk)))["fwd"]

        def measure(fwd_only=fwd_only, counts=counts):
            ms = host_ms(fwd_only, qkv, reps)
            return {"host_ms": ms, "tiles_run": "%d/%d" % counts, "peak_pct": peak_pct("fwd", ms, *counts)}

        report({**head, "flash_fwd_pallas": blk or "default"}, measure)


def main() -> int:
    import jax

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--no-sweep", action="store_true", help="only the program's own tiles against XLA")
    ap.add_argument("--row-lens", default=",".join(map(str, ROW_LENS)), help="row lengths to probe, a comma list")
    ap.add_argument("--segments", default="cell", help="cell | one | a comma list of sequence lengths (module docstring)")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("flash_attn_probe: needs a TPU (a CPU time is no speed)")
        return 2
    print(json.dumps({"causal_gflop_a_layer_3x4096": {k: causal_flops(k, 3, 4096) / 1e9 for k in MATMULS}}))
    for L in (int(n) for n in args.row_lens.split(",")):
        probe(L, segments=args.segments, seed=args.seed, reps=args.reps, sweep=not args.no_sweep)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
