"""Time one sparse-expert layer alone on the chip at the widths of the
benchmark's ``rollout-lfm2-8b-a1b-d14-grpo`` (32 experts of [2048, 1792],
top-4) or, with ``--shape kanana2``, of ``rollout-kanana-2-30b-a3b-ep8-grpo``
(the 16 experts of [2048, 768] a chip holds of the 128 its router scores,
top-6: a row's choices that fall on the other 112 are no work here), in the
forms of ``models/moe.py`` and at the tiles it would choose.

    chiprun -- python -m areal_tpu.tools.moe_probe
    chiprun -- python -m areal_tpu.tools.moe_probe --shape kanana2 --rows 24,64,512,1024
    chiprun -- python -m areal_tpu.tools.moe_probe --shape kanana2 --rows 64 --touched 4,8,12,16

For each row count (128: a decode step of 128 slots; 256 to 1024: a short
prompt's prefill, up to ``moe.DENSE_ROWS``; 4096: a batched prefill) it scans ``--layers`` stacked
layers, as a serving program does, and prints microseconds a layer for

  ``dense``          every expert on every row (``moe._experts_dense``)
  ``touched``        (up to ``moe.DENSE_ROWS`` rows) the dense form's sum over
                     the experts that got a row, read out of the stack by
                     ``ops/moe_touched_experts.py``; ``touched_nbuf<n>``
                     (``--nbuf``) the same with another ring of buffers
  ``routed``         sort, ``megablox.gmm`` at ``moe.gmm_tiles``, gather back
  ``routed_128``     the same at the library's 128 x 128 x 128 tiles (what
                     ``models/moe.py`` ran before PR 30)
  ``routed_wide``    (small row counts only) gmm with the whole contraction
                     a tile: tiles (128, k, n/2)
  ``routed_tm<n>``   (``--tm``) the chosen tiles with another m tile

with the share of the memory roofline the bytes of the touched experts'
weights reach (819 GB/s) and, for large row counts, of the MXU's peak.
Routing is drawn at random (uniform over experts, as seeded weights route)
for the first ``--live`` rows (default: all; the others hold no request: gate
0, no load); ``--touched n`` draws it so that exactly n of the held experts
get a row, to time the touched form at 4, 8, 12 and 16 of 16 alone.
TPU only: a CPU time is no speed.
"""

from __future__ import annotations

import argparse
import functools
import json
import time

# experts held (E) of those the router scores (E_ALL), experts a token, hidden and expert width
SHAPES = {"lfm2": (32, 32, 4, 2048, 1792), "kanana2": (16, 128, 6, 2048, 768)}
HBM_BYTES_S, FLOPS = 819e9, 197e12  # TPU v5e, as benchmarks/chip/benchlib/peaks.py


def draw_routing(rng, rows: int, live: int, E: int, E_ALL: int, K: int, touched: int | None):
    """(chosen experts [rows, K], the rows that hold a request [rows]): K
    distinct experts a row, uniform over the router's E_ALL or, with
    ``touched``, over that many of the E held and the ones held elsewhere,
    each of the named ones chosen by some live row."""
    import numpy as np

    pool = np.arange(E_ALL) if touched is None else np.concatenate([rng.permutation(E)[:touched], np.arange(E, E_ALL)])
    if len(pool) < K or (touched or 0) > live:
        raise ValueError(f"{touched} touched experts of {E} (router {E_ALL}) cannot be drawn for {live} live rows of top-{K}")
    top_e = np.stack([rng.permutation(pool)[:K] for _ in range(rows)])
    for r in range(touched or 0):  # row r makes sure of the r-th named expert
        if pool[r] not in top_e[r]:
            top_e[r, 0] = pool[r]
    return top_e.astype(np.int32), np.arange(rows) < live


def probe(rows: int, layers: int, reps: int, seed: int, tms=(), shape: str = "lfm2", live: int | None = None, touched: int | None = None, nbufs=(), only=None) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.models import moe

    E, E_ALL, K, D, F = SHAPES[shape]
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (rows, D), jnp.bfloat16)
    wg = 0.02 * jax.random.normal(ks[1], (layers, E, D, F), jnp.bfloat16)
    wu = 0.02 * jax.random.normal(ks[2], (layers, E, D, F), jnp.bfloat16)
    wd = 0.02 * jax.random.normal(ks[3], (layers, E, F, D), jnp.bfloat16)
    from areal_tpu.ops import moe_touched_experts

    top_e, is_live = draw_routing(np.random.default_rng(seed), rows, rows if live is None else live, E, E_ALL, K, touched)
    gates = jnp.asarray(is_live[:, None] * np.full((rows, K), 1.0 / K, np.float32))
    load = jnp.asarray(np.bincount(top_e[is_live].ravel(), minlength=E_ALL).astype(np.int32))
    here = top_e[is_live][top_e[is_live] < E]  # the live rows' choices that fall on the experts held (ids from 0)
    top_e = jnp.asarray(top_e)
    touched = int(np.unique(here).size)
    chosen = (moe.gmm_tiles(rows * K, D, F, E), moe.gmm_tiles(rows * K, F, D, E))

    def with_tiles(t_in, t_out):
        def patched(m, k, n, groups=1):
            return t_in if (k, n) == (D, F) else t_out

        return patched

    def run(form):
        def layer(h, w):
            a, b, c = w
            if form == "dense":
                out = moe._experts_dense(h, a, b, c, top_e, gates, 0)
            else:
                out = moe._experts_routed(h, a, b, c, top_e, gates, 0, False)
            return (h + out.astype(h.dtype) * 0.01), None

        def from_stack(h, i, wg, wu, wd):  # the stacks whole and the scan's index, as a decode chunk hands them over
            out = moe._experts_touched(h, moe.Stacked(wg, i), moe.Stacked(wu, i), moe.Stacked(wd, i), top_e, gates, load, 0)
            return h + out.astype(h.dtype) * 0.01, None

        def model(h, wg, wu, wd):
            if form.startswith("touched"):
                return jax.lax.scan(functools.partial(from_stack, wg=wg, wu=wu, wd=wd), h, jnp.arange(layers, dtype=jnp.int32))[0]
            return jax.lax.scan(layer, h, (wg, wu, wd))[0]

        tiles = {
            "routed_128": with_tiles((128, 128, 128), (128, 128, 128)),
            "routed_wide": with_tiles((min(rows * K, 128), D, F // 2), (min(rows * K, 128), F, D // 2)),
            **{f"routed_tm{tm}": with_tiles((tm, *chosen[0][1:]), (tm, *chosen[1][1:])) for tm in tms},
        }.get(form)
        keep, keep_nbuf = moe.gmm_tiles, moe_touched_experts._NBUF
        if tiles is not None:
            moe.gmm_tiles = tiles
        if form.startswith("touched_nbuf"):
            moe_touched_experts._NBUF = int(form[len("touched_nbuf"):])
        try:
            fn = jax.jit(model)
            fn(x, wg, wu, wd).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(reps):
                out = fn(x, wg, wu, wd)
            out.block_until_ready()
            return (time.perf_counter() - t0) / (reps * layers) * 1e6
        finally:
            moe.gmm_tiles, moe_touched_experts._NBUF = keep, keep_nbuf

    forms = ["dense"] + (["touched"] + [f"touched_nbuf{n}" for n in nbufs] if rows <= moe.DENSE_ROWS else []) + ["routed", "routed_128"] + (["routed_wide"] if rows <= 512 else []) + [f"routed_tm{tm}" for tm in tms]
    if rows > 2048:
        forms.remove("dense")  # 8 x the rows' arithmetic: nobody's path
    if only:
        forms = [f for f in forms if f in only]
    res = {"shape": shape, "rows": rows, "live": int(is_live.sum()), "experts_touched": touched, "assignments_here": int(here.size), "tiles": [list(t) for t in chosen]}
    least_bytes = touched * 3 * D * F * 2 / HBM_BYTES_S * 1e6
    least_flops = int(here.size) * 3 * 2 * D * F / FLOPS * 1e6
    for form in forms:
        try:
            us = run(form)
        except Exception as e:  # noqa: BLE001 — a tile the chip's compiler refuses is a finding, not a crash
            res[form] = f"refused: {str(e)[:200]}"
            continue
        res[f"{form}_us"] = round(us, 1)
        res[f"{form}_roofline_pct"] = round(100 * max(least_bytes, least_flops) / us, 1)
    res["least_us"] = {"bytes": round(least_bytes, 1), "flops": round(least_flops, 1)}
    return res


def main(argv=None) -> int:
    import jax

    p = argparse.ArgumentParser()
    p.add_argument("--rows", default="128,256,512,4096")
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--reps", type=int, default=20)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--tm", default="", help="more routed variants: the chosen tiles with these m tiles")
    p.add_argument("--shape", choices=sorted(SHAPES), default="lfm2")
    p.add_argument("--live", type=int, default=None, help="rows that hold a request (default: all)")
    p.add_argument("--touched", default="", help="held experts that get a row, drawn exactly: one line for each count")
    p.add_argument("--nbuf", default="", help="more touched variants: rings of this many experts' buffers")
    p.add_argument("--forms", default="", help="only these columns")
    a = p.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("moe_probe needs a TPU: a CPU time is no speed")
        return 2
    ints = lambda text: [int(t) for t in text.split(",") if t]  # noqa: E731
    for rows in ints(a.rows):
        for touched in ints(a.touched) or [None]:
            print(json.dumps(probe(rows, a.layers, a.reps, a.seed, ints(a.tm), a.shape, a.live, touched, ints(a.nbuf), a.forms.split(",") if a.forms else None)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
