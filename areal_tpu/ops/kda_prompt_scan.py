"""Pallas TPU kernel: a ``kda`` layer's recurrence over a block of ONE prompt,
a chunk of ``CHUNK`` tokens a grid step, the head's state in VMEM throughout.

The prompt pass's form of the delta rule with a decay of its own every key
channel (``models/hybrid.py kda_chunked_scan``: its docstring has the
algebra, and it stays the CPU path and this launch's oracle). The XLA form
keeps every operand of a block as float32 arrays in HBM (q, k, v, the log
decay, the keys as each sub-block sees them, the chunk matrices: 0.4 GB a
block of 1,024 tokens at 64 heads of 128 x 128), walks the own sub-block in
16 passes over them and carries the state through a ``lax.scan`` whose every
step moves the 4 MB state to HBM and back: 598 us a chunk and layer inside
the prompt program where the products are 28 us of the MXU, nine tenths of it
before the carried loop (PERF.md, PR 48; this launch: 116). Here a grid step reads a
chunk's q, k, v, log decay and beta ONCE, makes the ``[C, C]`` matrices, the
inverse, the writes and the reads in VMEM and vregs, and only ``o`` ``[C, V]``
and, at a block's last chunk, the state go back.

The same work, not other work: float32 throughout, every product at
``Precision.HIGHEST``, EVERY exponent <= 0 (a chunk is sub-blocks of ``SUB``
tokens; a query meets the keys of EARLIER sub-blocks through its sub-block's
first token and its OWN sub-block's keys one key offset at a time), the cut at
``n_state``, the carried ``s0``. No bound is assumed on the log decay.

The triangular system: the ``SUB``-blocks on the diagonal are inverted by
forward elimination, a column a step and the four blocks at once (what
``_unit_lower_inverse`` does a row a step), then doubled by
``inv([[A, 0], [B, D]]) = Z - Z [[0, 0], [B, 0]] Z`` with ``Z = diag(inv A,
inv D)``: two full-width products a level in place of that function's
concatenations. No Neumann series: its terms grow with the keys' overlap and
cancel.

Layout: q, k and the log decay ``[L, H * K]``, v ``[L, H * V]`` as the
projections leave them, so a ``(C, heads * 128)`` block at ``(chunk, head
group)`` is a group's chunk with no transpose; heads of a group are a static
loop over lane slices (independent chains for the scheduler to interleave:
the products are small and an MXU pass waits on the one before it); beta
``[H / heads, L, heads]``. Grid: head groups (parallel) x chunks (arbitrary):
the state's output block is the accumulator, set from ``s0`` at chunk 0.

The body binds ``jax.lax`` primitives only: on the benchmark machine's host
every ``jnp`` function or operator of a traced value inside a kernel body is
a jitted call traced apart, 1-3 ms each (PERF.md, PR 45).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK, SUB = 64, 16  # models/hybrid.py KDA_CHUNK, KDA_SUB
HEADS_PER_STEP = 4
_F32 = jnp.float32
_HI = lax.Precision.HIGHEST


def _dot(x, y, dims=((1,), (0,))):
    return lax.dot_general(x, y, (dims, ((), ())), precision=_HI, preferred_element_type=_F32)


def _rows(x, n):  # [1, W] -> [n, W]
    return lax.broadcast_in_dim(x, (n, x.shape[1]), (0, 1))


def _lanes(x, n):  # [R, 1] -> [R, n]
    return lax.broadcast_in_dim(x, (x.shape[0], n), (0, 1))


def _row_sums(x):  # [R, W] -> [R, 1]
    return lax.expand_dims(lax.reduce_sum(x, (1,)), (1,))


def _pick(cond, x, other=0.0):
    return lax.select(cond, x, lax.full_like(x, other))


def _kernel(
    n_ref,  # SMEM [1] int32: tokens of this block that enter the state
    q_ref,  # VMEM [C, hb * K] f32 (normalised and scaled)
    k_ref,  # VMEM [C, hb * K] f32 (normalised)
    v_ref,  # VMEM [C, hb * V] f32
    a_ref,  # VMEM [C, hb * K] f32: the log decay, <= 0
    beta_ref,  # VMEM [C, hb] f32
    s0_ref,  # VMEM [hb, K, V] f32: the state the block starts from
    o_ref,  # VMEM out [C, hb * V] f32
    s_ref,  # VMEM out [hb, K, V] f32: the state, resident over the block's chunks
    g_ref,  # VMEM [hb, C, K] f32: the log decay summed from each sub-block's first token, for its single rows
    k_row_ref,  # VMEM [hb, C, K] f32: a head's keys on lanes of their own (a row at a traced index wants lane 0)
    z_ref,  # VMEM [hb, C, C] f32: the diagonal blocks' inverses as they are eliminated
):
    hb, K, V = s_ref.shape
    C = q_ref.shape[0]
    nb = C // SUB
    c = pl.program_id(1)

    @pl.when(lax.eq(c, 0))
    def _start():
        s_ref[...] = s0_ref[...]

    i32 = jnp.int32
    row, col = (lax.broadcasted_iota(i32, (C, C), d) for d in (0, 1))
    shift = lax.full_like(row, SUB.bit_length() - 1)
    own_off = lax.sub(col, lax.mul(lax.shift_right_logical(row, shift), lax.full_like(row, SUB)))  # a key's offset in the query's own sub-block
    eye = _pick(lax.eq(col, row), lax.full((C, C), 1.0, _F32))
    strict = lax.lt(col, row)
    krow, kcol = (lax.broadcasted_iota(i32, (K, K), d) for d in (0, 1))
    eye_k = _pick(lax.eq(krow, kcol), lax.full((K, K), 1.0, _F32))
    tok = lax.broadcasted_iota(i32, (C, 1), 0)
    keep = lax.lt(tok, lax.full((C, 1), lax.sub(n_ref[0], lax.mul(c, C)), i32))
    in_sub = lax.bitwise_and(lax.broadcasted_iota(i32, (C, K), 0), lax.full((C, K), SUB - 1, i32))
    in_sub1 = lax.bitwise_and(tok, lax.full_like(tok, SUB - 1))
    zeros_sub = lax.full((SUB, C), 0.0, _F32)

    def head(h):  # a head's lanes of the key-wide and the value-wide blocks
        return slice(h * K, (h + 1) * K), slice(h * V, (h + 1) * V)

    # 1. a head's running log decay and its earlier sub-blocks, through the query's sub-block's first token
    gs, g_ends, betas, mats = [], [], [], []
    for h in range(hb):
        kl, _ = head(h)
        q, k = q_ref[:, kl], k_ref[:, kl]
        # <= 0 and falling; from each sub-block's first token (``own_g``: log2(SUB) shifted adds, four roundings a token,
        # and a sub-block's differences never see the chunk's magnitude) and from the chunk's (``g``)
        own_g = _pick(_lanes(keep, K), a_ref[:, kl])
        for step in (1 << i for i in range(SUB.bit_length() - 1)):
            own_g = lax.add(own_g, _pick(lax.ge(in_sub, lax.full_like(in_sub, step)), pltpu.roll(own_g, step, 0)))
        g_ref[h], k_row_ref[h] = own_g, k
        before = [lax.full((1, K), 0.0, _F32)]  # the sub-blocks before each one, and at last the whole chunk: [1, K]
        for i in range(nb):
            before.append(lax.add(before[-1], g_ref[h, (i + 1) * SUB - 1 : (i + 1) * SUB, :]))
        g = lax.add(own_g, lax.concatenate([_rows(b, SUB) for b in before[:nb]], 0))
        a_rows, b_rows = [zeros_sub], [zeros_sub]
        for i in range(1, nb):
            lo = i * SUB
            first = lax.slice(g, (lo, 0), (lo + 1, K))
            own = lax.exp(lax.sub(lax.slice(own_g, (lo, 0), (lo + SUB, K)), _rows(lax.slice(own_g, (lo, 0), (lo + 1, K)), SUB)))
            seen = lax.mul(lax.slice(k, (0, 0), (lo, K)), lax.exp(lax.sub(_rows(first, lo), lax.slice(g, (0, 0), (lo, K)))))
            seen = lax.concatenate([seen, lax.full((C - lo, K), 0.0, _F32)], 0)
            mine = [lax.mul(lax.slice(t, (lo, 0), (lo + SUB, K)), own) for t in (k, q)]
            e = _dot(lax.concatenate(mine, 0), seen, ((1,), (1,)))  # [2 SUB, C]
            a_rows.append(lax.slice(e, (0, 0), (SUB, C)))
            b_rows.append(lax.slice(e, (SUB, 0), (2 * SUB, C)))
        mats.append((lax.concatenate(a_rows, 0), lax.concatenate(b_rows, 0)))
        z_ref[h] = eye
        gs.append(g)
        g_ends.append(before[nb])
        betas.append(_pick(keep, beta_ref[:, h : h + 1]))  # [C, 1]

    # 2. the own sub-block, one key offset j at a time: exp(G_t - G_j) for t >= j; and with column j of the diagonal
    # blocks of tril(beta A, -1) in hand, step j of their elimination: (I + m)^-1 of the four blocks at once, a column a
    # step. ONE traced body for the 16 offsets (a launch site's equations are a quarter of the walk written out 16
    # times), the heads side by side in it; unrolled in the lowering: as a rolled loop each offset waits on the one
    # before it and a chunk takes twice as long on the chip (PERF.md, PR 48).
    def offset(j, mats):
        mats = list(mats)

        def sub_rows(row_at):  # row j of every sub-block over the sub-block's rows: [C, W]
            return lax.concatenate([_rows(row_at(pl.ds(lax.add(j, i * SUB), 1)), SUB) for i in range(nb)], 0)

        at = lax.eq(own_off, lax.full((C, C), j, i32))
        for h in range(hb):
            kl, _ = head(h)
            own_g = g_ref[h]
            gj, kj = sub_rows(lambda r: g_ref[h, r, :]), sub_rows(lambda r: k_row_ref[h, r, :])
            later = lax.ge(in_sub, lax.full((C, K), j, i32))
            e = lax.mul(lax.exp(lax.select(later, lax.sub(own_g, gj), lax.full_like(own_g, -jnp.inf))), kj)
            ca, cb = _row_sums(lax.mul(k_ref[:, kl], e)), _row_sums(lax.mul(q_ref[:, kl], e))
            mats[h] = (lax.select(at, _lanes(ca, C), mats[h][0]), lax.select(at, _lanes(cb, C), mats[h][1]))
            below = lax.mul(_pick(lax.gt(in_sub1, lax.full((C, 1), j, i32)), ca), betas[h])
            z_ref[h] = lax.sub(z_ref[h], lax.mul(_lanes(below, C), sub_rows(lambda r: z_ref[h, r, :])))
        return tuple(mats)

    mats = lax.fori_loop(0, SUB, offset, tuple(mats), unroll=True)

    # 3. the inverse doubled, inv([[A, 0], [B, D]]) = Z - Z [[0, 0], [B, 0]] Z; the writes, the reads, the state
    for h in range(hb):
        kl, vl = head(h)
        q, k, v, g, beta = q_ref[:, kl], k_ref[:, kl], v_ref[:, vl], gs[h], betas[h]
        m = lax.mul(_pick(strict, mats[h][0]), _lanes(beta, C))
        z = z_ref[h]
        size = SUB
        while size < C:
            sh = lax.full_like(row, size.bit_length() - 1)
            rb, cb_ = lax.shift_right_logical(row, sh), lax.shift_right_logical(col, sh)
            odd = lax.eq(lax.bitwise_and(rb, lax.full_like(rb, 1)), lax.full_like(rb, 1))
            left = lax.eq(lax.add(cb_, lax.full_like(cb_, 1)), rb)
            z = lax.sub(z, _dot(_dot(z, _pick(lax.bitwise_and(odd, left), m)), z))
            size *= 2
        eg = lax.exp(g)
        w0 = _dot(z, lax.mul(v, _lanes(beta, V)))  # the writes, had the chunk begun at zero
        k_cum = _dot(z, lax.mul(lax.mul(k, _lanes(beta, K)), eg))
        s = s_ref[h]
        read = _dot(lax.concatenate([k_cum, lax.mul(q, eg)], 0), s)  # [2 C, V]
        w = lax.sub(w0, lax.slice(read, (0, 0), (C, V)))
        o_ref[:, vl] = lax.add(lax.slice(read, (C, 0), (2 * C, V)), _dot(mats[h][1], w))
        k_out = lax.mul(k, lax.exp(lax.sub(_rows(g_ends[h], C), g)))  # writes as the chunk's end sees them
        decay = _row_sums(lax.mul(eye_k, _rows(lax.exp(g_ends[h]), K)))  # [K, 1]
        s_ref[h] = lax.add(lax.mul(s, _lanes(decay, V)), _dot(k_out, w, ((0,), (0,))))


@functools.partial(jax.jit, static_argnames=("heads_per_step", "interpret"))
def kda_prompt_scan(
    q: jax.Array,  # [L, H, K] f32, L2-normalised and scaled
    k: jax.Array,  # [L, H, K] f32, L2-normalised
    v: jax.Array,  # [L, H, V] f32
    a: jax.Array,  # [L, H, K] f32: the log decay, <= 0
    beta: jax.Array,  # [L, H] f32: the write strength
    n_state,  # scalar: only the first n_state tokens enter the state
    s0: jax.Array | None = None,  # [H, K, V] f32 (default zero)
    *,
    heads_per_step: int = HEADS_PER_STEP,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """``kda_chunked_scan``'s arguments and results under one launch:
    (state after min(n_state, L) tokens [H, K, V] float32, o [L, H, V]
    float32; ``o`` past ``n_state`` is not the model's). ``L`` that is not
    whole chunks is padded with zeros here (a prompt pass's block is whole).
    Jitted, so that a program's launch sites (a run of ``kda`` layers each)
    and the prompt buckets, whose blocks have one shape, share ONE trace."""
    L, H, K = q.shape
    V = v.shape[-1]
    C = CHUNK
    hb = heads_per_step
    while H % hb:
        hb //= 2
    pad = (-L) % C
    flat = [t.astype(_F32).reshape(L, -1) for t in (q, k, v, a, beta)]
    if pad:
        flat = [jnp.pad(t, ((0, pad), (0, 0))) for t in flat]
    q2, k2, v2, a2, beta2 = flat
    Lp = L + pad
    beta3 = jnp.swapaxes(beta2.reshape(Lp, H // hb, hb), 0, 1)  # [groups, Lp, hb]
    s0 = jnp.zeros((H, K, V), _F32) if s0 is None else s0.astype(_F32)
    n = jnp.minimum(jnp.asarray(n_state, jnp.int32), L).reshape(1)
    wide = lambda w: pl.BlockSpec((C, hb * w), lambda g, c, n: (c, g))  # noqa: E731
    state = pl.BlockSpec((hb, K, V), lambda g, c, n: (g, 0, 0))
    o, s = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[wide(K), wide(K), wide(V), wide(K), pl.BlockSpec((None, C, hb), lambda g, c, n: (g, c, 0)), state],
            out_specs=[wide(V), state],
            grid=(H // hb, Lp // C),
            scratch_shapes=(pltpu.VMEM((hb, C, K), _F32), pltpu.VMEM((hb, C, K), _F32), pltpu.VMEM((hb, C, C), _F32)),
        ),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        out_shape=(jax.ShapeDtypeStruct((Lp, H * V), _F32), jax.ShapeDtypeStruct((H, K, V), _F32)),
        name="kda_prompt_scan",
        interpret=interpret,
    )(n, q2, k2, v2, a2, beta3, s0)
    return s, o[:L].reshape(L, H, V)
