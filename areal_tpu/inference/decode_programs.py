"""The decode engine's jitted programs: which exist, under which key, and
which of them a start-up warms.

One of the three owners of what serves a request (docs/serving.md):
``decode_engine.DecodeEngine`` schedules passes and asks this module for
callables, ``slot_cache.SlotCache`` keeps the pages they read and write.
``DecodePrograms`` is built from the model's module and configuration, the
shape fields of ``ServerConfig`` and the mesh, and from nothing else: it
knows no request, no task, no page list and no prefix tree, and what a
program is handed (weights, the paged cache, a page table, the slot state)
is the caller's to own and to thread through the donated arguments.

- **the programs**: ``prefill`` (a group of prompts into their pages),
  its suffix-only form over cached prefix pages, ``chunk`` (n decode steps
  for all slots; for a block-diffusion model n PASSES over every slot's
  block in flight, under the same name and key: ``_block_chunk``), ``spec`` (one speculative verify-and-accept round),
  ``apply`` / ``clamp`` (slot-state scatters), ``copy_pages`` (a GRPO
  group's private pages) and the vision tower. Each is a plain ``jax.jit``
  callable under its Python name, which is the name a device trace shows
  (``jit_chunk``, ``jit_prefill``, ``jit_spec``: PERF.md section 3), cached
  under a key whose first entry is its kind.
- **the sampler** every program that emits tokens shares (``_sample_step``).
  The passes a step makes over its [S, V] float32 logits: the ``lm_head``
  matmul writes them (the temperature divide fused in), the sampler reads
  them once for its blocks' statistics and then one block a row
  (``_inverse_cdf_at``: two launches of ``ops/vocab_block_stats.py`` on one
  TPU; elsewhere ``jnp`` over a padded copy, a pass more); a top-k / top-p
  slot adds ``lax.top_k``'s pass and a greedy slot ``argmax``'s, both
  compiled out of the programs no such slot runs in.
- **kernel or gather**: decided once, from the platform, the mesh and the
  shapes (``use_kernel``), with the suffix kernel's override for a
  kernel-against-XLA comparison.
- **the warm set**: ``warm_keys()`` lists the key of every program the
  serving loop can reach, hot loop first, and ``lower()`` lowers one of
  them from abstract arguments; ``DecodeEngine.precompile()`` compiles them
  in that order inside its budget.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.api.config import ServerConfig
from areal_tpu.inference import paged_kv
from areal_tpu.utils import logging as alog
from areal_tpu.utils import compile_cache
from areal_tpu.utils.data import round_up_to_bucket

# one component, one logger: the three owners log as the decode engine
logger = alog.getLogger("decode_engine")

MAX_STOP = 8  # stop-token-id slots per request (padded with -1)
UPDATE_COLS = 11 + MAX_STOP  # columns of a packed slot-update row (``update_fn``)
BLOCK_COLS = 4  # and, for a block model, after them: positions a pass, the rule, its threshold, clean positions; then the block's ids
TOPK_CAP = 1024  # static candidate-set size for per-slot top-k/top-p
PREFILL_SIZES = (8, 4, 2, 1)  # batched-prefill group sizes (compile variants)
# bytes of residual stream (rows x bucket x hidden) a prefill program may hold:
# every group size at 8 x 1,024 tokens of hidden 4,096; ONE row a program
# where a prompt alone is past it (4,096 tokens of hidden 6,144 are 50 MB),
# since a long prompt amortises the weights by itself and a second row would
# only add its temporaries
_PREFILL_STREAM_BYTES = 64 << 20


def slot_state(n_slots: int, block_length: int = 1) -> dict[str, np.ndarray]:
    """The per-slot decode state every program reads and hands back, all
    slots idle: the host mirror's first value, and (put on the device) the
    ``state`` argument of ``chunk``, ``spec``, ``apply`` and ``clamp``.

    A block model (``block_length`` > 1) adds the slot's block in flight,
    which lives here and in no page until it is clean: ``pos`` is then the
    block's first position, ``blk_ids`` its committed ids, ``blk_masked`` the
    flags of the positions not committed yet (a FLAG, never ``id ==
    mask_token_id``: that id is an ordinary token too), and for each
    committed position the pass of the block that committed it (-1: a prompt
    token), its candidate's log-probability and the weights of that pass;
    ``blk_n`` counts the block's denoise passes so far. ``blk_k``,
    ``blk_rule`` and ``blk_thresh`` are the request's: positions a counted
    rule commits a pass, the rule (index into ``REMASKING_RULES``) and the
    dynamic rule's threshold. The host mirrors none of the ``blk_`` leaves
    after admission."""
    S, B = n_slots, block_length
    block = {} if B == 1 else {
        "blk_ids": np.zeros((S, B), np.int32),
        "blk_masked": np.zeros((S, B), bool),
        "blk_pass": np.zeros((S, B), np.int32),
        "blk_logp": np.zeros((S, B), np.float32),
        "blk_ver": np.zeros((S, B), np.int32),
        "blk_n": np.zeros(S, np.int32),
        "blk_k": np.ones(S, np.int32),
        "blk_rule": np.zeros(S, np.int32),
        "blk_thresh": np.ones(S, np.float32),
    }
    return {
        **block,
        "ids": np.zeros(S, np.int32),
        "pos": np.zeros(S, np.int32),
        "active": np.zeros(S, bool),
        "remaining": np.zeros(S, np.int32),
        "temp": np.ones(S, np.float32),
        "greedy": np.zeros(S, bool),
        "top_k": np.full(S, -1, np.int32),
        "top_p": np.ones(S, np.float32),
        # stop tokens are honored only once remaining - 1 <= min_rem
        # (the -1 accounts for the token being emitted), i.e. after
        # gconfig.min_new_tokens tokens have been generated
        "min_rem": np.zeros(S, np.int32),
        "freq_pen": np.zeros(S, np.float32),
        "stop_ids": np.full((S, MAX_STOP), -1, np.int32),
    }


def pack_row(
    state: dict[str, np.ndarray],
    slot: int,
    last_id: int,
    pos: int,
    active: bool,
    remaining: int,
    top_k: int = -1,
    greedy: bool = False,
    temp: float = 1.0,
    top_p: float = 1.0,
    stops: list[int] | None = None,
    min_rem: int | None = None,
    freq_pen: float = 0.0,
    block: tuple[int, int, float, list[int]] | None = None,
) -> np.ndarray:
    """The ONE place that knows the packed scatter-row column order (it
    matches ``apply`` of ``update_fn``): update the host mirror ``state`` and
    build the fp32 row. ``min_rem``: stops fire only once remaining-1 <=
    min_rem (the min_new_tokens gate); default = remaining, i.e. always
    allowed. A block model's row ends with ``block``: (positions a pass, the
    rule's index, its threshold, the clean ids the slot's first block starts
    with: the prompt's tokens past its last whole block); ``pos`` is then that
    block's first position."""
    stops = (list(stops or []) + [-1] * MAX_STOP)[:MAX_STOP]
    if min_rem is None:
        min_rem = remaining
    st = state
    st["ids"][slot] = last_id
    st["pos"][slot] = pos
    st["active"][slot] = active
    st["remaining"][slot] = remaining
    st["temp"][slot] = temp
    st["greedy"][slot] = greedy
    st["top_k"][slot] = top_k
    st["top_p"][slot] = top_p
    st["min_rem"][slot] = min_rem
    st["freq_pen"][slot] = freq_pen
    st["stop_ids"][slot] = stops
    tail: list = []
    if "blk_ids" in st:
        B = st["blk_ids"].shape[1]
        k, rule, thresh, clean = block or (1, 0, 1.0, [])
        tail = [k, rule, thresh, len(clean), *clean, *[0] * (B - len(clean))]
    return np.asarray(
        [slot, last_id, pos, active, remaining, top_k, greedy, temp, top_p, min_rem, freq_pen, *stops, *tail],
        np.float32,
    )


def _pow2(n: int) -> int:
    """The smallest power of two that is at least ``n``."""
    p = 1
    while p < n:
        p *= 2
    return p


def _inverse_cdf_sample(scaled, rng, use_kernel: bool = False):
    """Exact categorical sampling with ONE uniform per row: ``_inverse_cdf_at``
    at a uniform drawn from ``rng``."""
    u = jax.random.uniform(rng, (scaled.shape[0], 1), jnp.float32)
    return _inverse_cdf_at(scaled, u, use_kernel)


def _inverse_cdf_at(scaled, u, use_kernel: bool = False):
    """The token at quantile ``u`` [S, 1] of each row's softmax, in ONE pass
    over the [S, V] float32 logits and one read of a block a row.

    ``jax.random.categorical`` materializes gumbel noise for every vocab
    entry — [S, 152k] of threefry bits per decode step, measured ~9 ms of
    an 11 ms step at S=128 on v5e. The round-3 flat inverse-CDF replaced
    that with ``cumsum`` over [S, V] fp32 — which XLA lowers to ~log2(V)
    full-array passes (~2.5 GB of HBM traffic at S=128), nearly as slow.

    This version factorizes the CDF over blocks of ``W`` whole lane tiles
    (``ops/vocab_block_stats.py``; ``W`` follows V alone, the last block is
    partial):
      1. each block's maximum and sum of exponentials, a row: the one pass
         that reads all of ``scaled``, as the ``lm_head`` matmul laid it out
         (``use_kernel``: the ``vocab_block_stats`` launch; else the same
         blocks in ``jnp`` over a padded copy, for the CPU and a sharded
         vocabulary). Until PR 46 the blocks were a divisor of V wide, and a
         reshape to them cost the TPU two more passes and a relayout
      2. tiny cumsum over the blocks' masses picks the block
      3. the chosen block's W logits are the only other read of ``scaled``:
         S x W values, through the ``vocab_block_pick`` launch (XLA's
         gathers of a window of the row are an element gather or a loop over
         the rows), else a row of that padded copy; a partial last block's
         columns past V are masked
      4. inside the block the same step twice, over its lane tiles' sums and
         then over the chosen tile's 128 entries (one cumsum over W lanes is
         itself several passes over [S, W])
    The draw is exact (CDF decomposition): every level hands the position
    inside the entry it chose down as the next level's uniform
    (``_quantile_index``). Returns (ids [S], logp [S], lse [S, 1]) with logp
    the exact log-softmax of the drawn token."""
    # (imported here as the paged kernels are in ``DecodePrograms``: the module brings Pallas in)
    from areal_tpu.ops import vocab_block_stats

    S, V = scaled.shape
    W = vocab_block_stats.block_width(V)
    if use_kernel:
        stats, pick = vocab_block_stats.vocab_block_stats, vocab_block_stats.vocab_block_pick
    else:
        stats, pick = vocab_block_stats.vocab_block_stats_xla, vocab_block_stats.vocab_block_pick_xla
    bmax, bsum = stats(scaled)
    # [S, 128]: a lane a block, the lanes past the last block without mass
    row_max = jnp.max(bmax, axis=-1, keepdims=True)
    row_max = jnp.where(jnp.isneginf(row_max), 0.0, row_max)
    bmass = bsum * jnp.exp(bmax - row_max)
    lse = jnp.log(jnp.sum(bmass, axis=-1, keepdims=True)) + row_max
    b, u_in = _quantile_index(bmass, u[:, 0])
    blk = pick(scaled, b)  # [S, W]
    blk_max = jnp.take_along_axis(bmax, b[:, None], axis=-1)
    in_row = (b * W)[:, None] + jnp.arange(W, dtype=jnp.int32)[None] < V  # a partial last block's columns
    tiles = jnp.where(in_row, jnp.exp(blk - blk_max), 0.0).reshape(S, W // _LANES, _LANES)
    t, u_in = _quantile_index(tiles.sum(-1), u_in)
    i, _ = _quantile_index(jnp.take_along_axis(tiles, t[:, None, None], axis=1)[:, 0], u_in)
    idx = t * _LANES + i
    logp = (jnp.take_along_axis(blk, idx[:, None], axis=-1) - lse)[:, 0]
    return b * W + idx, logp, lse


_LANES = 128  # entries of the sampler's innermost level: one lane tile of a block


def _quantile_index(mass, u):
    """One level of the inverse CDF. ``mass`` [S, n] >= 0, ``u`` [S] in
    [0, 1): (the entry of each row that the quantile ``u`` of the row's mass
    falls in, where inside that entry it falls, in [0, 1]). The uniform is
    scaled by the realized total, so fp32 cumsum undershoot spreads
    proportionally instead of piling on the last index, and the entry is
    never one after the last that adds mass."""
    cum = jnp.cumsum(mass, axis=-1)
    total = cum[:, -1:]
    target = u[:, None] * total
    idx = jnp.minimum(
        jnp.sum(cum <= target, axis=-1, dtype=jnp.int32), jnp.sum(cum < total, axis=-1, dtype=jnp.int32)
    )
    below = jnp.where(idx > 0, jnp.take_along_axis(cum, jnp.maximum(idx - 1, 0)[:, None], axis=-1)[:, 0], 0.0)
    here = jnp.take_along_axis(mass, idx[:, None], axis=-1)[:, 0]
    return idx, (target[:, 0] - below) / jnp.maximum(here, 1e-30)


def _sample_step(logits, rng, state, capped: bool, greedy_any: bool = True, use_kernel: bool = False):
    """One sampling step. logits [S, V] fp32; all sampling knobs are
    *per-slot arrays* in ``state`` (temp, greedy, top_k, top_p) so one
    request's config can never leak into another slot (round-1 correctness
    bug: engine-global top_k/top_p compiled into the chunk).

    ``capped`` and ``greedy_any`` are static flags: when no active slot
    filters (resp. decodes greedily), the top-k candidate machinery (resp.
    the full-vocab argmax pass — a [S, V] fp32 HBM read per step) is
    compiled out entirely. ``use_kernel``: the sampler's pass over the
    logits is the compiled ``vocab_block_stats`` launch."""
    V = logits.shape[-1]
    temp, greedy = state["temp"], state["greedy"]
    safe_t = jnp.maximum(temp, 1e-6)[:, None]
    scaled = logits / safe_t
    rng_full, rng_cap = jax.random.split(rng)
    sampled, samp_logp, lse = _inverse_cdf_sample(scaled, rng_full, use_kernel)
    use_cap = None
    if capped:
        K = min(V, TOPK_CAP)
        top_vals, top_idx = jax.lax.top_k(scaled, K)  # sorted desc, [S, K]
        eff_k = jnp.where(state["top_k"] > 0, state["top_k"], V)
        mask_k = jnp.arange(K)[None, :] < eff_k[:, None]
        probs = jax.nn.softmax(top_vals, axis=-1)
        cum_excl = jnp.cumsum(probs, axis=-1) - probs
        mask_p = cum_excl < state["top_p"][:, None]
        keep = (mask_k & mask_p).at[:, 0].set(True)
        cap_logits = jnp.where(keep, top_vals, -1e30)
        cap_pos = jax.random.categorical(rng_cap, cap_logits, axis=-1)
        cap_ids = jnp.take_along_axis(top_idx, cap_pos[:, None], axis=-1)[:, 0]
        cap_logp = jnp.take_along_axis(
            jax.nn.log_softmax(cap_logits, axis=-1), cap_pos[:, None], axis=-1
        )[:, 0]
        use_cap = (state["top_k"] > 0) | (state["top_p"] < 1.0)
        sampled = jnp.where(use_cap, cap_ids, sampled)
    if greedy_any:
        arg = jnp.argmax(logits, axis=-1)
        next_ids = jnp.where(greedy, arg, sampled).astype(jnp.int32)
        greedy_logp = (
            jnp.take_along_axis(scaled, arg[:, None], axis=-1) - lse
        )[:, 0]
        logp = jnp.where(greedy, greedy_logp, samp_logp)
    else:
        next_ids = sampled.astype(jnp.int32)
        logp = samp_logp
    if capped:
        logp = jnp.where(use_cap & ~greedy, cap_logp, logp)
    return next_ids, logp


def select_positions(masked, logp, k, rule, thresh):
    """Which masked positions of each slot's block a denoise pass commits
    (``models/qwen.py REMASKING_RULES``, by index in ``rule`` [S]):
    ``sequential`` the first ``k`` [S] masked positions in sequence order;
    ``low_confidence_static`` the ``k`` whose candidates are the most probable
    (``logp`` [S, B], the candidate's log-probability under the request's
    temperature; ties to the earlier position); ``low_confidence_dynamic``
    every one whose candidate's probability is over ``thresh`` [S], and the
    most probable one whatever it is. ``masked`` [S, B] bool; fewer than
    ``k`` masked positions are all taken. Returns [S, B] bool."""
    B = masked.shape[1]
    at = jnp.arange(B)
    by_place = jnp.cumsum(masked, axis=-1) - 1  # rank among the masked, in sequence order
    conf = jnp.where(masked, logp, -jnp.inf)
    ahead = (conf[:, None, :] > conf[:, :, None]) | ((conf[:, None, :] == conf[:, :, None]) & (at[None, None, :] < at[None, :, None]))
    by_conf = jnp.sum(ahead & masked[:, None, :], axis=-1)  # masked positions that go before this one
    counted = jnp.where((rule == 0)[:, None], by_place, by_conf) < k[:, None]
    dynamic = (jnp.exp(logp) > thresh[:, None]) | (by_conf == 0)
    return masked & jnp.where((rule == 2)[:, None], dynamic, counted)


class DecodePrograms:
    """The jitted programs of one model replica and the cache they live in."""

    def __init__(self, model, model_cfg, config: ServerConfig, mesh, store: compile_cache.ProgramStore | None = None):
        self.model = model  # the module of model_cfg's family (models.family_of)
        self.model_cfg = model_cfg
        # read for its shape fields only: slots, context, page size, steps a
        # call, the window step, and whether the penalised chunk variants exist
        self.config = config
        self.mesh = mesh
        self._fn_cache: dict[tuple, Callable] = {}
        # where a program's first call finds its executable, or leaves it
        # (utils/compile_cache.py: off everywhere but on a TPU)
        self.store = compile_cache.default_store() if store is None else store
        psz = config.page_size
        self._maxp = -(-config.max_seq_len // psz)  # pages per sequence (ceil)
        # positions a slot's decode step covers: 1, or a block model's block
        # (models/qwen.py ``block_length``), whose blocks lie whole inside a page
        self.block = int(getattr(model_cfg, "block_length", 1))
        if self.block > 1 and (psz % self.block or config.max_seq_len % self.block):
            raise ValueError(f"page_size {psz} and max_seq_len {config.max_seq_len} must be whole blocks of {self.block} positions")
        self.update_cols = UPDATE_COLS + (BLOCK_COLS + self.block if self.block > 1 else 0)
        # the Pallas paged kernels run single-device; under TP the engine
        # takes the gather+einsum path, which GSPMD shards over the KV-head
        # axis like the dense engine did. Kernel or gather is decided HERE,
        # once, from the platform, the mesh and the shapes: on a TPU the
        # kernels are compiled, and one the chip's compiler refuses is an
        # error — nothing catches it, falls back to interpret mode or
        # swaps in the XLA path.
        # (imported here, inside ``areal.setup.engine_init``: the kernels'
        # module brings Pallas in, a second of start-up that span has always held)
        from areal_tpu.ops.paged_attention_q8 import paged_kernel_ok

        kv_quant = paged_kv.page_quant(config.kv_quantization)
        one_tpu = (
            jax.default_backend() == "tpu"
            and int(np.prod(list(mesh.shape.values()))) == 1
        )
        shapes_ok = paged_kernel_ok(model_cfg.kv_head_dim, psz, bool(kv_quant))
        if one_tpu and not shapes_ok:
            logger.warning(
                f"head_dim {model_cfg.kv_head_dim} / page_size {psz} / kv "
                f"{kv_quant or 'bf16'} is outside the Pallas paged kernels' "
                "tiling (ops/paged_attention_q8.py paged_kernel_ok): "
                "decode, suffix prefill and verify take the gather path"
            )
        self.use_kernel = one_tpu and shapes_ok
        # the sampler's pass over the logits (ops/vocab_block_stats.py) is
        # compiled where those kernels are, whatever the heads' shapes
        self.sample_kernel = one_tpu
        # suffix-prefill / tree-verify Pallas kernel
        # (ops/paged_suffix_attention.py): same condition, overridable at
        # runtime for kernel-vs-XLA A/B (off-TPU the kernel runs in
        # interpret mode)
        self._suffix_kernel_override: bool | None = None

    def keys(self) -> set[tuple]:
        """The keys of the programs built so far."""
        return set(self._fn_cache)

    @property
    def chunk_ahead(self) -> int:
        """Positions past a slot's own that one chunk can write: a step each;
        for a block model a block every second pass (a block takes a denoise
        pass and a commit pass at least) and the one a chunk may find clean."""
        n = self.config.decode_steps_per_call
        return n if self.block == 1 else (n // 2 + 1) * self.block

    def _first_call(self, key: tuple) -> compile_cache.FirstCall:
        """The program just cached under ``key`` for its first call, with what
        the store names it by: everything the builders close over (the
        model's module and configuration, the shape fields of the server's,
        the mesh, which kernels are compiled) and the key. Weights, cache and
        slot state are ARGUMENTS of every program: one closed over would be a
        constant of its executable, and the store refuses such a program."""
        cfg = self.config
        closed_over = (
            self.model, self.model_cfg, self.mesh, self.use_kernel, self.sample_kernel,
            cfg.max_batch_size, cfg.max_seq_len, cfg.page_size, cfg.decode_steps_per_call, cfg.attn_window_step,
            cfg.enable_frequency_penalty, cfg.kv_quantization, key,
        )
        return compile_cache.FirstCall(self._fn_cache, key, self.store, compile_cache.describe(closed_over))

    # prompt buckets above this warm only if on the round_up_to_bucket
    # 2^k/3*2^k series — the exact-reachable set at T=32K would otherwise be
    # every 256-multiple (512 prefill programs; a ~10x startup blowup).
    # Buckets outside the warmed set still work; they compile on first hit.
    _WARM_DENSE_CAP = 4096

    def prefill_sizes(self, bucket: int) -> tuple[int, ...]:
        """The group sizes a prefill program of this bucket comes in: those of
        ``PREFILL_SIZES`` whose rows' widest activations (the residual stream,
        or what the model's module says a row holds: ``prefill_row_bytes``)
        stay inside ``_PREFILL_STREAM_BYTES``, at least (1,)."""
        row = self.model.prefill_row_bytes(self.model_cfg, bucket)
        return tuple(a for a in PREFILL_SIZES if a * row <= _PREFILL_STREAM_BYTES) or (1,)

    # -- the shape rules: each is what the caller asks at run time AND what
    # the reachable set below it enumerates, so the two cannot drift apart
    def prompt_bucket(self, n_tokens: int) -> int:
        """The length a prefill program is compiled for, for a prompt (or a
        suffix behind a cached prefix) of ``n_tokens``."""
        return min(self.config.max_seq_len, round_up_to_bucket(n_tokens, 256))

    def window_pages(self, max_pos: int, ahead: int) -> int:
        """Page-table columns of the attention window that covers position
        ``max_pos`` and ``ahead`` more writes, bucketed by
        ``attn_window_step`` (a chunk: two chunks of steps, since the host's
        positions can be one in-flight chunk stale; a speculative round: its
        nodes)."""
        cfg = self.config
        window = min(
            cfg.max_seq_len,
            round_up_to_bucket(max_pos + 1 + ahead, cfg.attn_window_step),
        )
        return min(self._maxp, -(-window // cfg.page_size))

    def scatter_rows(self, n: int) -> int:
        """Rows of the slot-state scatter that takes ``n`` updates."""
        return min(_pow2(n), self.config.max_batch_size)

    def reachable_prompt_buckets(self) -> list[int]:
        """Values ``prompt_bucket`` can produce for the admission path
        (round-2 warmed linear multiples instead — compiling unreachable
        programs while missing the 3*2^k series and the T-cap; ADVICE r02
        #1), dense up to ``_WARM_DENSE_CAP`` then the sparse series tail
        only."""
        T = self.config.max_seq_len
        exact = {
            self.prompt_bucket(n)
            for n in range(1, max(2, min(T - 1, self._WARM_DENSE_CAP)))
        }
        b = self._WARM_DENSE_CAP
        while b < T:
            exact.add(self.prompt_bucket(b + 1))
            b *= 2
        exact.add(self.prompt_bucket(max(1, T - 2)))
        return sorted(exact)

    def reachable_chunk_wps(self) -> list[int]:
        """Window page counts a decode chunk can ask for — exact up to
        ``_WARM_DENSE_CAP`` rows, then the sparse bucket-series tail."""
        T = self.config.max_seq_len
        ahead = 2 * self.chunk_ahead
        wps = {self.window_pages(p, ahead) for p in range(min(T, self._WARM_DENSE_CAP))}
        b = self._WARM_DENSE_CAP
        while b < T:
            wps.add(self.window_pages(b, ahead))
            b *= 2
        wps.add(self.window_pages(T - 1, ahead))
        return sorted(wps)

    def reachable_scatter_sizes(self) -> list[int]:
        """Exact set of row counts ``scatter_rows`` produces: powers of two
        up to S, plus S itself when S is not a power of two."""
        S = self.config.max_batch_size
        return sorted({self.scatter_rows(n) for n in range(1, S + 1)})

    def prefill_fn(self, n_prompts: int, bucket: int, with_images: bool = False):
        """Batched prefill: A prompts (padded to ``bucket``) in one forward,
        their KV scattered into the A rows' pages and, for a model with
        recurrent layers, each row's post-prompt state into its slot (what
        exactly: ``prefill_into_cache`` of the model's family). Amortises
        the full-parameter read across admits.
        ``with_images`` adds a positioned [A, bucket, D] vision-embed input
        (VLM serving; embeds computed by _image_embeds_for at admission)."""
        key = ("prefill", n_prompts, bucket, with_images)
        if key not in self._fn_cache:
            mcfg = self.model_cfg
            psz = self.config.page_size
            model = self.model

            def prefill(params, cache, ids, plens, flat_pages, slots, img=None):
                # ids [A, bucket], plens [A], flat_pages [A * bucket/psz],
                # slots [A] (a padding row: one past the last slot)
                return model.prefill_into_cache(
                    params, mcfg, cache, ids, plens, flat_pages, slots,
                    page_size=psz, image_embeds=img,
                )

            self._fn_cache[key] = jax.jit(prefill, donate_argnames=("cache",))
            return self._first_call(key)
        return self._fn_cache[key]

    def prefill_paged_fn(self, n_prompts: int, bucket: int, wp: int):
        """Suffix-only prefill over a radix-cached prefix: A suffixes
        (padded to ``bucket``) in one forward, queries attending over each
        row's cached prefix pages (``wp`` page-table columns) plus the
        causal suffix; suffix KV scatters into fresh pages. The prefix
        pages are read-only (aliased, possibly shared across requests)."""
        use_kernel = self.suffix_kernel()
        key = ("prefill_sfx", n_prompts, bucket, wp, use_kernel)
        if key not in self._fn_cache:
            mcfg = self.model_cfg
            psz = self.config.page_size
            def prefill(params, cache, ids, plens, offs, flat_pages, ppt):
                # ids [A, bucket] suffix tokens; plens [A] suffix lengths;
                # offs [A] absolute start positions — page-aligned, so they
                # double as the cached-prefix lengths; ppt [A, wp] prefix
                # page table
                positions = offs[:, None] + jnp.arange(bucket, dtype=jnp.int32)[None]
                seg = (
                    jnp.arange(bucket, dtype=jnp.int32)[None] < plens[:, None]
                ).astype(jnp.int32)
                _, ks, vs = self.model.forward_prefill_paged(
                    params, mcfg, ids, positions, seg, cache, ppt, offs,
                    use_kernel=use_kernel,
                )
                with jax.named_scope("kv_write"):
                    return paged_kv.scatter_prefill(cache, ks, vs, flat_pages, psz)

            self._fn_cache[key] = jax.jit(prefill, donate_argnames=("cache",))
            return self._first_call(key)
        return self._fn_cache[key]

    def chunk_fn(
        self,
        n_steps: int,
        wp: int,
        capped: bool,
        greedy_any: bool = True,
        freq_any: bool = False,
    ):
        """n_steps of decode for all slots in one jitted call, attending over
        each slot's first ``wp`` KV pages (the window, bucketed in pages).

        Returns (cache, state, rng, packed) where ``packed`` is ONE int32
        array [2*n_steps + 3, S] — token rows, logprob-bit rows (fp32
        bitcast), then emit_count / final-active / final-pos rows — so the
        host pays a single device->host transfer per chunk. A model with
        sparse experts or delta-rule layers appends its counts of the chunk
        (``model_cfg.count_shapes``, flat, in whole rows of S). Emission is
        monotone within a chunk (a stopped slot never re-activates; admits
        happen between chunks), so per-slot counts fully describe the
        emit mask."""
        key = ("chunk", n_steps, wp, capped, greedy_any, freq_any)
        if key not in self._fn_cache and self.block > 1:
            assert not freq_any, "a block model serves no frequency penalty (models/qwen.py serving_limits)"
            self._fn_cache[key] = jax.jit(self._block_chunk(n_steps, wp, capped, greedy_any), donate_argnames=("cache", "state"))
            return self._first_call(key)
        if key not in self._fn_cache:
            mcfg = self.model_cfg
            T = self.config.max_seq_len
            psz = self.config.page_size
            use_kernel = self.use_kernel
            sample_kernel = self.sample_kernel
            model = self.model

            counts_of = dict(mcfg.count_shapes)

            def chunk(params, cache, page_table, state, rng):
                # the model's counts of this chunk's steps: zeroed here, added
                # to by the model's forward for the active slots only, and
                # handed back in ``packed`` (they are no part of the cache)
                cache = {**cache, **{k: jnp.zeros(shp, jnp.int32) for k, shp in counts_of.items()}}

                def step(carry, _):
                    ids, pos, active, remaining, counts, cache, rng = carry
                    hidden, cache = model.forward_decode_paged(
                        params,
                        mcfg,
                        ids,
                        pos,
                        cache,
                        page_table,
                        page_size=psz,
                        active=active,
                        use_kernel=use_kernel,
                    )
                    with jax.named_scope("lm_head"):
                        logits = model.compute_logits(params, mcfg, hidden)
                    with jax.named_scope("sampler"):
                        if freq_any:
                            # OpenAI-style frequency penalty on raw logits,
                            # proportional to this slot's generated-token counts
                            logits = logits - (
                                state["freq_pen"][:, None]
                                * counts.astype(jnp.float32)
                            )
                        rng, sub = jax.random.split(rng)
                        next_ids, logp = _sample_step(
                            logits, sub, state, capped, greedy_any, sample_kernel
                        )
                    if freq_any:
                        # saturating (uint16 .add would wrap at 65535 —
                        # reachable at max_seq_len > 64k, and negative
                        # penalties actively drive repeats toward it)
                        sl = jnp.arange(counts.shape[0])
                        cur = counts[sl, next_ids].astype(jnp.int32)
                        counts = counts.at[sl, next_ids].set(
                            jnp.minimum(
                                cur + active.astype(jnp.int32), 65535
                            ).astype(counts.dtype)
                        )
                    emitted = active
                    hit_stop = jnp.any(
                        next_ids[:, None] == state["stop_ids"], axis=-1
                    ) & (remaining - 1 <= state["min_rem"])
                    new_pos = pos + 1
                    remaining = remaining - active.astype(jnp.int32)
                    still = (
                        active
                        & ~hit_stop
                        & (remaining > 0)
                        & (new_pos < T - 1)
                    )
                    ids = jnp.where(active, next_ids, ids)
                    pos = jnp.where(active, new_pos, pos)
                    return (ids, pos, still, remaining, counts, cache, rng), (
                        next_ids,
                        logp,
                        emitted,
                    )

                carry = (
                    state["ids"],
                    state["pos"],
                    state["active"],
                    state["remaining"],
                    state["freq_counts"] if freq_any else jnp.zeros((), jnp.uint16),
                    cache,
                    rng,
                )
                (ids, pos, active, remaining, counts, cache, rng), (
                    toks,
                    logps,
                    emit,
                ) = jax.lax.scan(step, carry, None, length=n_steps)
                out_state = dict(state)
                out_state.update(ids=ids, pos=pos, active=active, remaining=remaining)
                if freq_any:
                    out_state["freq_counts"] = counts
                cache = dict(cache)
                extra = [cache.pop(k).reshape(-1) for k in counts_of]
                if extra:  # after the slots' rows, flat, padded to whole rows
                    flat = jnp.concatenate(extra)
                    S = toks.shape[1]
                    extra = [jnp.pad(flat, (0, -flat.size % S)).reshape(-1, S)]
                packed = jnp.concatenate(
                    [
                        toks.astype(jnp.int32),  # [n_steps, S]
                        jax.lax.bitcast_convert_type(
                            logps.astype(jnp.float32), jnp.int32
                        ),  # [n_steps, S]
                        emit.sum(0, dtype=jnp.int32)[None],  # emit_count [1, S]
                        active.astype(jnp.int32)[None],  # [1, S]
                        pos.astype(jnp.int32)[None],  # [1, S]
                        *extra,
                    ],
                    axis=0,
                )
                return cache, out_state, rng, packed

            self._fn_cache[key] = jax.jit(chunk, donate_argnames=("cache", "state"))
            return self._first_call(key)
        return self._fn_cache[key]

    def _block_chunk(self, n_steps: int, wp: int, capped: bool, greedy_any: bool):
        """The chunk program of a block-diffusion model: ``n_steps`` PASSES
        for all slots, each over a slot's current block of B positions
        (``model.forward_block_paged``), the slots at whatever phase of their
        blocks they are at.

        One pass: a slot whose block still has masked positions DENOISES: the
        head and the sampler give a candidate and its log-probability at
        every masked position FROM THAT POSITION's own row (no shift), and
        the request's rule commits some of them (``block_select``): they are
        clean from then on, with the pass's number, log-probability and
        weights kept beside them. A slot whose block has no masked position
        COMMITS: its rows' keys and values go to its pages, the block's
        generated tokens are emitted in sequence order (none after a stop
        token), and the next block starts, all masked. A request's last block
        is cut to its budget: the positions past the cut stay masked through
        every pass and are never committed, so every state a token was
        committed in can be told from the reply. Nothing of a block reaches a page before that pass. (A
        committing slot's rows pass the head and the sampler too and are
        discarded: one program, static shapes.)

        ``program(params, cache, page_table, state, rng, version)``;
        ``packed`` is int32 [4 * n_steps * B + n_steps + 2, S]: a pass's B
        token rows, the emitted ones first, then as many rows each of
        log-probability bits, commit passes and weight versions, the count a
        slot emitted in each pass, final-active and final-pos, then the
        model's counts as in the token chunk."""
        mcfg, model = self.model_cfg, self.model
        T, psz, B = self.config.max_seq_len, self.config.page_size, self.block
        use_kernel, sample_kernel = self.suffix_kernel(), self.sample_kernel
        counts_of = dict(mcfg.count_shapes)
        offs = jnp.arange(B, dtype=jnp.int32)
        from areal_tpu.ops.paged_attention_q8 import live_order

        def chunk(params, cache, page_table, state, rng, version):
            S = state["pos"].shape[0]
            rows_of = {k: jnp.repeat(state[k], B) for k in ("temp", "greedy", "top_k", "top_p")}  # the sampler's, a row
            fetched = partial(model.block_attn_tokens_fetched, wp=wp, page_size=psz, use_kernel=use_kernel)

            def one_pass(carry, _):
                pos, active, remaining, bids, bmask, bpass, blogp, bver, bn, counts, cache, rng = carry
                # the positions the request's budget still reaches: a last block is CUT to it, and what lies past the
                # cut holds the mask through every pass of the block, the commit pass too, and is never committed
                first = jnp.sum(bpass < 0, axis=-1, dtype=jnp.int32)  # a prompt's tail lies before the generated ones
                open_ = bmask & (offs[None] < (first + remaining)[:, None])
                masked_any = open_.any(-1)
                denoising, committing = active & masked_any, active & ~masked_any
                start = jnp.minimum(pos, T - B)  # an idle slot's stale position stays inside the table
                ids_in = jnp.where(bmask, mcfg.mask_token_id, bids)
                hidden, ks, vs, *loads = model.forward_block_paged(
                    params, mcfg, ids_in, start, active, cache, page_table, use_kernel=use_kernel
                )
                read = fetched(jnp.where(active, start, 0))  # what that forward's launches fetched, a layer and KV head
                with jax.named_scope("lm_head"):
                    logits = model.compute_logits(params, mcfg, hidden.reshape(S * B, -1))
                with jax.named_scope("sampler"):
                    rng, sub = jax.random.split(rng)
                    cand, logp = _sample_step(logits, sub, rows_of, capped, greedy_any, sample_kernel)
                with jax.named_scope("block_select"):
                    cand, logp = cand.reshape(S, B), logp.reshape(S, B)
                    take = select_positions(open_, logp, state["blk_k"], state["blk_rule"], state["blk_thresh"])
                    take = take & denoising[:, None]
                    bids = jnp.where(take, cand, bids)
                    bpass = jnp.where(take, bn[:, None], bpass)
                    blogp = jnp.where(take, logp, blogp)
                    bver = jnp.where(take, version, bver)
                    bmask = bmask & ~take
                    bn = bn + denoising.astype(jnp.int32)
                    # a clean block's generated tokens, in sequence order from the first one, and none after a stop
                    # token that may fire
                    order = jnp.minimum(offs[None] + first[:, None], B - 1)
                    etok, elogp, epass, ever = (jnp.take_along_axis(x, order, axis=1) for x in (bids, blogp, bpass, bver))
                    room = jnp.minimum(B - first, remaining)
                    stops = jnp.any(etok[:, :, None] == state["stop_ids"][:, None, :], axis=-1) & (
                        remaining[:, None] - offs[None] - 1 <= state["min_rem"][:, None]
                    ) & (offs[None] < room[:, None])
                    n_emit = jnp.where(stops.any(-1), jnp.argmax(stops, axis=-1).astype(jnp.int32) + 1, room)
                    n_emit = jnp.where(committing, n_emit, 0)
                with jax.named_scope("kv_write"):
                    # a clean block's rows into its pages, a layer and a position of the block at a time: the decode
                    # step's writer (one row a slot a launch; the committing slots alone on the kernel path, every
                    # other slot's rows to the trash page on the scatter path). ONE scatter over [S x B] rows and all
                    # KV heads re-lays the whole pool out and copies it twice a pass (PERF.md, PR 58: 2.8 of 8.7 s)
                    at = start[:, None] + offs[None]
                    pages = jnp.take_along_axis(page_table, jnp.clip(at // psz, 0, wp - 1), axis=1)
                    pages, rows = jnp.where(committing[:, None], pages, 0), at % psz
                    kv_live = live_order(committing) if use_kernel else None

                    def write_layer(li, c):
                        for j in range(B):
                            c = paged_kv.write_decode_rows(c, li, ks[li, :, j], vs[li, :, j], pages[:, j], rows[:, j], kv_live)
                        return c

                    cache = jax.lax.fori_loop(0, ks.shape[0], write_layer, dict(cache))
                with jax.named_scope("block_select"):
                    remaining = remaining - n_emit
                    new_pos = pos + B
                    ends = stops.any(-1) | (remaining <= 0) | (new_pos + B > T)
                    active = active & ~(committing & ends)
                    fresh = committing[:, None]
                    pos = jnp.where(committing, new_pos, pos)
                    bmask = bmask | fresh
                    bids, bpass = jnp.where(fresh, 0, bids), jnp.where(fresh, 0, bpass)
                    bn = jnp.where(committing, 0, bn)
                    counts = dict(counts)
                    for name, n in (
                        ("blk_denoise_passes", jnp.sum(denoising, dtype=jnp.int32)),
                        ("blk_commit_passes", jnp.sum(committing, dtype=jnp.int32)),
                        ("blk_blocks", jnp.sum(n_emit > 0, dtype=jnp.int32)),
                        ("blk_attn_tokens_read", read),
                    ):
                        counts[name] = counts[name] + n
                    if loads:
                        counts["moe_load"] = counts["moe_load"] + loads[0]
                        counts["moe_touched"] = counts["moe_touched"] + jnp.sum(loads[0] > 0, axis=-1, dtype=jnp.int32)
                        counts["moe_streamed"] = counts["moe_streamed"] + mcfg.num_experts  # every form here reads them all
                return (pos, active, remaining, bids, bmask, bpass, blogp, bver, bn, counts, cache, rng), (
                    etok, elogp, epass, ever, n_emit,
                )

            carry = (
                state["pos"], state["active"], state["remaining"],
                state["blk_ids"], state["blk_masked"], state["blk_pass"], state["blk_logp"], state["blk_ver"], state["blk_n"],
                {k: jnp.zeros(shp, jnp.int32) for k, shp in counts_of.items()}, cache, rng,
            )
            (pos, active, remaining, bids, bmask, bpass, blogp, bver, bn, counts, cache, rng), (
                toks, logps, passes, vers, n_emit,
            ) = jax.lax.scan(one_pass, carry, None, length=n_steps)
            out_state = dict(state)
            out_state.update(
                pos=pos, active=active, remaining=remaining,
                blk_ids=bids, blk_masked=bmask, blk_pass=bpass, blk_logp=blogp, blk_ver=bver, blk_n=bn,
            )
            flat = jnp.concatenate([counts[k].reshape(-1) for k in counts_of])

            def rows(x):  # [n, S, B] -> a row a (pass, place)
                return jnp.transpose(x, (0, 2, 1)).reshape(n_steps * B, S)

            packed = jnp.concatenate(
                [
                    rows(toks.astype(jnp.int32)),
                    rows(jax.lax.bitcast_convert_type(logps.astype(jnp.float32), jnp.int32)),
                    rows(passes),
                    rows(vers),
                    n_emit,  # [n_steps, S]
                    active.astype(jnp.int32)[None],
                    pos.astype(jnp.int32)[None],
                    jnp.pad(flat, (0, -flat.size % S)).reshape(-1, S),
                ],
                axis=0,
            )
            return cache, out_state, rng, packed

        return chunk

    def spec_fn(self, B: int, wp: int, capped: bool, greedy_any: bool = True):
        """One speculative verify+accept round in a single jitted call.

        Row 0 per slot is the pending token, rows 1..B-1 the draft tree
        nodes. ``forward_verify_paged`` scores all B nodes at once; an
        unrolled accept walk then re-runs the TARGET sampler position by
        position and follows the tree edge whose draft token equals the
        sampled target — so every emitted token is exactly what the
        sequential path would have produced (greedy byte-identity; sampled
        slots draw from the true per-position conditional, the token-match
        form of speculative rejection sampling). KV is scattered
        row-granularly: only visited (accepted-path) rows land in real
        pages, everything else routes to trash page 0, so rejected drafts
        never exist in committed KV and radix publication stays safe.

        ``packed`` has the exact chunk_fn layout with n_steps = B, so the
        normal ``_drain`` bookkeeping credits the round unchanged."""
        use_kernel = self.suffix_kernel()
        key = ("spec", B, wp, capped, greedy_any, use_kernel)
        if key not in self._fn_cache:
            mcfg = self.model_cfg
            T = self.config.max_seq_len
            psz = self.config.page_size
            K = B - 1
            sample_kernel = self.sample_kernel

            def spec(params, cache, page_table, state, rng, drafts):
                d_tokens = drafts["tokens"]  # [S, K]
                d_parent = drafts["parent_row"]  # [S, K] row of parent
                d_depth = drafts["depth"]  # [S, K]
                d_mask = drafts["mask"]  # [S, B, B]
                d_count = drafts["n_draft"]  # [S]
                S = state["ids"].shape[0]
                pos0 = state["pos"]
                ids_nodes = jnp.concatenate(
                    [state["ids"][:, None], d_tokens], axis=1
                )  # [S, B]
                depth_full = jnp.concatenate(
                    [jnp.zeros((S, 1), jnp.int32), d_depth], axis=1
                )
                # clamp keeps gather/scatter indices in range for inactive
                # slots with stale pos; their page-table rows are zeroed so
                # everything lands in trash anyway
                positions = jnp.minimum(pos0[:, None] + depth_full, T - 1)
                hidden, ks, vs = self.model.forward_verify_paged(
                    params,
                    mcfg,
                    ids_nodes,
                    positions,
                    d_mask,
                    cache,
                    page_table,
                    pos0,
                    use_kernel=use_kernel,
                )
                with jax.named_scope("lm_head"):
                    logits = self.model.compute_logits(params, mcfg, hidden)  # [S,B,V]
                row_valid = (
                    jnp.arange(1, B, dtype=jnp.int32)[None, :]
                    <= d_count[:, None]
                )  # [S, K]
                cur = jnp.zeros((S,), jnp.int32)  # row the walk is at
                cont = state["active"]  # still emitting THIS round
                alive = state["active"]  # slot lives past the round
                pos_c = pos0
                rem_c = state["remaining"]
                ids_c = state["ids"]
                # rows whose KV becomes committed context = rows the walk
                # visits (root + accepted path); matches the sequential
                # path's write set exactly
                row_ok = jnp.zeros((S, B), bool).at[:, 0].set(True)
                toks_rows, logp_rows, emit_rows = [], [], []
                for j in range(B):
                    lg = jnp.take_along_axis(
                        logits, cur[:, None, None], axis=1
                    )[:, 0]  # [S, V]
                    with jax.named_scope("sampler"):
                        rng, sub = jax.random.split(rng)
                        t_j, logp_j = _sample_step(
                            lg, sub, state, capped, greedy_any, sample_kernel
                        )
                    emit_rows.append(cont)
                    toks_rows.append(t_j)
                    logp_rows.append(logp_j)
                    # exact chunk_fn stop/budget semantics per emitted step
                    hit_stop = jnp.any(
                        t_j[:, None] == state["stop_ids"], axis=-1
                    ) & (rem_c - 1 <= state["min_rem"])
                    new_pos = pos_c + cont.astype(jnp.int32)
                    rem_c = rem_c - cont.astype(jnp.int32)
                    step_alive = (
                        cont & ~hit_stop & (rem_c > 0) & (new_pos < T - 1)
                    )
                    alive = jnp.where(cont, step_alive, alive)
                    ids_c = jnp.where(cont, t_j, ids_c)
                    pos_c = new_pos
                    if j < K:
                        # follow the tree edge matching the target token
                        match = (
                            (d_parent == cur[:, None])
                            & (d_tokens == t_j[:, None])
                            & row_valid
                        )  # [S, K] over rows 1..K
                        has = match.any(axis=1)
                        child = jnp.argmax(match, axis=1).astype(jnp.int32) + 1
                        cont = step_alive & has
                        cur = jnp.where(cont, child, cur)
                        row_ok = row_ok | (
                            (jnp.arange(B)[None, :] == child[:, None])
                            & cont[:, None]
                        )
                out_state = dict(state)
                out_state.update(
                    ids=ids_c, pos=pos_c, active=alive, remaining=rem_c
                )
                # selective KV commit: visited rows -> their real page rows,
                # everything else -> trash page 0
                page_idx = jnp.clip(positions // psz, 0, wp - 1)
                pages = jnp.take_along_axis(page_table, page_idx, axis=1)
                pages = jnp.where(row_ok, pages, 0)
                rows = positions % psz
                L = ks.shape[0]
                KH, hd = ks.shape[3], ks.shape[4]
                with jax.named_scope("kv_write"):
                    cache = paged_kv.scatter_token_rows(
                        cache,
                        ks.reshape(L, S * B, KH, hd),
                        vs.reshape(L, S * B, KH, hd),
                        pages.reshape(-1),
                        rows.reshape(-1),
                    )
                packed = jnp.concatenate(
                    [
                        jnp.stack(toks_rows).astype(jnp.int32),  # [B, S]
                        jax.lax.bitcast_convert_type(
                            jnp.stack(logp_rows).astype(jnp.float32),
                            jnp.int32,
                        ),  # [B, S]
                        jnp.stack(emit_rows).sum(0, dtype=jnp.int32)[None],
                        alive.astype(jnp.int32)[None],
                        pos_c.astype(jnp.int32)[None],
                    ],
                    axis=0,
                )
                return cache, out_state, rng, packed

            self._fn_cache[key] = jax.jit(spec, donate_argnames=("cache", "state"))
            return self._first_call(key)
        return self._fn_cache[key]

    def update_fn(self, n: int):
        """Jitted slot-state scatter: one packed fp32 [n, 11+MAX_STOP] upload
        (columns: slot, ids, pos, active, remaining, top_k, greedy, temp,
        top_p, min_rem, freq_pen, stop_ids...) applied on device. All values fit fp32 exactly
        (token ids < 2^24). Padded rows repeat row 0 (idempotent scatter)."""
        key = ("upd", n)
        if key not in self._fn_cache:

            def apply(state, upd):
                sl = upd[:, 0].astype(jnp.int32)
                state = dict(state)
                state["ids"] = state["ids"].at[sl].set(upd[:, 1].astype(jnp.int32))
                state["pos"] = state["pos"].at[sl].set(upd[:, 2].astype(jnp.int32))
                state["active"] = state["active"].at[sl].set(upd[:, 3] > 0)
                state["remaining"] = (
                    state["remaining"].at[sl].set(upd[:, 4].astype(jnp.int32))
                )
                state["top_k"] = state["top_k"].at[sl].set(upd[:, 5].astype(jnp.int32))
                state["greedy"] = state["greedy"].at[sl].set(upd[:, 6] > 0)
                state["temp"] = state["temp"].at[sl].set(upd[:, 7])
                state["top_p"] = state["top_p"].at[sl].set(upd[:, 8])
                state["min_rem"] = (
                    state["min_rem"].at[sl].set(upd[:, 9].astype(jnp.int32))
                )
                state["freq_pen"] = state["freq_pen"].at[sl].set(upd[:, 10])
                if "freq_counts" in state:
                    # (re)admission resets the slot's repeat counts
                    state["freq_counts"] = state["freq_counts"].at[sl].set(0)
                state["stop_ids"] = (
                    state["stop_ids"].at[sl].set(upd[:, 11 : 11 + MAX_STOP].astype(jnp.int32))
                )
                if "blk_ids" in state:
                    # a block model's columns (``pack_row``): the slot starts a block with its first ``clean``
                    # positions the prompt's tail and the rest masked, before its first pass
                    blk = upd[:, UPDATE_COLS:]
                    B = state["blk_ids"].shape[1]
                    masked = jnp.arange(B)[None] >= blk[:, 3:4].astype(jnp.int32)
                    for name, val in (
                        ("blk_k", blk[:, 0].astype(jnp.int32)),
                        ("blk_rule", blk[:, 1].astype(jnp.int32)),
                        ("blk_thresh", blk[:, 2]),
                        ("blk_ids", blk[:, BLOCK_COLS:].astype(jnp.int32)),
                        ("blk_masked", masked),
                        ("blk_pass", jnp.where(masked, 0, -1)),
                        ("blk_logp", jnp.zeros(masked.shape, jnp.float32)),
                        ("blk_ver", jnp.zeros(masked.shape, jnp.int32)),
                        ("blk_n", jnp.zeros(masked.shape[0], jnp.int32)),
                    ):
                        state[name] = state[name].at[sl].set(val)
                return state

            self._fn_cache[key] = jax.jit(apply, donate_argnames=("state",))
            return self._first_call(key)
        return self._fn_cache[key]

    def pagecopy_fn(self, n: int):
        """Jitted copy of n (page, slot-state) pairs: the pages and the
        recurrent state a GRPO group's siblings share with their primary."""
        key = ("pagecopy", n)
        if key not in self._fn_cache:
            self._fn_cache[key] = jax.jit(
                paged_kv.copy_pages, donate_argnames=("cache",)
            )
            return self._first_call(key)
        return self._fn_cache[key]

    def clamp_fn(self, n: int):
        """Jitted remaining-only scatter: remaining := min(remaining, cap)
        for n (slot, cap) rows, touching nothing else (pos/ids stay
        device-authoritative)."""
        key = ("clamp", n)
        if key not in self._fn_cache:

            def clamp(state, upd):
                sl = upd[:, 0]
                cap = upd[:, 1]
                state = dict(state)
                old_rem = state["remaining"][sl]
                new_rem = jnp.minimum(old_rem, cap)
                state["remaining"] = state["remaining"].at[sl].set(new_rem)
                # keep the min_new_tokens gate invariant: "tokens still
                # needed before stops unlock" (= remaining - min_rem) must
                # survive the budget clamp, or stops would fire immediately
                new_min = jnp.maximum(
                    0, state["min_rem"][sl] - (old_rem - new_rem)
                )
                state["min_rem"] = state["min_rem"].at[sl].set(new_min)
                state["active"] = (
                    state["active"].at[sl].set(state["active"][sl] & (new_rem > 0))
                )
                return state

            self._fn_cache[key] = jax.jit(clamp, donate_argnames=("state",))
            return self._first_call(key)
        return self._fn_cache[key]

    def suffix_kernel(self) -> bool:
        """Whether suffix-prefill / tree-verify runs the Pallas kernel."""
        if self._suffix_kernel_override is not None:
            return self._suffix_kernel_override
        return self.use_kernel

    def attention_impl(self, speculative: bool) -> dict[str, str]:
        """Which attention implementation each serving path uses (and which
        writer the chunk program puts a decode step's KV rows with) — logged
        once at start-up and read by chip_smoke.py; ``speculative`` is whether
        the scheduler runs verify rounds at all. ``pallas`` is the
        compiled TPU kernel, ``pallas-interpret`` the same body under the
        Pallas interpreter (off-TPU, kernel forced on), ``xla`` the
        gather + einsum path."""
        tpu = jax.default_backend() == "tpu"
        kern = "pallas" if tpu else "pallas-interpret"
        return {
            "decode": kern if self.use_kernel else "xla",
            # a decode step's KV rows: no choice of its own, it goes with
            # ``decode`` (ops/paged_kv_write.py beside the decode kernel, per-head
            # scatters beside the gather path; prefill and verify always scatter)
            "kv_write": kern if self.use_kernel else "xla",
            # cold prefill is plain causal attention over the prompt bucket: XLA's, but for the latent-attention
            # layers of a prompt of 1,024 tokens or more on a TPU (ops/latent_prefill_attention.py)
            "prefill": kern if self.model.prefill_attn_launch(self.model_cfg, 1024) else "xla",
            "suffix_prefill": kern if self.suffix_kernel() else "xla",
            "verify": (
                "off"
                if not speculative
                else kern
                if self.suffix_kernel()
                else "xla"
            ),
        }

    def set_suffix_kernel(self, on: bool | None) -> None:
        """Force the paged suffix-attention kernel on/off (None restores
        the platform default). Used by chip_smoke's kernel-vs-XLA check; takes
        effect on the next compiled prefill/verify fn (the fn-cache key
        carries the flag, so both variants can coexist warm)."""
        self._suffix_kernel_override = on

    def vision_fn(self, n_patches: int):
        """The vision tower over one image's ``n_patches`` patches."""
        key = ("vision", n_patches)
        if key not in self._fn_cache:
            from areal_tpu.models import vision as vis

            vcfg = self.model_cfg.vision
            self._fn_cache[key] = jax.jit(
                lambda vp, x, m, p: vis.vision_forward(vp, vcfg, x, m, p)
            )
        return self._fn_cache[key]

    # -- calls: host arrays in, a program and its padded arguments out -------
    # Each returns (program, args): the caller, which owns the device arrays
    # the program donates, makes the call itself, ``program(donated..., *args)``.
    # Not a convenience: a program's first call traces it, and jax lowers
    # every op's location from the Python stack of that moment. With ONE more
    # frame between the decode loop and the jitted call, lowering a prefill
    # program took three times as long on the chip (0.11 -> 0.31 s each, 16 of
    # them a start-up: PERF.md section 6, PR 42).
    def update_call(self, rows: list[np.ndarray]):
        """Scatter slot-update rows (``pack_row``) into the device state: one
        upload, one jitted execute, ``program(state, *args)``. Row count is
        bucketed (padding repeats row 0, an idempotent scatter) to bound
        compile variants."""
        n = self.scatter_rows(len(rows))
        upd = np.stack(rows + [rows[0]] * (n - len(rows)))
        return self.update_fn(n), (jnp.asarray(upd),)

    def clamp_call(self, rows: list[tuple[int, int]]):
        """Cap the remaining budget of (slot, cap) rows, ``program(state,
        *args)``. Padded rows repeat row 0 (idempotent: min with the same
        cap)."""
        n = _pow2(len(rows))
        upd = np.asarray(rows + [rows[0]] * (n - len(rows)), np.int32)
        return self.clamp_fn(n), (jnp.asarray(upd),)

    def pagecopy_call(self, dst: list[int], src: list[int], slot_dst: list[int], slot_src: list[int]):
        """Copy page ``src[i]`` to ``dst[i]`` and slot ``slot_src[i]``'s
        recurrent state to ``slot_dst[i]``, ``program(cache, *args)``.
        Padding repeats the first pair: the same copy twice."""
        n = _pow2(len(dst))
        pad = n - len(dst)
        pairs = tuple(jnp.asarray(np.asarray(x + x[:1] * pad, np.int32)) for x in (dst, src, slot_dst, slot_src))
        return self.pagecopy_fn(n), pairs

    def vision_call(self, px: np.ndarray, pos: np.ndarray):
        """One image's patches ``px`` [P, patch_dim] at rope positions
        ``pos`` [P, 2] through the vision tower, ``program(vision_params,
        *args)``. The patch count is bucketed: distinct image sizes must not
        each compile a fresh ViT (the mask handles the padding), by THE
        shared formula so serving and training embeds agree."""
        from areal_tpu.models.vision import pad_patch_bucket

        P = px.shape[0]
        Ppad = pad_patch_bucket(P, self.model_cfg.vision.spatial_merge**2)
        return self.vision_fn(Ppad), (
            jnp.asarray(np.pad(px, ((0, Ppad - P), (0, 0)))),
            jnp.asarray(np.arange(Ppad) < P),
            jnp.asarray(np.pad(pos, ((0, Ppad - P), (0, 0)))),
        )

    @staticmethod
    def _pad_group(ids, plens, page_rows):
        """Prefill group sizes are compiled variants: pad a group of A
        prompts to the smallest one that holds it, by rows of one token
        that scatter to the trash page. Returns the rows added too."""
        A = ids.shape[0]
        sizes = [a for a in PREFILL_SIZES if a >= A]
        pad = (min(sizes) if sizes else A) - A
        if pad:
            ids = np.pad(ids, ((0, pad), (0, 0)))
            ids[A:, 0] = 1
            plens = np.pad(plens, (0, pad), constant_values=1)
            page_rows = np.pad(page_rows, ((0, pad), (0, 0)))
        return pad, ids, plens, page_rows

    def prefill_call(self, ids, plens, page_rows, slots, img=None):
        """Prefill a group, ``program(params, cache, *args)``: ``ids`` [A,
        bucket] prompts, ``plens`` [A] their lengths, ``page_rows`` [A, pages
        a bucket] where each row's KV goes (0 = trash page past its prompt),
        ``slots`` [A], ``img`` positioned vision embeds or None. A padding
        row names the slot one past the last."""
        pad, ids, plens, page_rows = self._pad_group(ids, plens, page_rows)
        A, bucket = ids.shape
        if pad:
            slots = np.pad(slots, (0, pad), constant_values=self.config.max_batch_size)
            if img is not None:
                img = np.pad(img, ((0, pad), (0, 0), (0, 0)))
        args = (
            jnp.asarray(ids),
            jnp.asarray(plens),
            jnp.asarray(page_rows.reshape(-1)),
            jnp.asarray(slots),
        )
        if img is None:
            return self.prefill_fn(A, bucket), args
        return self.prefill_fn(A, bucket, with_images=True), (*args, jnp.asarray(img))

    def prefill_paged_call(self, ids, plens, offs, page_rows, prefix_pages: list[list[int]]):
        """Prefill a group's suffixes over their cached prefixes,
        ``program(params, cache, *args)``: ``ids`` [A, bucket] suffix tokens,
        ``plens`` [A] suffix lengths, ``offs`` [A] where each suffix starts,
        ``page_rows`` as in ``prefill_call``, ``prefix_pages`` each row's
        cached pages (the prefix page table's width compiles per power of
        two)."""
        pad, ids, plens, page_rows = self._pad_group(ids, plens, page_rows)
        A, bucket = ids.shape
        wp = _pow2(max(len(m) for m in prefix_pages))
        ppt = np.zeros((A, wp), np.int32)  # a padding row: no prefix
        for j, m in enumerate(prefix_pages):
            ppt[j, : len(m)] = m
        return self.prefill_paged_fn(A, bucket, wp), (
            jnp.asarray(ids),
            jnp.asarray(plens),
            jnp.asarray(np.pad(offs, (0, pad))),
            jnp.asarray(page_rows.reshape(-1)),
            jnp.asarray(ppt),
        )

    # -- the warm set ------------------------------------------------------
    def warm_keys(self, prompt_buckets: list[int] | None = None) -> list[tuple]:
        """The key of every program the serving loop can reach without a
        prefix-cache hit or a speculative round, hot loop first: decode
        chunks (reachable windows x the four (capped, greedy) variants, and
        the penalised ones where the configuration has them), then the
        slot-scatter, clamp and page-copy sizes, then the prefill programs
        (``PREFILL_SIZES`` group sizes x ``prompt_buckets``, by default
        every reachable one). The sets are derived from the shape rules
        themselves, so nothing unreachable is compiled and nothing
        reachable is missed.

        Suffix-only prefill variants (radix prefix-cache hits) are NOT in
        it: their (suffix bucket x prefix-table width) grid is
        workload-dependent, so they lazy-compile on first hit and land in
        the persistent cache — one admission-wave stall per shape, never a
        mid-decode stall. Nor is any speculative program (PERF.md section
        7)."""
        cfg = self.config
        if prompt_buckets is None:
            prompt_buckets = self.reachable_prompt_buckets()
        keys: list[tuple] = []
        freq_variants = (False, True) if cfg.enable_frequency_penalty else (False,)
        for wp in self.reachable_chunk_wps():
            for capped, greedy_any in (
                (False, False),  # the serving steady state (pure sampling)
                (False, True),
                (True, False),
                (True, True),
            ):
                for freq_any in freq_variants:
                    keys.append(
                        ("chunk", cfg.decode_steps_per_call, wp, capped, greedy_any, freq_any)
                    )
        for n in self.reachable_scatter_sizes():
            keys.append(("upd", n))
            keys.append(("clamp", n))
        # GRPO prefix-sharing page copies (dup counts pad to powers of two
        # up to next_pow2(S-1)) — a cold compile would stall all slots
        # mid-serving
        n = 1
        while True:
            keys.append(("pagecopy", n))
            if n >= max(1, cfg.max_batch_size - 1):
                break
            n *= 2
        for bucket in prompt_buckets:
            for A in self.prefill_sizes(bucket):
                keys.append(("prefill", A, bucket, False))
        return keys

    def _abstract_call(self, key: tuple, params_s, cache_s, state_s, rng_s):
        """(program, abstract arguments) of one of ``warm_keys()``: the
        arguments are the trees of ``jax.ShapeDtypeStruct`` the caller derives
        from its live weights, cache, slot state and rng, WITH their
        shardings (a program lowered from unplaced shapes is another program
        than the runtime call on committed arrays makes: it would be compiled
        twice, and stored under another name). Builds the program if the key
        is new."""
        cfg = self.config

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        kind, *rest = key
        if kind == "chunk":
            version = (i32(),) if self.block > 1 else ()  # as the page table: a host value, placed by the call
            return self.chunk_fn(*rest), (params_s, cache_s, i32(cfg.max_batch_size, rest[1]), state_s, rng_s, *version)
        if kind == "upd":
            (n,) = rest
            return self.update_fn(n), (state_s, jax.ShapeDtypeStruct((n, self.update_cols), jnp.float32))
        if kind == "clamp":
            (n,) = rest
            return self.clamp_fn(n), (state_s, i32(n, 2))
        if kind == "pagecopy":
            (n,) = rest
            return self.pagecopy_fn(n), (cache_s, *[i32(n)] * 4)
        if kind == "prefill":
            A, bucket, with_images = rest
            assert not with_images, key
            return self.prefill_fn(A, bucket), (
                params_s, cache_s, i32(A, bucket), i32(A), i32(A * -(-bucket // cfg.page_size)), i32(A),
            )
        raise KeyError(f"no start-up warms a program of kind {kind!r}")

    def lower(self, key: tuple, params_s, cache_s, state_s, rng_s):
        """Lower the program of one of ``warm_keys()`` from abstract arguments
        (``_abstract_call``): the jitted function's own ``lower``, for a look
        at the module or a compile for a described chip."""
        fn, args = self._abstract_call(key, params_s, cache_s, state_s, rng_s)
        return fn.lower(*args)

    def build(self, key: tuple, params_s, cache_s, state_s, rng_s) -> None:
        """Build the program of one of ``warm_keys()`` from abstract
        arguments, as its first call would and through the same function
        (``compile_cache.FirstCall``: the store's entry read, or the program
        traced, lowered, compiled and written), executing nothing. The
        runtime call then finds the loaded executable and traces nothing."""
        fn, args = self._abstract_call(key, params_s, cache_s, state_s, rng_s)
        if isinstance(fn, compile_cache.FirstCall):
            fn(*args)
