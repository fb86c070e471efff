"""The serving harness of the family model tests (``tests/test_<family>_model.py``):
a fresh paged cache with its page table, the engine's prefill program into
slots of it, and the decode step every such test walks, each written once.

The decode step and the prefill are ONE ``jax.jit`` a (configuration, page
size, path), the whole prompt pass one a configuration, kept for the life of the worker process: thirty decode steps
trace once, and a second test at the same configuration traces nothing.
Weights, cache, table and the live mask are arguments, so tests with other
seeds, other slots and other tables share the program. A test of the kernel
path patches the kernels' entries to ``interpret=True`` before its first call,
as ever: off a TPU nothing else runs them, so the program traced under the
patch is the only one that path has here. A test that patches anything else
a trace reads (a spy on a launch, ``jax.default_backend``) jits a step of its
own: a kept program must not carry one test's patches into the next.

A new family's model test goes through this file; a new family's
described-v5e compile is a row of ``FAMILIES`` in tests/test_tpu_compile.py.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.inference import paged_kv
from areal_tpu.models import hybrid

PAD = 7  # a prompt's padding is real tokens: only the masks keep it out


def fresh_cache(mcfg, slots: int, pages: int, page_size: int):
    """(a zeroed cache of ``slots`` slots over ``slots * pages`` pages and the
    trash page, the page table [slots, pages] as numpy: slot s holds pages
    1 + s * pages and on). A slot that holds no request gets its row zeroed by
    the caller, as the engine leaves it."""
    cache = paged_kv.init_paged_cache(mcfg, slots * pages + 1, page_size, slots=slots)
    return cache, 1 + np.arange(slots * pages, dtype=np.int32).reshape(slots, pages)


def with_counts(mcfg, cache: dict) -> dict:
    """The cache with the model's counters at zero beside it, as the engine's chunk carries them."""
    return {**cache, **{k: jnp.zeros(s, jnp.int32) for k, s in mcfg.count_shapes.items()}}


@functools.lru_cache(maxsize=None)
def prefill_program(mcfg, page_size: int):
    """The jitted ``hybrid.prefill_into_cache`` of a configuration and page size."""

    def prefill(params, cache, ids, plens, flat_pages, slots):
        return hybrid.prefill_into_cache(params, mcfg, cache, ids, plens, flat_pages, slots, page_size=page_size)

    return jax.jit(prefill)


@functools.lru_cache(maxsize=None)
def prefill_forward(mcfg):
    """The jitted prompt pass of a configuration: ``forward(params, ids [A, L],
    seg [A, L], n_state=None) -> (logits [A, L, V], what ``forward_prefill``
    returns beside the hidden rows)``. One trace a prompt shape; called
    eagerly the pass is a compile an operator."""

    def forward(params, ids, seg, n_state=None):
        hidden, *rest = hybrid.forward_prefill(params, mcfg, ids, seg, n_state=n_state)
        return hybrid.compute_logits(params, mcfg, hidden), rest

    return jax.jit(forward)


def program_logits(mcfg, params, ids) -> np.ndarray:
    """The program's logits [len(ids), V] float32 of one whole sequence through the prompt pass."""
    x = jnp.asarray(np.asarray(ids, np.int32))[None]
    return np.asarray(prefill_forward(mcfg)(params, x, jnp.ones_like(x))[0][0], np.float32)


@functools.lru_cache(maxsize=None)
def decode_step(mcfg, page_size: int, use_kernel: bool):
    """The jitted decode step of a configuration, page size and path:
    ``step(params, tokens, positions, cache, table, active) -> (logits [S, V],
    cache)``."""

    def step(params, tokens, positions, cache, table, active):
        hidden, cache = hybrid.forward_decode_paged(
            params, mcfg, tokens, positions, cache, table, page_size=page_size, active=active, use_kernel=use_kernel
        )
        return hybrid.compute_logits(params, mcfg, hidden), cache

    return jax.jit(step)


def prefill_into_slot(mcfg, params, cache, table, rows, bucket: int, page_size: int):
    """The cache after the engine's prefill program over ``rows``: [(slot,
    prompt ids)], each padded to ``bucket`` with real tokens. A slot past the
    table's last is a padding row: its pages are the trash page."""
    ids = np.full((len(rows), bucket), PAD, np.int32)
    for i, (_, prompt) in enumerate(rows):
        ids[i, : len(prompt)] = prompt
    n = bucket // page_size
    flat = np.concatenate([table[s, :n] if s < len(table) else np.zeros(n, np.int32) for s, _ in rows])
    return prefill_program(mcfg, page_size)(
        params, cache, jnp.asarray(ids), jnp.asarray([len(p) for _, p in rows], jnp.int32), jnp.asarray(flat),
        jnp.asarray([s for s, _ in rows], jnp.int32),
    )


def through_the_cache(mcfg, params, ids, n_prompt: int, bucket: int, slot: int = 1, slots: int = 3, use_kernel: bool = False, *, page_size: int):
    """Prefill ``ids[:n_prompt]`` padded to ``bucket`` into slot ``slot`` of a
    fresh cache, then decode the rest one token a step through the paged path.
    Returns (logits of every decode step [len(ids) - n_prompt + 1, V], the
    cache): decode feeds the prompt's last token first."""
    pages = max(-(-len(ids) // page_size), bucket // page_size)
    cache, table = fresh_cache(mcfg, slots, pages, page_size)
    cache = prefill_into_slot(mcfg, params, cache, table, [(slot, ids[:n_prompt])], bucket, page_size)
    step, table, active = decode_step(mcfg, page_size, use_kernel), jnp.asarray(table), jnp.arange(slots) == slot
    logits = []
    for t in range(n_prompt - 1, len(ids)):
        tokens = jnp.zeros((slots,), jnp.int32).at[slot].set(int(ids[t]))
        positions = jnp.zeros((slots,), jnp.int32).at[slot].set(t)
        out, cache = step(params, tokens, positions, cache, table, active)
        logits.append(np.asarray(out)[slot])
    return np.stack(logits), cache
