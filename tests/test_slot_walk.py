"""The live-slot walk (``ops/slot_walk.py``) under its three kernels, in
interpret mode on the CPU: what a slot gets must not depend on what else is in
the ring. A launch over ``n_live`` of 64 slots is held, with ``array_equal``
and no tolerance, to the SAME kernel launched once a slot with that slot alone
on the list: the live slots' new state and outputs, the dead slots' state (the
input's, bit for bit) and outputs (exact zeros). ``n_live`` runs over the
ring's edges: none, fewer slots than fetches ahead, as many, fewer than the
ring has buffers, more, and every slot. The compiled kernels at the published
sizes, and the ring each is given there, are in ``tests/test_tpu_compile.py``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.ops import slot_walk
from areal_tpu.ops.gdn_state_update import gdn_state_update_stacked, head_pack, pack_state
from areal_tpu.ops.kda_state_update import kda_state_update_stacked
from areal_tpu.ops.ssm_state_update import ssm_state_update_stacked

S, L, LAYER = 64, 2, 1


def _unit(t):
    return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)


def _ssm(key, dtype):
    H, P, N = 4, 8, 128
    ks = jax.random.split(key, 6)
    state = jax.random.normal(ks[0], (L, S, H, P, N)).astype(dtype)
    x, b, c = jax.random.normal(ks[1], (S, H, P)), jax.random.normal(ks[2], (S, 2, N)), jax.random.normal(ks[3], (S, 2, N))
    dt, a = jax.nn.softplus(jax.random.normal(ks[4], (S, H))), -jnp.exp(jax.random.normal(ks[5], (H,)))
    return state, lambda st, order, n: ssm_state_update_stacked(st, LAYER, x, b, c, dt, a, order, n, interpret=True)


def _gdn(key, dtype):
    H, K, V = 10, 8, 64  # two heads a tile: five tiles a slot
    ks = jax.random.split(key, 6)
    state = pack_state(jax.random.normal(ks[0], (L, S, H, K, V)), head_pack(H, V)).astype(dtype)
    q, k, v = _unit(jax.random.normal(ks[1], (S, H, K))), _unit(jax.random.normal(ks[2], (S, H, K))), jax.random.normal(ks[3], (S, H, V))
    alpha, beta = jnp.exp(-jax.random.uniform(ks[4], (S, H))), 2.0 * jax.random.uniform(ks[5], (S, H))
    return state, lambda st, order, n: gdn_state_update_stacked(st, LAYER, q, k, v, alpha, beta, order, n, interpret=True)


def _kda(key, dtype):
    H, K, V = 4, 8, 128
    ks = jax.random.split(key, 6)
    state = jax.random.normal(ks[0], (L, S, H, K, V)).astype(dtype)
    q, k, v = _unit(jax.random.normal(ks[1], (S, H, K))), _unit(jax.random.normal(ks[2], (S, H, K))), jax.random.normal(ks[3], (S, H, V))
    decay, beta = jnp.exp(-jax.random.uniform(ks[4], (S, H, K))), jax.random.uniform(ks[5], (S, H))
    return state, lambda st, order, n: kda_state_update_stacked(st, LAYER, q, k, v, decay, beta, order, n, interpret=True)


@functools.lru_cache(maxsize=None)
def _case(kernel, dtype):
    """(the stacked state, the jitted launch over (state, order, n_live)): one
    trace a kernel and dtype, whatever the list holds."""
    state, launch = {"ssm": _ssm, "gdn": _gdn, "kda": _kda}[kernel](jax.random.PRNGKey(7), dtype)
    return state, jax.jit(launch)


def _listed_first(live):
    """``live`` in the order given, then every other slot: a launch's ``order``."""
    rest = [s for s in range(S) if s not in set(live)]
    return jnp.asarray(list(live) + rest, jnp.int32)


def _held_to_one_slot_launches(kernel, dtype, live):
    state, launch = _case(kernel, dtype)
    new, out = (np.asarray(t, np.float32) for t in launch(state, _listed_first(live), len(live)))
    old = np.asarray(state, np.float32)
    dead = np.asarray([s for s in range(S) if s not in set(live)], int)
    assert np.array_equal(new[LAYER, dead], old[LAYER, dead]) and not out[dead].any()
    assert np.array_equal(np.delete(new, LAYER, 0), np.delete(old, LAYER, 0))  # the other layers
    for s in live:
        alone_new, alone_out = (np.asarray(t, np.float32) for t in launch(state, _listed_first([s]), 1))
        assert np.array_equal(new[LAYER, s], alone_new[LAYER, s]), (kernel, s)
        assert np.array_equal(out[s], alone_out[s]), (kernel, s)
        assert not np.array_equal(new[LAYER, s], old[LAYER, s])  # and it was advanced


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n_live", [0, 1, 2, 3, 4, 64])
@pytest.mark.parametrize("kernel", ["ssm", "gdn", "kda"])
def test_a_slot_gets_what_it_gets_alone(kernel, n_live, dtype):
    live = [int(s) for s in np.random.default_rng(n_live).permutation(S)[:n_live]]
    _held_to_one_slot_launches(kernel, dtype, live)


@pytest.mark.parametrize("kernel", ["ssm", "gdn", "kda"])
def test_slots_listed_in_descending_order(kernel):
    """A store of slot ``order[t]`` runs two trips behind a fetch of
    ``order[t + 2]``, a lower slot: nine slots, the ring lapped once."""
    _held_to_one_slot_launches(kernel, "float32", [61, 60, 47, 33, 32, 20, 9, 5, 0])


@pytest.mark.parametrize(
    "slot_shape,dtype,mib",
    [
        ((64, 64, 128), "float32", 8),  # cell 4's ssm: 2 MiB a buffer
        ((15, 96, 384), "float32", 8.4375),  # cell 6's gdn
        ((64, 128, 128), "float32", 16),  # cell 10's kda
        ((64, 128, 128), "bfloat16", 8),
        ((4, 8, 64), "float32", 0.0625),  # 64 lanes held as 128
        ((4, 8, 128), "bfloat16", 0.0625),  # 8 rows of bfloat16 held as a tile's 16
    ],
)
def test_ring_scratch_and_its_bytes(slot_shape, dtype, mib):
    buf, isem, osem = slot_walk.ring_scratch(slot_shape, dtype)
    assert slot_walk.RING >= slot_walk.AHEAD + 2  # a store behind the slot that is computed
    assert buf.shape == (slot_walk.RING, *slot_shape) and isem.shape == osem.shape == (slot_walk.RING,)
    assert slot_walk.ring_bytes(slot_shape, dtype) == mib * 2**20
