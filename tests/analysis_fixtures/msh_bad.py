"""MSH bad fixture: a collective naming an axis outside the mesh
vocabulary (MSH001), shard_map out_specs drifted from the callee's return
structure (MSH002), and a raw with_sharding_constraint that dies at
lowering inside shard_map manual regions (MSH003)."""

import jax
from jax.sharding import PartitionSpec as P

from jax import shard_map


def body(x):
    y = jax.lax.psum(x, "modle")  # MSH001: typo of 'model'
    # MSH003: raw constraint — manualized axes reject it at lowering
    return jax.lax.with_sharding_constraint(y, P("data"))


def two_outputs(x):
    return x, x


mapped = shard_map(
    two_outputs,
    mesh=None,
    in_specs=(P("data"),),
    # MSH002: 3 specs, 2 returned values
    out_specs=(P("data"), P("data"), P("data")),
)
