"""MSH good fixture: collectives on declared axes (package MESH_AXES plus
a file-local pmap axis_name binding), out_specs matching the callee's
return structure, and constraints routed through the jax_compat shim."""

import jax
from jax.sharding import PartitionSpec as P

from jax import shard_map

from areal_tpu.utils.jax_compat import with_sharding_constraint


def body(x):
    y = jax.lax.psum(x, "model")
    y = jax.lax.all_gather(y, "data")
    return with_sharding_constraint(y, P("data"))


def two_outputs(x):
    return x, x


mapped = shard_map(
    two_outputs,
    mesh=None,
    in_specs=(P("data"),),
    out_specs=(P("data"), P(("data", "fsdp"))),
)


def locally_bound(x):
    # axis bound by this file's own pmap extends the vocabulary
    return jax.pmap(lambda v: jax.lax.pmean(v, "batch"), axis_name="batch")(x)
