"""SHD good fixture: declared axes only, locally-declared ad-hoc mesh
axes, arity-matched shard_map, and a non-PartitionSpec P() helper that
must not be mistaken for a spec."""

import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

ROW = P("data", ("fsdp", "seq"), None)
FULL = P(("data", "fsdp"))

# a file may declare its own mesh: those axes are legitimate here
stage_mesh = Mesh(np.arange(4).reshape(4), ("stage",))
STAGED = P("stage")


def body(x, y):
    return x


mapped = shard_map(
    body,
    mesh=None,
    in_specs=(P("data"), P()),
    out_specs=P("data"),
)


def P_unrelated(a, b):  # noqa: N802 — deliberately spec-shaped name
    return a + b


# calls an unrelated helper whose name shadows nothing: the checker only
# follows names imported from jax.sharding.PartitionSpec
checksum = P_unrelated("not_an_axis", "also_not_an_axis")
