"""SHD bad fixture: typo'd axes, duplicate axes, arity-mismatched
shard_map (checked against the package mesh axes from
parallel/mesh.py)."""

from jax.sharding import PartitionSpec as P

from jax import shard_map

ROW = P("data", "modle")  # SHD001: 'modle' is a typo of 'model'
DUP = P("model", ("model", None))  # SHD003: 'model' consumed twice


def body(x, y):
    return x


mapped = shard_map(
    body,
    mesh=None,
    in_specs=(P("data"),),  # SHD002: one spec, two arguments
    out_specs=P(),
)
