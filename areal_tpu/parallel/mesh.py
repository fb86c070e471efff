"""Device mesh construction + sharding helpers.

One GSPMD mesh replaces the reference's three per-backend parallel-dims
systems (FSDP DeviceMesh areal/engine/fsdp_utils/parallel.py:34-214, Megatron
mpu, Archon ParallelDims areal/experimental/models/archon/parallel_dims.py):

    axes = (data, fsdp, seq, model, expert)

- ``data``×``fsdp``: batch rows (DP); params ZeRO-3-shard over ``fsdp``
  (set fsdp=world, data=1 for pure FSDP; data>1 gives HSDP-style replication)
- ``seq``: sequence/context parallelism (Ulysses all-to-all inserted by XLA
  between seq- and head-sharded regions; ring attention via Pallas kernel)
- ``model``: tensor parallelism (TP all-reduces inserted by XLA)
- ``expert``: MoE expert parallelism

Collectives ride ICI within a pod; multi-host extends the same mesh over DCN
via jax.distributed (axis order puts ``model``/``seq`` innermost so their
collectives stay on ICI).
"""

from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from areal_tpu.api.config import MeshConfig

MESH_AXES = ("data", "fsdp", "seq", "model", "expert", "pipe")
BATCH_AXES = ("data", "fsdp")


def make_mesh(cfg: MeshConfig | None = None, devices=None) -> Mesh:
    """Build the 5-axis mesh. ``data == -1`` absorbs all remaining devices."""
    cfg = cfg or MeshConfig()
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    sizes = dict(
        data=cfg.data,
        fsdp=cfg.fsdp,
        seq=cfg.seq,
        model=cfg.model,
        expert=cfg.expert,
        pipe=getattr(cfg, "pipe", 1),
    )
    fixed = math.prod(v for v in sizes.values() if v != -1)
    wildcard = [k for k, v in sizes.items() if v == -1]
    if wildcard:
        assert len(wildcard) == 1, "at most one mesh axis may be -1"
        assert n % fixed == 0, (n, sizes)
        sizes[wildcard[0]] = n // fixed
    total = math.prod(sizes.values())
    assert total == n, f"mesh {sizes} needs {total} devices, have {n}"
    shape = tuple(sizes[a] for a in MESH_AXES)
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, MESH_AXES)


def batch_sharding(mesh: Mesh, extra: tuple = ()) -> NamedSharding:
    """Sharding for [G, L, ...] microbatch grids: rows over data×fsdp."""
    return NamedSharding(mesh, P(BATCH_AXES, *extra))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def dp_size(mesh: Mesh) -> int:
    return mesh.shape["data"] * mesh.shape["fsdp"]


def param_sharding(mesh: Mesh, spec_tree):
    """PartitionSpec tree -> NamedSharding tree."""
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_for_path(shardings: dict, path: str):
    """Walk a PartitionSpec/NamedSharding tree by a flat "a/b/c" param path
    (works for the stacked-layer text tree AND the nested vision tree)."""
    node = shardings
    for seg in path.split("/"):
        node = node[seg]
    return node
