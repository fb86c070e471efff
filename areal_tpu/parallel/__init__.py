from areal_tpu.parallel.mesh import (  # noqa: F401
    MESH_AXES,
    BATCH_AXES,
    make_mesh,
    batch_sharding,
    replicated,
)
