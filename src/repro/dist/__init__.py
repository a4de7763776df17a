# Data-axis placement for the engine's sharded execute_many waves: the
# batch axis over the mesh's data axes, the catalog replicated.
from repro.dist.sharding import (
    batch_sharding,
    data_axis_size,
    pick_data_axes,
    replicated_sharding,
)

__all__ = ["pick_data_axes", "data_axis_size", "batch_sharding",
           "replicated_sharding"]
