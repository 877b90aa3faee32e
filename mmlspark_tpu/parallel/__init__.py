from . import cluster
from .cluster import (ClusterInfo, Heartbeat, barrier, broadcast_from_leader,
                      global_array, initialize_cluster,
                      padded_process_rows, process_row_range)
from .mesh import (DATA_AXIS, MODEL_AXIS, PIPE_AXIS, SEQ_AXIS,
                   data_mesh, grid_mesh,
                   full_mesh, row_sharding, replicated, pad_to_multiple,
                   shard_rows, valid_row_mask, device_count)

__all__ = ["DATA_AXIS", "MODEL_AXIS", "PIPE_AXIS", "SEQ_AXIS",
           "ClusterInfo", "Heartbeat", "barrier",
           "broadcast_from_leader", "cluster", "data_mesh", "grid_mesh",
           "full_mesh", "global_array", "initialize_cluster",
           "pad_to_multiple", "padded_process_rows", "process_row_range",
           "replicated",
           "row_sharding", "shard_rows", "valid_row_mask", "device_count"]
