"""Device-mesh parallelism for the batch scheduler."""

from .sharded import (
    BATCH_AXIS,
    NODE_AXIS,
    make_node_mesh,
    sharded_fused_pass,
    sharded_placement_rounds,
    sharded_schedule_step,
)
