"""Device-mesh parallelism for the batch scheduler."""

from .sharded import (
    NODE_AXIS,
    make_node_mesh,
    sharded_fused_pass,
)
