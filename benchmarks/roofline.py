"""The chip's peaks and the least work of one placement pass.

Peaks live in one table keyed by ``device_kind``; a device that is not in
the table is an error, not a default.  The work is reckoned from the
batch's shapes, not from the operations the current kernel happens to
run, so a rewrite of the kernel cannot move the yardstick.
"""
from __future__ import annotations

from typing import Dict

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM.
# The placement pass is float32/int32 vector work, for which no peak is
# published; the bf16 figure stands as the ceiling on operations, which
# only lowers the share.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
    "TPU v5e": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                "source": "cloud.google.com/tpu/docs/v5e"},
}

RESOURCE_DIMS = 4          # cpu, memory, disk, iops: the capacity row
SCORED_DIMS = 2            # cpu and memory enter ScoreFit
OPS_PER_NODE_SCORE = 2 * RESOURCE_DIMS + 8 * SCORED_DIMS + 4
# per spec and node: add + compare per dimension; per scored dimension a
# divide, a subtract and a power (exp and multiply counted as 6); the sum,
# the clip and the compare that ranks it.


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to benchmarks/roofline.py with its source") from None


def placement_work(nodes: int, specs: int, asks: int, rounds: int = 1
                   ) -> Dict[str, float]:
    """Least operations and bytes to place ``asks`` allocations of
    ``specs`` distinct task groups on ``nodes`` nodes in ``rounds``
    passes over the node table (a spec whose count exceeds the feasible
    nodes needs another pass).

    Every spec has to score every node once per round: read the node's
    capacity and usage rows (int32), write nothing.  Every allocation
    writes its node index and score (8 bytes) and reads and writes one
    usage row."""
    row = RESOURCE_DIMS * 4
    ops = float(specs) * nodes * rounds * OPS_PER_NODE_SCORE
    nbytes = (float(specs) * nodes * rounds * 2 * row
              + float(asks) * (8 + 2 * row))
    return {"ops": ops, "bytes": nbytes}


def least_seconds(work: Dict[str, float], device_kind: str) -> Dict[str, float]:
    pk = peaks(device_kind)
    t_ops = work["ops"] / pk["flops_per_s"]
    t_bytes = work["bytes"] / pk["hbm_bytes_per_s"]
    return {"seconds": max(t_ops, t_bytes),
            "bound_by": "bytes" if t_bytes >= t_ops else "ops"}
