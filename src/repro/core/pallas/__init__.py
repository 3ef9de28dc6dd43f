"""Pallas kernel layer: on-accelerator segment reductions for the
partition→metrics→mapping pipeline.

`segsum` holds the tiled segment-sum primitive (sorted-segment-ids
contract, per-block carry, 32-bit precision contract; compiled on a TPU
backend, interpreted elsewhere); `boundary` holds the host<->device
copies (`to_device` / `to_host`, obs spans `device.put` /
`device.get`); `metrics` ports the hot consumers —
the finalize's replica CSR, loads and counts,
`cluster_interaction_graphs`, and the simulator accumulations — onto
it.  They are the reductions of the engine table's `pallas` record
(`repro.core.engines`, `backend="pallas"`); the numpy paths remain the
oracle.

The engine table imports this subpackage lazily, at the first
`backend="pallas"` call.
"""
from .boundary import to_device, to_host
from .segsum import DEFAULT_BLOCK, keyed_sum, narrow, segment_sum

__all__ = ["DEFAULT_BLOCK", "keyed_sum", "narrow", "segment_sum",
           "to_device", "to_host"]
