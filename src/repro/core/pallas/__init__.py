"""Pallas kernel layer: on-accelerator segment reductions for the
partition→metrics→mapping pipeline.

`segsum` holds the tiled segment-sum primitive (sorted-segment-ids
contract, per-block carry, 32-bit precision contract; compiled on a TPU
backend, interpreted elsewhere); `boundary` holds the host<->device
copies (`to_device` / `to_host`, obs spans `device.put` /
`device.get`); `metrics` ports the hot consumers —
`_finalize`'s replica reduction, the replica CSR,
`cluster_interaction_graphs`, and the simulator accumulations — onto
it.  Selected through the existing engine switch as `backend="pallas"`;
the numpy paths remain the oracle.

The core modules import this subpackage lazily, at the first
`backend="pallas"` call.
"""
from .boundary import to_device, to_host
from .segsum import DEFAULT_BLOCK, keyed_sum, narrow, segment_sum

__all__ = ["DEFAULT_BLOCK", "keyed_sum", "narrow", "segment_sum",
           "to_device", "to_host"]
