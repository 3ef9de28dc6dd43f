"""The host-device boundary of the kernel layer.

Every copy of host data onto the device and every fetch of a result
back on the `backend="pallas"` path goes through `to_device` or
`to_host`, one call per call site.  With telemetry on, each call is one
obs span carrying `bytes`, the summed `nbytes` of the arrays as they
cross (padding included):

  * `device.put` — the host side of the copies (staging and enqueue;
    the transfer itself may still be in flight when it ends);
  * `device.get` (`cat="wait"`) — the host blocked until the device has
    produced the values and they are in host memory.

With telemetry off both are the bare `jnp.asarray` / `jax.device_get`.
Both take and return a pytree (an array, or a tuple or list of them),
and neither changes a dtype: callers narrow before and widen after.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ... import obs

__all__ = ["to_device", "to_host"]


def _span(name: str, tree, cat: str = "op"):
    if not obs.enabled():
        return obs.span(name)           # the shared no-op
    nbytes = sum(int(a.nbytes) for a in jax.tree.leaves(tree))
    return obs.span(name, cat=cat, bytes=nbytes)


def to_device(tree):
    """`jnp.asarray` of each host array in `tree`, as one `device.put`."""
    with _span("device.put", tree):
        return jax.tree.map(jnp.asarray, tree)


def to_host(tree):
    """Numpy copies of the device arrays in `tree`, as one `device.get`."""
    with _span("device.get", tree, cat="wait"):
        return jax.device_get(tree)
