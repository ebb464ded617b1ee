"""Small host-side utilities."""

from __future__ import annotations

import os

import jax
import numpy as np


def const_array(shape, fill, dtype) -> np.ndarray:
    """Shared immutable constant array: allocate ONCE at module scope and
    reuse per pod.  Per-pod feature dicts are full of all-pad arrays (a pod
    with no host ports still carries port slots, a pod with no claims still
    carries claim slots…) — allocating them per pod is a measurable slice
    of featurize cost.  Read-only; np.stack copies it into the batch."""
    a = np.full(shape, fill, dtype)
    a.flags.writeable = False
    return a


def device_fetch(tree, span=None):
    """jax.device_get with the per-leaf copies PIPELINED: start every
    leaf's device→host copy asynchronously, then collect.  device_get alone
    blocks on one copy PER LEAF; started together, a 5-leaf result costs
    one DMA wait and one sync per batch instead of five.

    With ``span`` (the scheduler's span primitive) the wait is split in
    two: `pass/fetch_wait`, the host blocked until the device has
    produced the tree, and `pass/fetch_copy`, the collect that follows.
    The copies are started first either way, so the order of device calls
    is the same."""
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "copy_to_host_async"):
            leaf.copy_to_host_async()
    if span is None:
        return jax.device_get(tree)
    with span("pass/fetch_wait"):
        jax.block_until_ready(tree)
    with span("pass/fetch_copy"):
        return jax.device_get(tree)


def backend_initialized() -> bool:
    """Has THIS process initialised a JAX backend?  A chip belongs to one
    process at a time, so a process that answers True with a non-CPU
    platform holds the device and must not start a child that needs it."""
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def refuse_if_holding_device(what: str) -> None:
    """One process for each chip: a launcher that has itself initialised
    a non-CPU backend holds the device, and a child that needs it would
    fail or hang — refuse by name before spawning instead of waiting for
    a socket that will never bind."""
    if not backend_initialized():
        return
    platform = jax.devices()[0].platform
    if platform != "cpu":
        raise RuntimeError(
            f"cannot start {what}: this process (pid {os.getpid()}) has "
            f"initialised the {platform} backend and holds the chip, so a "
            "child that needs the device cannot get it — launch children "
            "from a process that has not touched JAX"
        )


def require_device() -> dict:
    """The device contract: ``platform``, ``device_kind`` and
    ``n_devices`` as JAX reports them (initialising the backend if
    nothing has yet), as the three fields every printed result carries.
    Raises when the backend is ``cpu`` and JAX_PLATFORMS does not name
    ``cpu`` — a run that was not explicitly sent to the CPU must not land
    there quietly.  Called by the process that owns the device (serve,
    the bench entry points), never computed in a client."""
    asked = os.environ.get("JAX_PLATFORMS", "")
    try:
        devices = jax.devices()
    except RuntimeError as exc:
        raise RuntimeError(
            f"no accelerator: JAX_PLATFORMS={asked!r} and the backend "
            f"failed to initialise ({exc})"
        ) from exc
    dev = devices[0]
    if dev.platform == "cpu" and "cpu" not in asked.lower().split(","):
        raise RuntimeError(
            f"no accelerator: JAX found only {dev.device_kind!r} "
            f"({dev.platform}) and JAX_PLATFORMS={asked!r} does not name "
            "cpu — set JAX_PLATFORMS=cpu to run on the CPU on purpose"
        )
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "n_devices": len(devices),
    }
