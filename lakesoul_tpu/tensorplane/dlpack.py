"""Host-buffer → device delivery + the empirical device-put aliasing probe.

Two exports, both about the same question — *where does the copy happen
when a collated host buffer becomes a jax.Array?*

- :func:`deliver` places a collated pytree on the delivery target (the
  given sharding, else JAX's default device) and checks that every leaf
  landed there.  It is plain ``jax.device_put``: for a 64-byte-aligned
  buffer whose dtype jax keeps, the CPU backend aliases the host bytes (no
  copy anywhere) and an accelerator pays the H2D DMA and nothing else;
  demoted dtypes (int64/float64 under disabled x64) pay the cast, a real
  copy.  (The module keeps its name from the ``jax.dlpack.from_dlpack``
  import it used to ride.  That import returns an array *committed to the
  host CPU device*, so on an accelerator host the batch never left the
  CPU backend, and on the CPU backend it aliased nothing ``device_put``
  does not alias already.)
- :func:`device_put_copies` measures, per (dtype, target backend), whether
  ``jax.device_put`` of a host array is a REAL copy or an alias of the host
  buffer: a measurement, not a guess from the platform's name (on a CPU
  backend a float32 buffer aliases; an int64 one is demoted, which copies).
  The smoke check of the delivery claim reads it.

Probe results are cached per (dtype, device kind) for the process — the
answer is a property of the backend, not of the call site.
"""

from __future__ import annotations

import numpy as np

from lakesoul_tpu.obs import registry

# (np dtype str, device platform) -> device_put makes a real copy
_COPY_CACHE: dict[tuple[str, str], bool] = {}

# XLA's CPU client only zero-copies host buffers aligned to this; anything
# less falls back to a silent staging copy.  Collate output buffers are
# allocated through aligned_empty so the zero-copy delivery claim holds
# deterministically instead of depending on where malloc happened to land —
# and the probe below uses it so "can this dtype alias?" is answered for
# the aligned case (the conservative one: an unaligned probe would report
# "copies" while a real, aligned collate buffer aliased).
ALIGNMENT = 64


def aligned_empty(shape, dtype) -> np.ndarray:
    """``np.empty`` with the buffer start aligned to :data:`ALIGNMENT`
    bytes (the backing allocation stays alive via ``.base``)."""
    dt = np.dtype(dtype)
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    nbytes = int(np.prod(shape)) * dt.itemsize if shape else dt.itemsize
    raw = np.empty(nbytes + ALIGNMENT, dtype=np.uint8)
    off = (-raw.ctypes.data) % ALIGNMENT
    return raw[off:off + nbytes].view(dt).reshape(shape)


def default_device():
    """The device an untargeted ``jax.device_put`` lands on: the
    ``jax.default_device`` setting when one is active (a device, or a
    platform name), else the first local device of the default backend."""
    import jax

    dev = jax.config.jax_default_device
    if dev is None:
        return jax.local_devices()[0]
    if isinstance(dev, str):
        return jax.local_devices(backend=dev)[0]
    return dev


def _probe_device(sharding=None):
    """The single device a probe targets: aliasing is a per-backend
    property, so one device of the sharding's set stands for all of it."""
    if sharding is not None:
        devices = getattr(sharding, "device_set", None)
        if devices:
            return sorted(devices, key=lambda d: d.id)[0]
    return default_device()


def device_put_copies(dtype, sharding=None) -> bool:
    """True when ``jax.device_put`` of a host numpy array of ``dtype``
    onto the delivery target is a REAL copy (the produced jax.Array owns
    bytes disjoint from the source buffer); False when it aliases.  A
    probe that fails on a live backend reports False — "assume aliasing"
    is the safe answer for a caller; a backend that fails to start raises."""
    import jax

    dt = np.dtype(dtype)
    device = _probe_device(sharding)
    key = (dt.str, getattr(device, "platform", "unknown"))
    hit = _COPY_CACHE.get(key)
    if hit is not None:
        return hit
    try:
        probe = aligned_empty((8,), dt)
        probe[:] = 0
        arr = jax.device_put(probe, device)
        arr.block_until_ready()
        try:
            dst = arr.unsafe_buffer_pointer()
        except Exception:
            # no single addressable buffer (or API absent): prove the copy
            # behaviorally — mutate the source and check the device value
            probe[0] = 1
            copied = bool(int(arr[0]) == 0)
            _COPY_CACHE[key] = copied
            return copied
        src = probe.ctypes.data
        copied = not (src <= dst < src + probe.nbytes)
    except Exception:
        copied = False
    _COPY_CACHE[key] = copied
    return copied


def deliver(batch, sharding=None):
    """Collated host pytree → device pytree on the delivery target.

    With a ``sharding`` every leaf is laid out by it; without one the batch
    goes to the default device uncommitted, exactly as a bare
    ``jax.device_put`` would, so a jitted step may still move it to where
    its params live.  Either way the placement is verified leaf by leaf —
    a batch left on another backend would train on the host, or fail
    inside the step, with nothing pointing back here.  The caller owns the
    lifetime question: an aliased delivery borrows the collate buffer
    (:func:`device_put_copies` says whether a dtype's does)."""
    import jax

    from lakesoul_tpu.errors import IOError_

    want = {default_device()} if sharding is None else sharding.device_set
    out = jax.device_put(batch, sharding)
    placed = 0
    for leaf in jax.tree_util.tree_leaves(out):
        if leaf.devices() != want:
            raise IOError_(
                f"delivered leaf is on {sorted(map(str, leaf.devices()))},"
                f" expected {sorted(map(str, want))}"
            )
        placed += leaf.nbytes
    # the bytes as they lie on the device (after any dtype demotion): what
    # the link carried, counted where it was dispatched
    registry().counter("lakesoul_tensorplane_h2d_bytes_total").inc(placed)
    return out
