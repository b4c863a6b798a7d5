"""Cross-chip top-k merge for device-sharded planes.

When shards live in different chips' HBM, each chip produces its local
top-k and the plane needs ONE global top-k without shipping full candidate
sets to the host.  The merge is a ``shard_map`` over the shard axis:
``lax.all_gather`` the (distances, local rows) pairs — k entries per chip,
tiny — then every chip computes the identical merged top-k with
``lax.top_k`` (replicated output, no host round-trip in the middle).

Row ids cross the collective as int32 LOCAL row indices (JAX x64 stays
off); the host maps (source shard, local row) back to u64 ids after the
single readback.  ``dryrun_multichip`` runs the whole merge on
``xla_force_host_platform_device_count`` CPU devices — the same discipline
as ``__graft_entry__.dryrun_multichip`` — and verifies against the host
oracle merge."""

from __future__ import annotations

import functools

import numpy as np

from lakesoul_tpu.errors import VectorIndexError

AXIS = "shards"


@functools.cache
def _merge_fn(n_dev: int, k: int):
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    devices = jax.devices()[:n_dev]
    mesh = Mesh(np.array(devices), (AXIS,))

    def body(d, r):
        from jax import lax

        gd = lax.all_gather(d[0], AXIS)            # [n_dev, k_local]
        gr = lax.all_gather(r[0], AXIS)            # [n_dev, k_local]
        k_local = gd.shape[1]
        flat_d = gd.reshape(-1)
        neg, idx = lax.top_k(-flat_d, k)
        src = (idx // k_local).astype(np.int32)
        slot = (idx % k_local).astype(np.int32)
        rows = gr.reshape(-1)[idx]
        return (-neg)[None], rows[None], src[None], slot[None]

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(AXIS, None), P(AXIS, None)),
        out_specs=(P(AXIS, None),) * 4,
        check_vma=False,
    )
    return jax.jit(fn), mesh


def cross_chip_topk(dists: np.ndarray, rows: np.ndarray, *, k: int | None = None):
    """Merge per-shard top-k candidates on-device.

    ``dists``/``rows``: [n_shards, k_local] (f32 / int32 local row indices);
    needs ``n_shards`` visible devices (one shard per chip).  Returns
    (merged dists [k], rows [k], source shard [k]) as numpy."""
    import jax

    dists = np.asarray(dists, np.float32)
    rows = np.asarray(rows, np.int32)
    n_dev, k_local = dists.shape
    if rows.shape != dists.shape:
        raise VectorIndexError("dists/rows shape mismatch")
    if len(jax.devices()) < n_dev:
        raise VectorIndexError(
            f"cross_chip_topk needs {n_dev} devices, only"
            f" {len(jax.devices())} visible"
        )
    k = k_local if k is None else min(k, n_dev * k_local)
    fn, _mesh = _merge_fn(n_dev, k)
    d, r, src, _slot = fn(dists, rows)
    # out specs shard the replicated result over the axis again; every
    # shard's slice is identical, so read shard 0's copy
    return np.asarray(d)[0], np.asarray(r)[0], np.asarray(src)[0]


def dryrun_multichip(n_devices: int = 8, *, k: int = 10, seed: int = 0) -> dict:
    """One cross-chip merge over ``n_devices`` with seeded candidates,
    verified against the host oracle.  Raises on any divergence; returns
    the merged result for the record."""
    rng = np.random.default_rng(seed)
    local_k = 2 * k
    dists = rng.random((n_devices, local_k)).astype(np.float32)
    rows = rng.integers(0, 1 << 20, (n_devices, local_k)).astype(np.int32)
    d, r, src = cross_chip_topk(dists, rows, k=k)

    flat_d = dists.reshape(-1)
    order = np.argsort(flat_d, kind="stable")[:k]
    np.testing.assert_allclose(d, flat_d[order], rtol=1e-6)
    np.testing.assert_array_equal(r, rows.reshape(-1)[order])
    np.testing.assert_array_equal(src, (order // local_k).astype(np.int32))
    return {"devices": n_devices, "k": k, "dists": d.tolist()}
