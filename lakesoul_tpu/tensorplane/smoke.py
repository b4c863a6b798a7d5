"""The on-chip claim register: every Pallas kernel, multichip shape and
tensorplane delivery path, each as one runnable check.

- a **register** of :class:`SmokeCase`\\ s — one per Pallas kernel (each
  case names the kernel functions it compiles, by lakelint device-index
  qname), one per multichip shape (the annplane cross-chip top-k merge
  and the parallel mesh/pipeline dryrun), and one per tensorplane
  delivery/replay path;
- :func:`enumerate_pallas_kernels` — the ground truth: lakelint's device
  index re-parses the package and lists every ``pl.pallas_call`` kernel,
  so the "register covers 100% of Pallas kernels" claim is machine-checked
  (a new kernel that forgets to register fails :func:`run_smoke` and its
  CI test);
- :func:`run_smoke` — run the register at the sizes and in the Pallas
  mode the caller names.  ``chip_smoke.py`` runs it compiled
  (``interpret=False``) at :func:`deployed` sizes on the chip; tier-1 runs
  it in the interpreter at :data:`TINY` sizes on the CPU.  Nothing here
  looks at the platform to pick a mode, and the first failing case raises.

Each Pallas case compares the kernel with its ``jnp`` twin evaluated at
full float32 matmul precision (an accelerator's default rounds matmul
inputs to bfloat16, which would make the reference the less exact side).

Host readbacks below exist to *verify* device results — that is the one
sanctioned reason to round-trip device memory in this package, and each
site carries its ``replay-host-roundtrip`` pragma saying so.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable

import numpy as np


@dataclass(frozen=True)
class SmokeSizes:
    """Problem sizes of one register run."""

    rows: int     # corpus rows for the packed and brute-force kernels
    items: int    # (query, cluster-tile) work items for the ragged kernel
    queries: int  # query batch for packed_dot_batch and the ragged kernel
    d: int        # vector width


TINY = SmokeSizes(rows=600, items=5, queries=4, d=64)


def deployed(d: int) -> SmokeSizes:
    """What a served shard looks like: a million packed rows, a ragged
    micro-batch of 64 queries x 64 probed tiles."""
    return SmokeSizes(rows=1 << 20, items=4096, queries=64, d=d)


# float32 accumulation over <= 768 terms, both sides at full precision
RTOL = 2e-4
# the twins materialize [rows, d] float32 (3 GB at 1M x 768): run them in
# row chunks so the reference never needs more memory than the kernel
TWIN_CHUNK = 1 << 17


@dataclass(frozen=True)
class SmokeCase:
    """One on-chip claim.  A ``pallas`` case is ``run(interpret, sizes)``;
    the other kinds take no argument.  ``run`` raises on any divergence
    and returns a detail dict for the record.  ``kernels`` are the lakelint
    device-index qnames this case compiles (empty for non-Pallas shapes);
    ``min_devices`` gates collective shapes."""

    name: str
    kind: str  # "pallas" | "multichip" | "tensorplane"
    run: Callable[..., dict]
    kernels: tuple[str, ...] = ()
    min_devices: int = 1


# ------------------------------------------------------------------ pallas


def _rng(seed: int = 0):
    return np.random.default_rng(seed)


def _packed_inputs(n: int, d: int, seed: int):
    rng = _rng(seed)
    codes = rng.integers(0, 256, (n, d // 8), dtype=np.uint8)
    norms = rng.random(n, dtype=np.float32) + 0.1
    factors = rng.random(n, dtype=np.float32) + 0.5
    q_rot = rng.standard_normal(d, dtype=np.float32)
    return codes, norms, factors, q_rot


def _twin_in_chunks(twin, n: int, chunk: int = TWIN_CHUNK) -> np.ndarray:
    """``twin(lo, hi)`` over ``range(n)`` in chunks, at full matmul
    precision, concatenated on the host."""
    import jax

    with jax.default_matmul_precision("highest"):
        parts = [
            np.asarray(twin(lo, min(n, lo + chunk)))  # lakelint: ignore[replay-host-roundtrip] verification readback: the jnp twin's reference values
            for lo in range(0, n, chunk)
        ]
    return np.concatenate(parts)


def _agree(got, want, *, atol: float | None = None) -> dict:
    got = np.asarray(got)  # lakelint: ignore[replay-host-roundtrip] verification readback: differential-test the on-chip result against the jnp twin
    scale = max(1.0, float(np.max(np.abs(want))))
    if atol is None:
        atol = RTOL * scale
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=atol)
    return {"max_abs_err": float(np.max(np.abs(got - want))), "scale": round(scale, 3)}


def _run_packed_scan(interpret: bool, sizes: SmokeSizes) -> dict:
    import jax.numpy as jnp

    from lakesoul_tpu.vector.kernels import packed_scan_pallas
    from lakesoul_tpu.vector.rabitq import estimate_distances

    n, d = sizes.rows, sizes.d
    codes, norms, factors, q_rot = _packed_inputs(n, d, seed=0)
    q = jnp.asarray(q_rot)
    got = packed_scan_pallas(
        jnp.asarray(codes), jnp.asarray(norms), jnp.asarray(factors), q,
        d=d, interpret=interpret,
    )
    want = _twin_in_chunks(
        lambda lo, hi: estimate_distances(
            jnp.asarray(codes[lo:hi]), jnp.asarray(norms[lo:hi]),
            jnp.asarray(factors[lo:hi]), q, d=d,
        ),
        n,
    )
    return {"rows": n, "d": d, **_agree(got, want)}


def _run_packed_dot(interpret: bool, sizes: SmokeSizes) -> dict:
    import jax.numpy as jnp

    from lakesoul_tpu.vector.kernels import _packed_dot_jnp, packed_dot_pallas

    n, d = sizes.rows, sizes.d
    codes, _, _, q_rot = _packed_inputs(n, d, seed=1)
    q = jnp.asarray(q_rot)
    got = packed_dot_pallas(jnp.asarray(codes), q, interpret=interpret)
    want = _twin_in_chunks(
        lambda lo, hi: _packed_dot_jnp(jnp.asarray(codes[lo:hi]), q), n
    )
    return {"rows": n, "d": d, **_agree(got, want)}


def _run_packed_dot_batch(interpret: bool, sizes: SmokeSizes) -> dict:
    import jax
    import jax.numpy as jnp

    from lakesoul_tpu.vector.kernels import packed_dot_batch_pallas
    from lakesoul_tpu.vector.rabitq import unpack_bits_jnp

    n, d = sizes.rows, sizes.d
    codes, _, _, _ = _packed_inputs(n, d, seed=2)
    host_queries = _rng(3).standard_normal((sizes.queries, d), dtype=np.float32)
    queries = jnp.asarray(host_queries)
    got = packed_dot_batch_pallas(jnp.asarray(codes), queries, interpret=interpret)
    twin = jax.jit(lambda c: unpack_bits_jnp(c, d) @ queries.T)
    want = _twin_in_chunks(lambda lo, hi: twin(jnp.asarray(codes[lo:hi])), n)
    # this one is a real [tile, d] x [d, Q] matmul, and the MXU takes its
    # inputs as bfloat16 (as XLA's default precision does for the jnp form
    # of the same product): the bits are exact, each query term is off by at
    # most 2^-9 of itself.  Measured on a v5e: 0.069 at d=128, 0.18 at
    # d=768; the four matvec kernels are float32-exact.
    atol = 2.0**-9 * float(np.max(np.sum(np.abs(host_queries), axis=1)))
    return {"rows": n, "d": d, "queries": sizes.queries,
            **_agree(got, want, atol=atol), "atol": round(atol, 4)}


def _run_bruteforce(interpret: bool, sizes: SmokeSizes) -> dict:
    import jax.numpy as jnp

    from lakesoul_tpu.vector.kernels import (
        _bruteforce_jnp,
        bruteforce_distances_pallas,
    )

    n, d = sizes.rows, sizes.d
    rng = _rng(4)
    vectors = rng.standard_normal((n, d), dtype=np.float32)
    query = jnp.asarray(rng.standard_normal(d, dtype=np.float32))
    got = bruteforce_distances_pallas(
        jnp.asarray(vectors), query, interpret=interpret
    )
    want = _twin_in_chunks(
        lambda lo, hi: _bruteforce_jnp(jnp.asarray(vectors[lo:hi]), query), n
    )
    return {"rows": n, "d": d, **_agree(got, want)}


def _run_ragged(interpret: bool, sizes: SmokeSizes) -> dict:
    from lakesoul_tpu.annplane.ragged import (
        TILE,
        ragged_score_jnp,
        ragged_score_pallas,
    )

    rng = _rng(5)
    d, m, nq = sizes.d, sizes.items, sizes.queries
    ntiles = max(1, min(sizes.rows // TILE, 1024))
    rows = ntiles * TILE
    codes = rng.standard_normal((rows, d), dtype=np.float32)
    a = rng.random(rows, dtype=np.float32)
    b = rng.random(rows, dtype=np.float32)
    h = rng.random(rows, dtype=np.float32)
    q_glob = rng.standard_normal((nq, d), dtype=np.float32)
    # query-major items, as plan_items emits them
    item_q = np.sort(rng.integers(0, nq, m)).astype(np.int32)
    item_tile = rng.integers(0, ntiles, m).astype(np.int32)
    csq = rng.random(m, dtype=np.float32)
    csum = rng.random(m, dtype=np.float32)
    got = ragged_score_pallas(
        item_q, item_tile, csq, csum, q_glob, codes, a, b, h,
        interpret=interpret,
    )
    # the twin gathers [items, TILE, d]: 1.6 GB at 4096 x 768, so chunk it
    want = _twin_in_chunks(
        lambda lo, hi: ragged_score_jnp(
            item_q[lo:hi], item_tile[lo:hi], csq[lo:hi], csum[lo:hi],
            q_glob, codes, a, b, h,
        ),
        m, chunk=1024,
    )
    return {"items": m, "tile": TILE, "d": d, "rows": rows, **_agree(got, want)}


def _run_unit_lower_inverse(interpret: bool, sizes: SmokeSizes) -> dict:
    import jax.numpy as jnp

    from lakesoul_tpu.models.qwen3_next import (
        GDN_CHUNK,
        _unit_lower_inverse_jnp,
        _unit_lower_inverse_pallas,
    )

    # one chunk system a work item (not a multiple of the kernel's block at
    # tiny sizes): small products, as normalised keys make them, under a
    # running log decay, as the DeltaNet layers call it
    n, c = sizes.items, GDN_CHUNK
    rng = _rng(7)
    a = jnp.asarray(rng.standard_normal((n, c, c), dtype=np.float32)) * 0.05
    g = jnp.cumsum(-jnp.asarray(rng.random((n, c), dtype=np.float32)), axis=-1)
    got = _unit_lower_inverse_pallas(a, jnp.bfloat16, g, interpret=interpret)
    want = _twin_in_chunks(
        lambda lo, hi: _unit_lower_inverse_jnp(a[lo:hi], jnp.bfloat16, g[lo:hi]), n, chunk=1024
    )
    return {"systems": n, "chunk": c, **_agree(got, want)}


def _run_gated_delta(interpret: bool, sizes: SmokeSizes) -> dict:
    import jax
    import jax.numpy as jnp

    from lakesoul_tpu.models.qwen3_next import (
        GDN_CHUNK,
        _gated_delta_backward,
        _gated_delta_forward,
        _gated_delta_scan,
    )

    # a row of the Qwen cell's DeltaNet layer, 16 key heads serving 32 value
    # heads of 128 over 8,192 tokens at deployed sizes (one key head and two
    # chunks at tiny ones): the output and the six cotangents of the kernel
    # pair against the scan over the same chunk body, operands bfloat16 as
    # the model passes them.  The two run the same products on the same
    # roundings; XLA and Mosaic order a sum's terms differently, so they agree
    # to a few bfloat16 roundings by norm, not element by element
    t, (hk, hv) = (8192, (16, 32)) if sizes.rows >= 1 << 20 else (2 * GDN_CHUNK, (1, 2))
    n, c, d, lo = t // GDN_CHUNK, GDN_CHUNK, 128, jnp.bfloat16
    keys = jax.random.split(jax.random.key(10), 7)
    q, k = (jax.random.normal(key, (1, t, hk, d)).astype(lo) for key in keys[:2])
    v, do = (jax.random.normal(key, (1, t, hv, d)) for key in keys[2:4])
    v = v.astype(lo)
    gc = jnp.cumsum(-jnp.exp(jax.random.normal(keys[4], (1, n, hv, c)) - 2), axis=-1)
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (1, n, hv, c)))
    inv = (jnp.eye(c) + 0.05 * jnp.tril(jax.random.normal(keys[6], (1, n, hv, c, c)), -1)).astype(lo)
    operands, eps = (q, k, v, gc, beta, inv), 1e-6
    o, states = _gated_delta_forward(*operands, eps=eps, interpret=interpret)
    got = (o, *_gated_delta_backward(*operands, states, do, eps=eps, interpret=interpret))
    o_twin, pull = jax.vjp(lambda *operands: _gated_delta_scan(*operands, eps), *operands)
    errors = []
    for a, b in zip(got, (o_twin, *pull(do)), strict=True):
        a, b = (np.asarray(x.astype(jnp.float32)) for x in (a, b))  # lakelint: ignore[replay-host-roundtrip] verification readback: the kernels' results against the scan twin's
        errors.append(float(np.linalg.norm(a - b) / np.linalg.norm(b)))
    if not max(errors) < 1e-2:
        raise AssertionError(f"gated delta rule at {(hk, hv, t)}: o, dq, dk, dv, dg, dbeta, dinv off by {errors}")
    return {"tokens": t, "heads": [hk, hv], "rel_err": [round(e, 6) for e in errors]}


def _run_selective_scan(interpret: bool, sizes: SmokeSizes) -> dict:
    import jax
    import jax.numpy as jnp

    from lakesoul_tpu.models.selective_scan import _scan_backward, _scan_forward, _scan_twin, scan_takes

    # a row of a Mamba-1 layer, 5,120 channels of 16 states over 8,192 tokens at
    # deployed sizes (256 channels over two blocks of tokens at tiny ones): the
    # output and the six cotangents of the kernel pair against the token-by-token
    # ``lax.scan`` and its autodiff (at deployed sizes over the row's first 512
    # tokens: the twin's transpose keeps every state), u and dy bfloat16 as the
    # model passes them.  Both run the recurrence in float32; the kernels round y
    # and du once, to bfloat16
    t, e, n = (8192, 5120, 16) if sizes.rows >= 1 << 20 else (256, 256, 16)
    held, lo, f32 = min(t, 512), jnp.bfloat16, jnp.float32
    keys = jax.random.split(jax.random.key(12), 7)
    u, dy = (jax.random.normal(key, (1, t, e)).astype(lo) for key in keys[:2])
    delta = jax.nn.softplus(jax.random.normal(keys[2], (1, t, e)) - 2.0)
    a = -jnp.exp(0.5 * jax.random.normal(keys[3], (e, n)))
    b, c = (jax.random.normal(key, (1, t, n)) for key in keys[4:6])
    d = jax.random.normal(keys[6], (e,))
    eb = scan_takes(e, n)
    y, bounds = _scan_forward(u, delta, a.T, b, c, d, eb=eb, interpret=interpret)
    cut = (u[:, :held], delta[:, :held], a, b[:, :held], c[:, :held], d)
    first = _scan_forward(cut[0], cut[1], a.T, cut[3], cut[4], d, eb=eb, interpret=interpret)
    du, ddt, da, db, dc, dd = _scan_backward(
        cut[0], cut[1], a.T, cut[3], cut[4], d, first[1], dy[:, :held], eb=eb, interpret=interpret
    )
    y_twin, pull = jax.vjp(lambda *xs: _scan_twin(*xs).astype(f32), *cut)
    errors = []
    for got, want in zip((y[:, :held], du, ddt, da.T, db, dc, dd), (y_twin, *pull(dy[:, :held].astype(f32))), strict=True):
        got, want = (np.asarray(x.astype(f32)) for x in (got, want))  # lakelint: ignore[replay-host-roundtrip] verification readback: the kernels' results against the scan twin's
        errors.append(float(np.linalg.norm(got - want) / np.linalg.norm(want)))
    if not max(errors) < 1e-2:
        raise AssertionError(f"selective scan at {(t, e, n)}: y, du, ddelta, dA, dB, dC, dD off by {errors}")
    return {"tokens": t, "channels": e, "states": n, "rel_err": [round(x, 6) for x in errors]}


def _run_row_copies(interpret: bool, sizes: SmokeSizes) -> dict:
    import jax.numpy as jnp

    from lakesoul_tpu.parallel.moe import EXPERT_TILE, put_rows, take_rows

    # the expert tile loop's float32 sums over a step's tokens (16,384 rows at
    # deployed sizes): tiles of distinct rows read, summed and written back,
    # with none, some and all of a tile's slots valid.  Copies, so exact
    n_rows = max(64, sizes.rows // 64)
    width = -(-sizes.d // 128) * 128
    tile = min(EXPERT_TILE, n_rows // 2)
    rng = _rng(8)
    acc = jnp.asarray(rng.standard_normal((n_rows, 1, width), dtype=np.float32))
    want = np.array(acc)  # lakelint: ignore[replay-host-roundtrip] verification readback: the host copy the indexing twin updates
    counts = [0, tile, *map(int, rng.integers(1, tile, size=6))]
    for n in counts:
        idx = rng.permutation(n_rows)[:tile].astype(np.int32)
        idx[n:] = idx[:1] if n else 0  # slots past the prefix repeat a row of it: never written
        rows = jnp.asarray(rng.standard_normal((tile, 1, width), dtype=np.float32))
        seen = take_rows(acc, jnp.asarray(idx), jnp.int32(n), interpret=interpret)
        np.testing.assert_array_equal(np.asarray(seen)[:n], want[idx[:n]])  # lakelint: ignore[replay-host-roundtrip] verification readback: rows fetched by DMA against indexing
        acc = put_rows(acc, jnp.asarray(idx), jnp.int32(n), seen + rows, interpret=interpret)
        want[idx[:n]] += np.asarray(rows)[:n]  # lakelint: ignore[replay-host-roundtrip] verification readback: the twin's sum on the host
    np.testing.assert_array_equal(np.asarray(acc), want)  # lakelint: ignore[replay-host-roundtrip] verification readback: rows written by DMA against indexing
    return {"rows": n_rows, "width": width, "tile": tile, "valid": counts}


def _run_expert_dw(interpret: bool, sizes: SmokeSizes) -> dict:
    import jax
    import jax.numpy as jnp

    from lakesoul_tpu.parallel.moe import DW_SEGMENT, EXPERT_TILE, _expert_dw_twin, expert_dw, put_tiles

    # two segments of the backward loop's rows into the held experts' float32
    # weight-gradient sums (an LFM2 expert's [2048, 1792] at deployed sizes,
    # 64 tiles of 512 rows a segment): an expert no tile names, one of a single
    # tile, one that the segments' boundary splits, a last segment that stops
    # short of its buffers.  bfloat16 products summed in float32 tile by tile
    # on both sides: the same bits
    wide = sizes.rows >= 1 << 16
    (a, b), tile, span = ((2048, 1792), EXPERT_TILE, DW_SEGMENT) if wide else ((256, 128), 128, 4)
    tiles_of = [span // 2 - 1, 0, 1, span - 1, span // 4]  # the fourth expert begins in the first segment and ends in the second
    experts = np.repeat(np.arange(len(tiles_of), dtype=np.int32), tiles_of)
    keys = jax.random.split(jax.random.key(9), 2)
    lhs, rhs = (jax.random.normal(key, (2 * span * tile, width)).astype(jnp.bfloat16) for key, width in zip(keys, (a, b)))
    # the rows reach their buffers as the loop leaves them there, a tile at a time by DMA: copies, so exact
    held = (jnp.zeros_like(lhs), jnp.zeros_like(rhs))
    for t in range(2 * span):
        rows = slice(t * tile, (t + 1) * tile)
        held = put_tiles(held, (lhs[rows], rhs[rows]), jnp.int32(t * tile), interpret=interpret)
    for m, want in zip(held, (lhs, rhs)):
        np.testing.assert_array_equal(np.asarray(m), np.asarray(want))  # lakelint: ignore[replay-host-roundtrip] verification readback: tiles written by DMA against the rows they came from
    lhs, rhs = held
    got = want = jnp.zeros((len(tiles_of), a, b), jnp.float32)
    for t0 in range(0, len(experts), span):
        n = min(span, len(experts) - t0)
        held = jnp.asarray(np.concatenate([experts[t0:t0 + n], np.full(span - n, len(tiles_of), np.int32)]))
        rows = slice(t0 * tile, (t0 + span) * tile)
        got = expert_dw(got, lhs[rows], rhs[rows], held, jnp.int32(n), interpret=interpret)
        want = _expert_dw_twin(want, lhs[rows], rhs[rows], held, jnp.int32(n))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))  # lakelint: ignore[replay-host-roundtrip] verification readback: the kernel's sums against the indexing twin's
    return {"sums": [len(tiles_of), a, b], "tile": tile, "segment": span, "tiles": int(len(experts))}


def _run_grouped_experts(interpret: bool, sizes: SmokeSizes) -> dict:
    import jax
    import jax.numpy as jnp

    from lakesoul_tpu.parallel.moe import (
        EXPERT_TILE, GROUP_SEGMENT, _group_rows, _tile_dx, _tile_operands, _tile_outputs, experts_bwd, experts_fwd, stage_rows,
    )

    # a segment of each pass through the grouped kernels against the tile
    # loop's body, tile by tile, at two experts' shapes (at deployed sizes the
    # LFM2 cell's, [2048, 1792], eight held, several tiles an expert, and the
    # Qwen3-Next cell's, [2048, 512], 32 held, a tile an expert; 16 tiles of
    # 512 rows): a full tile, a tile of one row, a tile that ends short of its
    # last block, a run that stops short of the segment's end, and tokens that
    # come again in the next tile, whose rows the kernel must have written
    # before it reads them.  The same bfloat16 products in float32 sums, a row
    # at a time, on both sides; the compiler orders a sum over an expert's
    # width as it likes, so bfloat16 outputs may sit a rounding apart
    wide = sizes.rows >= 1 << 16
    shapes = ((2048, 1792, 8), (2048, 512, 32)) if wide else ((256, 128, 3), (128, 256, 5))
    tile, span = EXPERT_TILE, (GROUP_SEGMENT if wide else 4)
    block, run = _group_rows(tile), span - 1
    outputs, operands_of, dx_of = jax.jit(_tile_outputs), jax.jit(_tile_operands), jax.jit(_tile_dx)
    report = []
    for case, (h, f, count) in enumerate(shapes):
        rng = _rng(20 + case)
        experts = np.sort(rng.integers(0, count, size=span)).astype(np.int32)
        experts[run:] = count
        counts = np.full(span, tile, np.int32)
        counts[1], counts[run - 1], counts[run:] = 1, tile - block - 3, 0
        n = 3 * tile  # tokens: a tile takes a third of them, so the next tile meets some again
        keys = jax.random.split(jax.random.key(20 + case), 6)
        x, dy = (jax.random.normal(key, (n, h)).astype(jnp.bfloat16) for key in keys[:2])
        tok = np.stack([rng.permutation(n)[:tile] for _ in range(span)]).astype(np.int32)
        wt = np.asarray(jax.random.uniform(keys[2], (span, tile))) * (np.arange(tile) < counts[:, None])  # lakelint: ignore[replay-host-roundtrip] verification setup: weights masked on the host
        wg, wu, wd = (0.03 * jax.random.normal(key, (count, *shape)).astype(jnp.bfloat16)
                      for key, shape in zip(keys[3:], ((h, f), (h, f), (f, h))))
        (x32, dy32), zeros = stage_rows((x, dy), interpret=interpret)
        plan = (jnp.asarray(tok.reshape(-1)), jnp.asarray(wt.reshape(-1), jnp.float32), wg, wu, wd, jnp.asarray(experts), jnp.asarray(counts))
        y = experts_fwd(zeros, x32, *plan, interpret=interpret)
        dx, *held, dw = experts_bwd(zeros, x32, dy32, *plan, interpret=interpret)
        want_y, want_dx = np.zeros((n, h), np.float32), np.zeros((n, h), np.float32)
        worst = 0.0

        def off(a, b):
            a, b = (np.asarray(m, np.float32) for m in (a, b))  # lakelint: ignore[replay-host-roundtrip] verification readback: the kernels' rows against the tile loop's body
            return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))

        for t in range(run):
            rows, e, c = slice(t * tile, (t + 1) * tile), int(experts[t]), int(counts[t])
            reach = -(-c // block) * block  # the blocks that run; past them the kernel leaves zeros
            weights, wt_t = (wg[e], wu[e], wd[e]), jnp.asarray(wt[t], jnp.float32)[:, None]
            want_y[tok[t, :c]] += np.asarray(outputs(x[tok[t]], wt_t, *weights))[:c]  # lakelint: ignore[replay-host-roundtrip] verification readback: the twin's sum on the host
            *want, want_dw = operands_of(x[tok[t]], dy[tok[t]], wt_t, *weights)
            want_dx[tok[t, :c]] += np.asarray(dx_of(want[2], want[3], wg[e], wu[e]))[:c]  # lakelint: ignore[replay-host-roundtrip] verification readback: the twin's sum on the host
            for got, ref in zip(held, (x[tok[t]], *want), strict=True):
                worst = max(worst, off(got[rows][:reach], ref[:reach]))
                if np.abs(np.asarray(got[rows][reach:], np.float32)).max(initial=0.0):  # lakelint: ignore[replay-host-roundtrip] verification readback: zeros past an expert's last block
                    raise AssertionError(f"grouped experts at {(h, f, count)}: tile {t} holds rows past its last block")
            worst = max(worst, off(dw[rows][:c], want_dw[:c, 0]))
        worst = max(worst, off(y[:, 0], want_y), off(dx[:, 0], want_dx))
        if not worst < 1e-2:  # PR 52's kernels, the same products, read 1.8e-3 on a v5e
            raise AssertionError(f"grouped experts at {(h, f, count)}: off the tile loop's body by {worst}")
        report.append({"experts": [count, h, f], "tiles": run, "rel_err": round(worst, 6)})
    return {"tile": tile, "segment": span, "block": block, "cases": report}


def _run_stage_rows(interpret: bool, sizes: SmokeSizes) -> dict:
    import jax
    import jax.numpy as jnp

    from lakesoul_tpu.parallel.moe import stage_rows, unstage_rows

    # a layer's tokens into the layout a DMA takes a row of and back (at
    # deployed sizes 8,192 tokens of 2,048 bfloat16): casts and copies, so exact
    n, h = (8192, 2048) if sizes.rows >= 1 << 16 else (72, 128)
    keys = jax.random.split(jax.random.key(30), 2)
    x, dy = (jax.random.normal(key, (n, h)).astype(jnp.bfloat16) for key in keys)
    (x32, dy32), zeros = stage_rows((x, dy), interpret=interpret)
    for got, want in ((x32, x), (dy32, dy)):
        np.testing.assert_array_equal(np.asarray(got[:, 0]), np.asarray(want.astype(jnp.float32)))  # lakelint: ignore[replay-host-roundtrip] verification readback: staged rows against the cast
    if zeros.shape != (n, 1, h) or np.asarray(zeros).any():  # lakelint: ignore[replay-host-roundtrip] verification readback: the sums start from zeros
        raise AssertionError("stage_rows: the sums do not start from zeros")
    back = unstage_rows(x32 + dy32, jnp.bfloat16, interpret=interpret)
    want = (x.astype(jnp.float32) + dy.astype(jnp.float32)).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(back.astype(jnp.float32)), np.asarray(want.astype(jnp.float32)))  # lakelint: ignore[replay-host-roundtrip] verification readback: the sums' rows against the cast
    return {"rows": n, "width": h}


def _run_loss_tile(interpret: bool, sizes: SmokeSizes) -> dict:
    import jax
    import jax.numpy as jnp

    from lakesoul_tpu.models.head_loss import _tile_nll
    from lakesoul_tpu.models.loss_tile import block_rows, loss_tile

    # a tile of a head's float32 logits to each row's NLL and the logits' cotangent, against
    # ``jax.nn.log_softmax`` and autodiff (``_tile_nll``, the tile loop's default body): at deployed sizes the Ouro
    # cell's tile (2,736 rows of 49,152: whole lane tiles, a ragged last block of rows), the LFM2 cell's (2,736 of
    # 16,384) and the Trinity-Mini cell's (1,368 of 25,024: not whole lane tiles, the last lanes masked); a fifth of
    # the rows without a label, a weight a row.  Float32 on both sides with the sums in another order, and the
    # bfloat16 cotangent the LM steps write: the float32 one rounded once, bit for bit
    wide = sizes.rows >= 1 << 16
    shapes = ((2736, 49152), (2736, 16384), (1368, 25024)) if wide else ((40, 384), (24, 250))
    seen = []
    for rows, vocab in shapes:
        keys = jax.random.split(jax.random.key(rows + vocab), 4)
        logits = 4.0 * jax.random.normal(keys[0], (rows, vocab), jnp.float32)
        labels = jnp.where(jax.random.uniform(keys[1], (rows,)) < 0.8, jax.random.randint(keys[2], (rows,), 0, vocab), -100)
        coef = jnp.where(labels >= 0, jax.random.uniform(keys[3], (rows,), jnp.float32, 0.1, 1.0) / rows, 0.0)
        # the twin: the logits as the head's rows under the identity, a weight a row; its gradient into them is the cotangent
        (_, want_nll), want_g = jax.jit(jax.value_and_grad(
            lambda z, labels, coef: _tile_nll(lambda head, x: x, None, z, labels, None, coef), has_aux=True
        ))(logits, labels, coef)
        nll, g = loss_tile(logits, labels, coef, dtype=jnp.float32, interpret=interpret)
        _, g16 = loss_tile(logits, labels, coef, dtype=jnp.bfloat16, interpret=interpret)
        np.testing.assert_allclose(np.asarray(nll), np.asarray(want_nll), rtol=1e-5, atol=1e-5)  # lakelint: ignore[replay-host-roundtrip] verification readback: the kernel's NLL against log_softmax's
        scale = float(jnp.max(jnp.abs(want_g)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(want_g), rtol=0, atol=1e-5 * scale)  # lakelint: ignore[replay-host-roundtrip] verification readback: the kernel's cotangent against autodiff's
        assert not np.asarray(g)[np.asarray(labels) < 0].any()  # lakelint: ignore[replay-host-roundtrip] verification readback: a row without a label carries no gradient
        assert bool(jnp.array_equal(g16, g.astype(jnp.bfloat16)))  # one rounding, at the end
        seen.append([rows, vocab, block_rows(rows, vocab, jnp.float32), block_rows(rows, vocab, jnp.bfloat16)])
    return {"tiles [rows, vocab, rows a block: float32, bfloat16]": seen}


def _run_causal_attention(interpret: bool, sizes: SmokeSizes) -> dict:
    import jax
    import jax.numpy as jnp

    from lakesoul_tpu.models.attention import (
        ATTN_BAND,
        ATTN_ROWS,
        _blockwise_attention,
        _flash_backward,
        _flash_forward,
        _flash_tiles,
        _token_major,
    )

    # a row of each causal-LM cell's attention layer (key-value heads, query
    # heads each serves, head size, value width), 8,192 tokens at deployed sizes (one
    # key-value head of 256 at tiny ones): output
    # and the three gradients against the blockwise twin, operands bfloat16
    # as the models pass them; the output and its cotangent token-major,
    # [1, T, heads x D] through the kernels' block specs, where the head is
    # whole lane tiles and the value as wide (every shape here but the first and the last two), the twin's output
    # transposed to that.  Both sides round the same float32 softmax to
    # bfloat16 (the probabilities, the output), each with its own maximum at
    # the time of rounding, so they agree to a few bfloat16 roundings by
    # norm (measured on a v5e at 8,192 tokens: output 1.2e-3 to 1.4e-3,
    # gradients 4.0e-3 to 5.1e-3), not element by element
    t = min(8192, max(256, sizes.rows // 128))
    detail = {"tokens": t}
    # and a Trinity-Mini row's window layer: 4 key-value heads of 8 query heads at head 128 under a window of
    # 2,048 (half the row at tiny sizes), the twin's bands cut the same way; an Ouro row: 16 key-value heads
    # of one query head at head 128; and a Phi-4-mini-flash row's paired maps: 20 key-value heads of two query heads
    # at head 64 beside a value of 128, without a window and under the window layers' 512
    shapes = ((8, 4, 64, 64, None), (2, 8, 256, 256, None), (20, 1, 256, 256, None), (4, 8, 128, 128, 2048),
              (16, 1, 128, 128, None), (20, 2, 64, 128, None), (20, 2, 64, 128, 512))
    for hkv, groups, d, dv, window in shapes:
        hkv = hkv if t == 8192 else 1
        window = window and min(window, t // 2)
        keys = jax.random.split(jax.random.key(9), 4)
        q = (jax.random.normal(keys[0], (hkv, groups, t, d)) * d**-0.5).astype(jnp.bfloat16)
        k, v = (jax.random.normal(key, (hkv, t, n)).astype(jnp.bfloat16) for key, n in zip(keys[1:3], (d, dv)))
        tiles = dict(zip(("bq", "bk"), _flash_tiles(t, groups, d, dv), strict=True))
        batch = 1 if _token_major(t, groups, d, dv) else None
        o, lse = _flash_forward(q, k, v, **tiles, window=window, batch=batch, interpret=interpret)
        do = jax.random.normal(keys[3], o.shape).astype(jnp.bfloat16)
        got = (o, *_flash_backward(q, k, v, o, lse, do, **tiles, window=window, interpret=interpret))

        def twin(*qkv):
            out = _blockwise_attention(*(a[None] for a in qkv), ATTN_BAND, ATTN_ROWS, window)
            return out[0] if batch is None else out.transpose(0, 3, 1, 2, 4).reshape(o.shape)

        o_twin, pull = jax.vjp(twin, q, k, v)
        errors = []
        for a, b in zip(got, (o_twin, *pull(do)), strict=True):
            a, b = (np.asarray(x.astype(jnp.float32)) for x in (a, b))  # lakelint: ignore[replay-host-roundtrip] verification readback: the kernels' results against the blockwise twin's
            errors.append(float(np.linalg.norm(a - b) / np.linalg.norm(b)))
        if not max(errors) < 1e-2:
            raise AssertionError(f"flash attention at {(hkv, groups, d, dv, window)}: o, dq, dk, dv off by {errors}")
        detail[f"group{groups}.head{d}" + (f".value{dv}" if dv != d else "") + (f".window{window}" if window else "")] = {
            "tiles": list(tiles.values()), "output": "heads" if batch is None else "tokens",
            "rel_err": [round(e, 5) for e in errors],
        }
    return detail


def _run_attention_operands(interpret: bool, sizes: SmokeSizes) -> dict:
    import jax
    import jax.numpy as jnp

    from lakesoul_tpu.models.attention import (
        _operand_tiles,
        _operands_backward,
        _operands_forward,
        _turn_tables,
        _xla_operands,
    )

    # a Trinity-Mini row's two kinds of mixer at deployed sizes (8,192 tokens, 32 query heads over 4 key-value
    # heads at head 128; 8 over 2 on 256 tokens at tiny ones), with positions over the whole head and without,
    # and an Ouro row's (16 over 16; 4 over 4), with positions and WITHOUT head norms:
    # the flash kernels' operands and every gradient (raw q, k, v, both norm weights) against the ``jnp`` lines
    # the kernels stand for.  Both sides compute in float32 and round once, so the operands agree to one
    # bfloat16 unit element by element (a sum in another order moves a float32 result by its last bits, and
    # now and then across a rounding boundary); the gradients by norm
    t = min(8192, max(256, sizes.rows // 128))
    d, eps, theta = 128, 1e-5, 10000.0
    keys = jax.random.split(jax.random.key(43), 8)
    detail = {"tokens": t}
    recipes = {
        "turned": ((32, 4) if t == 8192 else (8, 2), d, True), "plain": ((32, 4) if t == 8192 else (8, 2), None, True),
        "turned_no_norm": ((16, 16) if t == 8192 else (4, 4), d, False),
    }
    for name, ((heads, kv), rotary_dim, normed) in recipes.items():
        raw = [jax.random.normal(key, (1, t, n, d)).astype(jnp.bfloat16) for key, n in zip(keys, (heads, kv, kv))]
        wq, wk = [1.0 + 0.1 * jax.random.normal(key, (d,)) for key in keys[3:5]] if normed else (None, None)
        bt = _operand_tiles(t, heads, kv, d, rotary_dim)
        want, pull = jax.vjp(
            jax.jit(lambda *a: _xla_operands(*a, eps=eps, centred=False, rotary_dim=rotary_dim, theta=theta)), *raw, wq, wk  # noqa: B023
        )
        cots = [jax.random.normal(key, a.shape).astype(a.dtype) for key, a in zip(keys[5:], want)]
        turn = None if rotary_dim is None else _turn_tables(t, d, theta)
        flat = [a.reshape(1, t, -1) for a in raw]
        got = _operands_forward(*flat, wq, wk, turn, d=d, eps=eps, bt=bt, interpret=interpret)
        grads = _operands_backward(*cots, *flat[:2], wq, wk, turn, d=d, eps=eps, bt=bt, interpret=interpret)
        units, errors = [], []
        for a, b in zip(got, want, strict=True):
            a, b = (np.asarray(x.astype(jnp.float32)) for x in (a, b))  # lakelint: ignore[replay-host-roundtrip] verification readback: the kernel's operands against the jnp lines'
            units.append(float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)) * 2**7))
            errors.append(float(np.mean(a != b)))
        if not (max(units) <= 1.0 and max(errors) < 1e-2):
            raise AssertionError(f"attention operands, {name}: q, k, v off by {units} units, {errors} of the elements")
        for a, b in zip(grads[:5 if normed else 3], pull(tuple(cots))[:5 if normed else 3], strict=True):
            a, b = (np.asarray(x.astype(jnp.float32)).reshape(b.shape) for x in (a, b))  # lakelint: ignore[replay-host-roundtrip] verification readback: the kernel's gradients against the jnp lines'
            errors.append(float(np.linalg.norm(a - b) / np.linalg.norm(b)))
        if not max(errors[3:]) < 1e-3:
            raise AssertionError(f"attention operands, {name}: dq, dk, dv, dw_q, dw_k off by {errors[3:]}")
        detail[name] = {
            "heads": [heads, kv, d], "block": bt, "units": [round(u, 3) for u in units], "differ": [round(e, 6) for e in errors[:3]],
            "rel_err": [float(f"{e:.3g}") for e in errors[3:]],
        }
    return detail


# --------------------------------------------------------------- multichip


def _run_cross_chip_topk() -> dict:
    import jax

    from lakesoul_tpu.annplane.collective import dryrun_multichip

    n = len(jax.devices())
    dryrun_multichip(n)
    return {"devices": n, "k": 10}


def _run_parallel_dryrun() -> dict:
    """The two parallel multichip shapes (mesh scan→train, pipeline) via
    the repo's dryrun entry: tiny models, real collectives."""
    import importlib.util
    import pathlib

    import jax

    root = pathlib.Path(__file__).resolve().parents[2]
    spec = importlib.util.spec_from_file_location(
        "_lakesoul_graft_entry", root / "__graft_entry__.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    n = len(jax.devices())
    mod.dryrun_multichip(n)
    return {"devices": n}


# -------------------------------------------------------------- tensorplane


def _run_delivery() -> dict:
    """The delivery claim, checked against where the batch landed: on the
    CPU backend the delivered float32 leaf must ALIAS the collate buffer
    (no host copy anywhere); on an accelerator ``device_put`` must be a
    REAL copy across the link."""
    from lakesoul_tpu.tensorplane.dlpack import (
        aligned_empty,
        deliver,
        device_put_copies,
    )

    rng = _rng(6)
    batch = {
        "x": aligned_empty((256, 8), np.float32),
        "y": aligned_empty((256,), np.int32),
    }
    batch["x"][:] = rng.normal(size=(256, 8)).astype(np.float32)
    batch["y"][:] = rng.integers(0, 100, 256).astype(np.int32)
    out = deliver(batch)
    for k in batch:
        np.testing.assert_array_equal(
            np.asarray(out[k]), batch[k]  # lakelint: ignore[replay-host-roundtrip] verification readback: delivered values must round-trip exactly
        )
    platform = next(iter(out["x"].devices())).platform
    f32_copies = device_put_copies(np.float32)
    if platform == "cpu":
        if out["x"].unsafe_buffer_pointer() != batch["x"].ctypes.data:
            raise AssertionError(
                "delivery on the CPU backend must alias the collate buffer"
                " (zero host copies)"
            )
    elif not f32_copies:
        raise AssertionError(
            f"device_put(float32) onto {platform} must be a REAL copy across the link"
        )
    return {"platform": platform, "f32_device_put_copies": bool(f32_copies)}


def _run_replay_cache() -> dict:
    """Pin a four-batch epoch, replay it twice from device memory, and
    check byte-exact equality plus the permutation contract under a pinned
    seed."""
    from lakesoul_tpu.tensorplane.dlpack import deliver
    from lakesoul_tpu.tensorplane.replay import DeviceReplayCache

    rng = _rng(7)
    host = [
        {"x": rng.normal(size=(64, 4)).astype(np.float32)} for _ in range(4)
    ]
    cache = DeviceReplayCache(budget_bytes=1 << 20)
    for hb in host:
        assert cache.offer(64, deliver(hb))
    cache.seal()
    for _ in range(2):
        got = [b for _, b in cache.replay()]
        assert len(got) == len(host)
        for dev, hb in zip(got, host):
            np.testing.assert_array_equal(
                np.asarray(dev["x"]), hb["x"]  # lakelint: ignore[replay-host-roundtrip] verification readback: replayed shards must be byte-identical to the pinned epoch
            )
    perm = DeviceReplayCache(budget_bytes=1 << 20, permute=True, seed=3)
    for hb in host:
        assert perm.offer(64, deliver(hb))
    perm.seal()
    seen = [b for _, b in perm.replay()]
    flat_in = np.sort(np.concatenate([hb["x"].ravel() for hb in host]))
    flat_out = np.sort(
        np.concatenate([np.asarray(b["x"]).ravel() for b in seen])  # lakelint: ignore[replay-host-roundtrip] verification readback: permutation must preserve the multiset
    )
    np.testing.assert_array_equal(flat_out, flat_in)
    return {"batches": len(host), "epochs": 2}


# ------------------------------------------------------------ the register


def smoke_cases() -> list[SmokeCase]:
    return [
        SmokeCase(
            "vector.packed_scan", "pallas", _run_packed_scan,
            kernels=("lakesoul_tpu/vector/kernels.py::_packed_scan_kernel",),
        ),
        SmokeCase(
            "vector.packed_dot", "pallas", _run_packed_dot,
            kernels=("lakesoul_tpu/vector/kernels.py::_packed_dot_kernel",),
        ),
        SmokeCase(
            "vector.packed_dot_batch", "pallas", _run_packed_dot_batch,
            kernels=(
                "lakesoul_tpu/vector/kernels.py::_packed_dot_batch_kernel",
            ),
        ),
        SmokeCase(
            "vector.bruteforce", "pallas", _run_bruteforce,
            kernels=("lakesoul_tpu/vector/kernels.py::_bruteforce_kernel",),
        ),
        SmokeCase(
            "annplane.ragged_score", "pallas", _run_ragged,
            kernels=("lakesoul_tpu/annplane/ragged.py::_ragged_score_kernel",),
        ),
        SmokeCase(
            "models.unit_lower_inverse", "pallas", _run_unit_lower_inverse,
            kernels=("lakesoul_tpu/models/qwen3_next.py::_unit_lower_inverse_kernel",),
        ),
        SmokeCase(
            "models.gated_delta_rule", "pallas", _run_gated_delta,
            kernels=(
                "lakesoul_tpu/models/qwen3_next.py::_gated_delta_fwd_kernel",
                "lakesoul_tpu/models/qwen3_next.py::_gated_delta_bwd_kernel",
            ),
        ),
        SmokeCase(
            "models.causal_attention", "pallas", _run_causal_attention,
            kernels=(
                "lakesoul_tpu/models/attention.py::_flash_fwd_kernel",
                "lakesoul_tpu/models/attention.py::_flash_bwd_kernel",
            ),
        ),
        SmokeCase(
            "models.attention_operands", "pallas", _run_attention_operands,
            kernels=(
                "lakesoul_tpu/models/attention.py::_operands_fwd_kernel",
                "lakesoul_tpu/models/attention.py::_operands_bwd_kernel",
            ),
        ),
        SmokeCase(
            "models.loss_tile", "pallas", _run_loss_tile,
            kernels=("lakesoul_tpu/models/loss_tile.py::_loss_tile_kernel",),
        ),
        SmokeCase(
            "models.selective_scan", "pallas", _run_selective_scan,
            kernels=(
                "lakesoul_tpu/models/selective_scan.py::_scan_fwd_kernel",
                "lakesoul_tpu/models/selective_scan.py::_scan_bwd_kernel",
            ),
        ),
        SmokeCase(
            "parallel.moe_row_copies", "pallas", _run_row_copies,
            kernels=(
                "lakesoul_tpu/parallel/moe.py::_take_rows_kernel",
                "lakesoul_tpu/parallel/moe.py::_put_rows_kernel",
            ),
        ),
        SmokeCase(
            "parallel.moe_expert_dw", "pallas", _run_expert_dw,
            kernels=(
                "lakesoul_tpu/parallel/moe.py::_expert_dw_kernel",
                "lakesoul_tpu/parallel/moe.py::_put_tiles_kernel",
            ),
        ),
        SmokeCase(
            "parallel.moe_grouped_experts", "pallas", _run_grouped_experts,
            kernels=(
                "lakesoul_tpu/parallel/moe.py::_experts_fwd_kernel",
                "lakesoul_tpu/parallel/moe.py::_experts_bwd_kernel",
            ),
        ),
        SmokeCase(
            "parallel.moe_stage_rows", "pallas", _run_stage_rows,
            kernels=(
                "lakesoul_tpu/parallel/moe.py::_stage_rows_kernel",
                "lakesoul_tpu/parallel/moe.py::_unstage_rows_kernel",
            ),
        ),
        SmokeCase(
            "annplane.cross_chip_topk", "multichip", _run_cross_chip_topk,
            min_devices=2,
        ),
        SmokeCase(
            "parallel.mesh_pipeline", "multichip", _run_parallel_dryrun,
            min_devices=2,
        ),
        SmokeCase("tensorplane.delivery", "tensorplane", _run_delivery),
        SmokeCase("tensorplane.replay_cache", "tensorplane", _run_replay_cache),
    ]


def enumerate_pallas_kernels() -> list[str]:
    """Ground truth for the 100%-coverage claim: lakelint's device index
    re-parses the package and returns every ``pl.pallas_call`` kernel
    qname.  The register is checked against THIS, not against a hand list
    that rots."""
    from lakesoul_tpu.analysis.engine import Module, Project, package_root
    from lakesoul_tpu.analysis.rules.jaxtpu import device_index

    pkg = package_root()
    project = Project(root=pkg.parent)
    for path in sorted(pkg.rglob("*.py")):
        mod = Module.load(path, pkg.parent)
        if mod is not None:
            project.modules.append(mod)
    return sorted(device_index(project).pallas_kernels)


def uncovered_kernels() -> list[str]:
    """Enumerated Pallas kernels no register case names."""
    covered = {k for c in smoke_cases() for k in c.kernels}
    return sorted(set(enumerate_pallas_kernels()) - covered)


def run_smoke(
    *, interpret: bool, sizes: SmokeSizes,
    kinds: tuple[str, ...] = ("pallas", "multichip", "tensorplane"),
) -> dict:
    """Run every register case of ``kinds`` and return the report.

    Nothing is caught: the first failing case raises, and so does a Pallas
    kernel the register does not cover — a new kernel cannot land without
    joining it.  A case that needs more devices than are visible is
    reported as ``not run`` (which is not a pass)."""
    import jax

    missing = uncovered_kernels()
    if missing:
        raise AssertionError(f"Pallas kernels missing from the smoke register: {missing}")
    n_devices = len(jax.devices())
    results = []
    for case in smoke_cases():
        if case.kind not in kinds:
            continue
        entry = {"name": case.name, "kind": case.kind}
        if case.min_devices > n_devices:
            entry["status"] = f"not run: {n_devices} device(s), needs {case.min_devices}"
        else:
            t0 = time.perf_counter()
            detail = case.run(interpret, sizes) if case.kind == "pallas" else case.run()
            entry.update(status="pass", seconds=round(time.perf_counter() - t0, 3),
                         detail=detail)
        results.append(entry)
    return {
        "platform": jax.devices()[0].platform,
        "device_count": n_devices,
        "interpret": interpret,
        "cases": results,
    }
