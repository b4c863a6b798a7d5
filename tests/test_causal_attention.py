"""``models/attention.py: causal_attention``: the flash kernels (in the Pallas
interpreter here) against the plain masked softmax and against their blockwise
twin, forward and every gradient, at the five published group and head sizes,
under the causal mask and under a window; the tile lists; which shapes take
the kernels; a value wider than its key (differential attention's: a head of
64 beside a value of 128); where the output lives (token-major through the
kernels' block specs where a head is whole lane tiles: the heads-first kernels'
bits at other addresses); what a checkpoint around the caller keeps; and the mixer's recipe
WITHOUT head norms (the Ouro family's plain attention) on both operand paths.
"""

from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp
import pytest

from lakesoul_tpu.models import attention, causal_lm

# query heads a key-value head, head size
PUBLISHED = {"lfm2": (4, 64), "qwen3-next": (8, 256), "glm-4.7-flash": (1, 256), "trinity-mini": (8, 128),
             "ouro": (1, 128)}
# (tokens, FLASH_KEYS, FLASH_ROWS as a multiple of the group) → the (query, key) tiles of a row
TILINGS = {
    "one-tile": (128, 512, 128),            # 1 x 1: the diagonal tile alone
    "three-by-three": (384, 128, 128),      # skipped, diagonal and full tiles: 6 steps of 9
    "two-query-tiles-a-key-tile": (512, 256, 128),  # 4 x 2: the diagonal crosses a key tile twice
}


def plain_attention(q, k, v, window=None):
    """The whole score matrix, masked, in float32: key ``j`` is visible to
    query ``i`` iff ``0 <= i - j`` and, under a window, ``i - j < window``."""
    f32 = jnp.float32
    t = q.shape[3]
    s = jnp.einsum("bhgqd,bhkd->bhgqk", q.astype(f32), k.astype(f32))
    back = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    s = jnp.where((back >= 0) if window is None else (back >= 0) & (back < window), s, -jnp.inf)
    return tokens_first(jnp.einsum("bhgqk,bhkd->bhgqd", jax.nn.softmax(s, axis=-1), v.astype(f32)))


def tokens_first(o):
    """[B, Hkv, G, T, D] → [B, T, heads, D]: ``causal_attention``'s output
    layout, by the transpose its callers ran before it wrote that itself."""
    b, hkv, groups, t, d = o.shape
    return o.transpose(0, 3, 1, 2, 4).reshape(b, t, hkv * groups, d)


def transposed_sizes(jaxpr_text: str) -> list[int]:
    """The element counts of every array a ``transpose`` in the text writes."""
    return [math.prod(int(n) for n in shape.split(",")) for shape in
            re.findall(r"\w+:\w+\[([\d,]+)\] = transpose\[", jaxpr_text)]


def blockwise(q, k, v, band=256, rows=64, window=None):
    return tokens_first(attention._blockwise_attention(q, k, v, band, rows, window))


def operands(groups, d, t, dtype, *, kv_heads=1, rows=1, dv=None):
    """q, k, v heads first (the value ``dv`` wide where given, else the
    head's size) and a cotangent of the output, tokens first."""
    keys = jax.random.split(jax.random.key(t + d), 4)
    q = (jax.random.normal(keys[0], (rows, kv_heads, groups, t, d)) * d**-0.5).astype(dtype)
    k, v = (jax.random.normal(key, (rows, kv_heads, t, width)).astype(dtype) for key, width in zip(keys[1:3], (d, dv or d)))
    return q, k, v, jax.random.normal(keys[3], (rows, t, kv_heads * groups, dv or d))


def out_and_grads(fn, q, k, v, weigh):
    """(output, (dQ, dK, dV) of ``sum(weigh * output)``)."""
    out, pull = jax.vjp(fn, q, k, v)
    return out, pull(weigh.astype(out.dtype))


def assert_close(got, want, tol):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30)) < tol


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-4), (jnp.bfloat16, 1e-2)], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("tiling", sorted(TILINGS))
@pytest.mark.parametrize("family", sorted(PUBLISHED))
def test_flash_kernels_equal_the_masked_softmax_and_their_twin(monkeypatch, family, tiling, dtype, tol):
    """Output and dQ, dK, dV.  In float32 within the tolerance the families'
    tests hold the blockwise path to; in bfloat16 (operands and output rounded,
    everything between in float32) within a bfloat16 rounding of the float32
    softmax over the same rounded operands, and no further from it than the
    twin is."""
    (groups, d), (t, keys, rows) = PUBLISHED[family], TILINGS[tiling]
    monkeypatch.setattr(attention, "FLASH_KEYS", keys)
    monkeypatch.setattr(attention, "FLASH_ROWS", rows * groups)
    bq, bk = attention._flash_tiles(t, groups, d)
    assert (bq, bk) == (min(rows, t), min(keys, t))
    q, k, v, weigh = operands(groups, d, t, dtype)
    got = out_and_grads(attention.causal_attention, q, k, v, weigh)
    want = out_and_grads(plain_attention, q, k, v, weigh)
    twin = out_and_grads(blockwise, q, k, v, weigh)
    assert_close(got, want, tol)
    assert_close(got, twin, tol)
    assert got[0].dtype == v.dtype and got[1][0].dtype == q.dtype and got[1][1].dtype == k.dtype


WINDOWS = {"one": 1, "one-tile": 128, "two-tiles": 256, "2048": 2048, "no-multiple-of-a-tile": 200}


@pytest.mark.parametrize("window", sorted(WINDOWS))
@pytest.mark.parametrize("family", sorted(PUBLISHED))
def test_window_kernels_equal_the_masked_softmax_and_their_twin(monkeypatch, family, window):
    """Output and dQ, dK, dV under a window, at each published group and head
    size, over 4 x 4 tiles of 128 (a window of 2,048 is the 512-token row's
    causal mask): the banded tile list, the lower edge's mask on a query
    tile's first key tiles, both edges on one tile (a window of 1: a query
    sees itself alone), against the whole mask and against the twin, whose
    bands slice the keys from the first a band sees."""
    (groups, d), t, window = PUBLISHED[family], 512, WINDOWS[window]
    monkeypatch.setattr(attention, "FLASH_KEYS", 128)
    monkeypatch.setattr(attention, "FLASH_ROWS", 128 * groups)
    assert attention._flash_tiles(t, groups, d) == (128, 128)
    q, k, v, weigh = operands(groups, d, t, jnp.float32)
    got = out_and_grads(lambda *qkv: attention.causal_attention(*qkv, window), q, k, v, weigh)
    want = out_and_grads(lambda *qkv: plain_attention(*qkv, window), q, k, v, weigh)
    twin = out_and_grads(lambda *qkv: blockwise(*qkv, window=window), q, k, v, weigh)
    if window == 1:  # every query sees its own key alone: the output is v's rows, and no score has a gradient
        assert_close(got[0], tokens_first(jnp.broadcast_to(v[:, :, None], q.shape)), 1e-6)
        for found in (got, twin):
            assert max(float(jnp.max(jnp.abs(g))) for g in found[1][:2]) < 1e-3  # dQ, dK: 0 but for rounding, where dV is of order 1
        got, want, twin = ((found[0], found[1][2]) for found in (got, want, twin))
    assert_close(got, want, 2e-4)
    assert_close(twin, want, 2e-4)


@pytest.mark.parametrize("family", sorted(PUBLISHED))
def test_a_window_of_the_row_length_is_the_causal_path_bit_for_bit(family):
    """No window, a window of the row's length and a longer one are one
    program: the same tables, the same kernels, the same bits, forward and
    gradients, in the kernels (256 tokens) and in the twin (150)."""
    groups, d = PUBLISHED[family]
    for t in (256, 150):
        q, k, v, weigh = operands(groups, d, t, jnp.bfloat16)
        want = out_and_grads(attention.causal_attention, q, k, v, weigh)
        for window in (t, t + 1, 10 * t):
            got = out_and_grads(lambda *qkv, w=window: attention.causal_attention(*qkv, w), q, k, v, weigh)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
                assert a.dtype == b.dtype and a.tobytes() == b.tobytes()
    assert attention.key_tile_steps(256, groups, d, 256) == attention.key_tile_steps(256, groups, d)


def test_without_a_window_the_tile_tables_are_what_they_were():
    """``_flash_steps(t, bq, bk)`` element for element as the list of every
    (query tile, key tile) on or under the diagonal, which is what the kernels
    walked before they knew a window; ``window=None`` names the same."""
    for t, bq, bk in ((8192, 128, 512), (8192, 256, 512), (8192, 512, 512), (512, 128, 256), (384, 128, 128)):
        before = [(i, j) for i in range(t // bq) for j in range((i * bq + bq - 1) // bk + 1)]
        for tables in (attention._flash_steps(t, bq, bk), attention._flash_steps(t, bq, bk, None)):
            assert [a.dtype for a in tables] == [jnp.int32] * 2
            assert list(zip(*(a.tolist() for a in tables), strict=True)) == before
        assert attention._flash_pairs(t, bq, bk, t) == before  # a window of the row's length hides no tile


def test_a_windows_tile_list_is_the_band():
    """At the Trinity-Mini cell's shape (8,192 tokens, groups of 8 at head 128:
    tiles of 128 queries x 512 keys) a causal list holds 544 steps a key-value
    head and a window of 2,048 holds 280: for every query tile the key tiles
    from the one that holds the first key its first query sees to the
    diagonal's, and no other."""
    assert attention._flash_tiles(8192, 8, 128) == (128, 512)
    assert attention.key_tile_steps(8192, 8, 128) == (544, 544)
    assert attention.key_tile_steps(8192, 8, 128, 2048) == (280, 544)
    assert attention.key_tile_steps(8192, 8, 128, 8192) == (544, 544)
    assert attention.key_tile_steps(150, 8, 128, 40) == (0, 0)  # the twin lists no tile
    pairs = attention._flash_pairs(8192, 128, 512, 2048)
    assert len(pairs) == 280 and pairs[:3] == [(0, 0), (1, 0), (2, 0)] and pairs[-5:] == [(63, j) for j in (11, 12, 13, 14, 15)]
    for i in range(64):
        keys = [j for q, j in pairs if q == i]
        seen = set(range(max(0, 128 * i - 2047), 128 * i + 128))  # the keys any query of the tile sees
        assert keys == sorted({key // 512 for key in seen})
    # the pairs the mask lets through: 43.7% of the triangle, on 51.5% of its tiles
    assert sum(min(i + 1, 2048) for i in range(8192)) == 14_681_088
    qi, kj = attention._flash_steps(512, 128, 128, 200)
    assert list(zip(qi.tolist(), kj.tolist(), strict=True)) == [
        (0, 0), (1, 0), (1, 1), (2, 0), (2, 1), (2, 2), (3, 1), (3, 2), (3, 3)
    ]


WIDE = (2, 64, 128)  # query heads a key-value head, head size, value width: the Phi-4-mini-flash cell's pairs


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-4), (jnp.bfloat16, 1e-2)], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("window", [None, 200], ids=["causal", "window-no-multiple-of-a-tile"])
def test_a_value_wider_than_its_key_equals_the_masked_softmax_and_its_twin(monkeypatch, window, dtype, tol):
    """A head of 64 beside a value of 128, two query heads a key-value head
    (two rows of two key-value heads, 4 x 4 tiles of 128): the output
    [B, T, heads, 128] and dQ, dK [.., 64], dV [.., 128] against whole
    softmaxes in float32 and their ``jax.grad``, and against the twin; the
    kernels run, heads first."""
    groups, d, dv = WIDE
    t = 512
    monkeypatch.setattr(attention, "FLASH_KEYS", 128)
    monkeypatch.setattr(attention, "FLASH_ROWS", 128 * groups)
    assert attention._flash_tiles(t, groups, d, dv) == attention._flash_tiles(t, groups, d) == (128, 128)
    q, k, v, weigh = operands(groups, d, t, dtype, kv_heads=2, rows=2, dv=dv)
    calls = []
    kernel = attention._flash_forward
    monkeypatch.setattr(attention, "_flash_forward", lambda *a, **kw: calls.append((a[2].shape, kw["batch"])) or kernel(*a, **kw))
    got = out_and_grads(lambda *qkv: attention.causal_attention(*qkv, window), q, k, v, weigh)
    assert calls == [((4, t, dv), None)]
    want = out_and_grads(lambda *qkv: plain_attention(*qkv, window), q, k, v, weigh)
    twin = out_and_grads(lambda *qkv: blockwise(*qkv, window=window), q, k, v, weigh)
    assert got[0].shape == (2, t, 2 * groups, dv) and [g.shape for g in got[1]] == [q.shape, k.shape, v.shape]
    assert got[0].dtype == v.dtype and [g.dtype for g in got[1]] == [q.dtype, k.dtype, v.dtype]
    assert_close(got, want, tol)
    assert_close(got, twin, tol)


@pytest.mark.parametrize("window", [None, 200], ids=["causal", "window-no-multiple-of-a-tile"])
def test_a_wide_values_columns_are_the_narrow_kernels_bit_for_bit(monkeypatch, window):
    """A column of ``P V`` does not know its neighbours, and maximum, sum and
    rescaling are the score map's own: the output and dV over a value of 128
    are, half by half, the kernels' over each half of 64 as a value of its
    own, bit for bit (bfloat16, as the models pass them).  dQ and dK come
    from one ``ds`` over the whole value where the halves' calls each round
    their own and the caller adds two bfloat16 numbers: close, and no
    further from the float32 softmax's than that sum is."""
    groups, d, dv = WIDE
    t = 384
    monkeypatch.setattr(attention, "FLASH_KEYS", 128)
    monkeypatch.setattr(attention, "FLASH_ROWS", 128 * groups)
    q, k, v, weigh = operands(groups, d, t, jnp.bfloat16, kv_heads=2, rows=2, dv=dv)
    run = lambda *qkv: attention.causal_attention(*qkv, window)  # noqa: E731
    o, (dq, dk, dvalue) = out_and_grads(run, q, k, v, weigh)
    halves = [out_and_grads(run, q, k, v[..., half], weigh[..., half]) for half in (slice(0, d), slice(d, dv))]
    _bits_equal(o, jnp.concatenate([o_half for o_half, _ in halves], axis=-1))
    _bits_equal(dvalue, jnp.concatenate([grads[2] for _, grads in halves], axis=-1))
    f32 = jnp.float32
    summed = [sum(grads[n].astype(f32) for _, grads in halves) for n in (0, 1)]
    assert_close((dq, dk), summed, 1e-2)
    want = out_and_grads(lambda *qkv: plain_attention(*qkv, window), *(a.astype(f32) for a in (q, k, v)), weigh)[1][:2]
    for got, halved, exact in zip((dq, dk), summed, want, strict=True):
        off = lambda a: float(jnp.linalg.norm(a.astype(f32) - exact))  # noqa: E731
        assert off(got) <= 1.05 * off(halved.astype(jnp.bfloat16))


@pytest.mark.parametrize("t, groups, d, dv, tiles", [
    (8192, 2, 64, 128, (512, 512)),    # the Phi-4-mini-flash cell's pairs: the head's tiles
    (8192, 2, 64, 64, (512, 512)),
    (8192, 2, 64, None, (512, 512)),
    (8192, 8, 128, 256, (128, 512)),
    (256, 2, 64, 96, None),            # a value the kernels were not compiled for
    (16384, 8, 128, 256, None),        # a row's dV outgrows VMEM where its dK does not
    (16384, 8, 128, 128, (128, 512)),
])
def test_which_value_widths_take_the_kernels(t, groups, d, dv, tiles):
    assert attention._flash_tiles(t, groups, d, dv) == tiles
    assert attention.key_tile_steps(t, groups, d, None, dv)[0] == (0 if tiles is None else len(attention._flash_pairs(t, *tiles)))
    assert attention._token_major(t, groups, d, dv) == (tiles is not None and d % 128 == 0 and dv in (None, d))


def test_a_key_tile_after_the_query_tile_is_no_step():
    """The grid is the list of tiles on or under the diagonal: 6 of 8 where two
    query tiles share a key tile, each query tile's last step its diagonal."""
    qi, kj = attention._flash_steps(512, 128, 256)
    assert list(zip(qi.tolist(), kj.tolist(), strict=True)) == [(0, 0), (1, 0), (2, 0), (2, 1), (3, 0), (3, 1)]
    qi, kj = attention._flash_steps(8192, 256, 512)
    assert qi.shape[0] == sum(i // 2 + 1 for i in range(32)) == 272  # of 32 x 16 = 512


@pytest.mark.parametrize("t, groups, d, tiles", [
    (8192, 4, 64, (256, 512)),    # the LFM2 cell's layer
    (8192, 8, 256, (128, 512)),   # the Qwen cell's
    (8192, 1, 128, (512, 512)),
    (8192, 8, 128, (128, 512)),   # the Trinity-Mini cell's: window and full layers alike
    (8192, 1, 256, (512, 512)),   # the GLM cell's: latent attention, 20 key-value heads of one query head each
    (256, 4, 64, (256, 256)),
    (128, 16, 64, (128, 128)),    # 128 queries at least: the log-sum-exp's lane tile
    (150, 4, 64, None),           # not whole tiles of 128 keys: the families' tests' length
    (256, 4, 8, None), (256, 4, 96, None), (256, 4, 512, None),  # heads the kernels were not compiled for
    (16384, 8, 256, None),        # a row's dK and dV outgrow VMEM
    (16384, 4, 64, (256, 512)),
])
def test_which_shapes_take_the_kernels(monkeypatch, t, groups, d, tiles):
    assert attention._flash_tiles(t, groups, d) == tiles
    if t > 256:
        return
    calls = []
    kernel = attention._flash_forward
    monkeypatch.setattr(attention, "_flash_forward", lambda *a, **k: calls.append(k) or kernel(*a, **k))
    q, k, v, _ = operands(groups, d, t, jnp.float32)
    attention.causal_attention(q, k, v)
    # no TPU here: the interpreter
    # no TPU here: the interpreter; ``batch``: the rows, where the output is written token-major
    batch = 1 if d % 128 == 0 else None
    assert calls == ([] if tiles is None else [
        {"bq": tiles[0], "bk": tiles[1], "window": None, "batch": batch, "interpret": True}
    ])


def _bits_equal(got, want):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert a.shape == b.shape and a.dtype == b.dtype and a.tobytes() == b.tobytes()


def _heads_first_kernels(q, k, v, weigh, window):
    """The flash pair as it writes the output heads first (no ``batch``), its
    output laid tokens first by a transpose and the cotangent heads first by
    the transpose back: what ``causal_attention``'s callers ran before the
    kernels' block specs wrote that layout themselves."""
    b, hkv, groups, t, d = q.shape
    bq, bk = attention._flash_tiles(t, groups, d)
    q4, k3, v3 = q.reshape(b * hkv, groups, t, d), k.reshape(b * hkv, t, d), v.reshape(b * hkv, t, d)
    o, lse = attention._flash_forward(q4, k3, v3, bq=bq, bk=bk, window=window, interpret=True)
    assert o.shape == q4.shape
    do = weigh.astype(o.dtype).reshape(b, t, hkv, groups, d).transpose(0, 2, 3, 1, 4).reshape(q4.shape)
    dq, dk, dv = attention._flash_backward(q4, k3, v3, o, lse, do, bq=bq, bk=bk, window=window, interpret=True)
    return tokens_first(o.reshape(q.shape)), (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape))


# query heads a key-value head, head size, window: a head of whole lane tiles
TOKEN_MAJOR = {"trinity-mini": (8, 128, None), "trinity-mini-window": (8, 128, 200), "ouro": (1, 128, None),
               "glm-4.7-flash": (1, 256, None), "qwen3-next": (8, 256, None)}


@pytest.mark.parametrize("case", sorted(TOKEN_MAJOR))
def test_the_token_major_output_is_the_heads_first_kernels_bit_for_bit(monkeypatch, case):
    """Where a head is whole 128-lane tiles the forward kernel writes ``o``
    token-major through its block spec and the backward kernel reads ``do``
    and the kept ``o`` there (and sums ``delta`` from them once a query tile,
    where the heads-first kernel is handed XLA's sum): the same arithmetic at
    other addresses, so the output and dQ, dK, dV for a random cotangent are
    the heads-first kernels' bit for bit (two rows of two key-value heads: the
    block index is the row's and the head's; 3 x 3 tiles; bfloat16 as the
    models pass them)."""
    groups, d, window = TOKEN_MAJOR[case]
    t = 384
    monkeypatch.setattr(attention, "FLASH_KEYS", 128)
    monkeypatch.setattr(attention, "FLASH_ROWS", 128 * groups)
    assert attention._flash_tiles(t, groups, d) == (128, 128) and attention._token_major(t, groups, d)
    q, k, v, weigh = operands(groups, d, t, jnp.bfloat16, kv_heads=2, rows=2)
    calls = []
    kernel = attention._flash_forward
    monkeypatch.setattr(attention, "_flash_forward", lambda *a, **kw: calls.append(kw["batch"]) or kernel(*a, **kw))
    got = out_and_grads(lambda *qkv: attention.causal_attention(*qkv, window), q, k, v, weigh)
    assert calls == [2] and got[0].shape == (2, t, 2 * groups, d)
    monkeypatch.setattr(attention, "_flash_forward", kernel)
    _bits_equal(got, _heads_first_kernels(q, k, v, weigh, window))
    text = str(jax.make_jaxpr(lambda *qkv: jax.vjp(attention.causal_attention, *qkv)[1](weigh.astype(q.dtype)))(q, k, v))
    # the transposes left are inside the kernels (the interpreter shows them): the log-sum-exp's and ``delta``'s
    # [G*bq, 128], a float a query and head along the lanes; nothing the size of a row's output
    sizes = transposed_sizes(text)
    assert sizes and set(sizes) == {128 * groups * 128}


@pytest.mark.parametrize("groups, d, t", [(4, 64, 256), (8, 128, 150), (4, 96, 256)],
                         ids=["head-64", "no-whole-tiles", "head-96"])
def test_a_head_of_64_and_a_refused_shape_are_laid_out_by_the_transpose(groups, d, t):
    """Two heads of 64 share a lane tile, and a shape the kernels refuse runs
    the blockwise twin: both write heads first as before and
    ``causal_attention`` lays the output tokens first by the transpose its
    callers ran; values and gradients bit for bit today's."""
    assert not attention._token_major(t, groups, d)
    q, k, v, weigh = operands(groups, d, t, jnp.bfloat16, kv_heads=2, rows=2)
    got = out_and_grads(attention.causal_attention, q, k, v, weigh)
    if attention._flash_tiles(t, groups, d) is None:
        want = out_and_grads(lambda *qkv: blockwise(*qkv, attention.ATTN_BAND, attention.ATTN_ROWS), q, k, v, weigh.astype(q.dtype))
    else:
        want = _heads_first_kernels(q, k, v, weigh, None)
    _bits_equal(got, want)
    assert max(transposed_sizes(str(jax.make_jaxpr(attention.causal_attention)(q, k, v)))) == q.size


def test_a_row_checkpoint_keeps_the_output_and_the_log_sum_exp():
    """``_row_by_row`` rematerialises a mixer a row at a time; of the attention
    kernels' residuals it keeps the two that are named, so the backward pass
    runs the forward kernel for no row again: once in the program, where a
    plain ``jax.checkpoint`` has it twice."""
    groups, d, t = 4, 64, 128
    q, k, v, weigh = operands(groups, d, t, jnp.float32)
    x = jnp.stack([q[0], 2 * q[0]])

    def mixer(row, p):
        return attention.causal_attention(row * p, k, v)

    def grad(rows):
        return jax.grad(lambda p: jnp.sum(weigh[0] * rows(mixer, x, p)))

    def forward_kernels(rows):
        return str(jax.make_jaxpr(grad(rows))(jnp.float32(1.0))).count("name=flash_attention_fwd")

    def plain_rows(mixer, x, p):
        return jax.lax.map(jax.checkpoint(lambda row: mixer(row[None], p)[0]), x)

    def kept_rows(mixer, x, p):
        return causal_lm._row_by_row(mixer, x, p, None)

    assert forward_kernels(plain_rows) == 2
    assert forward_kernels(kept_rows) == 1
    assert_close(grad(kept_rows)(jnp.float32(1.0)), grad(plain_rows)(jnp.float32(1.0)), 1e-6)


# ------------------------------------------------- the recipe without head norms


def _f32(x):
    import numpy as np

    return np.asarray(x.astype(jnp.float32))


@pytest.mark.parametrize("turned", [True, False], ids=["rotary-whole", "rotary-none"])
@pytest.mark.parametrize("heads, kv", [(4, 4), (8, 2)], ids=["groups-of-1", "groups-of-4"])
def test_the_operand_kernels_without_head_norms_are_the_xla_lines(heads, kv, turned):
    """No ``q_norm`` and no ``k_norm``: the kernel pair turns, scales, casts
    and lays out, and norms nothing; operands bit for bit or within one
    bfloat16 unit of ``_xla_operands`` with both weights None, the raw q, k, v
    cotangents by norm, and no gradient for a weight that is not there."""
    import numpy as np

    t, d = 256, 128
    keys = jax.random.split(jax.random.key(11), 6)
    q, k, v = (jax.random.normal(key, (2, t, n, d)).astype(jnp.bfloat16) for key, n in zip(keys, (heads, kv, kv)))
    recipe = dict(eps=1e-6, centred=False, rotary_dim=d if turned else None, theta=1e6)
    bt = attention._operand_tiles(t, heads, kv, d, recipe["rotary_dim"])
    assert bt == t
    want, pull_want = jax.vjp(lambda *a: attention._xla_operands(*a, None, None, **recipe), q, k, v)
    got, pull_got = jax.vjp(lambda *a: attention._kernel_operands(*a, None, None, bt, **recipe), q, k, v)
    for a, b in zip(got, want, strict=True):
        assert a.shape == b.shape and a.dtype == b.dtype == jnp.bfloat16
        a, b = _f32(a), _f32(b)
        # one bfloat16 unit; an element whose two turned halves cancel (1e-6 where each is 1) keeps float32's last bits
        assert np.all(np.abs(a - b) <= np.abs(b) * 2.0**-7 + 1e-5) and np.mean(a != b) < 1e-3
    # not normed: the raw query times its scale and, unturned, nothing else
    if not turned:
        scaled = (q.astype(jnp.float32) * d**-0.5).astype(jnp.bfloat16).reshape(2, t, kv, heads // kv, d)
        np.testing.assert_array_equal(_f32(got[0]), _f32(scaled.transpose(0, 2, 3, 1, 4)))
    cots = tuple(jax.random.normal(key, a.shape).astype(a.dtype) for key, a in zip(keys[3:], want))
    for a, b, limit in zip(pull_got(cots), pull_want(cots), (2e-4, 2e-4, 0.0), strict=True):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert np.linalg.norm(_f32(a) - _f32(b)) <= limit * np.linalg.norm(_f32(b))


@pytest.mark.parametrize("path", ["operand-kernels", "xla-lines"])
def test_a_mixer_whose_weights_hold_no_head_norm_norms_no_head(path, monkeypatch):
    """``softmax_attention`` over weights without ``q_norm`` and ``k_norm``,
    through ``_row_by_row``'s checkpoint, value and every gradient, on either
    operand path: equal to the plain masked softmax over the rotated raw
    heads, and far from the same mixer with norms of weight 1."""
    import functools

    import numpy as np

    h, heads, kv, d, t = 64, 2, 2, 128, 256
    keys = jax.random.split(jax.random.key(13), 6)
    x = jax.random.normal(keys[0], (1, t, h))
    p = {name: causal_lm.normal_init(key, *shape) * 10 for name, key, shape in (
        ("w_q", keys[1], (h, heads * d)), ("w_k", keys[2], (h, kv * d)), ("w_v", keys[3], (h, kv * d)),
        ("w_o", keys[4], (heads * d, h)))}
    cot = jax.random.normal(keys[5], x.shape)
    mixer = functools.partial(
        causal_lm.softmax_attention, heads=heads, kv_heads=kv, head_dim=d, rotary_dim=d, theta=1e6, eps=1e-6,
        centred=False, gated=False,
    )
    if path == "xla-lines":
        monkeypatch.setattr(attention, "_operand_tiles", lambda *shape: None)
    counts = attention.mixer_counts(mixer, x, p)
    assert (counts["attn_operands_kernel"], counts["attn_operands_xla"]) == ((1, 0) if path == "operand-kernels" else (0, 1))

    def plain(x, p):
        q, k, v = ((x @ p[w]).reshape(1, t, n, d) for w, n in (("w_q", heads), ("w_k", kv), ("w_v", kv)))
        positions = jnp.arange(t)
        q, k = (attention._rotary(a, positions, d, 1e6) for a in (q, k))
        o = plain_attention(q.transpose(0, 2, 1, 3)[:, :, None] * d**-0.5, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
        return o.reshape(1, t, heads * d) @ p["w_o"]

    got = out_and_grads(lambda x, p, _: causal_lm._row_by_row(mixer, x, p, None), x, p, None, cot)
    want = out_and_grads(lambda x, p, _: plain(x, p), x, p, None, cot)
    assert_close(got, want, 2e-4)
    normed = mixer(x, dict(p, q_norm=jnp.ones((d,)), k_norm=jnp.ones((d,))))
    assert float(jnp.linalg.norm(normed - got[0]) / jnp.linalg.norm(got[0])) > 0.1
