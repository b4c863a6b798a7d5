"""The selective scan of a Mamba-1 layer (Gu & Dao, arXiv:2312.00752, section
3): per channel ``e`` and state ``n`` a scalar recurrence whose decay and
input depend on the token,

    ``s_t[e, n] = exp(Delta_t[e] A[e, n]) s_{t-1}[e, n] + Delta_t[e] u_t[e] B_t[n]``,  ``s_0 = 0``
    ``y_t[e]    = sum_n s_t[e, n] C_t[n] + D[e] u_t[e]``

over u [rows, T, E], Delta [rows, T, E] float32, A [E, N] (negative), B, C
[rows, T, N], D [E] → y [rows, T, E] in u's type.  There is no product in it:
``T x E x N`` exponentials and a handful of multiply-adds each, one after the
other along ``T``.  Written out, the discretised ``[T, E, N]`` tensors are
2.7 GB each in float32 for one 8k row at E 5,120 and N 16, so neither pass
may hold them in HBM.

Where :func:`scan_takes` takes the shape (channels in whole 128-lane tiles,
states in whole sublane tiles) a Pallas kernel pair under one ``custom_vjp``
does it, compiled on a TPU and in the Pallas interpreter elsewhere
(``selective_scan_fwd``, ``selective_scan_bwd``): a block of channels' state
[N, Eb] float32 stays in VMEM through a row's :data:`SCAN_TOKENS`-token
blocks, channels along the lanes and states down the sublanes.  The forward
kernel also writes the state each block starts from (``[T / 128, N, E]``
float32: 21 MB a row, named :data:`SCAN_KEPT` for a checkpoint around the
caller); the backward kernel walks the blocks from the last, computes a
block's states again from that boundary into VMEM, and runs the cotangent's
recurrence back through them.  Every other shape runs :func:`_scan_twin`, a
``lax.scan`` over tokens, which is also what the kernels are held to.

Nothing here names a family, a layer or a loss, and nothing of a BERT step's
process imports this module.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lakesoul_tpu.utils import platform

SCAN_KEPT = "ssm_bounds"   # ``checkpoint_name`` of what the backward kernel keeps: the state each block starts from
SCAN_TOKENS = 128          # tokens a block holds: the backward kernel keeps a block's states in VMEM (4 MB at 512 channels)
SCAN_CHANNELS = (512, 256, 128)  # channels a block holds: the largest that divides E
SCAN_VMEM_BYTES = 64 * 2**20     # of a v5e's 128 MiB
SCAN_UNROLL = 8            # tokens a trip of the kernels' loops holds: one sublane tile of the row blocks


def scan_takes(e: int, n: int) -> int | None:
    """Channels a block of the kernels holds, or None where they do not take
    the shape: channels in whole 128-lane tiles, states in whole 8-sublane
    tiles (any row length: a row is padded to whole blocks with tokens that
    leave the state as it is)."""
    if e % 128 or n % 8:
        return None
    return next(c for c in SCAN_CHANNELS if e % c == 0)


def _scan_twin(u, delta, a, b, c, d):
    """The recurrence a token at a time as a ``lax.scan`` over whole arrays,
    float32 (its transpose keeps every state: sizes a test runs)."""
    f32 = jnp.float32
    uf = u.astype(f32)

    def token(s, xs):
        u_t, dt, b_t, c_t = xs  # [rows, E], [rows, E], [rows, N], [rows, N]
        s = jnp.exp(dt[..., None] * a) * s + (dt * u_t)[..., None] * b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], axis=-1) + d * u_t

    s0 = jnp.zeros((u.shape[0], *a.shape), f32)
    _, y = jax.lax.scan(token, s0, tuple(jnp.moveaxis(x.astype(f32), 1, 0) for x in (uf, delta, b, c)))
    return jnp.moveaxis(y, 0, 1).astype(u.dtype)


def _lanes(x, n: int):
    """x [N, 128], every lane of a row the same, as [N, n]."""
    return x if n == 128 else jnp.tile(x, (1, n // 128))


def _advanced(s, dt, u_t, a, b_t):
    """A token's update of a block's state [N, Eb]: ``exp(Delta_t A) s +
    (Delta_t u_t) B_t``, Delta_t and u_t rows [1, Eb], B_t [N, Eb]."""
    return jnp.exp(dt * a) * s + (dt * u_t) * b_t


def _scan_fwd_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, bounds_ref, s_ref, uf_ref, yf_ref):
    """One block: :data:`SCAN_TOKENS` tokens of ``Eb`` channels.  u [Tb, Eb],
    Delta [Tb, Eb] float32, A^T [N, Eb], B and C [Tb, N, 128] (a token's
    states down the sublanes, every lane the same), D [1, Eb] → y [Tb, Eb] and
    the state the block starts from [N, Eb]; the state lives in ``s_ref``
    through a row's blocks."""
    tokens, eb = u_ref.shape
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    bounds_ref[...] = s_ref[...]
    uf_ref[...] = u_ref[...].astype(f32)
    a, d = a_ref[...], d_ref[...]

    def trip(i, s):
        base = pl.multiple_of(i * SCAN_UNROLL, SCAN_UNROLL)
        dts, us = dt_ref[pl.ds(base, SCAN_UNROLL), :], uf_ref[pl.ds(base, SCAN_UNROLL), :]
        rows = []
        for j in range(SCAN_UNROLL):
            dt, u_t = dts[j:j + 1], us[j:j + 1]
            s = _advanced(s, dt, u_t, a, _lanes(b_ref[base + j], eb))
            rows.append(jnp.sum(s * _lanes(c_ref[base + j], eb), axis=0, keepdims=True) + d * u_t)
        yf_ref[pl.ds(base, SCAN_UNROLL), :] = jnp.concatenate(rows, axis=0)
        return s

    s_ref[...] = jax.lax.fori_loop(0, tokens // SCAN_UNROLL, trip, s_ref[...])
    y_ref[...] = yf_ref[...].astype(y_ref.dtype)


def _scan_bwd_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, bounds_ref, dy_ref,
                     du_ref, ddt_ref, da_ref, db_ref, dc_ref, dd_ref,
                     st_ref, h_ref, uf_ref, dyf_ref, duf_ref):
    """One block of the backward pass, the blocks of a row walked from the
    last: the block's states again from its boundary into ``st_ref``
    [Tb + 1, N, Eb], then the tokens from the last with the state's cotangent
    carried in ``h_ref`` (already decayed into the token before).  With
    ``a_t = exp(Delta_t A)``, ``g_t = C_t dy_t + a_{t+1} g_{t+1}`` the state's
    cotangent and ``w_t = a_t g_t s_{t-1}``:

        ``dC_t = sum_e s_t dy_t``, ``dB_t = sum_e g_t Delta_t u_t``, ``q_t = sum_n g_t B_t``,
        ``dDelta_t = sum_n w_t A + u_t q_t``, ``du_t = Delta_t q_t + D dy_t``,
        ``dA += w_t Delta_t``, ``dD += dy_t u_t``.

    dB and dC leave as [N, Tb] tiles (a token a lane), summed over this
    block's channels alone; dA [N, Eb] and dD [1, Eb] add up over a row's
    blocks in their output blocks."""
    tokens, eb = u_ref.shape
    n = a_ref.shape[0]
    f32 = jnp.float32
    first = pl.program_id(2) == 0  # the row's LAST block: the walk starts here

    @pl.when(first)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    uf_ref[...] = u_ref[...].astype(f32)
    dyf_ref[...] = dy_ref[...].astype(f32)
    a, d = a_ref[...], d_ref[...]

    def forward(i, s):
        base = pl.multiple_of(i * SCAN_UNROLL, SCAN_UNROLL)
        dts, us = dt_ref[pl.ds(base, SCAN_UNROLL), :], uf_ref[pl.ds(base, SCAN_UNROLL), :]
        for j in range(SCAN_UNROLL):
            dt, u_t = dts[j:j + 1], us[j:j + 1]
            s = _advanced(s, dt, u_t, a, _lanes(b_ref[base + j], eb))
            st_ref[base + j + 1] = s
        return s

    st_ref[0] = bounds_ref[...]
    jax.lax.fori_loop(0, tokens // SCAN_UNROLL, forward, bounds_ref[...])

    lane = jax.lax.broadcasted_iota(jnp.int32, (n, tokens), 1)

    def backward(i, carry):
        h, da, dd, db, dc = carry
        base = pl.multiple_of(tokens - (i + 1) * SCAN_UNROLL, SCAN_UNROLL)
        dts, us = dt_ref[pl.ds(base, SCAN_UNROLL), :], uf_ref[pl.ds(base, SCAN_UNROLL), :]
        dys = dyf_ref[pl.ds(base, SCAN_UNROLL), :]
        du_rows, ddt_rows = [None] * SCAN_UNROLL, [None] * SCAN_UNROLL
        for j in reversed(range(SCAN_UNROLL)):
            t = base + j
            dt, u_t, dy = dts[j:j + 1], us[j:j + 1], dys[j:j + 1]
            b_t = _lanes(b_ref[t], eb)
            g = h + _lanes(c_ref[t], eb) * dy
            dc_t = jnp.sum(st_ref[t + 1] * dy, axis=1, keepdims=True)  # [N, 1]
            db_t = jnp.sum(g * (dt * u_t), axis=1, keepdims=True)
            dc = jnp.where(lane == t, dc_t, dc)
            db = jnp.where(lane == t, db_t, db)
            q = jnp.sum(g * b_t, axis=0, keepdims=True)  # [1, Eb]
            h = jnp.exp(dt * a) * g
            w = h * st_ref[t]
            ddt_rows[j] = jnp.sum(w * a, axis=0, keepdims=True) + u_t * q
            du_rows[j] = dt * q + d * dy
            da = da + w * dt
            dd = dd + dy * u_t
        duf_ref[pl.ds(base, SCAN_UNROLL), :] = jnp.concatenate(du_rows, axis=0)
        ddt_ref[pl.ds(base, SCAN_UNROLL), :] = jnp.concatenate(ddt_rows, axis=0)
        return h, da, dd, db, dc

    zeros = jnp.zeros((n, tokens), f32)
    h, da, dd, db, dc = jax.lax.fori_loop(
        0, tokens // SCAN_UNROLL, backward, (h_ref[...], da_ref[...], dd_ref[...], zeros, zeros)
    )
    h_ref[...], da_ref[...], dd_ref[...] = h, da, dd
    db_ref[...], dc_ref[...] = db, dc
    du_ref[...] = duf_ref[...].astype(du_ref.dtype)


def _scan_grid(u, n: int, eb: int, *, back: bool, in_specs, out_specs, scratch_shapes):
    """What the two kernels' ``pallas_call``s share, over u's [rows, T, E]:
    the grid (rows, channel blocks, token blocks; the backward kernel's token
    blocks from the last) and block specs by what a block follows: a
    ``tokens`` block [Tb, Eb], a block's ``states`` B or C [Tb, N, 128],
    ``channels`` [N, Eb] of A^T, ``one`` [1, Eb] of D, a ``boundary`` [N, Eb]
    of the [rows, T / Tb, N, E] states, and the backward kernel's sums: ``da``
    [N, Eb] and ``dd`` [1, Eb] a row, ``dstates`` [N, Tb] a row and channel
    block."""
    rows, t, e = u.shape
    tb = SCAN_TOKENS
    blocks = t // tb

    def at(k):
        return blocks - 1 - k if back else k

    specs = {
        "tokens": pl.BlockSpec((None, tb, eb), lambda r, c, k: (r, at(k), c)),
        "states": pl.BlockSpec((None, tb, n, 128), lambda r, c, k: (r, at(k), 0, 0)),
        "channels": pl.BlockSpec((n, eb), lambda r, c, k: (0, c)),
        "one": pl.BlockSpec((1, eb), lambda r, c, k: (0, c)),
        "boundary": pl.BlockSpec((None, None, n, eb), lambda r, c, k: (r, at(k), 0, c)),
        "da": pl.BlockSpec((None, n, eb), lambda r, c, k: (r, 0, c)),
        "dd": pl.BlockSpec((None, 1, eb), lambda r, c, k: (r, 0, c)),
        "dstates": pl.BlockSpec((None, None, n, tb), lambda r, c, k: (r, c, 0, at(k))),
    }
    return dict(
        grid=(rows, e // eb, blocks),
        in_specs=[specs[s] for s in in_specs], out_specs=[specs[s] for s in out_specs],
        scratch_shapes=scratch_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=SCAN_VMEM_BYTES
        ),
    )


def _states_along_lanes(x):
    """B or C [rows, T, N] float32 as the kernels read it, [rows, T, N, 128]:
    a token's states down the sublanes, every lane the same."""
    return jnp.broadcast_to(x.astype(jnp.float32)[..., None], (*x.shape, 128))


@functools.partial(jax.jit, static_argnames=("eb", "interpret"))
def _scan_forward(u, delta, at, b, c, d, *, eb: int, interpret: bool):
    """u [rows, T, E] (T whole blocks), Delta float32, A^T [N, E], B, C
    [rows, T, N], D [E] → (y as u, the state each block starts from
    [rows, T / Tb, N, E] float32)."""
    rows, t, e = u.shape
    n = at.shape[0]
    f32 = jnp.float32
    grid = _scan_grid(
        u, n, eb, back=False, in_specs=("tokens", "tokens", "channels", "states", "states", "one"),
        out_specs=("tokens", "boundary"),
        scratch_shapes=[pltpu.VMEM((n, eb), f32)] + [pltpu.VMEM((SCAN_TOKENS, eb), f32)] * 2,
    )
    return pl.pallas_call(
        _scan_fwd_kernel,
        out_shape=(jax.ShapeDtypeStruct(u.shape, u.dtype), jax.ShapeDtypeStruct((rows, t // SCAN_TOKENS, n, e), f32)),
        name="selective_scan_fwd", interpret=interpret, **grid,
    )(u, delta, at, _states_along_lanes(b), _states_along_lanes(c), d[None])


@functools.partial(jax.jit, static_argnames=("eb", "interpret"))
def _scan_backward(u, delta, at, b, c, d, bounds, dy, *, eb: int, interpret: bool):
    """The six cotangents in their arguments' shapes, float32 but u's."""
    rows, t, e = u.shape
    n = at.shape[0]
    f32 = jnp.float32
    tb = SCAN_TOKENS
    grid = _scan_grid(
        u, n, eb, back=True,
        in_specs=("tokens", "tokens", "channels", "states", "states", "one", "boundary", "tokens"),
        out_specs=("tokens", "tokens", "da", "dstates", "dstates", "dd"),
        scratch_shapes=[pltpu.VMEM((tb + 1, n, eb), f32), pltpu.VMEM((n, eb), f32)] + [pltpu.VMEM((tb, eb), f32)] * 3,
    )
    per_block = jax.ShapeDtypeStruct((rows, e // eb, n, t), f32)
    du, ddt, da, db, dc, dd = pl.pallas_call(
        _scan_bwd_kernel,
        out_shape=(jax.ShapeDtypeStruct(u.shape, u.dtype), jax.ShapeDtypeStruct(u.shape, f32),
                   jax.ShapeDtypeStruct((rows, n, e), f32), per_block, per_block,
                   jax.ShapeDtypeStruct((rows, 1, e), f32)),
        name="selective_scan_bwd", interpret=interpret, **grid,
    )(u, delta, at, _states_along_lanes(b), _states_along_lanes(c), d[None], bounds, dy)
    db, dc = (jnp.swapaxes(x.sum(1), 1, 2) for x in (db, dc))  # over the channel blocks; [rows, T, N]
    return du, ddt, da.sum(0), db, dc, dd.sum((0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(u, delta, at, b, c, d, eb):
    return _scan_fwd(u, delta, at, b, c, d, eb)[0]


def _scan_fwd(u, delta, at, b, c, d, eb):
    y, bounds = _scan_forward(u, delta, at, b, c, d, eb=eb, interpret=not platform.on_tpu())
    # a checkpoint around the caller may keep the boundaries and run no second forward kernel
    return y, (u, delta, at, b, c, d, checkpoint_name(bounds, SCAN_KEPT))


def _scan_bwd(eb, kept, dy):
    return _scan_backward(*kept, dy, eb=eb, interpret=not platform.on_tpu())


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(u, delta, a, b, c, d):
    """The recurrence at the head of this file: u [rows, T, E], Delta
    [rows, T, E] float32, A [E, N] float32, B, C [rows, T, N] float32, D [E]
    float32 → y [rows, T, E] in u's type; the state, the exponentials and the
    sums float32.  By the kernel pair where :func:`scan_takes` takes the shape
    (the row padded to whole blocks with tokens of ``Delta = 0``, which leave
    the state as it is), else by :func:`_scan_twin`."""
    f32 = jnp.float32
    e, n = a.shape
    eb = scan_takes(e, n)
    delta, a, b, c, d = (x.astype(f32) for x in (delta, a, b, c, d))
    if eb is None:
        return _scan_twin(u, delta, a, b, c, d)
    t = u.shape[1]
    pad = -t % SCAN_TOKENS
    if pad:
        u, delta, b, c = (jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in (u, delta, b, c))
    return _scan(u, delta, a.T, b, c, d, eb)[:, :t]
