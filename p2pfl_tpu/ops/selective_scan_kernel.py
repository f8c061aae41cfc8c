"""Pallas TPU kernels for the recurrence of ``ops/selective_scan.py``.

``p2pfl_ssm_scan_fwd``: grid (batch, channel blocks, chunks); time runs INSIDE
the kernel (a ``fori_loop`` over the chunk's steps) and the state of one
channel block lives in VMEM across the chunk axis, so HBM sees the inputs once,
the output once and one boundary state a chunk — never a per-step state.

``p2pfl_ssm_scan_bwd``: grid (batch, chunks LAST TO FIRST, channel blocks). A
grid step re-runs its chunk forward from the saved boundary state, keeping the
chunk's per-step states in VMEM (``chunk × N`` tiles, 4 MB at 64 steps), then
walks the chunk backwards with the state's gradient carried in VMEM from the
chunk after it. ``dB_t[n]`` and ``dC_t[n]`` are sums over ALL channels: the
kernel adds up whole ``[8, 128]`` tiles across the channel blocks (the grid's
innermost axis, so the output block stays resident) and leaves the last
reduction, 1024 lanes to one, to XLA — no cross-lane reduction in the loop.

Both are called by ``selective_scan``'s forward and backward rules, under their
``p2pfl.ssm_scan_fwd`` / ``p2pfl.ssm_scan_bwd`` scopes.

Layout. Everything in the recurrence is elementwise over channels, so a block
of 1024 channels is laid out as one ``[8, 128]`` vreg tile and the state of the
block is ``N`` such tiles. ``B_t[n]`` and ``C_t[n]`` are the same for every
channel: they sit in SMEM and enter as scalar operands — no transposes, no
cross-lane reductions, every vector op on full tiles. The price is paid
outside: ``u``, ``Δ`` and ``y`` cross between ``[T, Dm]`` and
``[T, Dm / 128, 128]`` in XLA (one relayout pass each).

The forward's chunk axis is ``arbitrary`` (the VMEM state carries across it),
batch and channel blocks ``parallel``; the backward's chunk and channel-block
axes are both ``arbitrary`` (carried gradient, accumulated output block).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _rows(dm: int) -> int:
    """Sublane rows of a channel block: 8 (a whole vreg) where they divide the
    ``dm / 128`` rows, else all rows in one block (small widths: the tests)."""
    rows = dm // LANES
    return 8 if rows % 8 == 0 else rows


def _padded(x: jax.Array, chunk: int) -> jax.Array:
    """``[B, T, ...]`` in float32, zero-padded to whole chunks (Δ = 0 steps)."""
    return jnp.pad(x.astype(jnp.float32), ((0, 0), (0, -x.shape[1] % chunk), (0, 0)))


def _tiles(x: jax.Array, chunk: int) -> jax.Array:
    """``[B, T, Dm] -> float32 [B, T', Dm / 128, 128]``: channels as lane tiles."""
    x = _padded(x, chunk)
    return x.reshape(*x.shape[:2], -1, LANES)


def _rate_tiles(a: jax.Array) -> jax.Array:
    """``A [Dm, N] -> float32 [N, Dm / 128, 128]``."""
    return a.astype(jnp.float32).T.reshape(a.shape[1], -1, LANES)


def _fwd_kernel(u_ref, d_ref, a_ref, b_ref, c_ref, y_ref, s_ref, h_ref, *, chunk: int, n_state: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    s_ref[...] = h_ref[...]  # the state this chunk starts from
    a = [a_ref[n] for n in range(n_state)]

    def step(t, h):
        d_t = d_ref[t]
        du_t = d_t * u_ref[t]
        y = jnp.zeros_like(d_t)
        new = []
        for n in range(n_state):
            h_n = jnp.exp(d_t * a[n]) * h[n] + b_ref[t, n] * du_t
            y = y + c_ref[t, n] * h_n
            new.append(h_n)
        y_ref[t] = y
        return tuple(new)

    h = lax.fori_loop(0, chunk, step, tuple(h_ref[n] for n in range(n_state)))
    for n in range(n_state):
        h_ref[n] = h[n]


def scan_fwd(u, delta, a, b, c, chunk: int, *, interpret: bool = False):
    """``(Σ_n h_t C_t`` as float32 ``[B, T, Dm]``, boundary states
    ``[B, T / chunk, N, Dm]``) — what ``selective_scan._scan_xla`` returns."""
    bsz, t, dm = u.shape
    n_state = a.shape[1]
    pad = -t % chunk
    nc, rows = (t + pad) // chunk, dm // LANES
    block = _rows(dm)
    tile = pl.BlockSpec((None, chunk, block, LANES), lambda i, j, k: (i, k, j, 0))
    scalars = pl.BlockSpec((None, chunk, n_state), lambda i, j, k: (i, k, 0), memory_space=pltpu.SMEM)
    call = pl.pallas_call(
        partial(_fwd_kernel, chunk=chunk, n_state=n_state),
        name="p2pfl_ssm_scan_fwd",
        grid=(bsz, rows // block, nc),
        in_specs=[
            tile, tile,
            pl.BlockSpec((n_state, block, LANES), lambda i, j, k: (0, j, 0)),
            scalars, scalars,
        ],
        out_specs=[
            tile,
            pl.BlockSpec((None, None, n_state, block, LANES), lambda i, j, k: (i, k, 0, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, t + pad, rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((bsz, nc, n_state, rows, LANES), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n_state, block, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )
    y, starts = call(_tiles(u, chunk), _tiles(delta, chunk), _rate_tiles(a), _padded(b, chunk), _padded(c, chunk))
    return y.reshape(bsz, t + pad, dm)[:, :t], starts.reshape(bsz, nc, n_state, dm)


def _bwd_kernel(
    u_ref, d_ref, gy_ref, a_ref, b_ref, c_ref, s_ref,
    du_ref, dd_ref, ys_ref, db_ref, dc_ref, da_ref,
    h_ref, dh_ref,
    *, chunk: int, n_state: int,
):
    j = pl.program_id(2)
    states = range(n_state)

    @pl.when(pl.program_id(1) == 0)  # the LAST chunk: nothing comes back from beyond the sequence
    def _():
        dh_ref[j] = jnp.zeros(dh_ref.shape[1:], jnp.float32)

    @pl.when(j == 0)  # first channel block of this chunk: dB, dC accumulate over the blocks
    def _():
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    da_ref[...] = jnp.zeros_like(da_ref)
    a = [a_ref[n] for n in states]

    # the chunk again, forward: h_ref[t + 1] is the state after step t, h_ref[0] the start
    for n in states:
        h_ref[0, n] = s_ref[n]

    def forward(t, h):
        d_t = d_ref[t]
        du_t = d_t * u_ref[t]
        y = jnp.zeros_like(d_t)
        new = []
        for n in states:
            h_n = jnp.exp(d_t * a[n]) * h[n] + b_ref[t, n] * du_t
            h_ref[t + 1, n] = h_n
            y = y + c_ref[t, n] * h_n
            new.append(h_n)
        ys_ref[t] = y
        return tuple(new)

    lax.fori_loop(0, chunk, forward, tuple(s_ref[n] for n in states))

    def backward(i, dh):
        t = chunk - 1 - i
        d_t, u_t, gy_t = d_ref[t], u_ref[t], gy_ref[t]
        du_t = d_t * u_t
        d_du = jnp.zeros_like(d_t)
        d_dt = jnp.zeros_like(d_t)
        new = []
        for n in states:
            decay = jnp.exp(d_t * a[n])
            g = dh[n] + c_ref[t, n] * gy_t  # the whole gradient at h_t[n]
            dc_ref[t, n] += h_ref[t + 1, n] * gy_t
            db_ref[t, n] += g * du_t
            d_du = d_du + b_ref[t, n] * g
            through = g * h_ref[t, n] * decay  # d(exp(Δ·A)) · exp(Δ·A)
            d_dt = d_dt + through * a[n]
            da_ref[n] += through * d_t
            new.append(g * decay)
        dd_ref[t] = d_dt + d_du * u_t
        du_ref[t] = d_du * d_t
        return tuple(new)

    dh = lax.fori_loop(0, chunk, backward, tuple(dh_ref[j, n] for n in states))
    for n in states:
        dh_ref[j, n] = dh[n]


def scan_bwd(u, delta, a, b, c, starts, gy, chunk: int, *, interpret: bool = False):
    """``(Σ_n h_t C_t`` again, ``(du, dΔ, dA, dB, dC))`` given the cotangent
    ``gy`` of ``Σ_n h_t C_t`` — what ``selective_scan._scan_bwd_xla`` returns."""
    bsz, t, dm = u.shape
    n_state = a.shape[1]
    pad = -t % chunk
    nc, rows = (t + pad) // chunk, dm // LANES
    block = _rows(dm)
    blocks = rows // block
    last = nc - 1
    tile = pl.BlockSpec((None, chunk, block, LANES), lambda i, k, j: (i, last - k, j, 0))
    scalars = pl.BlockSpec((None, chunk, n_state), lambda i, k, j: (i, last - k, 0), memory_space=pltpu.SMEM)
    state = pl.BlockSpec((None, None, n_state, block, LANES), lambda i, k, j: (i, last - k, 0, j, 0))
    summed = pl.BlockSpec((None, chunk, n_state, block, LANES), lambda i, k, j: (i, last - k, 0, 0, 0))
    tiles = jax.ShapeDtypeStruct((bsz, t + pad, rows, LANES), jnp.float32)
    sums = jax.ShapeDtypeStruct((bsz, t + pad, n_state, block, LANES), jnp.float32)
    call = pl.pallas_call(
        partial(_bwd_kernel, chunk=chunk, n_state=n_state),
        name="p2pfl_ssm_scan_bwd",
        grid=(bsz, nc, blocks),
        in_specs=[
            tile, tile, tile,
            pl.BlockSpec((n_state, block, LANES), lambda i, k, j: (0, j, 0)),
            scalars, scalars, state,
        ],
        out_specs=[tile, tile, tile, summed, summed, state],
        out_shape=[
            tiles, tiles, tiles, sums, sums,
            jax.ShapeDtypeStruct((bsz, nc, n_state, rows, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((chunk + 1, n_state, block, LANES), jnp.float32),
            pltpu.VMEM((blocks, n_state, block, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024,  # the chunk's states and the two summed blocks, double-buffered
        ),
        interpret=interpret,
    )
    du, dd, ys, db, dc, da = call(
        _tiles(u, chunk), _tiles(delta, chunk), _tiles(gy, chunk), _rate_tiles(a),
        _padded(b, chunk), _padded(c, chunk), starts.reshape(bsz, nc, n_state, rows, LANES),
    )
    flat = lambda x: x.reshape(bsz, t + pad, dm)[:, :t]  # noqa: E731
    grads = (
        flat(du), flat(dd), jnp.sum(da, axis=(0, 1)).reshape(n_state, dm).T,
        jnp.sum(db, axis=(-2, -1))[:, :t], jnp.sum(dc, axis=(-2, -1))[:, :t],
    )
    return flat(ys), grads
