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
kernel adds up whole ``[8, 128]`` tiles across the channel blocks in VMEM
scratch (the grid's innermost axis), and at a chunk's last block folds each
tile's sublanes and writes ``[T, N, 128]`` lane sums — an eighth of a tile; the
last 128-to-one sum is XLA's, over 2 MB a sequence and state index.

Both are called by ``selective_scan``'s forward and backward rules, under their
``p2pfl.ssm_scan_fwd`` / ``p2pfl.ssm_scan_bwd`` scopes.

Layout. Everything in the recurrence is elementwise over channels, so a block
of 1024 channels is worked on as one ``[8, 128]`` vreg tile and the state of
the block is ``N`` such tiles. ``B_t[n]`` and ``C_t[n]`` are the same for every
channel: they sit in SMEM and enter as scalar operands — no cross-lane
reductions, every vector op on full tiles. HBM keeps ``[T, Dm]`` as the mixer
has it: ``u`` (its own dtype), ``Δ`` and ``gy`` come in, and ``y``, ``du``,
``dΔ`` leave, as ``[chunk, 1024]`` blocks (time on sublanes, channels on
lanes). A grid step turns its blocks once in VMEM — ``[chunk, 1024] ->
[chunk, 8, 128]``, the float32 cast on the way — and turns its results back;
no XLA pass stands between the mixer and the kernels. Only ``A`` and the
boundary states (0.3 MB, 21 MB a sequence) cross in tiled form.

On a TPU ``chunk`` is a multiple of 16 (a bfloat16 block's sublane tile).

The forward's chunk axis is ``arbitrary`` (the VMEM state carries across it),
batch and channel blocks ``parallel``; the backward's chunk and channel-block
axes are both ``arbitrary`` (carried gradient, accumulated scratch).
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


def _padded(x: jax.Array, chunk: int, dtype=jnp.float32) -> jax.Array:
    """``[B, T, ...]`` in ``dtype``, zero-padded to whole chunks (Δ = 0 steps)."""
    return jnp.pad(x.astype(dtype), ((0, 0), (0, -x.shape[1] % chunk), (0, 0)))


def _rate_tiles(a: jax.Array) -> jax.Array:
    """``A [Dm, N] -> float32 [N, Dm / 128, 128]``."""
    return a.astype(jnp.float32).T.reshape(a.shape[1], -1, LANES)


def _turn(ref) -> jax.Array:
    """A ``[chunk, rows · 128]`` block as float32 ``[chunk, rows, 128]``: a
    step's channels from eight lane tiles of one sublane into one tile."""
    x = ref[...].astype(jnp.float32)
    return x.reshape(x.shape[0], -1, LANES)


def _turn_back(ref) -> jax.Array:
    """Inverse of :func:`_turn` for a float32 ``[chunk, rows, 128]`` scratch."""
    x = ref[...]
    return x.reshape(x.shape[0], -1)


def _fwd_kernel(u_ref, d_ref, a_ref, b_ref, c_ref, y_ref, s_ref, h_ref, ux, dx, yx, *, chunk, n_state):
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    s_ref[...] = h_ref[...]  # the state this chunk starts from
    ux[...] = _turn(u_ref)
    dx[...] = _turn(d_ref)
    a = [a_ref[n] for n in range(n_state)]

    def step(t, h):
        d_t = dx[t]
        du_t = d_t * ux[t]
        y = jnp.zeros_like(d_t)
        new = []
        for n in range(n_state):
            h_n = jnp.exp(d_t * a[n]) * h[n] + b_ref[t, n] * du_t
            y = y + c_ref[t, n] * h_n
            new.append(h_n)
        yx[t] = y
        return tuple(new)

    h = lax.fori_loop(0, chunk, step, tuple(h_ref[n] for n in range(n_state)))
    for n in range(n_state):
        h_ref[n] = h[n]
    y_ref[...] = _turn_back(yx)


def _check_chunk(chunk: int, interpret: bool) -> None:
    if not interpret and chunk % 16:
        raise ValueError(f"the scan kernels put time on sublanes: chunk {chunk} is not a multiple of 16")


def scan_fwd(u, delta, a, b, c, chunk: int, *, interpret: bool = False):
    """``(Σ_n h_t C_t`` as float32 ``[B, T, Dm]``, boundary states
    ``[B, T / chunk, N, Dm]``) — what ``selective_scan._scan_xla`` returns."""
    _check_chunk(chunk, interpret)
    bsz, t, dm = u.shape
    n_state = a.shape[1]
    pad = -t % chunk
    nc, rows = (t + pad) // chunk, dm // LANES
    block = _rows(dm)
    wide = pl.BlockSpec((None, chunk, block * LANES), lambda i, j, k: (i, k, j))
    scalars = pl.BlockSpec((None, chunk, n_state), lambda i, j, k: (i, k, 0), memory_space=pltpu.SMEM)
    turned = pltpu.VMEM((chunk, block, LANES), jnp.float32)
    call = pl.pallas_call(
        partial(_fwd_kernel, chunk=chunk, n_state=n_state),
        name="p2pfl_ssm_scan_fwd",
        grid=(bsz, rows // block, nc),
        in_specs=[
            wide, wide,
            pl.BlockSpec((n_state, block, LANES), lambda i, j, k: (0, j, 0)),
            scalars, scalars,
        ],
        out_specs=[
            wide,
            pl.BlockSpec((None, None, n_state, block, LANES), lambda i, j, k: (i, k, 0, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, t + pad, dm), jnp.float32),
            jax.ShapeDtypeStruct((bsz, nc, n_state, rows, LANES), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n_state, block, LANES), jnp.float32), turned, turned, turned],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )
    y, starts = call(
        _padded(u, chunk, u.dtype), _padded(delta, chunk), _rate_tiles(a),
        _padded(b, chunk), _padded(c, chunk),
    )
    return y[:, :t], starts.reshape(bsz, nc, n_state, dm)


def _bwd_kernel(
    u_ref, d_ref, gy_ref, a_ref, b_ref, c_ref, s_ref,
    du_ref, dd_ref, ys_ref, db_ref, dc_ref, da_ref,
    h_ref, dh_ref, ux, dx, gx, dux, ddx, ysx, db_acc, dc_acc,
    *, chunk: int, n_state: int,
):
    j = pl.program_id(2)
    states = range(n_state)
    rows = ux.shape[1]
    tile = lambda n: pl.ds(n * rows, rows)  # noqa: E731  state n's tile of an accumulator's [N · rows, 128]

    @pl.when(pl.program_id(1) == 0)  # the LAST chunk: nothing comes back from beyond the sequence
    def _():
        dh_ref[j] = jnp.zeros(dh_ref.shape[1:], jnp.float32)

    @pl.when(j == 0)  # first channel block of this chunk: dB, dC accumulate over the blocks
    def _():
        db_acc[...] = jnp.zeros_like(db_acc)
        dc_acc[...] = jnp.zeros_like(dc_acc)

    da_ref[...] = jnp.zeros_like(da_ref)
    ux[...] = _turn(u_ref)
    dx[...] = _turn(d_ref)
    gx[...] = _turn(gy_ref)
    a = [a_ref[n] for n in states]

    # the chunk again, forward: h_ref[t + 1] is the state after step t, h_ref[0] the start
    for n in states:
        h_ref[0, n] = s_ref[n]

    def forward(t, h):
        d_t = dx[t]
        du_t = d_t * ux[t]
        y = jnp.zeros_like(d_t)
        new = []
        for n in states:
            h_n = jnp.exp(d_t * a[n]) * h[n] + b_ref[t, n] * du_t
            h_ref[t + 1, n] = h_n
            y = y + c_ref[t, n] * h_n
            new.append(h_n)
        ysx[t] = y
        return tuple(new)

    lax.fori_loop(0, chunk, forward, tuple(s_ref[n] for n in states))

    def backward(i, dh):
        t = chunk - 1 - i
        d_t, u_t, gy_t = dx[t], ux[t], gx[t]
        du_t = d_t * u_t
        d_du = jnp.zeros_like(d_t)
        d_dt = jnp.zeros_like(d_t)
        new = []
        for n in states:
            decay = jnp.exp(d_t * a[n])
            g = dh[n] + c_ref[t, n] * gy_t  # the whole gradient at h_t[n]
            dc_acc[t, tile(n)] += h_ref[t + 1, n] * gy_t
            db_acc[t, tile(n)] += g * du_t
            d_du = d_du + b_ref[t, n] * g
            through = g * h_ref[t, n] * decay  # d(exp(Δ·A)) · exp(Δ·A)
            d_dt = d_dt + through * a[n]
            da_ref[n] += through * d_t
            new.append(g * decay)
        ddx[t] = d_dt + d_du * u_t
        dux[t] = d_du * d_t
        return tuple(new)

    dh = lax.fori_loop(0, chunk, backward, tuple(dh_ref[j, n] for n in states))
    for n in states:
        dh_ref[j, n] = dh[n]
    du_ref[...] = _turn_back(dux)
    dd_ref[...] = _turn_back(ddx)
    ys_ref[...] = _turn_back(ysx)

    @pl.when(j == pl.num_programs(2) - 1)  # every channel is in: sublane s of all N tiles at a time
    def _():
        def fold(t, carry):
            for acc, out in ((db_acc, db_ref), (dc_acc, dc_ref)):
                total = acc[t, pl.ds(0, n_state, stride=rows)]
                for s in range(1, rows):
                    total = total + acc[t, pl.ds(s, n_state, stride=rows)]
                out[t] = total
            return carry

        lax.fori_loop(0, chunk, fold, 0)


def scan_bwd(u, delta, a, b, c, starts, gy, chunk: int, *, interpret: bool = False):
    """``(Σ_n h_t C_t`` again, ``(du, dΔ, dA, dB, dC))`` given the cotangent
    ``gy`` of ``Σ_n h_t C_t`` — what ``selective_scan._scan_bwd_xla`` returns."""
    _check_chunk(chunk, interpret)
    bsz, t, dm = u.shape
    n_state = a.shape[1]
    pad = -t % chunk
    nc, rows = (t + pad) // chunk, dm // LANES
    block = _rows(dm)
    blocks = rows // block
    last = nc - 1
    wide = pl.BlockSpec((None, chunk, block * LANES), lambda i, k, j: (i, last - k, j))
    scalars = pl.BlockSpec((None, chunk, n_state), lambda i, k, j: (i, last - k, 0), memory_space=pltpu.SMEM)
    state = pl.BlockSpec((None, None, n_state, block, LANES), lambda i, k, j: (i, last - k, 0, j, 0))
    summed = pl.BlockSpec((None, chunk, n_state, LANES), lambda i, k, j: (i, last - k, 0, 0))
    full = jax.ShapeDtypeStruct((bsz, t + pad, dm), jnp.float32)
    sums = jax.ShapeDtypeStruct((bsz, t + pad, n_state, LANES), jnp.float32)
    turned = pltpu.VMEM((chunk, block, LANES), jnp.float32)
    accumulator = pltpu.VMEM((chunk, n_state * block, LANES), jnp.float32)
    call = pl.pallas_call(
        partial(_bwd_kernel, chunk=chunk, n_state=n_state),
        name="p2pfl_ssm_scan_bwd",
        grid=(bsz, nc, blocks),
        in_specs=[
            wide, wide, wide,
            pl.BlockSpec((n_state, block, LANES), lambda i, k, j: (0, j, 0)),
            scalars, scalars, state,
        ],
        out_specs=[wide, wide, wide, summed, summed, state],
        out_shape=[
            full, full, full, sums, sums,
            jax.ShapeDtypeStruct((bsz, nc, n_state, rows, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((chunk + 1, n_state, block, LANES), jnp.float32),
            pltpu.VMEM((blocks, n_state, block, LANES), jnp.float32),
            *[turned] * 6, accumulator, accumulator,
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024,  # the chunk's states and the two accumulators: 4 MB each
        ),
        interpret=interpret,
    )
    du, dd, ys, db, dc, da = call(
        _padded(u, chunk, u.dtype), _padded(delta, chunk), _padded(gy, chunk), _rate_tiles(a),
        _padded(b, chunk), _padded(c, chunk), starts.reshape(bsz, nc, n_state, rows, LANES),
    )
    grads = (
        du[:, :t], dd[:, :t], jnp.sum(da, axis=(0, 1)).reshape(n_state, dm).T,
        jnp.sum(db, axis=-1)[:, :t], jnp.sum(dc, axis=-1)[:, :t],
    )
    return ys[:, :t], grads
