"""Pallas TPU kernels for ``ops/delta_rule.py``: ``p2pfl_kda_fwd`` and
``p2pfl_kda_bwd``.

Both read the mixer's arrays as they lie, ``[B, T, H · d]`` (a head's 128
channels are one lane block: a grid step's ``[C, 128]`` block of q, k, v and the
cumulative decay is head ``h``'s chunk), and ``β`` as ``[B, T, H]`` (a head's
column picked in VMEM). A grid step is one chunk of one head and runs
``delta_rule.chunk_forward`` / ``chunk_backward`` on it — the XLA path's own
functions — so HBM sees each input once a pass and never a per-step state.

``p2pfl_kda_fwd``: grid (batch, chunks, heads), the state of every head in VMEM
scratch (``[H, dv, dk]`` float32, 4 MiB at 64 heads of 128) across the chunk
axis; it writes each chunk's output and the state the chunk STARTS from (the
backward's residual).

``p2pfl_kda_bwd``: grid (batch, chunks LAST TO FIRST, heads); a grid step
recomputes its chunk from the saved boundary state, with the state's cotangent
carried in VMEM scratch from the chunk after it. ``dβ`` is one ``[C, H]`` block
a chunk that the heads fill column by column (the head axis is innermost, so
the block stays in VMEM across them).

The chunk and head axes are ``arbitrary`` (carried state, shared ``β`` blocks),
batch ``parallel``. On a TPU ``chunk`` is a multiple of 8.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from p2pfl_tpu.ops.delta_rule import chunk_backward, chunk_forward

_VMEM_LIMIT = 64 * 1024 * 1024  # the heads' states (4 MiB) and a chunk's [C, C] / [C, 128] temporaries


def _column(block, h):
    """Head ``h``'s column of a ``[C, H]`` block, as ``[C, 1]``."""
    lanes = lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.sum(jnp.where(lanes == h, block, 0.0), axis=1, keepdims=True)


def _check(chunk: int, interpret: bool) -> None:
    if not interpret and chunk % 8:
        raise ValueError(f"the delta-rule kernels put a chunk on sublanes: chunk {chunk} is not a multiple of 8")


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, start_ref, s_ref):
    c, h = pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _():
        s_ref[h] = jnp.zeros(s_ref.shape[1:], jnp.float32)

    st = s_ref[h]
    start_ref[...] = st
    o, end = chunk_forward(st, q_ref[...], k_ref[...], v_ref[...], g_ref[...], _column(b_ref[...], h))
    o_ref[...] = o
    s_ref[h] = end


def kda_fwd(q, k, v, g, beta, chunk: int, *, interpret: bool = False):
    """``(o [B, T, H · dv], boundary states [B, T / C, H, dv, dk])`` — what
    ``delta_rule._fwd_xla`` returns. All inputs float32 (``[B, T, H · d]``, ``β``
    ``[B, T, H]``), ``T`` a whole number of chunks."""
    _check(chunk, interpret)
    bsz, t, heads = beta.shape
    dk, dv = q.shape[-1] // heads, v.shape[-1] // heads
    nc = t // chunk
    keys = pl.BlockSpec((None, chunk, dk), lambda b, c, h: (b, c, h))
    values = pl.BlockSpec((None, chunk, dv), lambda b, c, h: (b, c, h))
    o, starts = pl.pallas_call(
        _fwd_kernel,
        name="p2pfl_kda_fwd",
        grid=(bsz, nc, heads),
        in_specs=[keys, keys, values, keys, pl.BlockSpec((None, chunk, heads), lambda b, c, h: (b, c, 0))],
        out_specs=[values, pl.BlockSpec((None, None, None, dv, dk), lambda b, c, h: (b, c, h, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, t, heads * dv), jnp.float32),
            jax.ShapeDtypeStruct((bsz, nc, heads, dv, dk), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT
        ),
        interpret=interpret,
    )(q, k, v, g, beta)
    return o, starts


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_ref):
    c, h = pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)  # the LAST chunk: nothing comes back from beyond the sequence
    def _():
        ds_ref[h] = jnp.zeros(ds_ref.shape[1:], jnp.float32)

    @pl.when(h == 0)
    def _():
        db_ref[...] = jnp.zeros_like(db_ref)

    d_st, dq, dk, dv, dg, db = chunk_backward(
        s_ref[...], q_ref[...], k_ref[...], v_ref[...], g_ref[...], _column(b_ref[...], h), do_ref[...], ds_ref[h]
    )
    ds_ref[h] = d_st
    dq_ref[...] = dq
    dk_ref[...] = dk
    dv_ref[...] = dv
    dg_ref[...] = dg
    lanes = lax.broadcasted_iota(jnp.int32, db_ref.shape, 1)
    db_ref[...] = jnp.where(lanes == h, db, db_ref[...])


def kda_bwd(q, k, v, g, beta, starts, do, chunk: int, *, interpret: bool = False):
    """``(dq, dk, dv, dg, dβ)`` given the output's cotangent ``do`` — what
    ``delta_rule._bwd_xla`` returns."""
    _check(chunk, interpret)
    bsz, t, heads = beta.shape
    dk, dv = q.shape[-1] // heads, v.shape[-1] // heads
    nc = t // chunk
    last = nc - 1
    keys = pl.BlockSpec((None, chunk, dk), lambda b, c, h: (b, last - c, h))
    values = pl.BlockSpec((None, chunk, dv), lambda b, c, h: (b, last - c, h))
    betas = pl.BlockSpec((None, chunk, heads), lambda b, c, h: (b, last - c, 0))
    wide = lambda d: jax.ShapeDtypeStruct((bsz, t, heads * d), jnp.float32)  # noqa: E731
    return pl.pallas_call(
        _bwd_kernel,
        name="p2pfl_kda_bwd",
        grid=(bsz, nc, heads),
        in_specs=[
            keys, keys, values, keys, betas,
            pl.BlockSpec((None, None, None, dv, dk), lambda b, c, h: (b, last - c, h, 0, 0)),
            values,
        ],
        out_specs=[keys, keys, values, keys, betas],
        out_shape=[wide(dk), wide(dk), wide(dv), wide(dk), jax.ShapeDtypeStruct((bsz, t, heads), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT
        ),
        interpret=interpret,
    )(q, k, v, g, beta, starts, do)
