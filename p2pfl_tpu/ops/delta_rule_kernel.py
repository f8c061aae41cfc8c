"""Pallas TPU kernels for ``ops/delta_rule.py``: ``p2pfl_kda_fwd`` and
``p2pfl_kda_bwd``.

Both read the mixer's arrays as they lie, ``[B, T, H · d]``, and ``β`` as
``[B, T, H]`` (a head's column picked in VMEM). A grid step is one chunk of
``n`` heads (``delta_rule.heads_per_step``: the head count alone decides; a
step that passed ``_VMEM_LIMIT`` would be refused by Mosaic's compile; at
64 heads of 128, eight heads a step compiled under it and ran on a TPU): its
``[C, n · d]`` blocks of q, k, v and the cumulative decay are lane-aligned
slices of those arrays (a head's 128 channels are one lane block), so nothing
is transposed.
The step stacks its heads' slices (``[n, C, d]``) and runs
``delta_rule.chunk_forward`` / ``chunk_backward`` on the stack once — the XLA
path's own functions, which take a stack of heads as they take one head — so
each op serves the ``n`` heads in turn, their independent chains side by side
in program order, the two ``[C, C]`` inverses of a pair of heads swept in one
``[C, 128]`` row (``delta_rule.unit_lower_inverse``), and the pipeline's
per-step cost is paid once for ``n`` heads. Every head's arithmetic is what one
head a step computes, element for element; HBM sees each input once a pass and
never a per-step state.

``p2pfl_kda_fwd``: grid (batch, chunks, heads / n), the state of every head in
VMEM scratch (``[H, dv, dk]`` float32, 4 MiB at 64 heads of 128) across the
chunk axis; it writes each chunk's output and the states the chunk STARTS from
(the backward's residual), ``[n, dv, dk]`` a step.

``p2pfl_kda_bwd``: grid (batch, chunks LAST TO FIRST, heads / n); a grid step
recomputes its chunk from the saved boundary states, with the states'
cotangents carried in VMEM scratch from the chunk after it. ``dβ`` is one
``[C, H]`` block a chunk that the steps fill ``n`` columns at a time (the head
axis is innermost, so the block stays in VMEM across them).

The chunk and head axes are ``arbitrary`` (carried state, shared ``β`` blocks),
batch ``parallel``. On a TPU ``chunk`` is a multiple of 8. With ``n = 1`` the
kernels are the one-head-a-step kernels there were, op for op.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from p2pfl_tpu.ops.delta_rule import chunk_backward, chunk_forward, heads_per_step

_VMEM_LIMIT = 64 * 1024 * 1024  # the heads' states (4 MiB) and a step's blocks and [C, C] / [C, 128] temporaries


def _column(block, h):
    """Head ``h``'s column of a ``[C, H]`` block, as ``[C, 1]``."""
    lanes = lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.sum(jnp.where(lanes == h, block, 0.0), axis=1, keepdims=True)


def _heads(hb, n: int):
    """Head block ``hb``'s heads ``hb · n + i`` (the block's index at one head)."""
    return [hb] if n == 1 else [hb * n + i for i in range(n)]


def _states(hb, n: int):
    """Index of head block ``hb``'s states in the ``[H, dv, dk]`` scratch."""
    return hb if n == 1 else pl.ds(hb * n, n)


def _stack(ref, n: int):
    """A ``[C, n · d]`` block as the heads' stack ``[n, C, d]`` (one head: ``[C, d]``)."""
    if n == 1:
        return ref[...]
    d = ref.shape[-1] // n
    return jnp.stack([ref[:, i * d:(i + 1) * d] for i in range(n)])


def _unstack(ref, x, n: int) -> None:
    """Write the heads' stack ``x`` into their lanes of a ``[C, n · d]`` block."""
    if n == 1:
        ref[...] = x
        return
    d = ref.shape[-1] // n
    for i in range(n):
        ref[:, i * d:(i + 1) * d] = x[i]


def _columns(b_ref, hb, n: int):
    """The heads' ``β`` columns of the ``[C, H]`` block: ``[n, C, 1]`` (one head: ``[C, 1]``)."""
    if n == 1:
        return _column(b_ref[...], hb)
    block = b_ref[...]
    return jnp.stack([_column(block, h) for h in _heads(hb, n)])


def _state_block(n: int):
    """The head dimension of a boundary-state block: squeezed at one head."""
    return None if n == 1 else n


def _check(chunk: int, interpret: bool) -> None:
    if not interpret and chunk % 8:
        raise ValueError(f"the delta-rule kernels put a chunk on sublanes: chunk {chunk} is not a multiple of 8")


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, start_ref, s_ref, *, n: int):
    c, hb = pl.program_id(1), pl.program_id(2)
    states = _states(hb, n)

    @pl.when(c == 0)
    def _():
        s_ref[states] = jnp.zeros(start_ref.shape, jnp.float32)

    st = s_ref[states]
    start_ref[...] = st
    o, end = chunk_forward(st, _stack(q_ref, n), _stack(k_ref, n), _stack(v_ref, n), _stack(g_ref, n), _columns(b_ref, hb, n))
    _unstack(o_ref, o, n)
    s_ref[states] = end


def kda_fwd(q, k, v, g, beta, chunk: int, *, interpret: bool = False):
    """``(o [B, T, H · dv], boundary states [B, T / C, H, dv, dk])`` — what
    ``delta_rule._fwd_xla`` returns. All inputs float32 (``[B, T, H · d]``, ``β``
    ``[B, T, H]``), ``T`` a whole number of chunks."""
    _check(chunk, interpret)
    bsz, t, heads = beta.shape
    dk, dv = q.shape[-1] // heads, v.shape[-1] // heads
    nc = t // chunk
    n = heads_per_step(heads)
    keys = pl.BlockSpec((None, chunk, n * dk), lambda b, c, h: (b, c, h))
    values = pl.BlockSpec((None, chunk, n * dv), lambda b, c, h: (b, c, h))
    o, starts = pl.pallas_call(
        lambda *refs: _fwd_kernel(*refs, n=n),
        name="p2pfl_kda_fwd",
        grid=(bsz, nc, heads // n),
        in_specs=[keys, keys, values, keys, pl.BlockSpec((None, chunk, heads), lambda b, c, h: (b, c, 0))],
        out_specs=[values, pl.BlockSpec((None, None, _state_block(n), dv, dk), lambda b, c, h: (b, c, h, 0, 0))],
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


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_ref, *, n: int):
    c, hb = pl.program_id(1), pl.program_id(2)
    states = _states(hb, n)

    @pl.when(c == 0)  # the LAST chunk: nothing comes back from beyond the sequence
    def _():
        ds_ref[states] = jnp.zeros(s_ref.shape, jnp.float32)

    @pl.when(hb == 0)
    def _():
        db_ref[...] = jnp.zeros_like(db_ref)

    d_st, dq, dk, dv, dg, db = chunk_backward(
        s_ref[...], _stack(q_ref, n), _stack(k_ref, n), _stack(v_ref, n), _stack(g_ref, n), _columns(b_ref, hb, n),
        _stack(do_ref, n), ds_ref[states],
    )
    ds_ref[states] = d_st
    _unstack(dq_ref, dq, n)
    _unstack(dk_ref, dk, n)
    _unstack(dv_ref, dv, n)
    _unstack(dg_ref, dg, n)
    lanes = lax.broadcasted_iota(jnp.int32, db_ref.shape, 1)
    block = None
    for i, h in enumerate(_heads(hb, n)):
        block = jnp.where(lanes == h, db if n == 1 else db[i], db_ref[...] if block is None else block)
    db_ref[...] = block


def kda_bwd(q, k, v, g, beta, starts, do, chunk: int, *, interpret: bool = False):
    """``(dq, dk, dv, dg, dβ)`` given the output's cotangent ``do`` — what
    ``delta_rule._bwd_xla`` returns."""
    _check(chunk, interpret)
    bsz, t, heads = beta.shape
    dk, dv = q.shape[-1] // heads, v.shape[-1] // heads
    nc = t // chunk
    last = nc - 1
    n = heads_per_step(heads)
    keys = pl.BlockSpec((None, chunk, n * dk), lambda b, c, h: (b, last - c, h))
    values = pl.BlockSpec((None, chunk, n * dv), lambda b, c, h: (b, last - c, h))
    betas = pl.BlockSpec((None, chunk, heads), lambda b, c, h: (b, last - c, 0))
    wide = lambda d: jax.ShapeDtypeStruct((bsz, t, heads * d), jnp.float32)  # noqa: E731
    return pl.pallas_call(
        lambda *refs: _bwd_kernel(*refs, n=n),
        name="p2pfl_kda_bwd",
        grid=(bsz, nc, heads // n),
        in_specs=[
            keys, keys, values, keys, betas,
            pl.BlockSpec((None, None, _state_block(n), dv, dk), lambda b, c, h: (b, last - c, h, 0, 0)),
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
