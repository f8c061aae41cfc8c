"""The gated delta rule with channel-wise decay (Kimi Delta Attention), forward
and backward, chunked.

Per head, with a state ``S`` of ``dk × dv`` (128 × 128) and ``S_0 = 0``::

    S_t = (I − β_t k_t k_tᵀ) Diag(α_t) S_{t−1} + β_t k_t v_tᵀ        o_t = S_tᵀ q_t

``α_t = exp(log_alpha_t) ∈ (0, 1]^dk`` decays each KEY channel on its own, and
``β_t ∈ (0, 2)``: above 1 the transition ``I − β k kᵀ`` has a negative
eigenvalue (``kda_allow_neg_eigval``). Nothing here clamps ``β``.

The chunkwise WY form (Yang et al., Gated Delta Networks, arXiv:2412.06464; the
Kimi Linear report, arXiv:2510.26692). Inside a chunk of ``C`` positions, with
``g_i`` the chunk's cumulative log-decay up to and including ``i`` (``≤ 0``) and
the state the chunk starts from ``S_0``::

    S_i = Diag(e^{g_i}) S_0 + Σ_{j≤i} Diag(e^{g_i − g_j}) k_j u_jᵀ
    u_i = β_i (v_i − S_{i−1}ᵀ (α_i ⊙ k_i))
        ⇒ (I + diag(β) L) U = diag(β) (V − (K ⊙ e^g) S_0),   L_ij = Σ_c k_ic k_jc e^{g_ic − g_jc}  (j < i)
    O   = (Q ⊙ e^g) S_0 + P U,                                 P_ij = Σ_c q_ic k_jc e^{g_ic − g_jc}  (j ≤ i)
    S_C = Diag(e^{g_C}) S_0 + (K ⊙ e^{g_C − g})ᵀ U

``(I + diag(β) L)⁻¹`` is taken by forward substitution (a column sweep: ``C``
rank-one updates of a ``C × C`` block). The pairwise decays ``e^{g_i − g_j}``
are never split as ``e^{g_i} · e^{−g_j}`` (``e^{−g_j}`` overflows float32 once a
chunk decays by more than 88 nats): a pair of sub-blocks of ``SUB`` rows below
the diagonal is a matmul of ``k_i e^{g_i − r}`` and ``k_j e^{r − g_j}`` around
the cumulative decay ``r`` at the row sub-block's start (both factors ≤ 1), and
the diagonal sub-blocks are taken pair by pair. The state and every cumulative
decay are float32; every product here is float32 at ``HIGHEST`` precision.

The state is held TRANSPOSED, ``[dv, dk]``: a key channel's decay then scales a
column, a row vector broadcast over sublanes.

The chunk functions take one head's arrays, or a stack of heads' (a leading
axis ``m``: ``[m, C, d]``, states ``[m, dv, dk]``) that they compute together,
op by op — every head's arithmetic the one head's, element for element. In a
stack the inverses are swept side by side (:func:`unit_lower_inverse`).

``custom_vjp``: the forward keeps the chunk-boundary states
(``[B, T / C, H, dv, dk]``); the backward runs back across the chunks from the
last, recomputing a chunk's interior from its boundary state, with the state's
cotangent carried. The chunk's own arithmetic, forward and backward, is
:func:`chunk_forward` / :func:`chunk_backward`: the SAME functions run on
stacks of heads (:func:`heads_per_step`, one rule for both paths) under
``lax.scan`` in the XLA path (``vmap`` over the stacks)
and inside the two Pallas kernels (``ops/delta_rule_kernel.py``:
``p2pfl_kda_fwd``, ``p2pfl_kda_bwd``, a grid step's heads one stack), chosen by
backend as ``ops/selective_scan.py`` chooses. :func:`delta_rule_reference` is
the recurrence one token at a time, the operation's definition.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from p2pfl_tpu.management.profiling import scope
from p2pfl_tpu.ops.selective_scan_kernel import LANES

DEFAULT_CHUNK = 64
SUB = 16  # rows of a sub-block: the pairwise decays' reference is one a row sub-block
HEADS_PER_STEP = (4, 2, 1)  # the stack sizes heads_per_step tries, in this order
_HI = lax.Precision.HIGHEST
_F32 = jnp.float32


def _dot(a, b, ca: int, cb: int):
    """``a · b`` over ``a``'s axis ``ca`` and ``b``'s axis ``cb`` of the last two,
    float32; a stack's head axis is a batch axis."""
    heads = tuple(range(a.ndim - 2))
    lead = len(heads)
    return lax.dot_general(
        a, b, (((lead + ca,), (lead + cb,)), (heads, heads)), precision=_HI, preferred_element_type=_F32
    )


def _mm(a, b):
    """``a @ b``, float32."""
    return _dot(a, b, 1, 0)


def _mm_t(a, b):
    """``a @ bᵀ``, float32."""
    return _dot(a, b, 1, 1)


def _t_mm(a, b):
    """``aᵀ @ b``, float32."""
    return _dot(a, b, 0, 0)


def _iota(c: int):
    return lax.broadcasted_iota(jnp.int32, (c, c), 0), lax.broadcasted_iota(jnp.int32, (c, c), 1)


def _sub(c: int) -> int:
    return min(SUB, c)


def _off_blocks(g, c: int):
    """For each row sub-block below the first: (its rows and the earlier
    columns as a ``[C, C]`` mask, ``e^{min(g − r, 0)}``, ``e^{min(r − g, 0)}``)
    around ``r``, the cumulative decay just before the sub-block. On the rows
    and columns the mask keeps, the clamp never bites."""
    rows, cols = _iota(c)
    sub = _sub(c)
    for start in range(sub, c, sub):
        ref = g[..., start - 1:start, :]
        mask = (rows >= start) & (rows < start + sub) & (cols < start)
        yield mask, jnp.exp(jnp.minimum(g - ref, 0.0)), jnp.exp(jnp.minimum(ref - g, 0.0))


def _block_rows(x, j: int):
    """``[C, d]``: every row of a sub-block replaced by the sub-block's ``j``-th."""
    *heads, c, d = x.shape
    sub = _sub(c)
    blocks = x.reshape(*heads, c // sub, sub, d)
    return jnp.broadcast_to(blocks[..., j:j + 1, :], blocks.shape).reshape(x.shape)


def _to_block_row(x, j: int):
    """``[C, d]``: each sub-block's rows summed into its ``j``-th row, zeros elsewhere
    (the transpose of :func:`_block_rows`)."""
    *heads, c, d = x.shape
    sub = _sub(c)
    blocks = x.reshape(*heads, c // sub, sub, d)
    rows = lax.broadcasted_iota(jnp.int32, blocks.shape, blocks.ndim - 2)
    return jnp.where(rows == j, jnp.sum(blocks, axis=-2, keepdims=True), 0.0).reshape(x.shape)


def _diag_picks(c: int):
    """For each position ``j`` of a sub-block: ``(j, [C, C] mask)`` — row ``i``
    picks column ``first(i) + j``, its sub-block's ``j``-th."""
    rows, cols = _iota(c)
    sub = _sub(c)
    first = (rows // sub) * sub
    for j in range(sub):
        yield j, cols == first + j


def _pairs(q, k, g):
    """``(L, P)``, ``[C, C]`` each: ``L_ij = Σ_c k_ic k_jc e^{g_ic − g_jc}`` on
    ``j < i`` and ``P_ij = Σ_c q_ic k_jc e^{g_ic − g_jc}`` on ``j ≤ i``, zero
    elsewhere — one pass over the decays for both."""
    c = q.shape[-2]
    rows, cols = _iota(c)
    ell = p = jnp.zeros((c, c), _F32)
    for mask, down, up in _off_blocks(g, c):
        ku = k * up
        ell = ell + jnp.where(mask, _mm_t(k * down, ku), 0.0)
        p = p + jnp.where(mask, _mm_t(q * down, ku), 0.0)
    for j, pick in _diag_picks(c):
        ke = _block_rows(k, j) * jnp.exp(jnp.minimum(g - _block_rows(g, j), 0.0))
        ell = ell + jnp.where(pick, jnp.sum(k * ke, axis=-1, keepdims=True), 0.0)
        p = p + jnp.where(pick, jnp.sum(q * ke, axis=-1, keepdims=True), 0.0)
    return jnp.where(cols < rows, ell, 0.0), jnp.where(cols <= rows, p, 0.0)


def _pairs_vjp(q, k, g, d_ell, d_p):
    """``(dq, dk as the row operand, dk as the column operand)`` of :func:`_pairs`
    for the cotangents ``d_ell``, ``d_p``. ``g`` enters only as ``g_i − g_j``,
    so its cotangent is ``q ⊙ dq + k ⊙ dk_rows − k ⊙ dk_cols``."""
    c = q.shape[-2]
    rows, cols = _iota(c)
    d_ell = jnp.where(cols < rows, d_ell, 0.0)
    d_p = jnp.where(cols <= rows, d_p, 0.0)
    dq = jnp.zeros_like(q)
    dk_rows = jnp.zeros_like(k)
    dk_cols = jnp.zeros_like(k)
    for mask, down, up in _off_blocks(g, c):
        ku = k * up
        d_blk_ell, d_blk_p = jnp.where(mask, d_ell, 0.0), jnp.where(mask, d_p, 0.0)
        dq = dq + down * _mm(d_blk_p, ku)
        dk_rows = dk_rows + down * _mm(d_blk_ell, ku)
        dk_cols = dk_cols + up * (_t_mm(d_blk_ell, k * down) + _t_mm(d_blk_p, q * down))
    for j, pick in _diag_picks(c):
        e = jnp.exp(jnp.minimum(g - _block_rows(g, j), 0.0))
        ke = _block_rows(k, j) * e
        d_col_ell = jnp.sum(jnp.where(pick, d_ell, 0.0), axis=-1, keepdims=True)
        d_col_p = jnp.sum(jnp.where(pick, d_p, 0.0), axis=-1, keepdims=True)
        dq = dq + d_col_p * ke
        dk_rows = dk_rows + d_col_ell * ke
        dk_cols = dk_cols + _to_block_row((d_col_ell * k + d_col_p * q) * e, j)
    return dq, dk_rows, dk_cols


def unit_lower_inverse(n):
    """``(I + n)⁻¹`` for a strictly lower-triangular ``n`` ``[C, C]`` (or each
    block of a stack ``[m, C, C]``): forward substitution as a column sweep —
    after column ``j``, row ``j`` is final.

    A stack is swept side by side: its blocks laid next to each other in the
    lanes of ``[C, LANES]`` rows (two blocks a row at ``C = 64``), where one lane
    gather broadcasts column ``j`` of every block of a row, and the rows in
    lockstep, one column at a time. Element for element the sweep is the one
    block's."""
    if n.ndim == 2:
        c = n.shape[0]
        rows, cols = _iota(c)
        t = (rows == cols).astype(_F32)
        for j in range(c - 1):
            t = t - n[:, j:j + 1] * t[j:j + 1, :]
        return t
    m, c, _ = n.shape
    per = max(1, LANES // c)
    if m % per:
        per = 1
    rows = m // per
    sides = jnp.stack([jnp.concatenate([n[r * per + i] for i in range(per)], axis=1) for r in range(rows)])
    lanes = lax.broadcasted_iota(jnp.int32, sides.shape, 2)
    base = lanes // c * c  # each lane's block's first lane
    t = (lax.broadcasted_iota(jnp.int32, sides.shape, 1) + base == lanes).astype(_F32)
    flat = sides.reshape(rows * c, per * c)  # a lane gather takes two dimensions
    flat_base = base.reshape(flat.shape)
    for j in range(c - 1):
        col = jnp.take_along_axis(flat, flat_base + j, axis=1, mode="promise_in_bounds").reshape(sides.shape)
        t = t - col * t[:, j:j + 1, :]
    return jnp.stack([t[:, :, i * c:(i + 1) * c] for i in range(per)], axis=1).reshape(m, c, c)


def _chunk_parts(q, k, v, g, beta, st):
    """What both directions use: ``L``, ``P``, ``(I + diag(β) L)⁻¹``, ``e^g``,
    the last row of ``g``, ``K e^{g_C − g}``, ``R = V − (K e^g) S_0`` and ``U``."""
    lam = jnp.exp(g)
    last = g[..., g.shape[-2] - 1:, :]
    ell, p = _pairs(q, k, g)
    inv = unit_lower_inverse(beta * ell)
    r = v - _mm_t(k * lam, st)
    u = _mm(inv, beta * r)
    return ell, p, inv, lam, last, k * jnp.exp(last - g), r, u


def chunk_forward(st, q, k, v, g, beta, state_dtype=_F32):
    """One chunk: ``(O [C, dv], the state it ends with [dv, dk])`` from the
    state ``st`` ``[dv, dk]`` it starts with. ``q, k, g`` ``[C, dk]``, ``v``
    ``[C, dv]``, ``beta`` ``[C, 1]``; all float32 (or stacks of heads: a
    leading axis on each). ``state_dtype`` rounds the state carried out (tests
    and the planted fault pass bfloat16)."""
    _, p, _, lam, last, kb, _, u = _chunk_parts(q, k, v, g, beta, st)
    o = _mm_t(q * lam, st) + _mm(p, u)
    end = st * jnp.exp(last) + _t_mm(u, kb)
    return o, end.astype(state_dtype).astype(_F32)


def chunk_backward(st, q, k, v, g, beta, do, dst):
    """One chunk's gradients, given the cotangents of its output ``do`` and of
    the state it ends with ``dst`` ``[dv, dk]``: ``(d st, dq, dk, dv, dg, dβ)``."""
    ell, p, inv, lam, last, kb, r, u = _chunk_parts(q, k, v, g, beta, st)
    c = q.shape[-2]
    rows, cols = _iota(c)
    qg, kg = q * lam, k * lam
    d_p = jnp.where(cols <= rows, _mm_t(do, u), 0.0)
    d_u = _t_mm(p, do) + _mm_t(kb, dst)
    d_y = _t_mm(inv, d_u)
    d_m = jnp.where(cols < rows, -_mm_t(d_y, u), 0.0)
    d_r = beta * d_y
    d_beta = jnp.sum(r * d_y, axis=-1, keepdims=True) + jnp.sum(d_m * ell, axis=-1, keepdims=True)
    d_kg = -_mm(d_r, st)
    d_qg = _mm(do, st)
    d_kb = _mm(u, dst)
    d_st = _t_mm(do, qg) - _t_mm(d_r, kg) + dst * jnp.exp(last)
    dq_p, dk_rows, dk_cols = _pairs_vjp(q, k, g, beta * d_m, d_p)
    dq = dq_p + d_qg * lam
    dk = dk_rows + dk_cols + d_kg * lam + d_kb * jnp.exp(last - g)
    dg = q * dq_p + k * (dk_rows - dk_cols) + d_qg * qg + d_kg * kg - d_kb * kb
    d_last = jnp.sum(d_kb * kb, axis=-2, keepdims=True) + jnp.exp(last) * jnp.sum(st * dst, axis=-2, keepdims=True)
    dg = dg + jnp.where(rows[:, :1] == c - 1, d_last, 0.0)
    return d_st, dq, dk, d_r, dg, d_beta


def chunk_cumsum(log_alpha, chunk: int):
    """``g``: the cumulative log-decay from each chunk's start, ``[B, T, H · dk]``
    float32 (``T`` a whole number of chunks)."""
    b, t = log_alpha.shape[:2]
    g = log_alpha.astype(_F32).reshape(b, t // chunk, chunk, *log_alpha.shape[2:])
    return jnp.cumsum(g, axis=2).reshape(log_alpha.shape)


# ---- the XLA path: the same chunk functions under lax.scan --------------------------


def heads_per_step(heads: int) -> int:
    """The heads a stack holds — a kernel grid step's heads, and a stack of the
    XLA path: the first of ``HEADS_PER_STEP`` that divides ``heads``."""
    return next(n for n in HEADS_PER_STEP if heads % n == 0)


def _chunks(x, chunk: int, heads: int, m: int):
    """``[B, T, H · d] -> [T / C, B · H / m, m, C, d]`` float32: stacks of ``m``
    heads of one batch row (``m`` divides ``H``)."""
    b, t = x.shape[:2]
    x = x.astype(_F32).reshape(b, t // chunk, chunk, heads, -1)
    return x.transpose(1, 0, 3, 2, 4).reshape(t // chunk, b * heads // m, m, chunk, x.shape[-1])


def _unchunks(x, b: int):
    """Inverse of :func:`_chunks`."""
    nc, stacks, m, c, d = x.shape
    h = stacks * m // b
    return x.reshape(nc, b, h, c, d).transpose(1, 0, 3, 2, 4).reshape(b, nc * c, h * d)


def _fwd_xla(q, k, v, g, beta, chunk: int):
    b, h = q.shape[0], beta.shape[2]
    dk, dv = q.shape[2] // h, v.shape[2] // h
    m = heads_per_step(h)
    xs = tuple(_chunks(x, chunk, h, m) for x in (q, k, v, g, beta))
    one = jax.vmap(chunk_forward)

    def step(st, x):
        o, end = one(st, *x)
        return end, (o, st)

    st0 = jnp.zeros((b * h // m, m, dv, dk), _F32)
    _, (o, starts) = lax.scan(step, st0, xs)
    starts = starts.reshape(starts.shape[0], b, h, dv, dk).transpose(1, 0, 2, 3, 4)
    return _unchunks(o, b), starts


def _bwd_xla(q, k, v, g, beta, starts, do, chunk: int):
    b, h = q.shape[0], beta.shape[2]
    m = heads_per_step(h)
    xs = tuple(_chunks(x, chunk, h, m) for x in (q, k, v, g, beta, do))
    starts = starts.transpose(1, 0, 2, 3, 4).reshape(starts.shape[1], b * h // m, m, *starts.shape[3:])
    one = jax.vmap(chunk_backward)

    def step(dst, x):
        st, rest = x[0], x[1:]
        d_st, *grads = one(st, *rest, dst)
        return d_st, tuple(grads)

    _, grads = lax.scan(step, jnp.zeros_like(starts[0]), (starts, *xs), reverse=True)
    return tuple(_unchunks(x, b) for x in grads)


# ---- the op -----------------------------------------------------------------------


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_kernel(impl: Optional[str], dk: int, dv: int) -> bool:
    if impl not in (None, "xla", "pallas"):
        raise ValueError(f"gated_delta_rule impl {impl!r} (None|xla|pallas)")
    if impl == "pallas":
        return True
    return impl is None and _on_tpu() and dk % 128 == 0 and dv % 128 == 0  # a head's channels fill the lanes


@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _delta_rule(q, k, v, g, beta, chunk, impl):
    return _fwd(q, k, v, g, beta, chunk, impl)[0]


def _fwd(q, k, v, g, beta, chunk, impl):
    heads = beta.shape[-1]
    with scope("kda_fwd"):
        if _use_kernel(impl, q.shape[-1] // heads, v.shape[-1] // heads):
            from p2pfl_tpu.ops.delta_rule_kernel import kda_fwd

            o, starts = kda_fwd(q, k, v, g, beta, chunk, interpret=not _on_tpu())
        else:
            o, starts = _fwd_xla(q, k, v, g, beta, chunk)
    return o, (q, k, v, g, beta, starts)


def _bwd(chunk, impl, res, do):
    q, k, v, g, beta, starts = res
    heads = beta.shape[-1]
    with scope("kda_bwd"):
        if _use_kernel(impl, q.shape[-1] // heads, v.shape[-1] // heads):
            from p2pfl_tpu.ops.delta_rule_kernel import kda_bwd

            grads = kda_bwd(q, k, v, g, beta, starts, do, chunk, interpret=not _on_tpu())
        else:
            grads = _bwd_xla(q, k, v, g, beta, starts, do, chunk)
    return grads


_delta_rule.defvjp(_fwd, _bwd)


def gated_delta_rule(
    q: jax.Array,  # [B, T, H · dk] queries (normalised and scaled by the caller)
    k: jax.Array,  # [B, T, H · dk] keys (L2-normalised by the caller)
    v: jax.Array,  # [B, T, H · dv]
    log_alpha: jax.Array,  # [B, T, H · dk] log of each key channel's decay, <= 0
    beta: jax.Array,  # [B, T, H] in (0, 2)
    *,
    chunk: int = DEFAULT_CHUNK,
    impl: Optional[str] = None,
) -> jax.Array:
    """``o`` ``[B, T, H · dv]`` float32, differentiable in all five arguments;
    heads side by side on the last axis, as a projection lays them out (the
    kernels read them so: no transpose). ``impl``: ``None`` picks by backend
    (the Pallas kernels on a TPU, plain XLA elsewhere), ``"xla"`` / ``"pallas"``
    force one (the kernels interpreted off-TPU). A sequence that is not a whole
    number of chunks is padded with steps that leave the state alone
    (``k = v = 0``, ``β = 0``, no decay)."""
    if chunk < 1 or chunk % _sub(chunk):
        raise ValueError(f"chunk {chunk} is not a multiple of the sub-block ({SUB})")
    t = q.shape[1]
    pad = -t % chunk
    if pad:
        widen = lambda x: jnp.pad(x, ((0, 0), (0, pad), (0, 0)))  # noqa: E731
        q, k, v, log_alpha, beta = (widen(x) for x in (q, k, v, log_alpha, beta))
    f32 = lambda x: x.astype(_F32)  # noqa: E731
    return _delta_rule(f32(q), f32(k), f32(v), chunk_cumsum(log_alpha, chunk), f32(beta), chunk, impl)[:, :t]


def delta_rule_reference(q, k, v, log_alpha, beta, state_dtype=_F32):
    """The recurrence one token at a time in float32, differentiated by JAX:
    what the op is held to (same arguments, ``[B, T, H · d]``, same result).
    ``state_dtype`` rounds the state every step."""
    b, t, h = beta.shape
    heads = lambda x: x.reshape(b, t, h, -1)  # noqa: E731
    f32 = lambda x: jnp.moveaxis(x.astype(_F32), 1, 0)  # noqa: E731  time-major
    q, k, v, log_alpha = (heads(x) for x in (q, k, v, log_alpha))

    def step(s, xs):  # s: [B, H, dk, dv]
        q_t, k_t, v_t, la_t, b_t = xs
        s = jnp.exp(la_t)[..., None] * s
        s = s - b_t[..., None, None] * k_t[..., None] * jnp.einsum("bhk,bhkv->bhv", k_t, s, precision=_HI)[..., None, :]
        s = s + b_t[..., None, None] * k_t[..., None] * v_t[..., None, :]
        s = s.astype(state_dtype).astype(_F32)
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=_HI)

    s0 = jnp.zeros((b, h, k.shape[-1], v.shape[-1]), _F32)
    _, o = lax.scan(step, s0, tuple(f32(x) for x in (q, k, v, log_alpha, beta)))
    return jnp.moveaxis(o, 0, 1).reshape(b, t, -1)
