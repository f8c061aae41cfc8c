"""Pallas flash attention (single chip), forward AND backward, explicitly
configured.

Blockwise causal attention with online softmax: O(T·D) VMEM per program
instead of the O(T²) logits matrix. Grid is (batch, heads, q-groups); each
program owns ``q_span`` consecutive q blocks (wider q ownership amortizes
grid/bookkeeping overhead while each sub-tile keeps its OWN causal
frontier — one big block would stream every k block up to the LAST row's
frontier for all rows) and streams K/V blocks up to each sub-tile's
frontier, keeping running (max, denom, accumulator) statistics in fp32
while the matmuls feed the MXU in the input dtype (bf16 K/V loads in
production; casting operands to f32 first runs the systolic array at its
slow f32 rate — measured 5× at D=32).

Every schedule knob lives in :class:`FlashConfig` — a frozen, hashable
dataclass that rides jit/custom_vjp STATIC arguments, so flipping any knob
(block shapes, q ownership, backward mode) after a step has compiled
provably re-traces. There are no module-global kernel knobs (the old
``BWD_MODE`` global was read at trace time with no cache-key participation
— flipping it after compilation silently did nothing, ADVICE r5).
``config=None`` resolves through :mod:`p2pfl_tpu.ops.autotune`: pinned
config → autotune cache (in-process, then on-disk, keyed on device kind) →
shipped defaults table for v4 / v5e / CPU-interpret.

Training: the custom VJP is backed by Pallas kernels (the standard
flash-attention backward split):

- ``_dq_kernel``  — grid (B, H, q-blocks): recomputes P from the saved
  log-sum-exp and accumulates ``dQ_i += (P ∘ (dO V^T − Δ)) K · scale``;
- ``_dkv_kernel`` — grid (B, H, k-blocks): streams the q blocks at or past
  the causal frontier and accumulates ``dV_j += P^T dO`` and
  ``dK_j += (P ∘ (dO V^T − Δ))^T Q · scale``;
- ``_dkvq_kernel`` — the fused single-pass alternative (see its docstring):
  dK/dV per k-block AND dQ in one sweep via a persistent VMEM scratch,
  5 block matmuls instead of the split pair's 7. Selected by
  ``FlashConfig.bwd_mode`` (``"auto"`` picks fused whenever the fp32 dQ
  scratch fits comfortably in VMEM).

Residuals are just ``(q, k, v, o, lse)`` — the attention matrix is never
materialized in either direction, so training long sequences stays O(T·D)
memory end-to-end. The log-sum-exp is saved in a block-size-INDEPENDENT
``[B, H, 1, T]`` row layout (always mapped as the full ``(1, T)`` block,
which satisfies Mosaic's block==array tiling rule for any T): the backward
can pick any block shape without the old per-block-layout reshuffle, and
every kernel ref stays 2D (this environment's Mosaic compiler rejects
1D/`.at[]` ref views). Δ = rowsum(dO∘O) is a cheap elementwise XLA op
computed outside the kernels in the same row layout.

Grid dimension semantics are pinned explicitly on every ``pallas_call``
(``_compiler_params``): batch/head dims are ``parallel``; the forward's
q-group dim is ``arbitrary`` (all programs of one (b, h) write rows of the
SAME full lse block — a megacore split over that dim would race the block
flush); the fused backward's k-block dim is ``arbitrary`` because the
``dq_acc`` scratch accumulation REQUIRES sequential k blocks (this used to
be an accident of the default semantics — advisor round-5); the split
backward kernels write disjoint blocks and read shared blocks read-only,
so their grid is fully ``parallel``.

The reference has no attention anywhere (SURVEY §2.9) — this exists for the
BASELINE config-5 model family and the long-context path.

Playbook: /opt/skills/guides/pallas_guide.md (grid/BlockSpec, online
softmax accumulation, broadcasted_iota masking, @pl.when).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from p2pfl_tpu.management.profiling import scope

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class FlashConfig:
    """Static schedule of the flash kernels — hashable, jit-cache-key safe.

    Forward: ``block_q`` × ``block_k`` tiles, ``q_span`` q blocks owned per
    program (wider ownership amortizes grid bookkeeping; each sub-tile
    keeps its own causal frontier). Backward: ``block_q_bwd``/``block_k_bwd``
    override the backward tile shapes (``None`` → :func:`_bwd_blocks`
    decides: fused keeps the forward's, split upsizes at wide heads);
    ``bwd_mode`` picks the kernel structure — ``"fused"`` = one sweep with
    a persistent dQ scratch (5 block matmuls, the MFU-accounted minimum),
    ``"split"`` = separate dq/dkv kernels (7 — recomputes S and dP twice),
    ``"auto"`` = fused whenever the fp32 [T, D] dQ scratch fits comfortably
    in VMEM next to resident q/do.

    Pass it through ``flash_attention(config=...)``,
    ``TransformerConfig(flash_config=...)`` or
    ``resolve_attention(config=...)``; ``None`` anywhere resolves through
    :func:`p2pfl_tpu.ops.autotune.get_flash_config` (pinned → tune cache →
    defaults table). Because instances compare/hash by value, passing an
    EQUAL config re-uses the compiled program and passing a DIFFERENT one
    re-traces — the contract the old ``BWD_MODE`` module global broke.
    """

    block_q: int = 128
    block_k: int = 128
    q_span: int = 1
    block_q_bwd: Optional[int] = None
    block_k_bwd: Optional[int] = None
    bwd_mode: str = "auto"  # auto | fused | split

    def __post_init__(self) -> None:
        if self.bwd_mode not in ("auto", "fused", "split"):
            raise ValueError(f"bwd_mode {self.bwd_mode!r} (auto|fused|split)")
        if self.block_q < 1 or self.block_k < 1 or self.q_span < 1:
            raise ValueError("block_q/block_k/q_span must be >= 1")


def _resolve(
    config: Optional[FlashConfig], t: int, d: int, dtype, causal: bool, window: Optional[int] = None
) -> FlashConfig:
    """``config=None`` → the tuned/default config for this shape (and window)."""
    if config is not None:
        return config
    from p2pfl_tpu.ops.autotune import get_flash_config

    return get_flash_config(t, d, dtype=dtype, causal=causal, window=window)


def _pallas_call(kernel, *, scope_name: str, name: str, **kw):
    """``pl.pallas_call`` whose call is traced under ``p2pfl.<scope_name>``
    (what the benchmark's reduction by scope reads: forward or backward) and
    whose kernel carries ``name`` (what a person sees in Perfetto / xprof)."""
    call = pl.pallas_call(kernel, name=name, **kw)

    def scoped(*args):
        with scope(scope_name):
            return call(*args)

    return scoped


def _compiler_params(*dims: str, vmem_limit_bytes: Optional[int] = None) -> pltpu.CompilerParams:
    """Pin grid ``dimension_semantics`` ('parallel' dims may be split across
    megacore; 'arbitrary' dims MUST run sequentially on one core). Ignored
    in interpret mode."""
    if vmem_limit_bytes is None:
        return pltpu.CompilerParams(dimension_semantics=dims)
    return pltpu.CompilerParams(dimension_semantics=dims, vmem_limit_bytes=vmem_limit_bytes)


def _visible(rows, cols, window):
    """Causal mask of global ``rows`` x ``cols`` (int32 iotas), and under a
    sliding ``window`` only the ``window`` keys up to the row itself:
    ``row - window < col <= row``."""
    if window is None:
        return rows >= cols
    return (rows >= cols) & (cols > rows - window)


def _q_side_bounds(qi, block_q, block_k, window):
    """K-block loop bounds of q block ``qi`` under causal attention, ``(lo,
    lo_full, n_full, n_all)``: blocks ``[lo, lo_full)`` straddle the window's
    lower edge (masked), ``[lo_full, n_full)`` are wholly visible (no mask),
    ``[n_full, n_all)`` straddle the diagonal (masked). Blocks before ``lo`` lie
    wholly outside ``(row - window, row]`` of every row and are SKIPPED. Without a
    window ``lo = lo_full = 0``: the two loops every causal program has had."""
    n_full = lax.div(qi * block_q, block_k)
    n_all = lax.div((qi + 1) * block_q + block_k - 1, block_k)
    if window is None:
        return 0, 0, n_full, n_all
    first = qi * block_q
    lo = lax.div(jnp.maximum(first - window + 1, 0), block_k)
    # block j needs no window mask once its first column is inside the LAST row's window
    lo_full = lax.div(jnp.maximum(first + block_q - window, 0) + block_k - 1, block_k)
    return lo, jnp.clip(lo_full, lo, n_full), n_full, n_all


def _k_side_bounds(kj, block_q, block_k, window, nq):
    """Q-block loop bounds of k block ``kj``, ``(start, full, hi_full, end)``:
    q blocks ``[start, full)`` straddle the diagonal (masked), ``[full, hi_full)``
    see the whole k block (no mask), ``[hi_full, end)`` straddle the window's
    lower edge (masked); q blocks from ``end`` on no longer reach back to this k
    block (row >= col + window) and are SKIPPED. Without a window ``hi_full = end
    = nq``."""
    start = lax.div(kj * block_k, block_q)
    full = lax.div((kj + 1) * block_k + block_q - 1, block_q)
    if window is None:
        return start, full, nq, nq
    first = kj * block_k
    end = jnp.minimum(lax.div(first + block_k + window - 2, block_q) + 1, nq)
    full = jnp.minimum(full, end)
    # q block i sees the whole k block while its LAST row is inside the FIRST column's reach
    return start, full, jnp.clip(lax.div(first + window, block_q), full, end), end


def _fwd_tile(q, k_ref, v_ref, qi, *, block_q, block_k, causal, scale, t, window=None):
    """Online-softmax accumulation of ONE q sub-tile against its visible
    K/V stream. Returns (acc [BQ, D] f32, m [BQ, 1] f32, l [BQ, 1] f32)."""
    dt = q.dtype

    acc = jnp.zeros((block_q, q.shape[-1]), jnp.float32)
    m = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)

    def body(j, carry, *, masked):
        acc, m, l = carry
        k = k_ref[pl.ds(j * block_k, block_k), :]
        v = v_ref[pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [BQ, BK]
        if masked:
            rows = qi * block_q + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(_visible(rows, cols, window), s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:
            p = jnp.where(m_new <= NEG_INF / 2, 0.0, p)
            alpha = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - m_new))
        else:
            # s is finite, so m_new is too; a NEG_INF m (first block) gives
            # alpha = exp(-inf) = 0 without the select
            alpha = jnp.exp(m - m_new)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(dt), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        return acc, m_new, l

    if causal:
        # split the stream at the causal frontier: blocks fully below the
        # diagonal skip the iota/select mask work (half the VPU ops for the
        # majority of blocks — measured 4× at D=32 where the mask dominates)
        lo, lo_full, n_full, n_all = _q_side_bounds(qi, block_q, block_k, window)
        if window is not None:  # the window's lower edge; a row may see nothing of such a block
            acc, m, l = lax.fori_loop(lo, lo_full, partial(body, masked=True), (acc, m, l))
        acc, m, l = lax.fori_loop(lo_full, n_full, partial(body, masked=False), (acc, m, l))
        acc, m, l = lax.fori_loop(n_full, n_all, partial(body, masked=True), (acc, m, l))
    else:
        acc, m, l = lax.fori_loop(
            0, t // block_k, partial(body, masked=False), (acc, m, l)
        )
    return acc, m, l


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q, block_k, q_span, causal, scale, window=None
):
    t = k_ref.shape[0]
    for s in range(q_span):  # static unroll: q_span consecutive sub-tiles
        qi = pl.program_id(2) * q_span + s
        q = q_ref[pl.ds(s * block_q, block_q), :]  # [BQ, D]
        acc, m, l = _fwd_tile(
            q, k_ref, v_ref, qi, block_q=block_q, block_k=block_k,
            causal=causal, scale=scale, t=t, window=window,
        )
        o_ref[pl.ds(s * block_q, block_q), :] = (
            acc / jnp.maximum(l, 1e-30)
        ).astype(o_ref.dtype)
        # log-sum-exp per row; fully-masked rows keep NEG_INF (exp
        # underflows to 0). lse_ref is the block-size-INDEPENDENT [1, T]
        # row (full-array block — block == array dims satisfies Mosaic's
        # tiling rule); each sub-tile owns its T-slice.
        lse = jnp.where(m <= NEG_INF / 2, NEG_INF, m + jnp.log(jnp.maximum(l, 1e-30)))
        lse_ref[pl.ds(0, 1), pl.ds(qi * block_q, block_q)] = lse.reshape(1, block_q)


def _row(ref, i, block_q):
    """Read rows [i·BQ, (i+1)·BQ) of a [1, T] row-layout ref as [BQ, 1]."""
    return ref[pl.ds(0, 1), pl.ds(i * block_q, block_q)].reshape(block_q, 1)


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *, block_q, block_k, causal, scale, window=None
):
    qi = pl.program_id(2)
    t = k_ref.shape[0]
    dt = q_ref.dtype
    q = q_ref[:]  # [BQ, D]
    do = do_ref[:]  # [BQ, D]
    lse = _row(lse_ref, qi, block_q)  # [BQ, 1]
    delta = _row(delta_ref, qi, block_q)  # [BQ, 1]

    dq = jnp.zeros((block_q, q.shape[-1]), jnp.float32)

    def body(j, dq, *, masked):
        k = k_ref[pl.ds(j * block_k, block_k), :]
        v = v_ref[pl.ds(j * block_k, block_k), :]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [BQ, BK]
        if masked:
            rows = qi * block_q + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(_visible(rows, cols, window), s, NEG_INF)
        p = jnp.exp(s - lse)  # masked entries underflow to 0
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [BQ, BK]
        ds = p * (dp - delta)
        return dq + scale * jax.lax.dot_general(
            ds.astype(dt), k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    if causal:
        lo, lo_full, n_full, n_all = _q_side_bounds(qi, block_q, block_k, window)
        if window is not None:
            dq = lax.fori_loop(lo, lo_full, partial(body, masked=True), dq)
        dq = lax.fori_loop(lo_full, n_full, partial(body, masked=False), dq)
        dq = lax.fori_loop(n_full, n_all, partial(body, masked=True), dq)
    else:
        dq = lax.fori_loop(0, t // block_k, partial(body, masked=False), dq)
    dq_ref[:] = dq.astype(dq_ref.dtype)


def _dkv_step(
    i, dk, dv, *, q_ref, do_ref, lse_ref, delta_ref, k, v, kj,
    block_q, block_k, scale, dt, masked, dq_acc=None, window=None,
):
    """One q-block's contribution to (dK_j, dV_j) — the body shared by the
    split ``_dkv_kernel`` and the fused ``_dkvq_kernel``, which adds only
    the ``dq_acc`` accumulation on top of identical S/P/dP/ds math."""
    q = q_ref[pl.ds(i * block_q, block_q), :]
    do = do_ref[pl.ds(i * block_q, block_q), :]
    lse = _row(lse_ref, i, block_q)
    delta = _row(delta_ref, i, block_q)
    s = scale * jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [BQ, BK]
    if masked:
        rows = i * block_q + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        cols = kj * block_k + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(_visible(rows, cols, window), s, NEG_INF)
    p = jnp.exp(s - lse)  # [BQ, BK]
    dv = dv + jax.lax.dot_general(
        p.astype(dt), do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [BQ, BK]
    ds = (p * (dp - delta)).astype(dt)
    dk = dk + scale * jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    if dq_acc is not None:
        dq_acc[pl.ds(i * block_q, block_q), :] += scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
    return dk, dv


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    *, block_q, block_k, causal, scale, window=None,
):
    kj = pl.program_id(2)
    t = q_ref.shape[0]
    k = k_ref[:]  # [BK, D]
    v = v_ref[:]  # [BK, D]

    dk = jnp.zeros((block_k, k.shape[-1]), jnp.float32)
    dv = jnp.zeros((block_k, k.shape[-1]), jnp.float32)
    n_blocks = t // block_q

    def body(i, carry, *, masked):
        return _dkv_step(
            i, *carry, q_ref=q_ref, do_ref=do_ref, lse_ref=lse_ref,
            delta_ref=delta_ref, k=k, v=v, kj=kj, block_q=block_q,
            block_k=block_k, scale=scale, dt=q_ref.dtype, masked=masked, window=window,
        )

    if causal:
        # q blocks strictly before the frontier never see this K block; q
        # blocks fully past the diagonal band see all of it (no mask needed)
        start, full, hi_full, end = _k_side_bounds(kj, block_q, block_k, window, n_blocks)
        dk, dv = lax.fori_loop(start, full, partial(body, masked=True), (dk, dv))
        dk, dv = lax.fori_loop(full, hi_full, partial(body, masked=False), (dk, dv))
        if window is not None:  # the window's lower edge, seen from the key side
            dk, dv = lax.fori_loop(hi_full, end, partial(body, masked=True), (dk, dv))
    else:
        dk, dv = lax.fori_loop(0, n_blocks, partial(body, masked=False), (dk, dv))
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _dkvq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dq_ref,
    dq_acc, *, block_q, block_k, causal, scale, window=None,
):
    """Single-pass backward: dK/dV per k-block AND dQ in one sweep.

    The split backward (``_dq_kernel`` + ``_dkv_kernel``) recomputes
    S = QK^T and dP = dO V^T in BOTH passes — 7 block matmuls executed for
    the 5 the MFU accounting counts (measured: bwd trailed fwd by exactly
    that ~1.4× on a v5e at D=128). Here the grid's k-block dimension runs
    sequentially on the core (pinned via dimension_semantics — see the
    pallas_call site), so dQ accumulates across grid steps in a persistent
    fp32 VMEM scratch: S and dP are computed ONCE and all five products
    (dV, dK, dQ + the two recomputes) come out of one sweep. Scratch is
    zeroed at the first k-block and flushed to ``dq_ref`` at the last;
    q/do stay VMEM-resident (same full-block residency the split dkv
    kernel already required).
    """
    kj = pl.program_id(2)
    nk = pl.num_programs(2)
    t = q_ref.shape[0]
    k = k_ref[:]  # [BK, D]
    v = v_ref[:]  # [BK, D]

    @pl.when(kj == 0)
    def _zero():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    dk = jnp.zeros((block_k, k.shape[-1]), jnp.float32)
    dv = jnp.zeros((block_k, k.shape[-1]), jnp.float32)
    nq = t // block_q

    def body(i, carry, *, masked):
        return _dkv_step(
            i, *carry, q_ref=q_ref, do_ref=do_ref, lse_ref=lse_ref,
            delta_ref=delta_ref, k=k, v=v, kj=kj, block_q=block_q,
            block_k=block_k, scale=scale, dt=q_ref.dtype, masked=masked,
            dq_acc=dq_acc, window=window,
        )

    if causal:
        start, full, hi_full, end = _k_side_bounds(kj, block_q, block_k, window, nq)
        dk, dv = lax.fori_loop(start, full, partial(body, masked=True), (dk, dv))
        dk, dv = lax.fori_loop(full, hi_full, partial(body, masked=False), (dk, dv))
        if window is not None:
            dk, dv = lax.fori_loop(hi_full, end, partial(body, masked=True), (dk, dv))
    else:
        dk, dv = lax.fori_loop(0, nq, partial(body, masked=False), (dk, dv))
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)

    @pl.when(kj == nk - 1)
    def _flush():
        dq_ref[:] = dq_acc[...].astype(dq_ref.dtype)


def _specs(block_q, block_k, t, d, q_span: int = 1):
    qspec = pl.BlockSpec(
        (None, None, block_q * q_span, d), lambda bi, hi, i: (bi, hi, i, 0)
    )
    kvfull = pl.BlockSpec((None, None, t, d), lambda bi, hi, i: (bi, hi, 0, 0))
    # lse/delta live in the block-size-independent [B, H, 1, T] row layout;
    # always mapped as the FULL (1, T) block — block == array dims satisfies
    # Mosaic's tiling rule for any T, and programs slice their own rows, so
    # the backward re-blocks freely with NO relayout of the saved lse
    lse_row = pl.BlockSpec((None, None, 1, t), lambda bi, hi, i: (bi, hi, 0, 0))
    return qspec, kvfull, lse_row


def _windowed(window, **kw) -> dict:
    """Kernel keywords, with ``window`` only when there is one: a program
    without a window is built from exactly the partial it has always had."""
    return kw if window is None else dict(kw, window=window)


def _names(window, scope_name: str, name: str) -> dict:
    """Scope and kernel name of a call; a windowed call carries its own
    (``flash_win_fwd`` / ``p2pfl_flash_win_fwd``…) so that a trace tells a
    sliding layer from a full one."""
    if window is None:
        return {"scope_name": scope_name, "name": name}
    return {"scope_name": scope_name.replace("flash_", "flash_win_"), "name": name.replace("flash_", "flash_win_")}


def _flash_fwd_bthd(q, k, v, *, block_q, block_k, q_span, causal, interpret, window=None):
    """q,k,v: [B, H, T, D] → (out [B, H, T, D], lse [B, H, 1, T] f32)."""
    b, h, t, d = q.shape
    scale = d ** -0.5
    grid = (b, h, t // (block_q * q_span))
    qspec, kvfull, lse_row = _specs(block_q, block_k, t, d, q_span)
    kernel = partial(
        _flash_kernel, **_windowed(
            window, block_q=block_q, block_k=block_k, q_span=q_span, causal=causal, scale=scale
        )
    )
    return _pallas_call(
        kernel,
        grid=grid,
        in_specs=[qspec, kvfull, kvfull],
        out_specs=[qspec, lse_row],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, t), jnp.float32),
        ],
        # every program of one (b, h) writes rows of the SAME full lse
        # block: the q-group dim must not be megacore-split ('arbitrary')
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        **_names(window, "flash_fwd", "p2pfl_flash_fwd"),
    )(q, k, v)


_FUSED_SCRATCH_LIMIT = 4 * 1024 * 1024  # bytes of fp32 [T, D] dQ scratch


def _bwd_use_fused(t: int, d: int, mode: str) -> bool:
    if mode == "fused":
        return True
    if mode == "split":
        return False
    return t * d * 4 <= _FUSED_SCRATCH_LIMIT


def _fused_vmem_limit(t: int, d: int) -> Optional[int]:
    """Scoped-VMEM limit of the fused backward. Heads up to 128 wide at up to
    4096 tokens fit the compiler's 16 MiB default at every shipped shape and
    keep it (their programs do not change). At D = 256 / T = 4096 the resident
    q, dO and dQ blocks (2 MiB each, double-buffered) and the 4 MiB dQ scratch
    pass it by 0.8 MiB, and at D = 128 / T = 8192 — the same bytes: the scratch
    is exactly ``_FUSED_SCRATCH_LIMIT`` — by 1.0 MiB (host-only compiles for a
    v5e), so those ask for 32 MiB of the chip's 128 — which keeps the 5-matmul
    kernel instead of the 7-matmul split pair."""
    return 32 * 1024 * 1024 if t * d > 4096 * 128 else None


def _dq_scratch(t: int, d: int):
    """The fused backward's persistent fp32 [T, D] dQ accumulator."""
    return [pltpu.VMEM((t, d), jnp.float32)]


def _flash_bwd_bthd(q, k, v, do, lse, delta, *, block_q, block_k, causal, interpret, bwd_mode, window=None):
    b, h, t, d = q.shape
    scale = d ** -0.5
    kw = _windowed(window, block_q=block_q, block_k=block_k, causal=causal, scale=scale)
    qspec, kvfull, lse_row = _specs(block_q, block_k, t, d)
    qfull = pl.BlockSpec((None, None, t, d), lambda bi, hi, i: (bi, hi, 0, 0))
    kvspec = pl.BlockSpec((None, None, block_k, d), lambda bi, hi, j: (bi, hi, j, 0))

    if _bwd_use_fused(t, d, bwd_mode):
        dk, dv, dq = _pallas_call(
            partial(_dkvq_kernel, **kw),
            grid=(b, h, t // block_k),
            in_specs=[qfull, kvspec, kvspec, qfull, lse_row, lse_row],
            out_specs=[kvspec, kvspec, qfull],
            out_shape=[
                jax.ShapeDtypeStruct(k.shape, k.dtype),
                jax.ShapeDtypeStruct(v.shape, v.dtype),
                jax.ShapeDtypeStruct(q.shape, q.dtype),
            ],
            scratch_shapes=_dq_scratch(t, d),
            # the k-block dim MUST run sequentially: dq_acc accumulates
            # across its grid steps (and dq_ref flushes at the last) — this
            # encodes the requirement instead of relying on the default
            # semantics happening to serialize (advisor round-5)
            compiler_params=_compiler_params(
                "parallel", "parallel", "arbitrary", vmem_limit_bytes=_fused_vmem_limit(t, d)
            ),
            interpret=interpret,
            **_names(window, "flash_bwd", "p2pfl_flash_bwd_fused"),
        )(q, k, v, do, lse, delta)
        return dq, dk, dv

    # split kernels write disjoint output blocks and only read the shared
    # full blocks — every grid dim is safely parallel (megacore-splittable)
    split_params = _compiler_params("parallel", "parallel", "parallel")
    dq = _pallas_call(
        partial(_dq_kernel, **kw),
        grid=(b, h, t // block_q),
        in_specs=[qspec, kvfull, kvfull, qspec, lse_row, lse_row],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=split_params,
        interpret=interpret,
        **_names(window, "flash_bwd", "p2pfl_flash_bwd_dq"),
    )(q, k, v, do, lse, delta)

    dk, dv = _pallas_call(
        partial(_dkv_kernel, **kw),
        grid=(b, h, t // block_k),
        in_specs=[qfull, kvspec, kvspec, qfull, lse_row, lse_row],
        out_specs=[kvspec, kvspec],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        compiler_params=split_params,
        interpret=interpret,
        **_names(window, "flash_bwd", "p2pfl_flash_bwd_dkv"),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def flash_attention(
    q, k, v, causal: bool = True, config: Optional[FlashConfig] = None,
    interpret: bool = False, window: Optional[int] = None,
):
    """Flash attention. q,k,v: [B, T, H, D] (GQA heads pre-repeated).

    ``window`` (static, causal only): query ``i`` sees keys ``i - window < j <=
    i`` — ``window`` keys, itself included. K/V blocks wholly outside that band
    are SKIPPED (forward from the q side, both backwards from the k side) and
    only the blocks that straddle the band's lower edge or the diagonal are
    masked, so a sliding layer pays for the pairs it sees. ``window >= T`` is
    plain causal attention and ``window=None`` IS the program there has always
    been: the window is a static argument, nothing of it is traced. Windowed
    calls run under their own scopes and kernel names (``p2pfl.flash_win_fwd``
    / ``p2pfl_flash_win_fwd``, …) and resolve their own schedule (the window
    is part of the autotune key).

    ``config`` is the STATIC kernel schedule (:class:`FlashConfig` —
    forward/backward block shapes, q ownership, backward mode); it is a
    ``custom_vjp`` nondiff argument, so it participates in every enclosing
    jit's cache key and flipping any knob re-traces. ``None`` resolves the
    tuned/default config for this (T, D, dtype, causal) through
    :func:`p2pfl_tpu.ops.autotune.get_flash_config` — but note that this
    resolution happens at TRACE time against the autotune caches, and the
    enclosing jit's cache key then contains only ``None``: pinning or
    autotuning AFTER such a step has compiled does not re-trace it. To
    keep the schedule live-switchable, resolve the config BEFORE the jit
    boundary and pass it explicitly (``tiny_transformer`` does exactly
    this at model-build time). The saved log-sum-exp lives in a
    block-size-independent ``[B, H, 1, T]`` row layout, so the backward
    re-blocks freely without relayout.
    """
    if window is not None:
        if not causal:
            raise ValueError("flash_attention: a sliding window is causal (window=… with causal=False)")
        if window < 1:
            raise ValueError(f"flash_attention: window {window} (a row sees at least itself)")
        if window >= q.shape[1]:
            window = None  # every earlier key is inside it
    return _flash_attention(q, k, v, causal, config, interpret, window)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, causal, config, interpret, window):
    out, _ = _fwd(q, k, v, causal, config, interpret, window)
    return out


def _clamp_blocks(t, block_q, block_k):
    block_q, block_k = min(block_q, t), min(block_k, t)
    assert t % block_q == 0 and t % block_k == 0, "T must divide the block sizes"
    return block_q, block_k


def _fit_q_span(t: int, block_q: int, q_span: int) -> int:
    """Largest span <= q_span that divides the q-block count (a schedule
    knob degrades gracefully instead of asserting)."""
    nq = t // block_q
    return next(s for s in range(min(q_span, nq), 0, -1) if nq % s == 0)


def _fwd(q, k, v, causal, config, interpret, window):
    t, d = q.shape[1], q.shape[-1]
    cfg = _resolve(config, t, d, q.dtype, causal, window)
    block_q, block_k = _clamp_blocks(t, cfg.block_q, cfg.block_k)
    q_span = _fit_q_span(t, block_q, cfg.q_span)
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out, lse = _flash_fwd_bthd(
        qt, kt, vt, block_q=block_q, block_k=block_k, q_span=q_span,
        causal=causal, interpret=interpret, window=window,
    )
    return out.transpose(0, 2, 1, 3), (q, k, v, out, lse)


def _bwd_blocks(t: int, d: int, cfg: FlashConfig) -> tuple[int, int]:
    """The ONE place backward block sizes are decided (``block_q_bwd`` /
    ``block_k_bwd`` only override). Fused single-pass: the forward's own
    blocks are fastest (measured D=128/T=4096: 66.7% MFU at 512 vs 57.5%
    at 1024). Split two-pass at wide heads: the largest block <= 1024
    (measured 56% vs 45% at 512)."""
    if cfg.block_q_bwd is not None or cfg.block_k_bwd is not None:
        return _clamp_blocks(
            t, cfg.block_q_bwd or cfg.block_q, cfg.block_k_bwd or cfg.block_k
        )
    bq, bk = _clamp_blocks(t, cfg.block_q, cfg.block_k)
    if _bwd_use_fused(t, d, cfg.bwd_mode):
        return bq, bk
    if d >= 128:
        big = next(
            (b for b in range(min(1024, t), bq, -1) if t % b == 0 and b % 8 == 0),
            None,
        )
        if big:
            return big, big
    return bq, bk


def _bwd(causal, config, interpret, window, res, g):
    q, k, v, out_bhtd, lse = res
    t, d = q.shape[1], q.shape[-1]
    cfg = _resolve(config, t, d, q.dtype, causal, window)
    bq, bk = _bwd_blocks(t, d, cfg)
    b, h = out_bhtd.shape[:2]
    do = g.transpose(0, 2, 1, 3)  # [B, H, T, D]
    # Δ_i = Σ_d dO_id · O_id, in the same [B, H, 1, T] row layout as lse
    # (block-size independent — no relayout whatever blocks the bwd picks)
    delta = jnp.sum(
        do.astype(jnp.float32) * out_bhtd.astype(jnp.float32), axis=-1
    )[:, :, None, :]
    dq, dk, dv = _flash_bwd_bthd(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        do,
        lse,
        delta,
        block_q=bq,
        block_k=bk,
        causal=causal,
        interpret=interpret,
        bwd_mode=cfg.bwd_mode,
        window=window,
    )
    return tuple(x.transpose(0, 2, 1, 3) for x in (dq, dk, dv))


_flash_attention.defvjp(_fwd, _bwd)


# ---- offset-aware variants: flash blocks inside ring attention ----
#
# Ring attention hands each device K/V blocks from OTHER sequence shards;
# causal masking then depends on the blocks' global offsets, which are
# traced values (lax.axis_index) under shard_map. The offsets ride into the
# kernels as int32 scalars in SMEM — the causal frontier becomes a traced
# fori_loop bound and the mask compares global row/col indices.

_SMEM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _fwd_tile_offs(q, k_ref, v_ref, qi, q_off, k_off, *, block_q, block_k, scale, t):
    """Offset-aware sibling of :func:`_fwd_tile`: the causal frontier is in
    GLOBAL coordinates (traced offsets), so the loop bounds are traced."""
    dt = q.dtype
    acc = jnp.zeros((block_q, q.shape[-1]), jnp.float32)
    m = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)

    # causal frontier in global coordinates: stream k blocks whose first
    # column is <= this q sub-tile's last row; blocks whose last column is
    # <= this sub-tile's first row are fully visible and skip the mask
    last_row = q_off + (qi + 1) * block_q - 1
    n_blocks = jnp.clip(lax.div(last_row - k_off, block_k) + 1, 0, t // block_k)
    n_full = jnp.clip(
        lax.div(q_off + qi * block_q - k_off + 1, block_k), 0, n_blocks
    )

    def body(j, carry, *, masked):
        acc, m, l = carry
        k = k_ref[pl.ds(j * block_k, block_k), :]
        v = v_ref[pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if masked:
            rows = q_off + qi * block_q + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = k_off + j * block_k + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:
            p = jnp.where(m_new <= NEG_INF / 2, 0.0, p)
            alpha = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - m_new))
        else:
            alpha = jnp.exp(m - m_new)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(dt), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        return acc, m_new, l

    acc, m, l = lax.fori_loop(0, n_full, partial(body, masked=False), (acc, m, l))
    acc, m, l = lax.fori_loop(n_full, n_blocks, partial(body, masked=True), (acc, m, l))
    return acc, m, l


def _flash_kernel_offs(
    offs_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q, block_k, q_span, scale
):
    t = k_ref.shape[0]
    q_off, k_off = offs_ref[0], offs_ref[1]
    for s in range(q_span):
        qi = pl.program_id(2) * q_span + s
        q = q_ref[pl.ds(s * block_q, block_q), :]
        acc, m, l = _fwd_tile_offs(
            q, k_ref, v_ref, qi, q_off, k_off,
            block_q=block_q, block_k=block_k, scale=scale, t=t,
        )
        o_ref[pl.ds(s * block_q, block_q), :] = (
            acc / jnp.maximum(l, 1e-30)
        ).astype(o_ref.dtype)
        lse = jnp.where(m <= NEG_INF / 2, NEG_INF, m + jnp.log(jnp.maximum(l, 1e-30)))
        lse_ref[pl.ds(0, 1), pl.ds(qi * block_q, block_q)] = lse.reshape(1, block_q)


def _dq_kernel_offs(
    offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, glse_ref, dq_ref,
    *, block_q, block_k, scale,
):
    qi = pl.program_id(2)
    t = k_ref.shape[0]
    dt = q_ref.dtype
    q_off, k_off = offs_ref[0], offs_ref[1]
    q = q_ref[:]
    do = do_ref[:]
    lse = _row(lse_ref, qi, block_q)
    delta = _row(delta_ref, qi, block_q)
    # d lse / d s = softmax row, so the lse cotangent adds into ds
    glse = _row(glse_ref, qi, block_q)

    dq = jnp.zeros((block_q, q.shape[-1]), jnp.float32)
    last_row = q_off + (qi + 1) * block_q - 1
    n_blocks = jnp.clip(lax.div(last_row - k_off, block_k) + 1, 0, t // block_k)
    n_full = jnp.clip(
        lax.div(q_off + qi * block_q - k_off + 1, block_k), 0, n_blocks
    )

    def body(j, dq, *, masked):
        k = k_ref[pl.ds(j * block_k, block_k), :]
        v = v_ref[pl.ds(j * block_k, block_k), :]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if masked:
            rows = q_off + qi * block_q + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = k_off + j * block_k + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        # rows invisible in this hop have lse = -inf: p must be 0, not nan
        p = jnp.where(lse <= NEG_INF / 2, 0.0, jnp.exp(s - lse))
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta + glse)
        return dq + scale * jax.lax.dot_general(
            ds.astype(dt), k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    dq = lax.fori_loop(0, n_full, partial(body, masked=False), dq)
    dq = lax.fori_loop(n_full, n_blocks, partial(body, masked=True), dq)
    dq_ref[:] = dq.astype(dq_ref.dtype)


def _dkv_step_offs(
    i, dk, dv, *, q_ref, do_ref, lse_ref, delta_ref, glse_ref, k, v, kj,
    q_off, k_off, block_q, block_k, scale, dt, masked, dq_acc=None,
):
    """Offset-aware sibling of :func:`_dkv_step` (global-coordinate mask,
    lse sentinel guard, lse-cotangent term), shared by the split and fused
    offset backward kernels."""
    q = q_ref[pl.ds(i * block_q, block_q), :]
    do = do_ref[pl.ds(i * block_q, block_q), :]
    lse = _row(lse_ref, i, block_q)
    delta = _row(delta_ref, i, block_q)
    glse = _row(glse_ref, i, block_q)
    s = scale * jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    if masked:
        rows = q_off + i * block_q + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        cols = k_off + kj * block_k + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(rows >= cols, s, NEG_INF)
    p = jnp.where(lse <= NEG_INF / 2, 0.0, jnp.exp(s - lse))
    dv = dv + jax.lax.dot_general(
        p.astype(dt), do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = (p * (dp - delta + glse)).astype(dt)
    dk = dk + scale * jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    if dq_acc is not None:
        dq_acc[pl.ds(i * block_q, block_q), :] += scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
    return dk, dv


def _offs_kv_bounds(kj, q_off, k_off, block_q, block_k, nq):
    """(start, full): first q block whose last global row reaches this k
    block's first col, and first q block whose FIRST row clears its last
    col (q blocks past that see the whole k block — no mask)."""
    first_col = k_off + kj * block_k
    start = jnp.clip(lax.div(first_col - q_off, block_q), 0, nq)
    full = jnp.clip(
        lax.div(k_off + (kj + 1) * block_k - 1 - q_off + block_q - 1, block_q),
        start,
        nq,
    )
    return start, full


def _dkv_kernel_offs(
    offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, glse_ref, dk_ref, dv_ref,
    *, block_q, block_k, scale,
):
    kj = pl.program_id(2)
    t = q_ref.shape[0]
    q_off, k_off = offs_ref[0], offs_ref[1]
    k = k_ref[:]
    v = v_ref[:]

    dk = jnp.zeros((block_k, k.shape[-1]), jnp.float32)
    dv = jnp.zeros((block_k, k.shape[-1]), jnp.float32)
    nq = t // block_q
    start, full = _offs_kv_bounds(kj, q_off, k_off, block_q, block_k, nq)

    def body(i, carry, *, masked):
        return _dkv_step_offs(
            i, *carry, q_ref=q_ref, do_ref=do_ref, lse_ref=lse_ref,
            delta_ref=delta_ref, glse_ref=glse_ref, k=k, v=v, kj=kj,
            q_off=q_off, k_off=k_off, block_q=block_q, block_k=block_k,
            scale=scale, dt=q_ref.dtype, masked=masked,
        )

    dk, dv = lax.fori_loop(start, full, partial(body, masked=True), (dk, dv))
    dk, dv = lax.fori_loop(full, nq, partial(body, masked=False), (dk, dv))
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _dkvq_kernel_offs(
    offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, glse_ref,
    dk_ref, dv_ref, dq_ref, dq_acc, *, block_q, block_k, scale,
):
    """Offset-aware single-pass backward (see :func:`_dkvq_kernel`): dQ
    accumulates across the sequential k-block grid steps in a persistent
    fp32 scratch, so S and dP are computed once per (i, j) pair. q blocks
    invisible to every k block in this hop keep their zeroed scratch —
    the correct zero cotangent for rows the hop never attends."""
    kj = pl.program_id(2)
    nk = pl.num_programs(2)
    t = q_ref.shape[0]
    q_off, k_off = offs_ref[0], offs_ref[1]
    k = k_ref[:]
    v = v_ref[:]

    @pl.when(kj == 0)
    def _zero():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    dk = jnp.zeros((block_k, k.shape[-1]), jnp.float32)
    dv = jnp.zeros((block_k, k.shape[-1]), jnp.float32)
    nq = t // block_q
    start, full = _offs_kv_bounds(kj, q_off, k_off, block_q, block_k, nq)

    def body(i, carry, *, masked):
        return _dkv_step_offs(
            i, *carry, q_ref=q_ref, do_ref=do_ref, lse_ref=lse_ref,
            delta_ref=delta_ref, glse_ref=glse_ref, k=k, v=v, kj=kj,
            q_off=q_off, k_off=k_off, block_q=block_q, block_k=block_k,
            scale=scale, dt=q_ref.dtype, masked=masked, dq_acc=dq_acc,
        )

    dk, dv = lax.fori_loop(start, full, partial(body, masked=True), (dk, dv))
    dk, dv = lax.fori_loop(full, nq, partial(body, masked=False), (dk, dv))
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)

    @pl.when(kj == nk - 1)
    def _flush():
        dq_ref[:] = dq_acc[...].astype(dq_ref.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def flash_attention_block(
    q, k, v, q_off, k_off, config: Optional[FlashConfig] = None,
    interpret: bool = False,
):
    """One causal-by-global-offset attention block: q attends k/v where
    ``q_off + i >= k_off + j``. q,k,v: [B, T, H, D] (T = local shard).
    ``q_off``/``k_off`` are traced int32 scalars (e.g. ``axis_index * T``
    under ``shard_map``). Returns ``(out, lse)`` — the ``[B, H, 1, T]``
    log-sum-exp makes results mergeable across blocks (ring attention
    hops). ``config`` is the same static :class:`FlashConfig` schedule as
    :func:`flash_attention` (None resolves the tuned/default)."""
    out, lse, _ = _fab_fwd_impl(q, k, v, q_off, k_off, config, interpret)
    return out, lse


def _fab_fwd_impl(q, k, v, q_off, k_off, config, interpret):
    b, t, h, d = q.shape
    cfg = _resolve(config, t, d, q.dtype, True)
    block_q, block_k = _clamp_blocks(t, cfg.block_q, cfg.block_k)
    q_span = _fit_q_span(t, block_q, cfg.q_span)
    scale = d ** -0.5
    offs = jnp.stack([q_off, k_off]).astype(jnp.int32)
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    qspec, kvfull, lse_row = _specs(block_q, block_k, t, d, q_span)
    out, lse = _pallas_call(
        partial(
            _flash_kernel_offs, block_q=block_q, block_k=block_k,
            q_span=q_span, scale=scale,
        ),
        grid=(b, h, t // (block_q * q_span)),
        in_specs=[_SMEM_SPEC, qspec, kvfull, kvfull],
        out_specs=[qspec, lse_row],
        out_shape=[
            jax.ShapeDtypeStruct(qt.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, t), jnp.float32),
        ],
        # shared-write lse row block — same reason as _flash_fwd_bthd
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        scope_name="flash_fwd",
        name="p2pfl_flash_fwd",
    )(offs, qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse, out


def _fab_fwd(q, k, v, q_off, k_off, config, interpret):
    out, lse, out_bhtd = _fab_fwd_impl(q, k, v, q_off, k_off, config, interpret)
    return (out, lse), (q, k, v, q_off, k_off, out_bhtd, lse)


def _fab_bwd(config, interpret, res, cts):
    g, g_lse = cts  # the ring merge differentiates through lse too
    q, k, v, q_off, k_off, out_bhtd, lse = res
    b, t, h, d = q.shape
    cfg = _resolve(config, t, d, q.dtype, True)
    block_q, block_k = _bwd_blocks(t, d, cfg)
    scale = d ** -0.5
    offs = jnp.stack([q_off, k_off]).astype(jnp.int32)
    do = g.transpose(0, 2, 1, 3)
    delta = jnp.sum(
        do.astype(jnp.float32) * out_bhtd.astype(jnp.float32), axis=-1
    )[:, :, None, :]
    qspec, kvfull, lse_row = _specs(block_q, block_k, t, d)
    qfull = pl.BlockSpec((None, None, t, d), lambda bi, hi, i: (bi, hi, 0, 0))
    kvspec = pl.BlockSpec((None, None, block_k, d), lambda bi, hi, j: (bi, hi, j, 0))
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))

    # rows invisible in this hop (lse at the -1e30 sentinel) carry no lse
    # gradient; NEG_INF is finite, so compare, don't isfinite
    g_lse = jnp.where(lse <= NEG_INF / 2, 0.0, g_lse.astype(jnp.float32))
    if _bwd_use_fused(t, d, cfg.bwd_mode):
        dk, dv, dq = _pallas_call(
            partial(_dkvq_kernel_offs, block_q=block_q, block_k=block_k, scale=scale),
            grid=(b, h, t // block_k),
            in_specs=[
                _SMEM_SPEC, qfull, kvspec, kvspec, qfull, lse_row, lse_row, lse_row,
            ],
            out_specs=[kvspec, kvspec, qfull],
            out_shape=[
                jax.ShapeDtypeStruct(kt.shape, k.dtype),
                jax.ShapeDtypeStruct(vt.shape, v.dtype),
                jax.ShapeDtypeStruct(qt.shape, q.dtype),
            ],
            scratch_shapes=_dq_scratch(t, d),
            # sequential k-block accumulation into dq_acc — see _dkvq_kernel
            compiler_params=_compiler_params(
                "parallel", "parallel", "arbitrary", vmem_limit_bytes=_fused_vmem_limit(t, d)
            ),
            interpret=interpret,
            scope_name="flash_bwd",
            name="p2pfl_flash_bwd_fused",
        )(offs, qt, kt, vt, do, lse, delta, g_lse)
    else:
        split_params = _compiler_params("parallel", "parallel", "parallel")
        dq = _pallas_call(
            partial(_dq_kernel_offs, block_q=block_q, block_k=block_k, scale=scale),
            grid=(b, h, t // block_q),
            in_specs=[_SMEM_SPEC, qspec, kvfull, kvfull, qspec, lse_row, lse_row, lse_row],
            out_specs=qspec,
            out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
            compiler_params=split_params,
            interpret=interpret,
            scope_name="flash_bwd",
            name="p2pfl_flash_bwd_dq",
        )(offs, qt, kt, vt, do, lse, delta, g_lse)
        dk, dv = _pallas_call(
            partial(_dkv_kernel_offs, block_q=block_q, block_k=block_k, scale=scale),
            grid=(b, h, t // block_k),
            in_specs=[_SMEM_SPEC, qfull, kvspec, kvspec, qfull, lse_row, lse_row, lse_row],
            out_specs=[kvspec, kvspec],
            out_shape=[
                jax.ShapeDtypeStruct(kt.shape, k.dtype),
                jax.ShapeDtypeStruct(vt.shape, v.dtype),
            ],
            compiler_params=split_params,
            interpret=interpret,
            scope_name="flash_bwd",
            name="p2pfl_flash_bwd_dkv",
        )(offs, qt, kt, vt, do, lse, delta, g_lse)
    dq, dk, dv = (x.transpose(0, 2, 1, 3) for x in (dq, dk, dv))
    zero = jnp.zeros((), jnp.float32)  # int offsets carry no gradient
    return dq, dk, dv, zero, zero


flash_attention_block.defvjp(_fab_fwd, _fab_bwd)
