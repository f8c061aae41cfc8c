"""Attention ops: fused causal attention + ring attention for long context.

The reference has no attention anywhere (SURVEY §2.9) — this exists for the
BASELINE config-5 model family (TinyLlama LoRA) and makes long-context
first-class: sequences longer than one chip's HBM are sharded over a mesh
axis and attended with **ring attention** (Liu et al. 2023): K/V blocks
rotate around the ring via ``ppermute`` while each device keeps an online
(flash-style) softmax accumulator — full attention, O(T_local) memory per
device, communication overlapped by XLA with the per-block matmuls.

All softmax statistics accumulate in float32; inputs stay bfloat16 on the
MXU.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def causal_attention(q, k, v, scale: Optional[float] = None, window: Optional[int] = None) -> jax.Array:
    """Plain fused causal attention. q,k,v: [B, T, H, D] (k/v may have fewer
    heads — GQA — already repeated by the caller). Returns [B, T, H, D].
    ``window``: query ``i`` sees keys ``i - window < j <= i`` only (``window``
    keys, itself included); the ``[T, T]`` logits are still formed whole."""
    b, t, h, d = q.shape
    scale = (d ** -0.5) if scale is None else scale
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    mask = jnp.tril(jnp.ones((t, t), dtype=bool))
    if window is not None and window < t:
        mask = mask & ~jnp.tril(jnp.ones((t, t), dtype=bool), -window)
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _block_attend(q, k, v, q_off, k_off, scale, causal):
    """One flash block: returns (numerator [B,Tq,H,D] f32, denom [B,H,Tq] f32,
    running max [B,H,Tq] f32) for q against one K/V block with global offsets."""
    tq, tk = q.shape[1], k.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = q_off + jnp.arange(tq)
        k_pos = k_off + jnp.arange(tk)
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)  # [B,H,Tq]
    p = jnp.exp(s - m[..., None])
    # fully-masked rows: exp(NEG_INF - NEG_INF) = 1 would pollute the denom
    p = jnp.where(m[..., None] <= NEG_INF / 2, 0.0, p)
    num = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v).astype(jnp.float32)
    den = jnp.sum(p, axis=-1)  # [B,H,Tq]
    return num, den, m


@partial(jax.jit, static_argnames=("axis_name", "causal"))
def _ring_attention_sharded(q, k, v, *, axis_name: str, causal: bool = True):
    """Per-device body; call under ``shard_map`` with T sharded on ``axis_name``.

    q,k,v local blocks: [B, T_local, H, D]. K/V rotate ``ring_size`` hops;
    accumulators merge with the standard online-softmax rescaling.
    """
    ring = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    b, tl, h, d = q.shape
    scale = d ** -0.5

    acc = jnp.zeros((b, tl, h, d), jnp.float32)
    den = jnp.zeros((b, h, tl), jnp.float32)
    m = jnp.full((b, h, tl), NEG_INF, jnp.float32)
    # mark accumulators device-varying so the loop carry types line up with
    # the sharded K/V blocks (shard_map's varying-axes typing)
    acc, den, m = lax.pcast((acc, den, m), (axis_name,), to="varying")
    perm = [(j, (j + 1) % ring) for j in range(ring)]

    def body(i, carry):
        acc, den, m, kb, vb = carry
        src = (my - i) % ring  # which shard this K/V block came from
        num_i, den_i, m_i = _block_attend(
            q, kb, vb, q_off=my * tl, k_off=src * tl, scale=scale, causal=causal
        )
        m_new = jnp.maximum(m, m_i)
        # guard: rows where nothing is visible yet keep NEG_INF stats
        alpha = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - m_new))
        beta = jnp.where(m_i <= NEG_INF / 2, 0.0, jnp.exp(m_i - m_new))
        acc = acc * alpha.transpose(0, 2, 1)[..., None] + num_i * beta.transpose(0, 2, 1)[..., None]
        den = den * alpha + den_i * beta
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return acc, den, m_new, kb, vb

    acc, den, m, _, _ = lax.fori_loop(0, ring, body, (acc, den, m, k, v))
    out = acc / jnp.maximum(den, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def _ring_flash_sharded(q, k, v, *, axis_name: str, config, interpret: bool):
    """Per-device ring body with Pallas flash blocks: each hop runs the
    offset-aware flash kernel on the local Q against the incoming K/V shard
    (O(T_local·D) memory instead of the dense body's O(T_local²) logits),
    then merges via log-sum-exp — the differentiable ring-flash composition.
    ``config`` is the static :class:`~p2pfl_tpu.ops.flash_attention.FlashConfig`
    kernel schedule for every hop's kernel.
    """
    from p2pfl_tpu.ops.flash_attention import flash_attention_block

    ring = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    b, tl, h, d = q.shape
    perm = [(j, (j + 1) % ring) for j in range(ring)]

    out = jnp.zeros((b, tl, h, d), jnp.float32)
    # lse rides the kernels' block-size-independent [B, H, 1, T_local] row
    # layout, so hop merges never depend on the configured block shapes
    lse = jnp.full((b, h, 1, tl), NEG_INF, jnp.float32)
    out, lse = lax.pcast((out, lse), (axis_name,), to="varying")

    kb, vb = k, v
    for i in range(ring):  # ring size is static: plain python loop
        src = (my - i) % ring  # which shard this K/V block came from
        ob, lb = flash_attention_block(
            q, kb, vb, my * tl, src * tl, config, interpret
        )
        new = jnp.logaddexp(lse, lb)
        # NEG_INF is a large finite sentinel (-1e30), so test against the
        # same <= NEG_INF/2 convention the kernels use — not isfinite
        wo = jnp.where(lse <= NEG_INF / 2, 0.0, jnp.exp(lse - new))
        wn = jnp.where(lb <= NEG_INF / 2, 0.0, jnp.exp(lb - new))

        def as_bthd(w):  # [B,H,1,T] -> [B,T,H,1]
            return w.reshape(b, h, tl).transpose(0, 2, 1)[..., None]

        out = out * as_bthd(wo) + ob.astype(jnp.float32) * as_bthd(wn)
        lse = new
        if i + 1 < ring:
            kb = lax.ppermute(kb, axis_name, perm)
            vb = lax.ppermute(vb, axis_name, perm)
    return out.astype(q.dtype)


def ring_attention(
    q, k, v, mesh, axis_name: str, causal: bool = True, impl: str = "dense",
    block: int = 128, flash_config=None, window: Optional[int] = None,
) -> jax.Array:
    """Full-sequence attention with T sharded over ``axis_name`` of ``mesh``.

    q,k,v: [B, T, H, D] global arrays (T divisible by the axis size).
    ``impl="flash"`` runs each ring hop through the offset-aware Pallas
    flash kernel — O(T_local·D) memory per device instead of the dense
    body's O(T_local²) logits matrix (causal only). ``flash_config`` pins
    the hops' full static kernel schedule
    (:class:`~p2pfl_tpu.ops.flash_attention.FlashConfig`); ``block`` is the
    square-block shorthand used when no config is given. ``window`` exists to
    be refused: the ring has no sliding window (it raises, and says why).
    """
    from jax.sharding import PartitionSpec as P

    if window is not None:
        raise NotImplementedError(
            f"ring_attention takes no sliding window (window={window}): its hops (flash_attention_block, "
            "_block_attend) mask by global offset only, and a hop wholly outside the window would still be "
            "sent and attended; run sliding layers with attn='flash' or 'dense' on one chip"
        )
    spec = P(None, axis_name, None, None)
    if impl == "flash":
        if not causal:
            raise ValueError("impl='flash' supports causal attention only")
        from p2pfl_tpu.ops.flash_attention import FlashConfig

        interpret = jax.default_backend() != "tpu"
        tl = q.shape[1] // mesh.shape[axis_name]
        config = flash_config or FlashConfig(
            block_q=min(block, tl), block_k=min(block, tl)
        )
        body = partial(
            _ring_flash_sharded,
            axis_name=axis_name,
            config=config,
            interpret=interpret,
        )
        # pallas_call's out_shape carries no vma typing — disable the check
        # for the flash body (the collectives are still the same ring)
        fn = jax.shard_map(
            body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )
        return fn(q, k, v)
    body = partial(_ring_attention_sharded.__wrapped__, axis_name=axis_name, causal=causal)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
