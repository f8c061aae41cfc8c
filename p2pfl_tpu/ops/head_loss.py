"""The output head and its cross-entropy as ONE function with its own derivative.

    head_loss(hidden [..., dim], embedding [vocab, dim], labels [...]) -> mean CE

``logits = hidden · embeddingᵀ`` followed by a softmax cross-entropy holds
``[rows, vocab]`` several times over: the logits, their float32 copy, the
softmax, the one-hot scatter of the label pick's backward. Nothing after the
loss needs any of them. Here the rows are taken a block at a time and each
block's logits live only inside the block:

- ``logits = h_block · Eᵀ`` in the hidden states' dtype (bf16 operands,
  float32 accumulation, the product rounded to bf16 exactly as ``jnp.dot``
  of two bf16 arrays rounds it);
- the row's log-sum-exp and its label's logit in float32, the label picked by
  a mask ``iota == label`` inside the same reduction (no gather, so no
  scatter backward);
- **the derivative in the same block**: ``g = softmax − onehot`` cast to the
  hidden dtype, ``dX = g · E`` and ``dE = gᵀ · h_block`` (summed over blocks),
  both accumulated in float32. They are the rule's residuals — ``[rows, dim]``
  in the hidden dtype, ``[vocab, dim]`` in the embedding's — and the backward
  rule only scales them by the incoming cotangent over the row count. That is
  right for ANY cotangent because the loss is a mean:
  ``d(ct · mean) = (ct / rows) · Σ d(row loss)``.

So each of the two ``[rows, vocab]`` matmuls a training step needs runs once
(a ``jax.checkpoint`` around the head would run the logits' a second time), and
whoever does not read a cotangent does not pay for it: under LoRA the
embedding is frozen, nobody reads ``dE``, and the compiler drops that product
and its accumulator from the loop.

The block's row count follows from the shapes: one block, and no loop, while
``rows × vocab`` stays under ``_ONE_BLOCK_ELEMENTS``; else the divisor of
``rows`` nearest ``_BLOCK_ROWS``. Both rules run under the scope
``p2pfl.head``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from p2pfl_tpu.management.profiling import scope

# rows of one block once the logits are too many to hold whole: of 512 / 1024 /
# 2048 read on the chip in glm_silo4_seq4096 ([4096, 2048] x [154880, 2048]) the
# fastest by 0.2 % of the round and the smallest — PERF.md section 6, PR 32
_BLOCK_ROWS = 512
# [4096, 65536]: up to here the whole logits are one block (0.54 GB in bf16)
_ONE_BLOCK_ELEMENTS = 1 << 28


def block_rows(rows: int, vocab: int) -> int:
    """Rows a block: all of them while ``rows × vocab`` is small, else the
    divisor of ``rows`` nearest ``_BLOCK_ROWS`` (by ratio; the smaller on a tie)."""
    if rows * vocab <= _ONE_BLOCK_ELEMENTS:
        return rows
    divisors = {d for i in range(1, math.isqrt(rows) + 1) if rows % i == 0 for d in (i, rows // i)}
    return min(sorted(divisors), key=lambda d: abs(math.log(d / _BLOCK_ROWS)))


def _block(h, emb, labels):
    """One block: (Σ row losses, dX ``[rows, dim]``, dE ``[vocab, dim]`` float32)
    of the SUM of the block's row losses."""
    logits = lax.dot_general(h, emb, (((1,), (1,)), ((), ())))  # [rows, vocab], h's dtype
    z = logits.astype(jnp.float32)
    at_label = lax.broadcasted_iota(jnp.int32, z.shape, 1) == labels[:, None]
    top = jnp.max(z, axis=-1)
    lse = top + jnp.log(jnp.sum(jnp.exp(z - top[:, None]), axis=-1))
    loss = jnp.sum(lse - jnp.sum(jnp.where(at_label, z, 0.0), axis=-1))
    # from lse, not from the sum's own exp(z - top): one more exp a logit, and
    # no float32 [rows, vocab] array that two consumers would share
    g = (jnp.exp(z - lse[:, None]) - at_label.astype(jnp.float32)).astype(h.dtype)
    dx = lax.dot_general(g, emb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    de = lax.dot_general(g, h, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    return loss, dx.astype(h.dtype), de


def _sums(hidden, embedding, labels):
    """(Σ row losses, dX, dE) over all rows, ``hidden`` ``[rows, dim]``."""
    rows, _ = hidden.shape
    emb = embedding.astype(hidden.dtype)
    per = block_rows(rows, embedding.shape[0])
    if per == rows:
        return _block(hidden, emb, labels)

    def body(carry, block):
        loss, de = carry
        block_loss, dx, block_de = _block(block[0], emb, block[1])
        return (loss + block_loss, de + block_de), dx

    blocks = (hidden.reshape(rows // per, per, -1), labels.reshape(rows // per, per))
    zero = (jnp.zeros((), jnp.float32), jnp.zeros(embedding.shape, jnp.float32))
    (loss, de), dx = lax.scan(body, zero, blocks)
    return loss, dx.reshape(rows, -1), de


@jax.custom_vjp
def head_loss(hidden, embedding, labels):
    """Mean over every position of the cross-entropy of
    ``softmax(hidden · embeddingᵀ)`` against ``labels`` — what
    ``optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()``
    gives on the float32 copy of ``jnp.dot(hidden, embedding.T.astype(hidden.dtype))``,
    without a ``[rows, vocab]`` array outliving its block."""
    return _head_loss_fwd(hidden, embedding, labels)[0]


def _head_loss_fwd(hidden, embedding, labels):
    with scope("head"):
        rows = labels.size
        loss, dx, de = _sums(hidden.reshape(rows, -1), embedding, labels.reshape(rows).astype(jnp.int32))
        return loss / rows, (dx.reshape(hidden.shape), de.astype(embedding.dtype))


def _head_loss_bwd(res, ct):
    with scope("head"):
        dx, _ = res
        scale = ct.astype(jnp.float32) / (dx.size // dx.shape[-1])  # the mean's 1 / rows
        return tuple((d.astype(jnp.float32) * scale).astype(d.dtype) for d in res) + (None,)


head_loss.defvjp(_head_loss_fwd, _head_loss_bwd)
