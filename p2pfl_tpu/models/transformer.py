"""Decoder-only language models with LoRA adapters.

BASELINE config 5: federated LoRA fine-tuning — nodes train and exchange
ONLY the low-rank adapters, so a round's gossip payload drops from the full
model to a few MB. A block is pre-RMSNorm → sequence mixer → residual, then
pre-RMSNorm → SwiGLU (or MoE) → residual (``post_norms``: a second RMSNorm on
each sublayer's OUTPUT before its residual — the sandwich); all matmuls in
bfloat16 on the MXU, norms, softmax statistics and the state-space recurrence
in float32. The output head is the embedding transposed unless ``tie_head`` is
off, when it is a matrix of its own (``lm_head``, frozen under LoRA).

Sequence mixers — ``TransformerConfig.layer_pattern`` names one per layer of a
period, and the stack repeats the period:

- ``"attention"`` (the default, alone: the Llama recipe): grouped-query causal
  attention, with RoPE unless ``rope_theta`` is ``None``;
- ``"mamba"``: the Mamba-1 mixer (:class:`MambaMixer`) — causal depthwise
  convolution, input-dependent step sizes, and the selective scan of
  ``ops/selective_scan.py`` (the Jamba hybrids interleave it with attention);
- ``"mla_dense"`` / ``"mla_experts"``: latent attention (:class:`MLAttention`,
  the DeepSeek-V3 block) followed by the dense SwiGLU or by the dropless
  sigmoid-routed expert layer (:class:`ExpertFFN`) — these two kinds name the
  feed-forward as well as the mixer (:data:`LAYER_KINDS`), so "one dense layer,
  then N expert layers" is one period of two runs;
- ``"conv_dense"`` / ``"conv_experts"``: the gated short convolution
  (:class:`ShortConvMixer`, the LFM2 operator) before the dense SwiGLU or the
  expert layer; ``"attention_experts"``: plain attention before the expert layer;
- ``"swa_dense"`` / ``"swa_experts"`` / ``"full_experts"``:
  sliding-window and full attention in ONE stack (the ``afmoe`` block of
  Trinity): a ``swa`` layer rotates at ``rope_theta`` and sees the last
  ``attn_window`` keys only, a ``full`` layer sees every earlier key and is NOT
  rotated — rotation and window belong to the layer kind, not to the config.

``TransformerConfig.leading_pattern`` names layers that run ONCE before the
periodic stack ("two dense layers, then periods of expert layers").

Attention backends — pick with ``tiny_transformer(attn=...)``:

- ``"dense"`` (default): fused XLA causal attention (``ops/attention.py``);
  a sliding layer masks the ``[T, T]`` logits to its window;
- ``"flash"``: the Pallas flash kernel with its Pallas backward
  (``ops/flash_attention.py``) — O(T·D) memory in both directions; a sliding
  layer SKIPS the key blocks outside its window;
- ``"ring"`` / ``"ring_flash"``: ring attention over a mesh axis (pass
  ``mesh=``) — the sequence is sharded across chips, K/V rotate via
  ``ppermute``. No sliding window: a model with ``swa`` layers is refused.

Power users can instead pass any ``attn_fn(q, k, v) -> out`` directly; a model
with sliding layers calls it as ``attn_fn(q, k, v, window=W)`` on those.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from p2pfl_tpu.management.profiling import scope
from p2pfl_tpu.models.base import FlaxModel
from p2pfl_tpu.ops.attention import causal_attention
from p2pfl_tpu.ops.flash_attention import FlashConfig


_SSM_OUT = ("ssm_y", "ssm_state")  # the scan's output and its boundary states
_REMAT_SAVE_NAMES = {
    "mlp": ("ffn_gate", "ffn_up"),
    "mlp_qkv": ("ffn_gate", "ffn_up", "attn_q", "attn_k", "attn_v"),
    "ssm": ("attn_q", "attn_k", "attn_v", *_SSM_OUT),
    "mlp_ssm": ("ffn_gate", "ffn_up", "attn_q", "attn_k", "attn_v", *_SSM_OUT),
    "mlp_ssm_in": ("ffn_gate", "ffn_up", "attn_q", "attn_k", "attn_v", *_SSM_OUT, "ssm_in", "ssm_dt"),
}
# layer kind -> (sequence mixer, feed-forward). ``None`` is the config-wide
# rule the first two kinds have always had (``MoEMLP`` if ``n_experts`` else
# ``MLP``); every other kind names its own: mixer x feed-forward is a product.
LAYER_KINDS = {
    "attention": ("attention", None),
    "mamba": ("mamba", None),
    "mla_dense": ("mla", "mlp"),
    "mla_experts": ("mla", "experts"),
    "attention_experts": ("attention", "experts"),
    "conv_dense": ("short_conv", "mlp"),
    "conv_experts": ("short_conv", "experts"),
    # attention whose rotation and window are the KIND's: "swa" rotates at
    # cfg.rope_theta under cfg.attn_window, "full" does neither
    "swa_dense": ("swa", "mlp"),
    "swa_experts": ("swa", "experts"),
    "full_experts": ("full", "experts"),
}


def _is_expert_run(kind: str) -> bool:
    return LAYER_KINDS[kind][1] == "experts"


def _remat_policy(name: Optional[str], kind: str = "attention"):
    """Map ``TransformerConfig.remat_policy`` to a jax.checkpoint policy for a
    block of ``kind``. An expert layer ALWAYS keeps its routing choice
    (``moe_chosen``, ``[S, k]`` int32): the re-forward must use the forward's
    experts, and recomputing the choice does not guarantee that — a TPU fuses
    the re-forward's norm differently, a bfloat16 input rounds the other way,
    and a near-tie flips (the backward would then differentiate another
    function than the forward ran)."""
    keep = ("moe_chosen",) if _is_expert_run(kind) else ()
    if name is None and not keep:
        return None  # full per-block remat: save nothing inside the block
    try:
        names = _REMAT_SAVE_NAMES[name] if name is not None else ()
    except KeyError:
        raise ValueError(
            f"unknown remat_policy {name!r} (None|{'|'.join(_REMAT_SAVE_NAMES)})"
        ) from None
    return jax.checkpoint_policies.save_only_these_names(*names, *keep)


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 2048
    dim: int = 256
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 4
    ffn_hidden: int = 688  # ~8/3 * dim rounded
    # width of one attention head; None = dim // n_heads (every model whose
    # heads tile the residual width). Trinity: 32 heads of 128 on 2048.
    head_dim: Optional[int] = None
    # None = no positional rotation at all (Jamba's attention layers: the
    # state-space layers around them carry position)
    rope_theta: Optional[float] = 10000.0
    # One period of the layer stack, a kind (:data:`LAYER_KINDS`) per layer;
    # ``n_layers`` less the leading layers must be a multiple of its length.
    # Jamba: 14 long, attention at index 7.
    layer_pattern: tuple = ("attention",)
    # Layers that run ONCE, before the first period (LFM2: two dense
    # short-convolution layers, then periods of expert layers). No expert kind:
    # an expert layer's stacked bank belongs to a period's run.
    leading_pattern: tuple = ()
    # per-head RMSNorm (its own scale, over ``head_dim``) on q and k before
    # RoPE (``"attention"`` mixers only)
    qk_norm: bool = False
    # keys a ``"swa_*"`` layer's query sees, itself included (row i: i - W < j <= i);
    # ``"full_*"`` layers and every other kind take no notice of it
    attn_window: Optional[int] = None
    # sigmoid output gate of ``Attention``: ``(P v) * sigmoid(x W_g)`` before
    # ``wo``; ``wg`` is a fifth LoRADense (``dim -> n_heads * head_dim``)
    attn_gate: bool = False
    # sandwich norms: an RMSNorm on the mixer's and on the feed-forward's
    # OUTPUT, before each residual add, beside the two pre-norms
    post_norms: bool = False
    # factor on the input embedding (muP models: sqrt(dim)); 1 = none
    embed_scale: float = 1.0
    # False: the output head is ``lm_head`` ``[vocab, dim]``, a base leaf of its
    # own — frozen under LoRA and outside its FedAvg — not the embedding
    tie_head: bool = True
    # taps of the gated short convolution (``"conv_*"`` layers only)
    conv_taps: int = 3
    # Mamba-1 widths (used by ``"mamba"`` layers only): inner width
    # ``ssm_expand * dim``, state per channel, depthwise-conv kernel, and the
    # rank of the step-size projection (None = ceil(dim / 16), Mamba's rule)
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_dt_rank: Optional[int] = None
    lora_rank: int = 8
    lora_alpha: float = 16.0
    lora_mlp: bool = False
    dtype: Any = jnp.bfloat16
    # Mixture-of-experts FFN (n_experts=0 => dense SwiGLU everywhere).
    # Experts stack on a leading [E, ...] axis that shards over the mesh's
    # model axis for expert parallelism (parallel/sharding.py EP rules).
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity: float = 1.25  # capacity factor: C = ceil(k*S/E * factor)
    moe_aux_coef: float = 1e-2  # Switch load-balance loss coefficient
    moe_zloss_coef: float = 1e-3  # router z-loss coefficient
    # per-block rematerialization: the backward pass keeps activations only
    # at block boundaries and recomputes the interior — the standard TPU
    # recipe for fitting big-model / long-sequence training in HBM. Coarser
    # than wrapping the WHOLE loss in jax.checkpoint (which re-runs the
    # full forward and still stashes every layer during the recompute);
    # per-block boundaries bound peak activation memory at one block.
    remat: bool = False
    # selective rematerialization policy (only meaningful with remat=True):
    #   None       — full per-block remat: nothing inside a block is saved,
    #                the backward re-runs the whole block forward (max
    #                memory savings, ~1/3 extra executed FLOPs);
    #   "mlp"      — save the FFN gate/up activations (the FFN is ~70% of a
    #                block's FLOPs) so the backward recomputes only the
    #                attention side;
    #   "mlp_qkv"  — additionally save post-RoPE q/k/v (k/v pre-GQA-repeat,
    #                so 2·kv_heads·head_dim + dim per token): the backward
    #                recomputes only the flash kernel forward (for its lse
    #                residual) and elementwise glue.
    #   "ssm"      — q/k/v as above, and of a Mamba layer the selective
    #                scan's output and chunk-boundary states: the re-forward
    #                runs the projections and the convolution again (the
    #                scan's backward needs them) but NO scan;
    #   "mlp_ssm"  — "mlp_qkv" + "ssm";
    #   "mlp_ssm_in" — additionally the Mamba in-projection's output and the
    #                step sizes (the re-forward skips its two largest matmuls).
    # Memory cost per token-layer (bf16): mlp = 2·ffn_hidden, mlp_qkv adds
    # dim + 2·(kv/heads)·dim. Pick the richest policy that fits HBM —
    # bench config5_nameplate_1b measures the ladder at 0.98B.
    remat_policy: Optional[str] = None
    # lax.scan over the block stack instead of Python-unrolled layers:
    # params stack on a leading [L, ...] axis and the compiled program
    # contains ONE block body regardless of depth — compile time and
    # program size stop scaling with n_layers (the unrolled 16L/768d
    # model's MLIR is big enough to overflow intermediaries; the scanned
    # one is ~1 layer's worth). The XLA-idiomatic deep-model form.
    # Incompatible with ``MoEMLP`` (n_experts>0: its sown aux LOSSES don't
    # thread through nn.scan broadcasts here); ``ExpertFFN`` sows none and scans.
    scan_layers: bool = False
    # Static flash-kernel schedule (ops/flash_attention.FlashConfig): when
    # set, any Block built from this config WITHOUT an explicit attn_fn
    # (the pipeline stages, spmd train steps, tiny_transformer(attn="flash"))
    # runs the Pallas flash kernel under exactly this schedule. Because the
    # config is a frozen, hashable field of this (frozen, hashable) config,
    # it participates in every jit cache key that treats the module/config
    # as static — flipping block shapes or bwd_mode after a compiled step
    # provably re-traces (the guarantee the old BWD_MODE global broke).
    # None = dense XLA attention unless the caller overrides attn/attn_fn.
    flash_config: Optional[FlashConfig] = None
    # epsilon of every RMSNorm (the published value of the model that is run)
    norm_eps: float = 1e-6
    # Latent attention (``"mla_*"`` layers only; DeepSeek-V3 / glm4_moe_lite
    # names): ranks of the two low-rank paths, the un-rotated and rotated parts
    # of a query/key head, and the value head. ``n_heads`` heads; the rotated
    # key part is ONE head shared by all of them.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # The dropless expert layer (``"mla_experts"`` layers only): routed experts
    # and how many a token takes, one expert's width, shared experts (run on
    # every token, one MLP of ``shared_experts * expert_hidden``), the factor on
    # the normalised routing weights, the row tile of the grouped matmul and
    # its path (None = the Mosaic kernel on a TPU, XLA elsewhere).
    routed_experts: int = 0
    experts_per_token: int = 0
    expert_hidden: int = 0
    shared_experts: int = 0
    routed_scale: float = 1.0
    expert_tile_m: int = 128
    expert_impl: Optional[str] = None
    # A HELD SHARE of the routed experts (expert parallelism seen from one
    # chip): this program holds experts ``[first_expert, first_expert +
    # experts_held)`` of every layer — banks ``[.., experts_held, ..]`` — while
    # the router keeps ``routed_experts`` outputs, its top-k and weights
    # normalised over ALL the chosen. Only assignments to a held expert get a
    # row, a product and a term in the combine; nothing is exchanged and nothing
    # stands in for the absent experts' results. None = all of them.
    experts_held: Optional[int] = None
    first_expert: int = 0

    @property
    def head_width(self) -> int:
        return self.head_dim or self.dim // self.n_heads

    @property
    def held_experts(self) -> int:
        return self.routed_experts if self.experts_held is None else self.experts_held

    def __post_init__(self) -> None:
        pattern = tuple(self.layer_pattern)
        object.__setattr__(self, "layer_pattern", pattern)  # a list would not hash
        leading = tuple(self.leading_pattern)
        object.__setattr__(self, "leading_pattern", leading)
        if not pattern or any(kind not in LAYER_KINDS for kind in pattern):
            raise ValueError(f"layer_pattern {pattern!r}: one or more of {tuple(LAYER_KINDS)}")
        if any(kind not in LAYER_KINDS or _is_expert_run(kind) for kind in leading):
            dense = tuple(kind for kind in LAYER_KINDS if not _is_expert_run(kind))
            raise ValueError(f"leading_pattern {leading!r}: any of {dense} (an expert layer belongs to the period)")
        if self.n_layers < len(leading) or (self.n_layers - len(leading)) % len(pattern):
            raise ValueError(
                f"n_layers {self.n_layers} is not {len(leading)} leading layer(s) (leading_pattern) and a whole "
                f"number of periods of {len(pattern)} layers (layer_pattern)"
            )
        if any(LAYER_KINDS[kind][0] == "swa" for kind in leading + pattern) and not self.attn_window:
            raise ValueError("a 'swa_*' layer needs attn_window (the keys a query sees, itself included)")
        if self.experts_held is not None and not (
            0 < self.experts_held and 0 <= self.first_expert and self.first_expert + self.experts_held <= self.routed_experts
        ):
            raise ValueError(
                f"experts [{self.first_expert}, {self.first_expert} + {self.experts_held}) are no share of "
                f"{self.routed_experts} routed experts"
            )
        if self.remat_policy is not None:
            _remat_policy(self.remat_policy)  # raises on an unknown name
            if not self.remat:
                raise ValueError(
                    "remat_policy is only meaningful with remat=True — a "
                    "policy on a no-remat model would silently change the "
                    "memory/FLOPs profile the caller asked for"
                )


class RMSNorm(nn.Module):
    dtype: Any = jnp.bfloat16
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        xf = x.astype(jnp.float32)
        norm = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + self.eps)
        return (norm * scale).astype(self.dtype)


class LoRADense(nn.Module):
    """Dense with optional low-rank adapter: ``y = xW + (alpha/r)·xAB``.

    ``A`` is normal-initialized, ``B`` zeros — adapters start as identity.
    Param names carry the ``lora_`` prefix the federated layer filters on.
    """

    features: int
    rank: int = 0
    alpha: float = 16.0
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(), (x.shape[-1], self.features)
        )
        with scope("base_cast"):
            w = kernel.astype(self.dtype)
        with scope("base_matmul"):
            y = jnp.dot(x.astype(self.dtype), w)
        if self.rank > 0:
            a = self.param(
                "lora_a", nn.initializers.normal(0.02), (x.shape[-1], self.rank)
            )
            b = self.param("lora_b", nn.initializers.zeros, (self.rank, self.features))
            with scope("adapter"):
                y = y + jnp.dot(
                    jnp.dot(x.astype(self.dtype), a.astype(self.dtype)), b.astype(self.dtype)
                ) * (self.alpha / self.rank)
        return y


def rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotary position embedding over [B, T, H, D] (D even)."""
    b, t, h, d = x.shape
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]  # [T, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


def _flash_attend(q, k, v, window=None, *, config, window_config, interpret):
    """Flash attention whose sliding calls run under a schedule of their own."""
    from p2pfl_tpu.ops.flash_attention import flash_attention

    return flash_attention(
        q, k, v, causal=True, config=config if window is None else window_config, interpret=interpret, window=window
    )


def _attend_fn(cfg: TransformerConfig, attn_fn: Optional[Callable]) -> Callable:
    """The ``(q, k, v) -> out`` of an attention module: the explicit callable,
    else the config's pinned flash schedule, else fused dense causal attention.
    A sliding layer calls it with ``window=``; all three take one."""
    if attn_fn is not None:
        return attn_fn
    if cfg.flash_config is not None:
        # cfg-pinned flash schedule: every path that builds Blocks from
        # the config alone (pipeline stages, spmd train steps) picks up
        # the SAME statically-keyed kernel without threading a callable
        from p2pfl_tpu.ops.flash_attention import flash_attention

        return partial(
            flash_attention,
            causal=True,
            config=cfg.flash_config,
            interpret=jax.default_backend() != "tpu",
        )
    return causal_attention


class Attention(nn.Module):
    """Grouped-query causal attention. ``mixer`` is the layer kind's
    (:data:`LAYER_KINDS`): ``"attention"`` rotates by the config-wide rule
    (``rope_theta`` unless it is ``None``) and sees every earlier key; ``"swa"``
    rotates at ``rope_theta`` and sees ``attn_window`` keys; ``"full"`` is not
    rotated and sees every earlier key. ``attn_gate``: the output is
    ``(P v) * sigmoid(x W_g)`` before ``wo``."""

    cfg: TransformerConfig
    attn_fn: Optional[Callable] = None  # (q, k, v) -> out; default fused causal
    mixer: str = "attention"

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        head_dim = cfg.head_width
        rotate = cfg.rope_theta is not None and self.mixer != "full"
        window = cfg.attn_window if self.mixer == "swa" else None
        dense = partial(LoRADense, rank=cfg.lora_rank, alpha=cfg.lora_alpha, dtype=cfg.dtype)
        q = dense(cfg.n_heads * head_dim, name="wq")(x)
        k = dense(cfg.n_kv_heads * head_dim, name="wk")(x)
        v = dense(cfg.n_kv_heads * head_dim, name="wv")(x)
        b, t = x.shape[:2]
        q = q.reshape(b, t, cfg.n_heads, head_dim)
        k = k.reshape(b, t, cfg.n_kv_heads, head_dim)
        v = v.reshape(b, t, cfg.n_kv_heads, head_dim)
        if cfg.qk_norm:
            with scope("qk_norm"):
                q = RMSNorm(cfg.dtype, cfg.norm_eps, name="q_norm")(q)
                k = RMSNorm(cfg.dtype, cfg.norm_eps, name="k_norm")(k)
        if rotate:
            q, k = rope(q, cfg.rope_theta), rope(k, cfg.rope_theta)
        # selective-remat tags: saved pre-GQA-repeat (kv_heads wide, the
        # repeat is a cheap broadcast to recompute)
        q = checkpoint_name(q, "attn_q")
        k = checkpoint_name(k, "attn_k")
        v = checkpoint_name(v, "attn_v")
        # GQA: repeat K/V heads to match Q heads
        rep = cfg.n_heads // cfg.n_kv_heads
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
        attend = _attend_fn(cfg, self.attn_fn)
        out = attend(q, k, v) if window is None else attend(q, k, v, window=window)
        out = out.reshape(b, t, cfg.n_heads * head_dim)
        if cfg.attn_gate:
            gate = dense(cfg.n_heads * head_dim, name="wg")(x)
            with scope("attn_gate"):
                out = (out.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(cfg.dtype)
        return dense(cfg.dim, name="wo")(out)


class MLP(nn.Module):
    cfg: TransformerConfig
    hidden: Optional[int] = None  # None = cfg.ffn_hidden (a shared expert gives its own)

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        rank = cfg.lora_rank if cfg.lora_mlp else 0
        dense = partial(LoRADense, rank=rank, alpha=cfg.lora_alpha, dtype=cfg.dtype)
        hidden = self.hidden or cfg.ffn_hidden
        gate = checkpoint_name(dense(hidden, name="w1")(x), "ffn_gate")
        up = checkpoint_name(dense(hidden, name="w3")(x), "ffn_up")
        return dense(cfg.dim, name="w2")(nn.silu(gate) * up)


class MoEMLP(nn.Module):
    """Mixture-of-experts SwiGLU FFN with capacity-based dense dispatch.

    The GShard/Switch formulation: routing becomes two einsums against a
    [S, E, C] dispatch tensor, so the whole layer is MXU matmuls with
    static shapes — no gather/scatter, no dynamic shapes, nothing XLA
    can't tile. Expert weights stack on a leading [E, ...] axis; sharding
    that axis over the ``model`` mesh axis is expert parallelism (XLA
    turns the dispatch/combine einsums into the token all-to-alls).

    Tokens beyond an expert's capacity ``C = ceil(k·S/E · capacity)`` are
    dropped (their combine weight is zero — the residual stream carries
    them unchanged, the standard Switch behavior).

    Two auxiliary scalars are sown into the ``"moe_losses"`` collection
    (read back via :func:`p2pfl_tpu.models.base.apply_with_aux`):
    the Switch load-balance loss ``E · Σ_e f_e · p̄_e`` and the router
    z-loss ``mean(logsumexp(logits)²)``.

    The reference has no MoE anywhere (its models are MLP/CNN,
    SURVEY §2.7) — this extends the transformer family for the
    expert-parallel axis of the multi-chip design.
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        e, k = cfg.n_experts, cfg.moe_top_k
        b, t, d = x.shape
        s = b * t
        f = cfg.ffn_hidden
        xs = x.reshape(s, d)

        router = self.param("router", nn.initializers.normal(0.02), (d, e))
        logits = jnp.dot(xs.astype(jnp.float32), router.astype(jnp.float32))  # [S, E]
        probs = jax.nn.softmax(logits, axis=-1)

        capacity = max(1, int(-(-k * s // e) * cfg.moe_capacity))

        # iterative top-k dispatch with a running per-expert fill count
        combine = jnp.zeros((s, e, capacity), jnp.float32)
        counts = jnp.zeros((e,), jnp.float32)
        p = probs
        top1_onehot = None
        for _ in range(k):
            idx = jnp.argmax(p, axis=-1)  # [S]
            onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)  # [S, E]
            if top1_onehot is None:
                top1_onehot = onehot
            gate = jnp.sum(p * onehot, axis=-1)  # [S]
            # position of each token within its chosen expert's buffer
            pos = jnp.cumsum(onehot, axis=0) - onehot + counts[None, :]  # [S, E]
            pos_in_e = jnp.sum(pos * onehot, axis=-1)  # [S]
            keep = (pos_in_e < capacity).astype(jnp.float32)
            slot = jax.nn.one_hot(
                jnp.minimum(pos_in_e, capacity - 1).astype(jnp.int32),
                capacity,
                dtype=jnp.float32,
            )  # [S, C]
            combine = combine + (gate * keep)[:, None, None] * onehot[:, :, None] * slot[:, None, :]
            counts = counts + jnp.sum(onehot, axis=0)
            p = p * (1.0 - onehot)  # mask the chosen expert for the next pass

        # renormalize the selected gates so each routed token's weights sum to 1
        total = jnp.sum(combine, axis=(1, 2), keepdims=True)
        combine = combine / jnp.maximum(total, 1e-9)
        dispatch = (combine > 0.0).astype(cfg.dtype)  # [S, E, C]

        w1 = self.param("w1", nn.initializers.lecun_normal(), (e, d, f))
        w3 = self.param("w3", nn.initializers.lecun_normal(), (e, d, f))
        w2 = self.param("w2", nn.initializers.lecun_normal(), (e, f, d))

        xe = jnp.einsum("sec,sd->ecd", dispatch, xs.astype(cfg.dtype))  # [E, C, D]
        # same selective-remat tags as the dense MLP: the "mlp" policy
        # saves the expert hidden activations so the backward skips the
        # two big expert einsums (the layer's dominant FLOPs)
        gate_h = checkpoint_name(
            jnp.einsum("ecd,edf->ecf", xe, w1.astype(cfg.dtype)), "ffn_gate"
        )
        up_h = checkpoint_name(
            jnp.einsum("ecd,edf->ecf", xe, w3.astype(cfg.dtype)), "ffn_up"
        )
        ye = jnp.einsum("ecf,efd->ecd", nn.silu(gate_h) * up_h, w2.astype(cfg.dtype))
        out = jnp.einsum("sec,ecd->sd", combine.astype(cfg.dtype), ye)  # [S, D]

        # Switch load-balance loss: E · Σ_e (top-1 token fraction · mean prob)
        frac = jnp.mean(top1_onehot, axis=0)  # [E]
        mean_p = jnp.mean(probs, axis=0)  # [E]
        balance = e * jnp.sum(frac * mean_p)
        zloss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
        self.sow(
            "moe_losses",
            "aux",
            cfg.moe_aux_coef * balance + cfg.moe_zloss_coef * zloss,
        )
        return out.reshape(b, t, d)


class MLAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V3 / ``glm4_moe_lite``)::

        cq = norm(x W_qa);             q = cq W_qb          -> [T, H, nope + rope]
        (ckv, kr) = split(x W_kva);    (k_nope, v) = split(norm(ckv) W_kvb -> [T, H, nope + v])
        q = [q_nope | rope(q_rope)];   k = [k_nope | rope(kr) for every head]
        out = causal softmax(q k^T / sqrt(nope + rope)) v;   y = out W_o

    Two low-rank paths with an inner RMSNorm each; the rotated key part is ONE
    head shared by all ``n_heads``. All five projections carry adapters. The
    attention itself is whatever ``attn_fn`` / ``cfg.flash_config`` says, as in
    :class:`Attention` — the flash kernels see ordinary q, k, v at the full
    head width, so q·k and v must be equally wide there (GLM-4.7-Flash: 256)."""

    cfg: TransformerConfig
    attn_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        heads, nope, rot, vd = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
        dense = partial(LoRADense, rank=cfg.lora_rank, alpha=cfg.lora_alpha, dtype=cfg.dtype)
        norm = partial(RMSNorm, cfg.dtype, cfg.norm_eps)
        b, t = x.shape[:2]
        cq = dense(cfg.q_lora_rank, name="q_a")(x)
        with scope("mla"):
            cq = norm(name="q_norm")(cq)
        q = dense(heads * (nope + rot), name="q_b")(cq)
        ckv_kr = dense(cfg.kv_lora_rank + rot, name="kv_a")(x)
        with scope("mla"):
            ckv, kr = ckv_kr[..., :cfg.kv_lora_rank], ckv_kr[..., cfg.kv_lora_rank:]
            ckv = norm(name="kv_norm")(ckv)
        kv = dense(heads * (nope + vd), name="kv_b")(ckv)
        with scope("mla"):
            q = q.reshape(b, t, heads, nope + rot)
            kv = kv.reshape(b, t, heads, nope + vd)
            kr = kr.reshape(b, t, 1, rot)
            q_rot = q[..., nope:]
            if cfg.rope_theta is not None:
                q_rot, kr = rope(q_rot, cfg.rope_theta), rope(kr, cfg.rope_theta)
            q = jnp.concatenate([q[..., :nope], q_rot], axis=-1)
            k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(kr, (b, t, heads, rot))], axis=-1)
            v = kv[..., nope:]
            q = checkpoint_name(q, "attn_q")
            k = checkpoint_name(k, "attn_k")
            v = checkpoint_name(v, "attn_v")
        out = _attend_fn(cfg, self.attn_fn)(q, k, v).reshape(b, t, heads * vd)
        return dense(cfg.dim, name="o")(out)


def _bank_init(key, shape, dtype=jnp.bfloat16):
    """An expert bank as a checkpoint stores it: drawn in float32 (lecun-normal
    over each expert's own fan-in), rounded ONCE to ``dtype``."""
    std = 1.0 / math.sqrt(shape[-2])
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def router_scores(x, router):
    """``s = sigmoid(x W_g)`` in float32 on the float32 cast of the input, as the
    published modelling code computes it. ``x``: ``[S, D]``."""
    logits = jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST
    )  # HIGHEST: a TPU's default float32 matmul is one bfloat16 pass
    return jax.nn.sigmoid(logits)


def choose_experts(s, bias, top_k: int):
    """``[S, k]`` int32: the ``top_k`` of ``s + bias`` — the bias CHOOSES only.
    No group limit (``n_group`` = ``topk_group`` = 1); no gradient (a choice)."""
    _, chosen = jax.lax.top_k(jax.lax.stop_gradient(s) + bias.astype(jnp.float32), top_k)
    return chosen.astype(jnp.int32)


def routing_weights(s, chosen, scale: float):
    """WEIGH by ``s`` without the bias: the chosen experts' scores, normalised
    over the chosen (``norm_topk_prob``), times ``scale``."""
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    return picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20) * scale


@jax.custom_batching.custom_vmap
def _rows_at(a, index):
    """``a[index]`` along the first axis for an ``index`` the layout made — in
    range by construction, and said so (``jnp.take`` checks every index and
    selects a fill value over the whole result)."""
    return a.at[index].get(mode="promise_in_bounds")


@_rows_at.def_vmap
def _rows_at_vmap(axis_size, in_batched, a, index):
    """The whole batch as ONE gather over ``[B · N, …]``, element ``b``'s rows
    ``b · N`` further down. ``gather``'s own batching rule keeps the batch as a
    dimension of the operand, and on the chip that is another, slower gather:
    in both expert cells, under the node-chunk ``vmap``, a ``[20352, 2048]``
    bfloat16 result took 0.47-0.59 ms that way and takes 0.13 ms flat (PERF.md,
    PR 36)."""
    a_batched, index_batched = in_batched
    if not a_batched:
        return _rows_at(a, index), True
    if not index_batched:
        index = jnp.broadcast_to(index, (axis_size, *index.shape))
    n = a.shape[1]
    first = (n * jnp.arange(axis_size, dtype=index.dtype)).reshape((axis_size,) + (1,) * (index.ndim - 1))
    return _rows_at(a.reshape(axis_size * n, *a.shape[2:]), index + first), True


def _token_rows(x, token_of_row):
    """``x[token_of_row]``, zeros where ``token_of_row`` is ``S`` (a padding
    row): the row appended here."""
    return _rows_at(jnp.pad(x, ((0, 1), (0, 0))), token_of_row)


# A gather's source of at most this many bytes is one the TPU compiler keeps in VMEM
# (a v5e has 128 MiB of it): a slab out of a 100 MB source takes 0.053 ms on the chip,
# out of 123 MB or more 0.286 — the index pattern moves that by a fifth, the source's
# extent by five times. In the Trinity cell 104 MiB still stayed there in all three
# passes and 112 MiB fell out in the backward; 96 keeps a margin (PERF.md, PR 38)
_GATHER_HEAD_BYTES = 96 * 1024 * 1024


@jax.custom_batching.custom_vmap
def _for_all(flag):
    return flag


@_for_all.def_vmap
def _for_all_vmap(axis_size, in_batched, flag):
    """ONE answer for the whole batch, unbatched: a ``lax.cond`` on it stays a
    branch under ``vmap`` (a batched predicate makes it a select: both sides run)."""
    return jnp.all(flag), False


@jax.jit
def _slabs(source, index):
    """``k`` gathers of an ``[S, D]`` slab each (``index``: ``[k, S]``). Jitted for
    the tracer's sake alone: every expert layer, pass and branch of one model asks
    for the same shapes, and each is traced once."""
    return tuple(_rows_at(source, index[j]) for j in range(index.shape[0]))


def _slab_sum(rows, row_of_assignment, weights=None, in_use=None, tail=0):
    """``Σ_j weights[j] · rows[row_of_assignment[j]]`` in float32: ``k`` gathers
    of an ``[S, D]`` slab each, added as they lie — no ``[S, k, D]`` array, whose
    ``k`` would sit in the sublanes of a tile.

    ``in_use`` (a traced count) and ``tail`` (static) say where the indices CAN
    point: under ``in_use`` or into the last ``tail`` rows — what a layout's used
    tiles and its spare tile are. Where ``rows`` is larger than
    ``_GATHER_HEAD_BYTES`` and those two stretches fit inside it, they are copied
    next to each other first (one pass, into VMEM) and the ``k`` slabs gathered
    out of the copy: the same rows in the same order, so the same sum to the last
    bit. Else, and always for a smaller ``rows``, straight out of ``rows``."""

    def sum_from(source, index):
        total = None
        for j, slab in enumerate(_slabs(source, index)):
            slab = slab.astype(jnp.float32)
            if weights is not None:
                slab = weights[j][:, None] * slab
            total = slab if total is None else total + slab
        return total

    n = rows.shape[0]
    head = _GATHER_HEAD_BYTES // (rows.shape[1] * rows.dtype.itemsize)
    if in_use is None or n <= head or head <= tail:
        return sum_from(rows, row_of_assignment)

    def from_head():
        # the first `head` rows with the last `tail` rows written over their end: one fused pass
        # (a concatenation of the two stretches copied the first to HBM before it: 0.44 ms for 0.13)
        source = jax.lax.dynamic_update_slice(rows[:head], rows[n - tail :], (head - tail, 0))
        moved = jnp.where(row_of_assignment >= n - tail, row_of_assignment - (n - head), row_of_assignment)
        return sum_from(source, moved)

    return jax.lax.cond(_for_all(in_use <= head - tail), from_head, lambda: sum_from(rows, row_of_assignment))


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _to_expert_rows(x, token_of_row, row_of_assignment, in_use=None, tail=0):
    """``[S, D]`` tokens -> ``[rows, D]`` in the grouped layout (padding rows
    zero). A gather forward AND backward: the cotangent of a token is the sum
    of its ``k`` rows', read back through ``row_of_assignment`` (``[k, S]``,
    assignment-major; ``in_use`` / ``tail``: :func:`_slab_sum`)."""
    del row_of_assignment, in_use, tail
    return _token_rows(x, token_of_row)


def _to_expert_rows_fwd(x, token_of_row, row_of_assignment, in_use, tail):
    return _to_expert_rows(x, token_of_row, row_of_assignment, in_use, tail), (row_of_assignment, in_use)


def _to_expert_rows_bwd(tail, res, g):
    row_of_assignment, in_use = res
    with scope("moe_experts"):
        return _slab_sum(g, row_of_assignment, None, in_use, tail).astype(g.dtype), None, None, None


_to_expert_rows.defvjp(_to_expert_rows_fwd, _to_expert_rows_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(6,))
def _from_expert_rows(rows, weights, row_of_assignment, token_of_row, assignment_of_row, in_use=None, tail=0):
    """``y[s] = Σ_j weights[s, j] · rows[row_of_assignment[j, s]]`` — float32
    sum, result in ``rows``' dtype. Gathers both ways: a row's cotangent is its
    token's, times its weight (zero for padding rows)."""
    del token_of_row, assignment_of_row
    return _slab_sum(rows, row_of_assignment, weights.T, in_use, tail).astype(rows.dtype)


def _from_expert_rows_fwd(rows, weights, row_of_assignment, token_of_row, assignment_of_row, in_use, tail):
    out = _from_expert_rows(rows, weights, row_of_assignment, token_of_row, assignment_of_row, in_use, tail)
    return out, (rows, weights, row_of_assignment, token_of_row, assignment_of_row)


def _from_expert_rows_bwd(tail, res, g):
    """``rows`` is read ONCE, in row order: a weight's cotangent
    ``⟨rows[row of (s, j)], g[s]⟩`` is the row's own dot with its token's
    cotangent — ``g_rows``, made for ``d_rows`` anyway — gathered as a scalar.
    A row no assignment names may hold anything (the kernel leaves tiles past
    the used count unwritten): it stays inside its own ``dot_of_row``, which
    nothing gathers."""
    del tail
    rows, weights, row_of_assignment, token_of_row, assignment_of_row = res
    with scope("moe_combine"):
        weight_of_row = jnp.take(weights.reshape(-1), assignment_of_row, mode="fill", fill_value=0)
        g_rows = _token_rows(g, token_of_row).astype(jnp.float32)
        d_rows = (weight_of_row[:, None] * g_rows).astype(rows.dtype)
        dot_of_row = jnp.sum(rows.astype(jnp.float32) * g_rows, axis=-1)
        d_weights = _rows_at(dot_of_row, row_of_assignment).T
    return d_rows, d_weights.astype(weights.dtype), None, None, None, None


_from_expert_rows.defvjp(_from_expert_rows_fwd, _from_expert_rows_bwd)


class ExpertFFN(nn.Module):
    """Dropless sigmoid-routed expert feed-forward (DeepSeek-V3's, as
    ``glm4_moe_lite`` runs it): every one of the ``S x k`` assignments is
    computed, at any imbalance — no capacity, no ``[S, E, C]`` tensor::

        s = router_scores(x);  experts = choose_experts(s, bias);  w = routing_weights(s, experts)
        rows = x's k copies, each where its expert's rows lie   # tile-aligned grouped layout, counted
        h = gmm(rows, W13);  h = silu(h[:, :F]) * h[:, F:];  out = gmm(h, W2)
        y = Σ_j w_j · out[row of (token, j)]  +  shared(x)

    Nothing is sorted: ``group_layout`` counts each assignment's row. Whatever
    is indexed by assignment is held assignment-major — ``row_of_assignment``
    ``[k, S]``, so a token's ``k`` rows are ``k`` aligned ``[S, D]`` slabs to add
    in float32, forward (the combine) and backward (the dispatch's cotangent);
    no ``[S, k, D]`` array exists. Each ``[·, D]`` array is gathered once a
    pass: the weights' cotangent is a row-order dot read back as scalars.

    The bank is two stacked parameters in the dtype a checkpoint stores
    (bfloat16), ``experts_w13`` ``[E, D, 2F]`` (gate | up) and ``experts_w2``
    ``[E, F, D]``, read as they lie by ``ops/grouped_matmul.py``. Inside a layer
    scan the layer does not own them: ``bank`` = ``(layer, w13, w2)`` hands it
    the whole run's stacks ``[L, E, ...]`` (loop constants, declared by
    :class:`CausalLM` outside every scan) and its index, and the kernel reads
    ``w13[layer]`` in place — sliced by the scan, each layer's 1.2 GB would be
    copied before every call. Bank, router and its bias carry no adapter: frozen
    under LoRA and outside its FedAvg. The shared expert is :class:`MLP`
    (adapters where ``lora_mlp``).

    A held share (``cfg.experts_held`` of ``cfg.routed_experts``, from
    ``cfg.first_expert``): the router, its top-k and the weights — normalised
    over ALL ``k`` chosen — are the whole model's; the banks are ``[experts_held,
    ...]``; only an assignment to a held expert gets a row. The layout keeps the
    static worst case (every assignment held) plus ONE spare row tile that no
    group owns: an absent assignment reads a row of it — its token's, so a slab's
    absent indices are spread over the tile — zero going in (padding), zero
    coming out of the matmul (the call's last tile, the one tile past the used
    count that the kernel writes) and with a zero cotangent both ways, so it costs
    no tile and needs no mask. What the static rows cost is paid by rows in use
    where it can be: the kernel writes no tile nobody owns, and the slab sums
    gather out of a copy of the used rows and the spare tile while those fit
    VMEM (:func:`_slab_sum`). Nothing is exchanged: the partial sum (+ the
    shared expert, whole) is the layer's output.

    Sows ``moe_stats/load_max_over_mean`` — rows on the fullest (held) expert over the
    even share ``S k / E`` — and ``moe_stats/rows_used_share`` — the rows of the
    used tiles, ``tile_m · Σ_g ceil(size_g / tile_m)``, over the layout's static
    rows: the share of a grouped-matmul call's tiles that are multiplied and
    written — both from the group sizes the matmul takes anyway,
    ``moe_stats/held_share`` — assignments computed here over ``S k`` (1 when
    every expert is held) — where a share is held, and
    ``moe_routing/chosen``, the ``[S, k]`` experts themselves.
    The routing choice is kept across remat (``moe_chosen``): the re-forward
    counts the layout again and weighs the forward's own assignments."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, bank=None):
        from p2pfl_tpu.ops.grouped_matmul import group_layout, grouped_matmul, tiles_and_fetches

        cfg = self.cfg
        e, k, f = cfg.routed_experts, cfg.experts_per_token, cfg.expert_hidden
        held = cfg.held_experts
        share = (cfg.first_expert, True) if held < e else (0, False)  # (first group, a spare tile)
        b, t, d = x.shape
        s = b * t
        xs = x.reshape(s, d)
        router = self.param("router", nn.initializers.normal(0.02), (d, e))
        bias = self.param("router_bias", nn.initializers.zeros, (e,))
        if bank is None:
            layer = None
            w13 = self.param("experts_w13", _bank_init, (held, d, 2 * f))
            w2 = self.param("experts_w2", _bank_init, (held, f, d))
        else:
            layer, w13, w2 = bank
        gmm = partial(grouped_matmul, layer=layer, tile_m=cfg.expert_tile_m, impl=cfg.expert_impl)
        with scope("moe_route"):
            s_ = router_scores(xs, router)
            # kept across remat (see _remat_policy): weights, rows and combine of the
            # re-forward all follow the forward's choice
            chosen = checkpoint_name(choose_experts(s_, bias, k), "moe_chosen")
            weights = routing_weights(s_, chosen, cfg.routed_scale)
            layout = group_layout(chosen.reshape(-1), held, cfg.expert_tile_m, *share, per_token=k)
            # assignment-major: whatever is indexed by assignment is k slabs of S, never [S, k, D]
            row_of_assignment = layout.slot_of_assignment.reshape(s, k).T
            token_of_row = jnp.where(
                layout.assignment_of_slot < s * k, layout.assignment_of_slot // k, s
            )  # s: a padding row reads the zero row `_token_rows` appends
            load = jnp.max(layout.group_sizes).astype(jnp.float32) / (s * k / e)
            # rows of the used tiles: every assignment's row lies under it or in the layout's last tile
            in_use = cfg.expert_tile_m * tiles_and_fetches(layout.group_sizes, cfg.expert_tile_m)[0]
            reach = (in_use, cfg.expert_tile_m)
        self.sow("moe_stats", "load_max_over_mean", load)
        self.sow("moe_stats", "rows_used_share", in_use.astype(jnp.float32) / layout.rows)
        if held < e:
            self.sow("moe_stats", "held_share", jnp.sum(layout.group_sizes).astype(jnp.float32) / (s * k))
        self.sow("moe_routing", "chosen", chosen)  # for whoever compares assignments (tests, the benchmark's check)
        with scope("moe_experts"):
            rows = _to_expert_rows(xs.astype(cfg.dtype), token_of_row, row_of_assignment, *reach)
            h = gmm(rows, w13, layout.group_sizes)
            h = nn.silu(h[:, :f]) * h[:, f:]
            out = gmm(h, w2, layout.group_sizes)
        shared = MLP(cfg, cfg.shared_experts * f, name="shared")(x) if cfg.shared_experts else None
        with scope("moe_combine"):
            y = _from_expert_rows(
                out, weights, row_of_assignment, token_of_row, layout.assignment_of_slot, *reach
            ).reshape(b, t, d)
            return y if shared is None else y + shared


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """Mamba's own: the bias whose softplus is log-uniform in [1e-3, 1e-1].
    (A zero-mean bias gives steps near 0.7, every decay collapses within a few
    tokens and the scan carries nothing.)"""
    lo, hi = math.log(1e-3), math.log(1e-1)
    dt = jnp.maximum(jnp.exp(lo + (hi - lo) * jax.random.uniform(key, shape, dtype)), 1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1


def _a_log_init(key, shape, dtype=jnp.float32):
    """``A = -(1..N)`` on every channel (S4D-real), kept as its log."""
    return jnp.log(jnp.broadcast_to(jnp.arange(1, shape[1] + 1, dtype=dtype), shape))


def causal_depthwise_conv(u: jax.Array, kernel: jax.Array, bias: Optional[jax.Array] = None) -> jax.Array:
    """``out[t] = bias + Σ_k kernel[k] · u[t − (K−1) + k]`` per channel, zeros
    before the sequence; ``u`` is ``[B, T, C]``, ``kernel`` ``[K, C]``, ``bias``
    ``[C]`` or none. Float32."""
    taps, t = kernel.shape[0], u.shape[1]
    padded = jnp.pad(u.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    out = None if bias is None else bias.astype(jnp.float32)
    for k in range(taps):
        term = kernel[k].astype(jnp.float32) * padded[:, k:k + t]
        out = term if out is None else out + term
    return out


def gated_short_conv(bcu: jax.Array, kernel: jax.Array, dtype) -> jax.Array:
    """``C ⊙ causal_depthwise_conv(B ⊙ u)`` of ``bcu`` = ``[B | C | u]``
    (``[batch, T, 3 C]``): both gate products and the taps in float32 under one
    scope, the result rounded once to ``dtype``."""
    with scope("short_conv"):
        b, c, u = (part.astype(jnp.float32) for part in jnp.split(bcu, 3, axis=-1))
        return (c * causal_depthwise_conv(b * u, kernel)).astype(dtype)


class ShortConvMixer(nn.Module):
    """The gated short convolution (the LFM2 operator; ``lfm2_moe``)::

        [B | C | u] = x W_in;    y = (C ⊙ causal_depthwise_conv(B ⊙ u)) W_out

    ``cfg.conv_taps`` taps a channel, no bias, no activation, no state beyond
    ``conv_taps − 1`` positions. ``in_proj`` and ``out_proj`` carry adapters;
    the taps are a base leaf: frozen under LoRA and outside its FedAvg. What
    lies between the projections is :func:`gated_short_conv`."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = partial(LoRADense, rank=cfg.lora_rank, alpha=cfg.lora_alpha, dtype=cfg.dtype)
        bcu = dense(3 * cfg.dim, name="in_proj")(x)
        kernel = self.param(
            "conv_kernel", nn.initializers.variance_scaling(1.0, "fan_in", "normal", in_axis=0, out_axis=1),
            (cfg.conv_taps, cfg.dim),
        )
        return dense(cfg.dim, name="out_proj")(gated_short_conv(bcu, kernel, cfg.dtype))


class MambaMixer(nn.Module):
    """The Mamba-1 sequence mixer as Jamba runs it (inner RMSNorms on the step
    size, ``B`` and ``C``)::

        [u, z] = x W_in;  u = silu(causal_depthwise_conv(u) + b_conv)
        [δ, B, C] = u W_x;  δ, B, C = norm(δ), norm(B), norm(C)
        Δ = softplus(δ W_dt + b_dt);  A = −exp(A_log)
        y = selective_scan(u, Δ, A, B, C, D, z);  out = y W_out

    ``in_proj``, ``x_proj`` and ``out_proj`` carry adapters; ``dt_proj`` does
    not. ``A_log``, ``D``, the convolution, ``dt_bias`` and the inner norms are
    base leaves: frozen under LoRA and outside its FedAvg.
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        from p2pfl_tpu.ops.selective_scan import selective_scan

        cfg = self.cfg
        inner, n = cfg.ssm_expand * cfg.dim, cfg.ssm_state
        dt_rank = cfg.ssm_dt_rank or -(-cfg.dim // 16)
        dense = partial(LoRADense, rank=cfg.lora_rank, alpha=cfg.lora_alpha, dtype=cfg.dtype)
        xz = checkpoint_name(dense(2 * inner, name="in_proj")(x), "ssm_in")
        u, z = xz[..., :inner], xz[..., inner:]
        kernel = self.param(
            "conv_kernel", nn.initializers.variance_scaling(1.0, "fan_in", "normal", in_axis=0, out_axis=1),
            (cfg.ssm_conv, inner),
        )
        conv_bias = self.param("conv_bias", nn.initializers.zeros, (inner,))
        with scope("ssm_conv"):
            u = nn.silu(causal_depthwise_conv(u, kernel, conv_bias)).astype(cfg.dtype)
        dbc = dense(dt_rank + 2 * n, name="x_proj")(u)
        dt = RMSNorm(cfg.dtype, cfg.norm_eps, name="dt_norm")(dbc[..., :dt_rank])
        b = RMSNorm(cfg.dtype, cfg.norm_eps, name="b_norm")(dbc[..., dt_rank:dt_rank + n])
        c = RMSNorm(cfg.dtype, cfg.norm_eps, name="c_norm")(dbc[..., dt_rank + n:])
        dt_bias = self.param("dt_bias", _dt_bias_init, (inner,))
        delta = LoRADense(inner, rank=0, dtype=cfg.dtype, name="dt_proj")(dt)
        delta = checkpoint_name(jax.nn.softplus(delta.astype(jnp.float32) + dt_bias), "ssm_dt")
        a_log = self.param("A_log", _a_log_init, (inner, n))
        skip = self.param("D", nn.initializers.ones, (inner,))
        y = selective_scan(u, delta, -jnp.exp(a_log), b, c, skip, z)
        return dense(cfg.dim, name="out_proj")(checkpoint_name(y, "ssm_y"))


class Block(nn.Module):
    cfg: TransformerConfig
    attn_fn: Optional[Callable] = None
    kind: str = "attention"  # the sequence mixer: one of LAYER_KINDS

    @nn.compact
    def __call__(self, x, bank=None):
        cfg = self.cfg
        mixer, ffn_kind = LAYER_KINDS[self.kind]
        norm = partial(RMSNorm, cfg.dtype, cfg.norm_eps)

        def post(name, y):  # the sandwich's second norm, on a sublayer's output
            if not cfg.post_norms:
                return y
            with scope("post_norm"):
                return norm(name=name)(y)

        if mixer == "mamba":
            x = x + post("mamba_post_norm", MambaMixer(cfg, name="mamba")(norm(name="mamba_norm")(x)))
        elif mixer == "short_conv":
            x = x + post("conv_post_norm", ShortConvMixer(cfg, name="conv")(norm(name="conv_norm")(x)))
        elif mixer == "mla":
            x = x + post("attn_post_norm", MLAttention(cfg, self.attn_fn, name="attn")(norm(name="attn_norm")(x)))
        else:
            x = x + post("attn_post_norm", Attention(cfg, self.attn_fn, mixer, name="attn")(norm(name="attn_norm")(x)))
        h = norm(name="mlp_norm")(x)
        if ffn_kind == "experts":
            return x + post("mlp_post_norm", ExpertFFN(cfg, name="mlp")(h, bank))
        ffn = MLP if ffn_kind == "mlp" or cfg.n_experts == 0 else MoEMLP
        return x + post("mlp_post_norm", ffn(cfg, name="mlp")(h))


class _ScanBlock(nn.Module):
    """nn.scan body: one Block step with the (carry, xs) -> (carry, ys)
    signature lax.scan wants. Params gain a leading [L] axis via
    ``variable_axes={"params": 0}``."""

    cfg: TransformerConfig
    attn_fn: Optional[Callable] = None
    kind: str = "attention"

    @nn.compact
    def __call__(self, x, layer, bank=None):
        block = Block(self.cfg, self.attn_fn, self.kind, name="block")
        return (block(x) if bank is None else block(x, (layer, *bank))), None


def layer_runs(pattern: tuple) -> list[tuple[str, int]]:
    """The maximal runs of same-kind layers of one period, in order:
    Jamba's period is ``[("mamba", 7), ("attention", 1), ("mamba", 6)]``."""
    runs: list[tuple[str, int]] = []
    for kind in pattern:
        if runs and runs[-1][0] == kind:
            runs[-1] = (kind, runs[-1][1] + 1)
        else:
            runs.append((kind, 1))
    return runs


def _rematted(body, cfg: TransformerConfig, kind: str = "attention", in_scan: bool = True):
    """``body`` (a block of ``kind``) rematerialised where the config says so.
    ``in_scan``: for use INSIDE a scan, prevent_cse=False — the remat thunk
    can't be CSE'd across iterations anyway, and True blocks the scan lowering
    (flax's documented scan-over-remat recipe)."""
    if not cfg.remat:
        return body
    return nn.remat(body, prevent_cse=not in_scan, policy=_remat_policy(cfg.remat_policy, kind))


def _scan_over(body, length: int, with_bank: bool = False):
    """``nn.scan`` of ``body`` over ``length`` stacked copies of its params.
    ``with_bank``: the body takes a third argument that is NOT scanned — the
    expert banks, whole, a constant of the loop."""
    # what ExpertFFN sows comes out stacked along the scan (only where a caller
    # makes the collection mutable; otherwise nothing is sown)
    return nn.scan(
        body, variable_axes={"params": 0, "moe_stats": 0, "moe_routing": 0},
        split_rngs={"params": True}, length=length, **({"in_axes": (0, nn.broadcast)} if with_bank else {}),
    )


def _apply_run(cfg: TransformerConfig, attn_fn, kind: str, count: int, name: str, x, bank=None, in_scan: bool = True):
    """One run of ``count`` same-kind layers under ``scan_layers``: a scan of
    its own over stacked params, or the block itself for a run of one. Params:
    ``<name>/block/...`` with a leading run-length axis, or ``<name>/...``.
    ``bank`` (an expert run): ``(index of the run's first layer in the stacks,
    w13, w2)``; ``in_scan``: whether the run sits inside the period scan."""
    if count > 1:
        scan = _scan_over(_rematted(_ScanBlock, cfg, kind), count, with_bank=bank is not None)(
            cfg, attn_fn, kind, name=name
        )
        if bank is None:
            return scan(x, None)[0]
        first, *stacks = bank
        return scan(x, first + jnp.arange(count, dtype=jnp.int32), tuple(stacks))[0]
    block = _rematted(Block, cfg, kind, in_scan)(cfg, attn_fn, kind, name=name)
    return block(x) if bank is None else block(x, bank)


class _ScanPeriod(nn.Module):
    """One period of ``cfg.layer_pattern`` as the body of the period scan. A run
    of same-kind layers is a scan of its own (a run of one layer is the block
    itself), so the compiled program holds one body per run whatever the
    depth. Params: ``run<i>_<kind>/block/...`` with a leading run-length axis,
    or ``run<i>_<kind>/...`` for a run of one.

    ``period`` / ``banks`` are for runs of EXPERT layers (``None`` without one):
    this period's index and ``{run name: (w13, w2)}``, every such run's banks
    stacked over ALL its layers ``[periods * count, E, ...]`` — see
    :class:`ExpertFFN`. Layer ``j`` of the run reads bank ``period * count + j``."""

    cfg: TransformerConfig
    attn_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, period, banks=None):
        cfg = self.cfg
        for i, (kind, count) in enumerate(layer_runs(cfg.layer_pattern)):
            name = f"run{i}_{kind}"
            # an expert run: this period's first layer in the run's stacks, and the stacks
            bank = (period * count, *banks[name]) if _is_expert_run(kind) else None
            x = _apply_run(cfg, self.attn_fn, kind, count, name, x, bank)
        return x, None


def sown_by_layer(cfg: TransformerConfig, sown, name: Optional[str] = None) -> jax.Array:
    """What the expert layers of a :class:`CausalLM` sowed under one name —
    ``mut["moe_routing"]`` or ``mut["moe_stats"]`` as ``apply`` hands it out;
    ``name`` picks one of several a layer sows (``"load_max_over_mean"``) —
    as ONE array ``[expert layers, ...]`` in the order the layers run. Scanned,
    each expert run of the period sows its own leaf, stacked along the period
    scan and, for a run of several layers, its own scan: the runs interleave by
    period. Unrolled, ``layer_<i>`` sows layer ``i``'s."""

    def one(tree):
        (leaf,) = [
            leaf for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
            if name is None or any(getattr(key, "key", None) == name for key in path)
        ]  # fmt: skip
        return leaf

    if not cfg.scan_layers:
        at = sorted((int(layer.split("_")[1]), layer) for layer in sown)
        return jnp.stack([one(sown[layer]) for _, layer in at])
    if len(cfg.layer_pattern) == 1:
        return one(sown)  # [periods, ...]: a period is a layer
    per_period = []
    for i, (kind, count) in enumerate(layer_runs(cfg.layer_pattern)):
        if _is_expert_run(kind):
            leaf = one(sown["layers"][f"run{i}_{kind}"])
            per_period.append(leaf if count > 1 else leaf[:, None])  # [periods, count, ...]
    stacked = jnp.concatenate(per_period, axis=1)
    return stacked.reshape(-1, *stacked.shape[2:])


def tied_logits(hidden, embedding):
    """``[B, T, dim] x [vocab, dim] -> [B, T, vocab]`` float32: the head —
    ``embedding`` is the embedding itself (tied) or ``lm_head`` — multiplied in
    the hidden states' dtype."""
    return jnp.dot(hidden, embedding.T.astype(hidden.dtype)).astype(jnp.float32)


class CausalLM(nn.Module):
    """Decoder-only LM: embedding (times ``cfg.embed_scale``), the layer stack
    (``leading_pattern`` once, then periods of ``layer_pattern``), a final
    RMSNorm, and the output head — the embedding transposed, or with
    ``cfg.tie_head`` off a matrix of its own, ``lm_head`` ``[vocab, dim]``
    (no ``lora_`` prefix: a base leaf, frozen under LoRA and in no FedAvg payload).

    ``__call__(tokens)`` gives float32 logits ``[B, T, vocab]`` — what
    evaluation, generation and ``SpmdLmFederation`` read. ``head=False`` stops
    before the head and hands out what it would take, ``(final norm's output
    [B, T, dim], the head's matrix [vocab, dim])``: a training loss that needs
    no logits whole takes both to :func:`p2pfl_tpu.ops.head_loss.head_loss`
    (``learning/lora.py::_lm_forward``) — the same function whichever matrix
    the head is."""

    cfg: TransformerConfig
    attn_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, tokens, head: bool = True):  # [B, T] int32 -> [B, T, vocab] f32 logits
        cfg = self.cfg
        emb = self.param(
            "embed", nn.initializers.normal(0.02), (cfg.vocab_size, cfg.dim)
        )
        if cfg.embed_scale == 1.0:
            x = emb[tokens].astype(cfg.dtype)
        else:
            x = (emb[tokens] * cfg.embed_scale).astype(cfg.dtype)
        pattern, leading = cfg.layer_pattern, cfg.leading_pattern
        if cfg.scan_layers:
            if cfg.n_experts > 0 and any(LAYER_KINDS[kind][1] is None for kind in leading + pattern):
                # MoEMLP's auxiliary LOSSES; ExpertFFN (noaux_tc routing) sows none
                raise NotImplementedError(
                    "scan_layers with MoE: sown aux losses don't thread through "
                    "the layer scan or the period scan — use unrolled layers for MoE"
                )
            # the layers before the first period: runs of their own, outside the period scan
            for i, (kind, count) in enumerate(layer_runs(leading)):
                x = _apply_run(cfg, self.attn_fn, kind, count, f"lead{i}_{kind}", x, in_scan=False)
            periods = (cfg.n_layers - len(leading)) // len(pattern)
            # the expert banks of every layer, declared OUTSIDE the scans: a scan
            # would slice its stacked params, and a Mosaic call reads no slice in place
            bank_shapes = {"w13": (cfg.dim, 2 * cfg.expert_hidden), "w2": (cfg.expert_hidden, cfg.dim)}
            banks = {
                f"run{i}_{kind}": tuple(
                    self.param(f"experts_{w}_run{i}", _bank_init, (periods * count, cfg.held_experts, *shape))
                    for w, shape in bank_shapes.items()
                )
                for i, (kind, count) in enumerate(layer_runs(pattern)) if _is_expert_run(kind)
            }
            if len(pattern) == 1:
                # a period of one layer is the scan body itself
                body, args = _rematted(_ScanBlock, cfg, pattern[0]), (cfg, self.attn_fn, pattern[0])
                bank = banks.get(f"run0_{pattern[0]}")
            else:
                body, args, bank = _ScanPeriod, (cfg, self.attn_fn), banks or None
            scan = _scan_over(body, periods, with_bank=bank is not None)
            xs = (None,) if bank is None else (jnp.arange(periods, dtype=jnp.int32), bank)
            x, _ = scan(*args, name="layers")(x, *xs)
        else:
            for i in range(cfg.n_layers):
                kind = leading[i] if i < len(leading) else pattern[(i - len(leading)) % len(pattern)]
                x = _rematted(Block, cfg, kind, in_scan=False)(cfg, self.attn_fn, kind, name=f"layer_{i}")(x)
        x = RMSNorm(cfg.dtype, cfg.norm_eps, name="final_norm")(x)
        head_matrix = emb if cfg.tie_head else self.param(
            "lm_head", nn.initializers.normal(0.02), (cfg.vocab_size, cfg.dim)
        )
        if not head:
            return x, head_matrix
        with scope("head"):
            return tied_logits(x, head_matrix)


def pick_attention(seq_len: int, backend: Optional[str] = None) -> str:
    """The ``attn="auto"`` policy: dense vs flash by sequence length.

    Uses the crossover measured on real hardware by bench config 7
    (``Settings.FLASH_MIN_SEQ_LEN``): fused dense XLA attention wins at
    short lengths (the O(T²) logits still fit in VMEM-friendly fusions and
    the Pallas kernel's block bookkeeping costs more than it saves), flash
    wins once the logits matrix stops fitting. TPU-only: on any other
    backend the Pallas kernel runs in interpret mode (orders of magnitude
    slower — a correctness path, not a performance one), so "auto" always
    answers dense there. Single-chip policy — the ring variants shard the
    sequence over a mesh and are chosen explicitly.
    """
    from p2pfl_tpu.settings import Settings

    backend = jax.default_backend() if backend is None else backend
    if backend != "tpu":
        return "dense"
    return "flash" if seq_len >= Settings.FLASH_MIN_SEQ_LEN else "dense"


def resolve_attention(
    attn: str,
    mesh: Any = None,
    axis_name: str = "model",
    block: Optional[int] = None,
    seq_len: Optional[int] = None,
    block_bwd: Optional[int] = None,
    config: Optional[FlashConfig] = None,
    window_config: Optional[FlashConfig] = None,
) -> Optional[Callable]:
    """Map an attention backend name to an ``(q, k, v) -> out`` callable.
    ``window_config`` (flash only): the schedule of the calls a sliding layer
    makes with ``window=``; without it they run under ``config`` too.

    ``config`` pins the full static kernel schedule
    (:class:`~p2pfl_tpu.ops.flash_attention.FlashConfig`); the legacy
    ``block``/``block_bwd`` square-block shorthands build one when no
    config is given. With neither, the kernel resolves the tuned/default
    config for its shape at trace time
    (:func:`p2pfl_tpu.ops.autotune.get_flash_config`).
    """
    if attn == "auto":
        if seq_len is None:
            raise ValueError("attn='auto' needs seq_len to pick a backend")
        attn = pick_attention(seq_len)
    if config is None and block is not None:
        config = FlashConfig(
            block_q=block, block_k=block,
            block_q_bwd=block_bwd, block_k_bwd=block_bwd,
        )
    if attn == "dense":
        return None  # Attention falls back to the fused causal path
    if attn == "flash":
        from p2pfl_tpu.ops.flash_attention import flash_attention

        # Pallas runs natively on TPU; anywhere else use interpret mode
        interpret = jax.default_backend() != "tpu"
        if window_config is not None:
            return partial(_flash_attend, config=config, window_config=window_config, interpret=interpret)
        return partial(
            flash_attention, causal=True, config=config, interpret=interpret
        )
    if attn in ("ring", "ring_flash"):
        if mesh is None:
            raise ValueError(f"attn={attn!r} needs a mesh (sequence is sharded over it)")
        from p2pfl_tpu.ops.attention import ring_attention

        impl = "flash" if attn == "ring_flash" else "dense"
        return partial(
            ring_attention, mesh=mesh, axis_name=axis_name, impl=impl,
            block=block or 128, flash_config=config if attn == "ring_flash" else None,
        )
    raise ValueError(f"unknown attention backend {attn!r} (dense|flash|ring|ring_flash)")


def tiny_transformer(
    seq_len: int = 128,
    seed: int = 0,
    cfg: Optional[TransformerConfig] = None,
    attn_fn: Optional[Callable] = None,
    attn: str = "dense",
    mesh: Any = None,
) -> FlaxModel:
    """A small LoRA-ready causal LM bound to concrete params.

    ``attn`` selects the attention backend
    (``"auto" | "dense" | "flash" | "ring" | "ring_flash"``); ``"auto"``
    picks dense vs flash from the sequence length using the measured
    crossover (:func:`pick_attention`). ``attn_fn`` overrides it with an
    explicit callable.
    """
    cfg = cfg or TransformerConfig()
    if attn == "auto":
        attn = pick_attention(seq_len)
    sliding = any(LAYER_KINDS[kind][0] == "swa" for kind in cfg.leading_pattern + cfg.layer_pattern)
    if attn_fn is None and sliding and attn in ("ring", "ring_flash"):
        raise ValueError(f"attn={attn!r} has no sliding window; a model with 'swa_*' layers runs 'dense' or 'flash'")
    if attn_fn is None:
        # flash blocks must divide the attended length: the GLOBAL sequence
        # for attn="flash", but the PER-DEVICE shard for "ring_flash" (each
        # hop's kernel sees T_local)
        basis = seq_len
        if attn == "ring_flash":
            if mesh is None:
                raise ValueError("attn='ring_flash' needs a mesh")
            from p2pfl_tpu.settings import Settings

            basis = seq_len // mesh.shape[Settings.MESH_MODEL_AXIS]
        if attn in ("flash", "ring_flash"):
            from p2pfl_tpu.ops.autotune import _fit

            # the one tiling rule (autotune._fit): blocks must divide the
            # basis and be a multiple of 8, with block == basis always
            # acceptable. Lengths <= 512 therefore always work (one full
            # block); longer lengths need SOME multiple-of-8 divisor or the
            # whole sequence becomes one VMEM-hostile block — reject those.
            if _fit(basis, 512) > 512:
                raise ValueError(
                    f"attn={attn!r} needs a flash block <= 512 dividing the "
                    f"attended length: {basis} (seq_len per shard) has no "
                    "multiple-of-8 divisor"
                )
            # kernel schedule resolution: an explicit cfg.flash_config pin
            # wins; otherwise Settings.FLASH_AUTOTUNE sweeps and caches the
            # schedule for this (T, D, dtype) here — at model-build time,
            # outside any trace — and get_flash_config serves it (pinned →
            # tune cache → shipped per-device-kind defaults table)
            from p2pfl_tpu.ops import autotune
            from p2pfl_tpu.settings import Settings

            head_dim = cfg.head_width
            flash_cfg = cfg.flash_config
            if flash_cfg is None and Settings.FLASH_AUTOTUNE:
                flash_cfg = autotune.autotune_flash(basis, head_dim, dtype=cfg.dtype)
            if flash_cfg is None:
                flash_cfg = autotune.get_flash_config(basis, head_dim, dtype=cfg.dtype)
            window_cfg = None
            if sliding and cfg.attn_window < basis and cfg.flash_config is None:
                # a sliding layer's calls are keyed (and tuned) with their window
                tune = autotune.autotune_flash if Settings.FLASH_AUTOTUNE else autotune.get_flash_config
                window_cfg = tune(basis, head_dim, dtype=cfg.dtype, window=cfg.attn_window)
            attn_fn = resolve_attention(attn, mesh=mesh, config=flash_cfg, window_config=window_cfg)
        else:
            attn_fn = resolve_attention(attn, mesh=mesh)
    module = CausalLM(cfg, attn_fn)
    rng = jax.random.PRNGKey(seed)
    dummy = jnp.zeros((1, seq_len), dtype=jnp.int32)
    variables = module.init(rng, dummy)
    model = FlaxModel(module, variables["params"], (seq_len,), cfg.vocab_size)
    model.extra["config"] = cfg
    return model
