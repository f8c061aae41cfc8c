"""Decoder-only language models with LoRA adapters.

BASELINE config 5: federated LoRA fine-tuning — nodes train and exchange
ONLY the low-rank adapters, so a round's gossip payload drops from the full
model to a few MB. A block is pre-RMSNorm → sequence mixer → residual, then
pre-RMSNorm → SwiGLU (or MoE) → residual; all matmuls in bfloat16 on the MXU,
norms, softmax statistics and the state-space recurrence in float32.

Sequence mixers — ``TransformerConfig.layer_pattern`` names one per layer of a
period, and the stack repeats the period:

- ``"attention"`` (the default, alone: the Llama recipe): grouped-query causal
  attention, with RoPE unless ``rope_theta`` is ``None``;
- ``"mamba"``: the Mamba-1 mixer (:class:`MambaMixer`) — causal depthwise
  convolution, input-dependent step sizes, and the selective scan of
  ``ops/selective_scan.py`` (the Jamba hybrids interleave it with attention).

Attention backends — pick with ``tiny_transformer(attn=...)``:

- ``"dense"`` (default): fused XLA causal attention (``ops/attention.py``);
- ``"flash"``: the Pallas flash kernel with its Pallas backward
  (``ops/flash_attention.py``) — O(T·D) memory in both directions;
- ``"ring"``: ring attention over a mesh axis (pass ``mesh=``) — the
  sequence is sharded across chips, K/V rotate via ``ppermute``.

Power users can instead pass any ``attn_fn(q, k, v) -> out`` directly.
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
LAYER_KINDS = ("attention", "mamba")


def _remat_policy(name: Optional[str]):
    """Map ``TransformerConfig.remat_policy`` to a jax.checkpoint policy."""
    if name is None:
        return None  # full per-block remat: save nothing inside the block
    try:
        names = _REMAT_SAVE_NAMES[name]
    except KeyError:
        raise ValueError(
            f"unknown remat_policy {name!r} (None|{'|'.join(_REMAT_SAVE_NAMES)})"
        ) from None
    return jax.checkpoint_policies.save_only_these_names(*names)


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 2048
    dim: int = 256
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 4
    ffn_hidden: int = 688  # ~8/3 * dim rounded
    # None = no positional rotation at all (Jamba's attention layers: the
    # state-space layers around them carry position)
    rope_theta: Optional[float] = 10000.0
    # One period of the layer stack, a sequence mixer per layer
    # (``"attention"`` | ``"mamba"``); ``n_layers`` must be a multiple of its
    # length. Jamba: 14 long, attention at index 7.
    layer_pattern: tuple = ("attention",)
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
    # Incompatible with n_experts>0 for now (sown MoE aux losses don't
    # thread through nn.scan broadcasts here).
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

    def __post_init__(self) -> None:
        pattern = tuple(self.layer_pattern)
        object.__setattr__(self, "layer_pattern", pattern)  # a list would not hash
        if not pattern or any(kind not in LAYER_KINDS for kind in pattern):
            raise ValueError(f"layer_pattern {pattern!r}: one or more of {LAYER_KINDS}")
        if self.n_layers % len(pattern):
            raise ValueError(
                f"n_layers {self.n_layers} is not a whole number of periods of {len(pattern)} layers"
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

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        xf = x.astype(jnp.float32)
        norm = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + 1e-6)
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


class Attention(nn.Module):
    cfg: TransformerConfig
    attn_fn: Optional[Callable] = None  # (q, k, v) -> out; default fused causal

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        head_dim = cfg.dim // cfg.n_heads
        dense = partial(LoRADense, rank=cfg.lora_rank, alpha=cfg.lora_alpha, dtype=cfg.dtype)
        q = dense(cfg.n_heads * head_dim, name="wq")(x)
        k = dense(cfg.n_kv_heads * head_dim, name="wk")(x)
        v = dense(cfg.n_kv_heads * head_dim, name="wv")(x)
        b, t = x.shape[:2]
        q = q.reshape(b, t, cfg.n_heads, head_dim)
        k = k.reshape(b, t, cfg.n_kv_heads, head_dim)
        v = v.reshape(b, t, cfg.n_kv_heads, head_dim)
        if cfg.rope_theta is not None:
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
        if self.attn_fn is not None:
            attend = self.attn_fn
        elif cfg.flash_config is not None:
            # cfg-pinned flash schedule: every path that builds Blocks from
            # the config alone (pipeline stages, spmd train steps) picks up
            # the SAME statically-keyed kernel without threading a callable
            from p2pfl_tpu.ops.flash_attention import flash_attention

            attend = partial(
                flash_attention,
                causal=True,
                config=cfg.flash_config,
                interpret=jax.default_backend() != "tpu",
            )
        else:
            attend = causal_attention
        out = attend(q, k, v).reshape(b, t, cfg.dim)
        return dense(cfg.dim, name="wo")(out)


class MLP(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        rank = cfg.lora_rank if cfg.lora_mlp else 0
        dense = partial(LoRADense, rank=rank, alpha=cfg.lora_alpha, dtype=cfg.dtype)
        gate = checkpoint_name(dense(cfg.ffn_hidden, name="w1")(x), "ffn_gate")
        up = checkpoint_name(dense(cfg.ffn_hidden, name="w3")(x), "ffn_up")
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


def causal_depthwise_conv(u: jax.Array, kernel: jax.Array, bias: jax.Array) -> jax.Array:
    """``out[t] = bias + Σ_k kernel[k] · u[t − (K−1) + k]`` per channel, zeros
    before the sequence; ``u`` is ``[B, T, C]``, ``kernel`` ``[K, C]``. Float32."""
    taps, t = kernel.shape[0], u.shape[1]
    padded = jnp.pad(u.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    out = bias.astype(jnp.float32)
    for k in range(taps):
        out = out + kernel[k].astype(jnp.float32) * padded[:, k:k + t]
    return out


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
        dt = RMSNorm(cfg.dtype, name="dt_norm")(dbc[..., :dt_rank])
        b = RMSNorm(cfg.dtype, name="b_norm")(dbc[..., dt_rank:dt_rank + n])
        c = RMSNorm(cfg.dtype, name="c_norm")(dbc[..., dt_rank + n:])
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
    def __call__(self, x):
        if self.kind == "mamba":
            x = x + MambaMixer(self.cfg, name="mamba")(RMSNorm(self.cfg.dtype, name="mamba_norm")(x))
        else:
            x = x + Attention(self.cfg, self.attn_fn, name="attn")(
                RMSNorm(self.cfg.dtype, name="attn_norm")(x)
            )
        ffn = MoEMLP if self.cfg.n_experts > 0 else MLP
        x = x + ffn(self.cfg, name="mlp")(RMSNorm(self.cfg.dtype, name="mlp_norm")(x))
        return x


class _ScanBlock(nn.Module):
    """nn.scan body: one Block step with the (carry, xs) -> (carry, ys)
    signature lax.scan wants. Params gain a leading [L] axis via
    ``variable_axes={"params": 0}``."""

    cfg: TransformerConfig
    attn_fn: Optional[Callable] = None
    kind: str = "attention"

    @nn.compact
    def __call__(self, x, _):
        return Block(self.cfg, self.attn_fn, self.kind, name="block")(x), None


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


def _rematted_in_scan(body, cfg: TransformerConfig):
    """``body`` rematerialised where the config says so, for use INSIDE a scan.
    prevent_cse=False: inside lax.scan the remat thunk can't be CSE'd across
    iterations anyway, and True blocks the scan lowering (flax's documented
    scan-over-remat recipe)."""
    if not cfg.remat:
        return body
    return nn.remat(body, prevent_cse=False, policy=_remat_policy(cfg.remat_policy))


def _scan_over(body, length: int):
    """``nn.scan`` of ``body`` over ``length`` stacked copies of its params."""
    return nn.scan(body, variable_axes={"params": 0}, split_rngs={"params": True}, length=length)


class _ScanPeriod(nn.Module):
    """nn.scan body over PERIODS of unlike layers: inside, every maximal run
    of same-kind layers is a scan of its own (a run of one layer is the block
    itself), so the compiled program holds one body per run whatever the
    depth. Params: ``run<i>_<kind>/block/...`` with a leading run-length axis,
    or ``run<i>_<kind>/...`` for a run of one."""

    cfg: TransformerConfig
    attn_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, _):
        cfg = self.cfg
        for i, (kind, count) in enumerate(layer_runs(cfg.layer_pattern)):
            name = f"run{i}_{kind}"
            if count > 1:
                scan = _scan_over(_rematted_in_scan(_ScanBlock, cfg), count)
                x, _ = scan(cfg, self.attn_fn, kind, name=name)(x, None)
            else:
                x = _rematted_in_scan(Block, cfg)(cfg, self.attn_fn, kind, name=name)(x)
        return x, None


class CausalLM(nn.Module):
    cfg: TransformerConfig
    attn_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, tokens):  # [B, T] int32 -> [B, T, vocab] f32 logits
        cfg = self.cfg
        emb = self.param(
            "embed", nn.initializers.normal(0.02), (cfg.vocab_size, cfg.dim)
        )
        x = emb[tokens].astype(cfg.dtype)
        pattern = cfg.layer_pattern
        if cfg.scan_layers:
            if cfg.n_experts > 0:
                raise NotImplementedError(
                    "scan_layers with MoE: sown aux losses don't thread through "
                    "the layer scan or the period scan — use unrolled layers for MoE"
                )
            if len(pattern) == 1:
                # a period of one layer is the scan body itself
                scan = _scan_over(_rematted_in_scan(_ScanBlock, cfg), cfg.n_layers)
                x, _ = scan(cfg, self.attn_fn, pattern[0], name="layers")(x, None)
            else:
                scan = _scan_over(_ScanPeriod, cfg.n_layers // len(pattern))
                x, _ = scan(cfg, self.attn_fn, name="layers")(x, None)
        else:
            block_cls = (
                nn.remat(Block, policy=_remat_policy(cfg.remat_policy))
                if cfg.remat
                else Block
            )
            for i in range(cfg.n_layers):
                x = block_cls(cfg, self.attn_fn, pattern[i % len(pattern)], name=f"layer_{i}")(x)
        x = RMSNorm(cfg.dtype, name="final_norm")(x)
        logits = jnp.dot(x, emb.T.astype(cfg.dtype))  # tied embeddings
        return logits.astype(jnp.float32)


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
) -> Optional[Callable]:
    """Map an attention backend name to an ``(q, k, v) -> out`` callable.

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

            head_dim = cfg.dim // cfg.n_heads
            flash_cfg = cfg.flash_config
            if flash_cfg is None and Settings.FLASH_AUTOTUNE:
                flash_cfg = autotune.autotune_flash(basis, head_dim, dtype=cfg.dtype)
            if flash_cfg is None:
                flash_cfg = autotune.get_flash_config(basis, head_dim, dtype=cfg.dtype)
            attn_fn = resolve_attention(attn, mesh=mesh, config=flash_cfg)
        else:
            attn_fn = resolve_attention(attn, mesh=mesh)
    module = CausalLM(cfg, attn_fn)
    rng = jax.random.PRNGKey(seed)
    dummy = jnp.zeros((1, seq_len), dtype=jnp.int32)
    variables = module.init(rng, dummy)
    model = FlaxModel(module, variables["params"], (seq_len,), cfg.vocab_size)
    model.extra["config"] = cfg
    return model
