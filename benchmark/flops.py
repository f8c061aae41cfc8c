"""Operations and bytes the algorithms need, from shapes alone.

Kept with the benchmark so a PR that claims a gain cannot change the
numerator. Counts are multiply-adds x 2 of matrix multiplications and
convolutions only (norms, activations, softmax, optimizer updates are not
counted: under 1% of either model's work). "Needed" means what the
mathematics requires once: a forward pass recomputed by rematerialisation does
not count (``remat_forwards`` below exists for the KERNEL's executed work, which
is a different number — see ``flash_executed_flops``).

Shapes come from the configuration file's own keys (Hugging Face names for the
language model, He et al.'s table for the ResNet).
"""

from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind`` (exact match) — a device the
    table does not know is an error, never a default."""
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(
            f"benchmark/peaks.json has no entry for device_kind {device_kind!r} "
            f"(known: {sorted(table)}); add a file entry with its published source"
        )
    return table[device_kind]


# ---- dense causal LM under LoRA ----------------------------------------------


def lm_layer_matrices(cfg: dict) -> list[tuple[str, int, int]]:
    """(name, in, out) of one block's seven projection matrices."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv, f = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd, cfg["intermediate_size"]
    return [
        ("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wo", q, d),
        ("w1", d, f), ("w3", d, f), ("w2", f, d),
    ]


def lm_layer_params(cfg: dict) -> int:
    return sum(i * o for _, i, o in lm_layer_matrices(cfg))


def causal_attention_flops(seq: int, heads: int, head_dim: int) -> tuple[float, float]:
    """(forward, backward) of causal softmax attention for one sequence and
    one layer: QK^T and PV forward (2 matmuls), dQ, dK, dV, dP backward (4),
    each 2*T*T*D per head, halved by the causal mask."""
    one = 2.0 * seq * seq * head_dim * heads * 0.5
    return 2 * one, 4 * one


def adapted_matrices(cfg: dict, lora_mlp: bool) -> list[tuple[int, int]]:
    """(in, out) of the projections that carry an adapter."""
    return [
        (i, o) for n, i, o in lm_layer_matrices(cfg)
        if lora_mlp or n in ("wq", "wk", "wv", "wo")
    ]


def lora_step_flops(cfg: dict, seq: int, *, rank: int, lora_mlp: bool) -> dict:
    """One local step on ONE sequence of ``seq`` tokens, base frozen:
    forward + dX through every frozen matrix (no dW: 4*P*T, not 6), the tied
    output head likewise, adapter forward + dA + dB + dX (6*T*r*(in+out)),
    and attention forward + backward. Returns the parts and their ``total``."""
    layers = cfg["num_hidden_layers"]
    base = 4.0 * lm_layer_params(cfg) * seq * layers
    head = 4.0 * cfg["hidden_size"] * cfg["vocab_size"] * seq
    adapters = 6.0 * seq * rank * sum(i + o for i, o in adapted_matrices(cfg, lora_mlp)) * layers
    fwd, bwd = causal_attention_flops(seq, cfg["num_attention_heads"], cfg["head_dim"])
    attention = (fwd + bwd) * layers
    return {
        "base": base, "head": head, "adapters": adapters, "attention": attention,
        "total": base + head + adapters + attention,
    }


def flash_executed_flops(cfg: dict, seq: int, *, remat_forwards: int = 1) -> float:
    """What the flash kernels of ONE sequence-step execute: the forward kernel
    ``1 + remat_forwards`` times (``mlp_qkv`` re-runs it in the backward for
    its lse residual) and the backward once, all layers. The fused backward
    also recomputes S = QK^T in VMEM; that fifth matmul is NOT counted, by the
    usual convention, so the share below is of the algorithm's operations."""
    fwd, bwd = causal_attention_flops(seq, cfg["num_attention_heads"], cfg["head_dim"])
    return ((1 + remat_forwards) * fwd + bwd) * cfg["num_hidden_layers"]


def lora_params(cfg: dict, *, rank: int, lora_mlp: bool) -> int:
    return rank * sum(i + o for i, o in adapted_matrices(cfg, lora_mlp)) * cfg["num_hidden_layers"]


# ---- bottleneck ResNet ---------------------------------------------------------


def conv_flops(h: int, w: int, cin: int, cout: int, k: int) -> float:
    """Forward of one ``k x k`` convolution producing ``h x w x cout``."""
    return 2.0 * h * w * cin * cout * k * k


def bottleneck_flops(h: int, w: int, cin: int, filters: int, stride: int) -> tuple[float, int, int, int]:
    """Forward of one bottleneck block (1x1, 3x3 with the stride, 1x1 x4, and
    the 1x1 projection when the shape changes) on an ``h x w x cin`` input.
    Returns (flops, h_out, w_out, c_out)."""
    ho, wo, cout = h // stride, w // stride, 4 * filters
    total = conv_flops(h, w, cin, filters, 1)
    total += conv_flops(ho, wo, filters, filters, 3)
    total += conv_flops(ho, wo, filters, cout, 1)
    if stride != 1 or cin != cout:
        total += conv_flops(ho, wo, cin, cout, 1)
    return total, ho, wo, cout


def resnet_forward_flops(cfg: dict) -> float:
    """Forward of the configured bottleneck ResNet on one image."""
    h, w, c = cfg["input_shape"]
    stem = cfg["stem"]["width"]
    total = conv_flops(h, w, c, stem, cfg["stem"]["kernel"])
    c = stem
    for i, (n_blocks, filters) in enumerate(zip(cfg["stage_sizes"], cfg["stage_widths"])):
        for j in range(n_blocks):
            stride = 2 if i > 0 and j == 0 else 1
            f, h, w, c = bottleneck_flops(h, w, c, filters, stride)
            total += f
    return total + 2.0 * c * cfg["num_classes"]


def resnet_step_flops(cfg: dict, batch: int) -> float:
    """One training step on ``batch`` images: forward + dX + dW = 3 x forward
    (the stem needs no dX; at 0.2% of the forward it is not split out)."""
    return 3.0 * resnet_forward_flops(cfg) * batch


def resnet_params(cfg: dict) -> int:
    """Weights of the configured ResNet (conv kernels, GroupNorm scale+bias,
    classifier with bias)."""
    h, w, c = cfg["input_shape"]
    stem = cfg["stem"]["width"]
    total = c * stem * cfg["stem"]["kernel"] ** 2 + 2 * stem
    c = stem
    for i, (n_blocks, filters) in enumerate(zip(cfg["stage_sizes"], cfg["stage_widths"])):
        for j in range(n_blocks):
            cout = 4 * filters
            total += c * filters + 2 * filters + 9 * filters * filters + 2 * filters
            total += filters * cout + 2 * cout
            if (i > 0 and j == 0) or c != cout:
                total += c * cout + 2 * cout
            c = cout
    return total + c * cfg["num_classes"] + cfg["num_classes"]


# ---- aggregation ---------------------------------------------------------------


def fedavg_fold_bytes(n_nodes: int, n_params: int, bytes_per: int = 4) -> dict:
    """Bytes a FedAvg fold over ``n_nodes`` stacked copies must move: read
    every copy, write the mean back to every copy; ``all_reduce`` is what one
    device contributes to and receives from the cross-chip sum."""
    return {
        "read": n_nodes * n_params * bytes_per,
        "write": n_nodes * n_params * bytes_per,
        "all_reduce": n_params * bytes_per,
    }
