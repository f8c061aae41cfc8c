"""Operations and bytes of the short-convolution / attention / sparse-expert LM
(the ``lfm2_moe`` block of LFM2-8B-A1B) under LoRA, from shapes alone — beside
``flops.py`` and ``flops_moe.py``, whose conventions hold: multiply-adds x 2 of
matrix multiplications only (the convolution's three taps and the gate products
are elementwise, and XLA fuses them into the two projections' matmuls: no
operations and no pass of their own to count), nothing recomputed counts, and a
token's routed work is its ``num_experts_per_tok`` experts, not executed tiles.

Shapes come from the configuration file's own keys (Hugging Face names).
"""

from __future__ import annotations

from benchmark import flops, flops_moe
from benchmark.flops_moe import DTYPE_BYTES, swiglu_matrices
from benchmark.reference.lfm2_moe_lm import KINDS, layer_kinds  # the one rule for the order of the layer kinds


def _as_moe(cfg: dict) -> dict:
    """This configuration under the key ``flops_moe`` reads the expert count from."""
    return dict(cfg, n_routed_experts=cfg["num_experts"])


def conv_matrices(cfg: dict) -> list[tuple[str, int, int]]:
    d = cfg["hidden_size"]
    return [("in_proj", d, 3 * d), ("out_proj", d, d)]


def attention_matrices(cfg: dict) -> list[tuple[str, int, int]]:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wo", q, d)]


def dense_matrices(cfg: dict, kind: str) -> list[tuple[str, int, int]]:
    """The ``LoRADense`` matrices of one layer (float32, every token)."""
    mixer, ffn = KINDS[kind]
    out = conv_matrices(cfg) if mixer == "conv" else attention_matrices(cfg)
    return out + (swiglu_matrices(cfg["hidden_size"], cfg["intermediate_size"]) if ffn == "dense" else [])


def bank_params(cfg: dict) -> int:
    """The routed experts of ONE expert layer (bfloat16, no adapter)."""
    return flops_moe.bank_params(_as_moe(cfg))


def layer_params(cfg: dict, kind: str) -> int:
    """Every parameter of one layer OUTSIDE the routed experts: matrices, the
    block's two norms, the taps or the two per-head norms, router + bias."""
    mixer, ffn = KINDS[kind]
    d = cfg["hidden_size"]
    total = sum(i * o for _, i, o in dense_matrices(cfg, kind)) + 2 * d
    total += cfg["conv_L_cache"] * d if mixer == "conv" else 2 * cfg["head_dim"]
    if ffn == "experts":
        total += d * cfg["num_experts"] + cfg["num_experts"]
    return total


def model_params(cfg: dict) -> dict:
    """Parameters as the program holds them, by the dtype they are kept in."""
    kinds = layer_kinds(cfg)
    d = cfg["hidden_size"]
    f32 = sum(layer_params(cfg, k) for k in kinds) + cfg["vocab_size"] * d + d
    bf16 = bank_params(cfg) * sum(KINDS[k][1] == "experts" for k in kinds)
    return {"float32": f32, "bfloat16": bf16, "total": f32 + bf16}


def adapted(cfg: dict, kind: str) -> list[tuple[int, int]]:
    targets = cfg["lora"]["targets"]
    return [(i, o) for name, i, o in dense_matrices(cfg, kind) if name in targets]


def lora_params(cfg: dict) -> int:
    rank = cfg["lora"]["rank"]
    return rank * sum(i + o for k in layer_kinds(cfg) for i, o in adapted(cfg, k))


def lora_step_flops(cfg: dict, seq: int) -> dict:
    """One local step on ONE sequence of ``seq`` tokens, base frozen: forward +
    dX through every frozen matrix (4·P·T, the routed experts at four a token),
    the tied head likewise, adapter forward + dA + dB + dX (6·T·r·(in+out)),
    causal attention forward + backward in the attention layers only."""
    kinds = layer_kinds(cfg)
    rank = cfg["lora"]["rank"]
    base = 4.0 * seq * sum(i * o for k in kinds for _, i, o in dense_matrices(cfg, k))
    routed = 2.0 * flops_moe.routed_flops(_as_moe(cfg), seq) * sum(KINDS[k][1] == "experts" for k in kinds)
    head = 4.0 * cfg["hidden_size"] * cfg["vocab_size"] * seq
    adapters = 6.0 * seq * rank * sum(i + o for k in kinds for i, o in adapted(cfg, k))
    fwd, bwd = flops.causal_attention_flops(seq, cfg["num_attention_heads"], cfg["head_dim"])
    attention = (fwd + bwd) * sum(KINDS[k][0] == "attention" for k in kinds)
    return {
        "base": base, "routed_experts": routed, "head": head, "adapters": adapters, "attention": attention,
        "total": base + routed + head + adapters + attention,
    }


def gmm_pass(cfg: dict, seq: int) -> tuple[float, float]:
    """(operations, bytes) of ONE pass over ONE expert layer's two grouped
    matmuls at these widths: ``flops_moe.gmm_pass``'s count."""
    return flops_moe.gmm_pass(_as_moe(cfg), seq)


def gmm_floor_seconds(cfg: dict, seq: int, peak: dict) -> float:
    """The least time over ALL the grouped matmuls of one sequence-step: per
    expert layer and pass the larger of bytes over the HBM peak and operations
    over the bf16 peak, forward + backward. Re-forwards count in the measured
    time only."""
    ops, moved = gmm_pass(cfg, seq)
    one = max(moved / peak["hbm_bytes_per_s"], ops / peak["bf16_flops_per_s"])
    return 2.0 * one * sum(KINDS[k][1] == "experts" for k in layer_kinds(cfg))


def gqa_flash_floor_seconds(cfg: dict, seq: int, peak: dict) -> float:
    """The least time over ALL the causal attention of one sequence-step (the
    attention layers only, forward + backward) at ``head_dim``: the larger of
    operations over the bf16 peak and q, o (every query head) and k, v (the
    key/value heads) with their cotangents over the HBM peak."""
    heads, kv_heads, width = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    fwd, bwd = flops.causal_attention_flops(seq, heads, width)
    act = DTYPE_BYTES[cfg["compute_dtype"]]
    fwd_bytes = 2 * seq * (heads + kv_heads) * width * act
    one = max(fwd / peak["bf16_flops_per_s"], fwd_bytes / peak["hbm_bytes_per_s"]) + max(
        bwd / peak["bf16_flops_per_s"], 2 * fwd_bytes / peak["hbm_bytes_per_s"]
    )
    return one * sum(KINDS[k][0] == "attention" for k in layer_kinds(cfg))
