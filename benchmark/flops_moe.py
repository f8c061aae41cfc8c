"""Operations and bytes of the latent-attention / sparse-expert LM (the
DeepSeek-V3 block as GLM-4.7-Flash runs it) under LoRA, from shapes alone —
beside ``flops.py``, whose conventions hold: multiply-adds x 2 of matrix
multiplications only, nothing recomputed counts. A token's routed work is its
``num_experts_per_tok`` experts — FOUR A TOKEN, NOT EXECUTED TILES: rows of
padding in the grouped matmul's tiles are the kernel's affair, not the model's.

Shapes come from the configuration file's own keys (Hugging Face names).
"""

from __future__ import annotations

from benchmark import flops
from benchmark.reference.glm_moe_lm import layer_kinds  # noqa: F401 (the one rule for the order of the layer kinds)

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def head_width(cfg: dict) -> int:
    return cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]


def mla_matrices(cfg: dict) -> list[tuple[str, int, int]]:
    """(name, in, out) of latent attention's five projection matrices."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return [
        ("q_a", d, cfg["q_lora_rank"]),
        ("q_b", cfg["q_lora_rank"], h * head_width(cfg)),
        ("kv_a", d, cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]),
        ("kv_b", cfg["kv_lora_rank"], h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])),
        ("o", h * cfg["v_head_dim"], d),
    ]


def swiglu_matrices(d: int, f: int) -> list[tuple[str, int, int]]:
    return [("w1", d, f), ("w3", d, f), ("w2", f, d)]


def dense_matrices(cfg: dict, kind: str) -> list[tuple[str, int, int]]:
    """The ``LoRADense`` matrices of one layer (float32, every token): latent
    attention, and the dense SwiGLU or the shared expert."""
    d = cfg["hidden_size"]
    if kind == "mla_dense":
        return mla_matrices(cfg) + swiglu_matrices(d, cfg["intermediate_size"])
    return mla_matrices(cfg) + swiglu_matrices(d, cfg["n_shared_experts"] * cfg["moe_intermediate_size"])


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def bank_params(cfg: dict) -> int:
    """The routed experts of ONE expert layer (bfloat16, no adapter)."""
    return cfg["n_routed_experts"] * expert_params(cfg)


def layer_params(cfg: dict, kind: str) -> int:
    """Every parameter of one layer OUTSIDE the routed experts: matrices, the
    four norms (two of the block, two inside latent attention), router + bias."""
    total = sum(i * o for _, i, o in dense_matrices(cfg, kind))
    total += 2 * cfg["hidden_size"] + cfg["q_lora_rank"] + cfg["kv_lora_rank"]
    if kind == "mla_experts":
        total += cfg["hidden_size"] * cfg["n_routed_experts"] + cfg["n_routed_experts"]
    return total


def model_params(cfg: dict) -> dict:
    """Parameters as the program holds them, by the dtype they are kept in."""
    kinds = layer_kinds(cfg)
    d = cfg["hidden_size"]
    f32 = sum(layer_params(cfg, k) for k in kinds) + cfg["vocab_size"] * d + d
    bf16 = bank_params(cfg) * kinds.count("mla_experts")
    return {"float32": f32, "bfloat16": bf16, "total": f32 + bf16}


def adapted(cfg: dict, kind: str) -> list[tuple[int, int]]:
    targets = cfg["lora"]["targets"]
    return [(i, o) for name, i, o in dense_matrices(cfg, kind) if name in targets]


def lora_params(cfg: dict) -> int:
    rank = cfg["lora"]["rank"]
    return rank * sum(i + o for k in layer_kinds(cfg) for i, o in adapted(cfg, k))


def routed_flops(cfg: dict, seq: int) -> float:
    """Forward of ONE expert layer's routed experts on one sequence: every token
    through ``num_experts_per_tok`` experts (3 matrices each) and the router."""
    d, e = cfg["hidden_size"], cfg["n_routed_experts"]
    return 2.0 * seq * (cfg["num_experts_per_tok"] * expert_params(cfg) + d * e)


def lora_step_flops(cfg: dict, seq: int) -> dict:
    """One local step on ONE sequence of ``seq`` tokens, base frozen: forward +
    dX through every frozen matrix (4·P·T, the routed experts at four a token),
    the tied head likewise, adapter forward + dA + dB + dX (6·T·r·(in+out)),
    causal attention forward + backward at the full head width."""
    kinds = layer_kinds(cfg)
    rank = cfg["lora"]["rank"]
    n_expert_layers = kinds.count("mla_experts")
    base = 4.0 * seq * sum(i * o for k in kinds for _, i, o in dense_matrices(cfg, k))
    routed = 2.0 * routed_flops(cfg, seq) * n_expert_layers
    head = 4.0 * cfg["hidden_size"] * cfg["vocab_size"] * seq
    adapters = 6.0 * seq * rank * sum(i + o for k in kinds for i, o in adapted(cfg, k))
    fwd, bwd = flops.causal_attention_flops(seq, cfg["num_attention_heads"], head_width(cfg))
    attention = (fwd + bwd) * len(kinds)
    return {
        "base": base, "routed_experts": routed, "head": head, "adapters": adapters, "attention": attention,
        "total": base + routed + head + adapters + attention,
    }


def gmm_pass(cfg: dict, seq: int) -> tuple[float, float]:
    """(operations, bytes) ONE pass — forward, or the input cotangent — over
    ONE expert layer's two grouped matmuls must take, whatever implements
    them: ``seq x k`` rows through gate|up and down; the bank read once, each
    row matrix (input, hidden twice — written and read —, output) once, in the
    compute dtype."""
    d, f, rows = cfg["hidden_size"], cfg["moe_intermediate_size"], seq * cfg["num_experts_per_tok"]
    ops = 2.0 * rows * expert_params(cfg)
    act = DTYPE_BYTES[cfg["compute_dtype"]]
    bank = bank_params(cfg) * DTYPE_BYTES[cfg["expert_dtype"]]
    moved = bank + act * rows * (d + 2 * f + 2 * f + f + f + d)  # x in, gate|up out and in, h out and in, y out
    return ops, float(moved)


def gmm_floor_seconds(cfg: dict, seq: int, peak: dict) -> float:
    """The least time the chip could take over ALL the grouped matmuls of one
    sequence-step: per expert layer and pass the larger of bytes over the HBM
    peak and operations over the bf16 peak, forward + backward (the same
    operations against the matrices' other axis). Re-forwards count in the
    measured time only."""
    ops, moved = gmm_pass(cfg, seq)
    one = max(moved / peak["hbm_bytes_per_s"], ops / peak["bf16_flops_per_s"])
    return 2.0 * one * layer_kinds(cfg).count("mla_experts")


def mla_flash_floor_seconds(cfg: dict, seq: int, peak: dict) -> float:
    """The least time over ALL the causal attention of one sequence-step (every
    layer, forward + backward) at the full head width: the larger of operations
    over the bf16 peak and q, k, v, o (and their cotangents) over the HBM peak."""
    heads, width = cfg["num_attention_heads"], head_width(cfg)
    fwd, bwd = flops.causal_attention_flops(seq, heads, width)
    act = DTYPE_BYTES[cfg["compute_dtype"]]
    fwd_bytes = 4 * seq * heads * width * act
    bwd_bytes = 8 * seq * heads * width * act
    one = max(fwd / peak["bf16_flops_per_s"], fwd_bytes / peak["hbm_bytes_per_s"]) + max(
        bwd / peak["bf16_flops_per_s"], bwd_bytes / peak["hbm_bytes_per_s"]
    )
    return one * len(layer_kinds(cfg))
