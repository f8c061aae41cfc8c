"""Operations and bytes of the sliding-window / full-attention sparse-expert LM
(the ``afmoe`` block of Trinity-Mini) under LoRA, as ONE CHIP'S SHARE runs it,
from shapes alone — beside ``flops.py`` and ``flops_moe.py``, whose conventions
hold: multiply-adds x 2 of matrix multiplications only (the gate product, the
sandwich norms, the embedding multiplier are elementwise), nothing recomputed
counts. What is this configuration's own:

- attention counts the pairs a query SEES: ``visible_pairs(T, W) = sum_i min(i +
  1, W)`` for a sliding layer, ``T (T + 1) / 2`` for a full one — not the causal
  triangle for both, so a windowed kernel's share of its floor cannot pass 100 %
  by skipping;
- the routed experts count the rows THIS CHIP computes: ``T x k x held_share``,
  the held share read from the program's counted layout (``moe_held_share``) or,
  before a run, the even share ``held / router width``;
- the head is the vocabulary slice the file gives (``vocab_size`` rows), untied:
  the embedding is a second matrix of that size and no operation.

Shapes come from the configuration file's own keys (Hugging Face names).

    python -m benchmark.flops_window_moe     # the hand counts below, checked
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

from benchmark.flops_moe import DTYPE_BYTES, swiglu_matrices
from benchmark.reference.afmoe_lm import KINDS, layer_kinds, share  # the one rule for the order of the layer kinds


def attention_matrices(cfg: dict) -> list[tuple[str, int, int]]:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wg", d, q), ("wo", q, d)]


def dense_matrices(cfg: dict, kind: str) -> list[tuple[str, int, int]]:
    """The ``LoRADense`` matrices of one layer (float32, every token): attention
    with its gate, and the dense SwiGLU or the shared expert."""
    d = cfg["hidden_size"]
    width = cfg["intermediate_size"] if KINDS[kind][1] == "dense" else cfg["num_shared_experts"] * cfg["moe_intermediate_size"]
    return attention_matrices(cfg) + swiglu_matrices(d, width)


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def bank_params(cfg: dict) -> int:
    """The HELD routed experts of ONE expert layer (bfloat16, no adapter)."""
    return share(cfg)[1] * expert_params(cfg)


def layer_params(cfg: dict, kind: str) -> int:
    """Every parameter of one layer OUTSIDE the routed experts: matrices, the
    four norms of the sandwich, the two per-head norms, router + bias (as wide
    as the whole model's experts)."""
    d, width = cfg["hidden_size"], share(cfg)[2]
    total = sum(i * o for _, i, o in dense_matrices(cfg, kind)) + 4 * d + 2 * cfg["head_dim"]
    if KINDS[kind][1] == "experts":
        total += d * width + width
    return total


def model_params(cfg: dict) -> dict:
    """Parameters as the program holds them, by the dtype they are kept in."""
    kinds = layer_kinds(cfg)
    d = cfg["hidden_size"]
    f32 = sum(layer_params(cfg, k) for k in kinds) + 2 * cfg["vocab_size"] * d + d  # embedding + untied head + final norm
    bf16 = bank_params(cfg) * sum(KINDS[k][1] == "experts" for k in kinds)
    return {"float32": f32, "bfloat16": bf16, "total": f32 + bf16}


def adapted(cfg: dict, kind: str) -> list[tuple[int, int]]:
    targets = cfg["lora"]["targets"]
    return [(i, o) for name, i, o in dense_matrices(cfg, kind) if name in targets]


def lora_params(cfg: dict) -> int:
    rank = cfg["lora"]["rank"]
    return rank * sum(i + o for k in layer_kinds(cfg) for i, o in adapted(cfg, k))


def visible_pairs(seq: int, window: int | None) -> int:
    """(query, key) pairs a causal layer scores: ``sum_i min(i + 1, window)``."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_flops(cfg: dict, seq: int, mixer: str) -> tuple[float, float]:
    """(forward, backward) of ONE layer's softmax attention on one sequence:
    QK^T and PV forward (2 matmuls), dQ, dK, dV, dP backward (4), each
    ``2 x pairs x head_dim`` a head, over the pairs a query sees."""
    pairs = visible_pairs(seq, cfg["sliding_window"] if mixer == "sliding" else None)
    one = 2.0 * pairs * cfg["head_dim"] * cfg["num_attention_heads"]
    return 2 * one, 4 * one


def even_share(cfg: dict) -> float:
    _, held, width = share(cfg)
    return held / width


def routed_flops(cfg: dict, seq: int, held_share: float | None = None) -> float:
    """Forward of ONE expert layer's routed experts on one sequence, HERE: the
    router's scores for every expert of the model, and ``seq x k x held_share``
    rows through an expert (3 matrices)."""
    part = even_share(cfg) if held_share is None else held_share
    rows = seq * cfg["num_experts_per_tok"] * part
    return 2.0 * (rows * expert_params(cfg) + seq * cfg["hidden_size"] * share(cfg)[2])


def lora_step_flops(cfg: dict, seq: int, held_share: float | None = None) -> dict:
    """One local step on ONE sequence of ``seq`` tokens, base frozen: forward +
    dX through every frozen matrix (4·P·T; the routed experts over the rows this
    chip computes), the untied sliced head likewise, adapter forward + dA + dB +
    dX (6·T·r·(in+out)), attention forward + backward over the visible pairs."""
    kinds = layer_kinds(cfg)
    rank = cfg["lora"]["rank"]
    base = 4.0 * seq * sum(i * o for k in kinds for _, i, o in dense_matrices(cfg, k))
    routed = 2.0 * routed_flops(cfg, seq, held_share) * sum(KINDS[k][1] == "experts" for k in kinds)
    head = 4.0 * cfg["hidden_size"] * cfg["vocab_size"] * seq
    adapters = 6.0 * seq * rank * sum(i + o for k in kinds for i, o in adapted(cfg, k))
    attention = sum(sum(attention_flops(cfg, seq, KINDS[k][0])) for k in kinds)
    return {
        "base": base, "routed_experts": routed, "head": head, "adapters": adapters, "attention": attention,
        "total": base + routed + head + adapters + attention,
    }


def gmm_pass(cfg: dict, seq: int, held_share: float | None = None) -> tuple[float, float]:
    """(operations, bytes) ONE pass over ONE expert layer's two grouped matmuls
    must take here: the rows this chip computes through gate|up and down; the
    HELD bank read once, each row matrix once, in the compute dtype."""
    part = even_share(cfg) if held_share is None else held_share
    d, f, rows = cfg["hidden_size"], cfg["moe_intermediate_size"], seq * cfg["num_experts_per_tok"] * part
    ops = 2.0 * rows * expert_params(cfg)
    act = DTYPE_BYTES[cfg["compute_dtype"]]
    bank = bank_params(cfg) * DTYPE_BYTES[cfg["expert_dtype"]]
    moved = bank + act * rows * (d + 2 * f + 2 * f + f + f + d)  # x in, gate|up out and in, h out and in, y out
    return ops, float(moved)


def gmm_floor_seconds(cfg: dict, seq: int, peak: dict, held_share: float | None = None) -> float:
    """The least time over ALL the grouped matmuls of one sequence-step: per
    expert layer and pass the larger of bytes over the HBM peak and operations
    over the bf16 peak, forward + backward. Re-forwards count in the measured
    time only."""
    ops, moved = gmm_pass(cfg, seq, held_share)
    one = max(moved / peak["hbm_bytes_per_s"], ops / peak["bf16_flops_per_s"])
    return 2.0 * one * sum(KINDS[k][1] == "experts" for k in layer_kinds(cfg))


def flash_floor_seconds(cfg: dict, seq: int, peak: dict, mixer: str) -> float:
    """The least time over the attention of the ``mixer`` layers (``"sliding"``
    or ``"full"``) of one sequence-step, forward + backward: the larger of
    operations over the bf16 peak — over the VISIBLE pairs — and q, o (every
    query head) and k, v (the key/value heads) with their cotangents over the
    HBM peak. Re-forwards count in the measured time only."""
    heads, kv_heads, width = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    fwd, bwd = attention_flops(cfg, seq, mixer)
    act = DTYPE_BYTES[cfg["compute_dtype"]]
    fwd_bytes = 2 * seq * (heads + kv_heads) * width * act
    one = max(fwd / peak["bf16_flops_per_s"], fwd_bytes / peak["hbm_bytes_per_s"]) + max(
        bwd / peak["bf16_flops_per_s"], 2 * fwd_bytes / peak["hbm_bytes_per_s"]
    )
    return one * sum(KINDS[k][0] == mixer for k in layer_kinds(cfg))


def flash_win_floor_seconds(cfg: dict, seq: int, peak: dict) -> float:
    """:func:`flash_floor_seconds` of the sliding layers."""
    return flash_floor_seconds(cfg, seq, peak, "sliding")


def hand_counts() -> list[tuple[str, object, object]]:
    """(what, the functions' answer, the count by hand) at the published widths
    of ``configs/trinity_mini_lora.json``, 8192 tokens."""
    from benchmark import flops

    cfg = json.loads((Path(__file__).resolve().parent / "configs" / "trinity_mini_lora.json").read_text())
    peak = flops.peaks("TPU v5 lite")
    attn = 3 * 2048 * 4096 + 2 * 2048 * 512  # q, gate, o: 2048 x 4096 each; k, v: 2048 x 512
    full, slid = 8192 * 8193 // 2, 2048 * 2049 // 2 + 6144 * 2048
    params = model_params(cfg)
    step = lora_step_flops(cfg, 8192)
    ops, moved = gmm_pass(cfg, 8192)
    win_fwd = 2 * 2.0 * slid * 128 * 32
    return [
        ("attention matrices a layer (gate included)", sum(i * o for _, i, o in attention_matrices(cfg)), attn),
        ("attention matrices a layer", attn, 27_262_976),
        ("one expert", expert_params(cfg), 6_291_456),
        ("held bank a layer", bank_params(cfg), 32 * 6_291_456),
        ("dense layer", layer_params(cfg, "swa_dense"), attn + 3 * 2048 * 6144 + 4 * 2048 + 2 * 128),
        ("expert layer outside its bank", layer_params(cfg, "full_experts"), attn + 6_291_456 + 2048 * 128 + 128 + 4 * 2048 + 2 * 128),
        ("float32 parameters", params["float32"], 2 * 50_048 * 2048 + 2048 + 2 * (attn + 37_748_736 + 8_448) + 8 * (attn + 6_291_456 + 262_272 + 8_448)),
        ("bfloat16 parameters", params["bfloat16"], 8 * 201_326_592),
        ("adapter parameters", lora_params(cfg), 8 * (10 * (3 * (2048 + 4096) + 2 * (2048 + 512)) + 2 * 3 * (2048 + 6144) + 8 * 3 * (2048 + 1024))),
        ("visible pairs, full layer", visible_pairs(8192, None), full),
        ("visible pairs, sliding layer", visible_pairs(8192, 2048), slid),
        ("visible pairs, window >= T", visible_pairs(2048, 2048), 2048 * 2049 // 2),
        ("attention operations a step", step["attention"], 6 * 2.0 * 128 * 32 * (8 * slid + 2 * full)),
        ("head operations a step", step["head"], 4.0 * 2048 * 50_048 * 8192),
        ("routed operations a step (even share)", step["routed_experts"], 8 * 2 * 2.0 * (8192 * 8 * 0.25 * 6_291_456 + 8192 * 2048 * 128)),
        ("grouped matmul operations a pass", ops, 2.0 * 16_384 * 6_291_456),
        ("grouped matmul bytes a pass", moved, 2.0 * 201_326_592 + 2 * 16_384 * (2048 + 6 * 1024 + 2048)),
        ("sliding flash floor a step", flash_win_floor_seconds(cfg, 8192, peak), 8 * 3 * win_fwd / 197e12),
    ]


def main() -> int:
    bad = 0
    for what, got, want in hand_counts():
        ok = got == want or (isinstance(got, float) and abs(got - want) <= 1e-12 * abs(want))
        bad += not ok
        print(f"{'ok' if ok else 'FAILED'}: {what}: {got} (by hand {want})")
    return int(bool(bad))


if __name__ == "__main__":
    sys.exit(main())
