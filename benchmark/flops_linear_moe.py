"""Operations and bytes of the hybrid linear-attention sparse-expert LM (the
``solar_open2`` block: gated delta-rule KDA layers 3 : 1 with gated NoPE GQA,
every layer an expert layer) under LoRA, as ONE CHIP'S SHARE runs it, from
shapes alone — beside ``flops_moe.py`` and ``flops_window_moe.py``, whose
conventions hold: multiply-adds x 2 of matrix multiplications only (norms,
gates, convolutions, decays are elementwise), nothing recomputed counts. What
is this configuration's own:

- the delta rule counts the CHUNKED algorithm's operations at a FIXED chunk of
  ``KDA_FLOOR_CHUNK`` = 64 positions, whatever chunk the kernel runs: per chunk
  and head ``K Kᵀ``, ``Q Kᵀ``, ``(K e^g) S``, ``T (β R)``, ``(Q e^g) S``,
  ``P U`` and ``(K e^{g_C − g})ᵀ U`` (``2 C² d`` or ``2 C d²`` each) and the
  triangular inverse (``C³ / 3``); its backward twice the forward;
- its bytes are what must move once a pass: q, k, v and o in the compute dtype,
  the cumulative decay and β in float32 (the configuration's state dtype), and
  in the backward the output's cotangent in and q, k, v, decay and β's out;
- the routed experts count the rows THIS CHIP computes (``flops_window_moe``'s
  rule); the head is the vocabulary slice, untied.

Shapes come from the configuration file's own keys (Hugging Face names).

    python -m benchmark.flops_linear_moe     # the hand counts below, checked
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

from benchmark.flops_moe import DTYPE_BYTES, swiglu_matrices
from benchmark.reference.solar_open2_lm import KINDS, layer_kinds, share

KDA_FLOOR_CHUNK = 64


def kda_width(cfg: dict) -> tuple[int, int]:
    lin = cfg["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"]


def mixer_matrices(cfg: dict, kind: str) -> list[tuple[str, int, int]]:
    """The ``LoRADense`` matrices of one layer's mixer (float32, every token)."""
    d = cfg["hidden_size"]
    if KINDS[kind][0] == "gqa":
        q, kv = cfg["num_attention_heads"] * cfg["head_dim"], cfg["num_key_value_heads"] * cfg["head_dim"]
        return [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wg", d, q), ("wo", q, d)]
    heads, hd = kda_width(cfg)
    w, rank = heads * hd, cfg["kda_low_rank"]
    return [
        ("wq", d, w), ("wk", d, w), ("wv", d, w), ("wo", w, d), ("f_a", d, rank), ("f_b", rank, w),
        ("b_proj", d, heads), ("g_a", d, rank), ("g_b", rank, w),
    ]


def dense_matrices(cfg: dict, kind: str) -> list[tuple[str, int, int]]:
    """Every ``LoRADense`` matrix of one layer: the mixer's and the shared expert's."""
    return mixer_matrices(cfg, kind) + swiglu_matrices(cfg["hidden_size"], cfg["n_shared_experts"] * cfg["moe_intermediate_size"])


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def bank_params(cfg: dict) -> int:
    """The HELD routed experts of ONE layer (bfloat16, no adapter)."""
    return share(cfg)[1] * expert_params(cfg)


def layer_params(cfg: dict, kind: str) -> int:
    """Every parameter of one layer OUTSIDE the routed experts: matrices, the two
    norms, router + bias (as wide as the whole model's experts), and of a KDA
    layer the three convolutions' taps, ``A_log``, ``dt_bias``, the gate's bias
    and the output norm's scale."""
    d, width = cfg["hidden_size"], share(cfg)[2]
    total = sum(i * o for _, i, o in dense_matrices(cfg, kind)) + 2 * d + d * width + width
    if KINDS[kind][0] == "kda":
        heads, hd = kda_width(cfg)
        total += 3 * cfg["linear_attn_config"]["short_conv_kernel_size"] * heads * hd + heads + 2 * heads * hd + hd
    return total


def model_params(cfg: dict) -> dict:
    """Parameters as the program holds them, by the dtype they are kept in."""
    kinds = layer_kinds(cfg)
    d = cfg["hidden_size"]
    f32 = sum(layer_params(cfg, k) for k in kinds) + 2 * cfg["vocab_size"] * d + d  # embedding + untied head + final norm
    bf16 = bank_params(cfg) * len(kinds)
    return {"float32": f32, "bfloat16": bf16, "total": f32 + bf16}


def adapted(cfg: dict, kind: str) -> list[tuple[int, int]]:
    targets = cfg["lora"]["targets"]
    return [(i, o) for name, i, o in dense_matrices(cfg, kind) if name in targets]


def lora_params(cfg: dict) -> int:
    rank = cfg["lora"]["rank"]
    return rank * sum(i + o for k in layer_kinds(cfg) for i, o in adapted(cfg, k))


def kda_pass(cfg: dict, seq: int) -> dict:
    """ONE KDA layer on ONE sequence: the chunked algorithm's forward operations
    at ``KDA_FLOOR_CHUNK``, the backward's (twice), and the bytes each pass must move."""
    heads, d = kda_width(cfg)
    c = KDA_FLOOR_CHUNK
    chunks = -(-seq // c)
    per_chunk = 4 * 2.0 * c * c * d + 3 * 2.0 * c * d * d + c ** 3 / 3.0
    fwd = per_chunk * chunks * heads
    act, state = DTYPE_BYTES[cfg["compute_dtype"]], DTYPE_BYTES[cfg["state_dtype"]]
    width = seq * heads * d
    inputs = 3 * act * width + state * width + state * seq * heads  # q, k, v; decay; beta
    return {
        "fwd_flops": fwd, "bwd_flops": 2.0 * fwd,
        "fwd_bytes": float(inputs + act * width),  # o out
        "bwd_bytes": float(2 * inputs + act * width),  # the inputs again, do in; dq, dk, dv, ddecay, dbeta out
    }


def kda_floor_seconds(cfg: dict, seq: int, peak: dict) -> float:
    """The least time over the delta rule of every KDA layer of one
    sequence-step: per layer and pass the larger of bytes over the HBM peak and
    operations over the bf16 peak. Re-forwards count in the measured time only."""
    one = kda_pass(cfg, seq)
    per_layer = max(one["fwd_bytes"] / peak["hbm_bytes_per_s"], one["fwd_flops"] / peak["bf16_flops_per_s"]) + max(
        one["bwd_bytes"] / peak["hbm_bytes_per_s"], one["bwd_flops"] / peak["bf16_flops_per_s"]
    )
    return per_layer * sum(KINDS[k][0] == "kda" for k in layer_kinds(cfg))


def even_share(cfg: dict) -> float:
    _, held, width = share(cfg)
    return held / width


def routed_flops(cfg: dict, seq: int, held_share: float | None = None) -> float:
    """Forward of ONE layer's routed experts on one sequence, HERE: the router's
    scores for every expert of the model, and ``seq x k x held_share`` rows through an expert."""
    part = even_share(cfg) if held_share is None else held_share
    rows = seq * cfg["num_experts_per_tok"] * part
    return 2.0 * (rows * expert_params(cfg) + seq * cfg["hidden_size"] * share(cfg)[2])


def attention_flops(cfg: dict, seq: int) -> float:
    """Forward + backward of ONE GQA layer's softmax attention (6 matmuls over the causal pairs)."""
    return 6 * 2.0 * seq * (seq + 1) // 2 * cfg["head_dim"] * cfg["num_attention_heads"]


def lora_step_flops(cfg: dict, seq: int, held_share: float | None = None) -> dict:
    """One local step on ONE sequence, base frozen: forward + dX through every
    frozen matrix (4·P·T), the routed experts over the rows this chip computes,
    the sliced head, adapter forward + dA + dB + dX (6·T·r·(in+out)), GQA
    attention and the delta rule forward + backward."""
    kinds = layer_kinds(cfg)
    rank = cfg["lora"]["rank"]
    kda = kda_pass(cfg, seq)
    base = 4.0 * seq * sum(i * o for k in kinds for _, i, o in dense_matrices(cfg, k))
    routed = 2.0 * routed_flops(cfg, seq, held_share) * len(kinds)
    head = 4.0 * cfg["hidden_size"] * cfg["vocab_size"] * seq
    adapters = 6.0 * seq * rank * sum(i + o for k in kinds for i, o in adapted(cfg, k))
    attention = attention_flops(cfg, seq) * sum(KINDS[k][0] == "gqa" for k in kinds)
    delta_rule = (kda["fwd_flops"] + kda["bwd_flops"]) * sum(KINDS[k][0] == "kda" for k in kinds)
    return {
        "base": base, "routed_experts": routed, "head": head, "adapters": adapters, "attention": attention,
        "delta_rule": delta_rule, "total": base + routed + head + adapters + attention + delta_rule,
    }


def gmm_pass(cfg: dict, seq: int, held_share: float | None = None) -> tuple[float, float]:
    """(operations, bytes) ONE pass over ONE layer's two grouped matmuls must
    take here (``flops_window_moe.gmm_pass``'s rule under this file's keys)."""
    part = even_share(cfg) if held_share is None else held_share
    d, f, rows = cfg["hidden_size"], cfg["moe_intermediate_size"], seq * cfg["num_experts_per_tok"] * part
    act = DTYPE_BYTES[cfg["compute_dtype"]]
    moved = bank_params(cfg) * DTYPE_BYTES[cfg["expert_dtype"]] + act * rows * (d + 2 * f + 2 * f + f + f + d)
    return 2.0 * rows * expert_params(cfg), float(moved)


def gmm_floor_seconds(cfg: dict, seq: int, peak: dict, held_share: float | None = None) -> float:
    """The least time over ALL the grouped matmuls of one sequence-step, forward + backward."""
    ops, moved = gmm_pass(cfg, seq, held_share)
    one = max(moved / peak["hbm_bytes_per_s"], ops / peak["bf16_flops_per_s"])
    return 2.0 * one * len(layer_kinds(cfg))


def hand_counts() -> list[tuple[str, object, object]]:
    """(what, the functions' answer, the count by hand) at the widths of
    ``configs/solar_open2_lora.json``, 4096 tokens."""
    from benchmark import flops

    cfg = json.loads((Path(__file__).resolve().parent / "configs" / "solar_open2_lora.json").read_text())
    peak = flops.peaks("TPU v5 lite")
    shared, router = 15_728_640, 4096 * 320 + 320
    kda_layer = 100_663_296 + 33_554_432 + 1_572_864 + 1_581_056 + 262_144 + 98_304 + 8_384 + shared + router + 8_192
    gqa_layer = 3 * 33_554_432 + 2 * 4_194_304 + shared + router + 8_192
    params = model_params(cfg)
    per_chunk = 8 * 64 * 64 * 128 + 6 * 64 * 128 * 128 + 64 ** 3 / 3.0
    kda = kda_pass(cfg, 4096)
    fwd_bytes = 3 * 2 * 4096 * 8192 + 4 * 4096 * 8192 + 4 * 4096 * 64 + 2 * 4096 * 8192
    step = lora_step_flops(cfg, 4096)
    ops, moved = gmm_pass(cfg, 4096)
    return [
        ("one routed expert", expert_params(cfg), 15_728_640),
        ("held bank a layer (40 experts)", bank_params(cfg), 40 * 15_728_640),
        ("KDA layer outside its bank", layer_params(cfg, "kda_experts"), 154_788_352),
        ("KDA layer, by part", kda_layer, 154_788_352),
        ("GQA layer outside its bank", layer_params(cfg, "full_experts"), 126_099_776),
        ("GQA layer, by part", gqa_layer, 126_099_776),
        ("float32 parameters", params["float32"], 791_795_520),
        ("bfloat16 parameters", params["bfloat16"], 2_516_582_400),
        ("adapter parameters", lora_params(cfg), 3 * 4 * 8 * (4096 + 8192) + 8 * (3 * 12_288 + 2 * 5120) + 4 * 3 * 8 * (4096 + 1280)),
        ("adapter parameters (the issue's count)", lora_params(cfg), 2_072_576),
        ("delta rule forward operations a layer (C = 64)", kda["fwd_flops"], per_chunk * 64 * 64),
        ("delta rule forward bytes a layer", kda["fwd_bytes"], float(fwd_bytes)),
        ("delta rule floor a step (bytes-bound)", kda_floor_seconds(cfg, 4096, peak), 3 * (fwd_bytes + 2 * fwd_bytes - 2 * 4096 * 8192 + 0.0) / 819e9),
        ("attention operations a step (one GQA layer)", step["attention"], 6 * 2.0 * 4096 * 4097 // 2 * 128 * 64),
        ("head operations a step", step["head"], 4.0 * 4096 * 24_576 * 4096),
        ("routed operations a step (even share)", step["routed_experts"], 4 * 2 * 2.0 * (4096 * 8 * 0.125 * 15_728_640 + 4096 * 4096 * 320)),
        ("grouped matmul operations a pass", ops, 2.0 * 4096 * 15_728_640),
        ("grouped matmul bytes a pass", moved, 2.0 * 629_145_600 + 2 * 4096 * (4096 + 6 * 1280 + 4096)),
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
