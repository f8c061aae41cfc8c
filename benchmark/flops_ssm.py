"""Operations and bytes of the hybrid state-space / attention LM (Jamba's
block stack) under LoRA, from shapes alone — beside ``flops.py``, whose
conventions hold: multiply-adds x 2 of matrix multiplications only, plus the
selective scan, which is the one part of a Mamba layer that is no matmul and
is counted on its own. Nothing recomputed counts.

Shapes come from the configuration file's own keys (Hugging Face names).
"""

from __future__ import annotations

from benchmark import flops
from benchmark.reference.jamba_lm import layer_kinds  # noqa: F401 (the one rule for the order of the layer kinds)

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def inner_width(cfg: dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def mlp_matrices(cfg: dict) -> list[tuple[str, int, int]]:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    return [("w1", d, f), ("w3", d, f), ("w2", f, d)]


def mamba_matrices(cfg: dict) -> list[tuple[str, int, int]]:
    """(name, in, out) of a Mamba mixer's four projection matrices."""
    d, e, n, r = cfg["hidden_size"], inner_width(cfg), cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    return [("in_proj", d, 2 * e), ("x_proj", e, r + 2 * n), ("dt_proj", r, e), ("out_proj", e, d)]


def attention_matrices(cfg: dict) -> list[tuple[str, int, int]]:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wo", q, d)]


def layer_matrices(cfg: dict, kind: str) -> list[tuple[str, int, int]]:
    mixer = mamba_matrices(cfg) if kind == "mamba" else attention_matrices(cfg)
    return mixer + mlp_matrices(cfg)


def layer_params(cfg: dict, kind: str) -> int:
    """Every parameter of one layer: matrices, and the leaves that are none."""
    d, e, n, r = cfg["hidden_size"], inner_width(cfg), cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    total = sum(i * o for _, i, o in layer_matrices(cfg, kind)) + 2 * d  # two norms
    if kind == "mamba":
        total += e * cfg["mamba_d_conv"] + e + e + e * n + e + (r + 2 * n)  # conv + its bias, dt bias, A_log, D, inner norms
    return total


def model_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    return sum(layer_params(cfg, k) for k in layer_kinds(cfg)) + cfg["vocab_size"] * d + d


def adapted(cfg: dict, kind: str) -> list[tuple[int, int]]:
    targets = cfg["lora"]["targets"]
    return [(i, o) for name, i, o in layer_matrices(cfg, kind) if name in targets]


def lora_params(cfg: dict) -> int:
    rank = cfg["lora"]["rank"]
    return rank * sum(i + o for k in layer_kinds(cfg) for i, o in adapted(cfg, k))


def scan_flops(cfg: dict, seq: int) -> tuple[float, float]:
    """(forward, backward) of ONE layer's selective scan on one sequence. Per
    token, channel and state element the forward needs 9 operations: Δ·A, exp,
    Δ·u·B (2), the decay's multiply and the add, h·C and its add into y, and a
    ninth for the skip and the gate spread over the state; the backward is
    counted as twice the forward, the matmul convention."""
    fwd = 9.0 * seq * inner_width(cfg) * cfg["mamba_d_state"]
    return fwd, 2.0 * fwd


def scan_min_bytes(cfg: dict, seq: int) -> tuple[float, float]:
    """(forward, backward) bytes ONE layer's scan must move for one sequence,
    whatever implements it: forward reads u, Δ, z, B, C, A, D once and writes y
    once; backward reads those and dy and writes du, dΔ, dz, dB, dC once (dA,
    dD: one write each). Activations in the compute dtype, Δ, A, D in the
    parameter dtype (the step sizes leave softplus in float32)."""
    e, n = inner_width(cfg), cfg["mamba_d_state"]
    act, par = DTYPE_BYTES[cfg["compute_dtype"]], DTYPE_BYTES[cfg["param_dtype"]]
    wide, narrow, fixed = seq * e, seq * n, e * n + e
    fwd = wide * (act + par + act) + 2 * narrow * act + fixed * par + wide * act
    bwd = fwd + wide * (act + par + act) + 2 * narrow * act + fixed * par
    return float(fwd), float(bwd)


def lora_step_flops(cfg: dict, seq: int) -> dict:
    """One local step on ONE sequence of ``seq`` tokens, base frozen: forward
    + dX through every frozen matrix (4·P·T), the tied head likewise, adapter
    forward + dA + dB + dX (6·T·r·(in+out)), the attention layers' attention
    forward + backward, and the Mamba layers' scans. ``total`` adds them up."""
    kinds = layer_kinds(cfg)
    rank = cfg["lora"]["rank"]
    base = 4.0 * seq * sum(i * o for k in kinds for _, i, o in layer_matrices(cfg, k))
    head = 4.0 * cfg["hidden_size"] * cfg["vocab_size"] * seq
    adapters = 6.0 * seq * rank * sum(i + o for k in kinds for i, o in adapted(cfg, k))
    fwd, bwd = flops.causal_attention_flops(seq, cfg["num_attention_heads"], cfg["head_dim"])
    attention = (fwd + bwd) * kinds.count("attention")
    scan = sum(scan_flops(cfg, seq)) * kinds.count("mamba")
    return {
        "base": base, "head": head, "adapters": adapters, "attention": attention, "scan": scan,
        "total": base + head + adapters + attention + scan,
    }


def scan_floor_seconds(cfg: dict, seq: int, peak: dict) -> float:
    """The least time the chip could take over ALL the scans of one
    sequence-step (every Mamba layer, forward + backward): the larger of bytes
    over the HBM peak and operations over the bf16 peak."""
    layers = layer_kinds(cfg).count("mamba")
    by_bytes = sum(scan_min_bytes(cfg, seq)) / peak["hbm_bytes_per_s"]
    by_flops = sum(scan_flops(cfg, seq)) / peak["bf16_flops_per_s"]
    return layers * max(by_bytes, by_flops)
