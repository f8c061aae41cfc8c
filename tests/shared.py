"""What more than one test file uses — test files import it from here, never
from one another:

- one tiny federation per SPMD engine of ``benchmark/engines`` (its layer
  pattern at a width of 64, four nodes, 128 tokens);
- :func:`draw`, the seeded perturbation every reference comparison starts from;
- the Trinity configuration (``benchmark/reference/afmoe_lm.py``) and the Solar
  one (``benchmark/reference/solar_open2_lm.py``) with their reference keys;
- :func:`run_module` and :func:`rehearse`: the benchmark's scripts in a fresh
  interpreter. A cell's ``rehearse`` case is the one place in tier-1 where its
  whole reference check runs unfaulted; :func:`rehearsal_cell` builds the cell
  in-process for the fault files, which run the comparisons one at a time.
"""

import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from benchmark import checks as ck
from benchmark import engines, run
from p2pfl_tpu.learning.dataset import FederatedDataset
from p2pfl_tpu.learning.lora import split_lora
from p2pfl_tpu.models.transformer import TransformerConfig, tiny_transformer
from p2pfl_tpu.parallel import SpmdFederation, SpmdLoraFederation

ROOT = Path(__file__).resolve().parent.parent


def run_module(module: str, *args: str, timeout: float) -> subprocess.CompletedProcess:
    """``python -m <module> <args>`` as the benchmark's command runs it: a fresh
    interpreter from the checkout, with the checkout on its path and the CPU.
    ``timeout``: a few times the run's measured seconds, so a hang costs one case."""
    return subprocess.run(
        [sys.executable, "-m", module, *args], cwd=ROOT, capture_output=True, text=True, timeout=timeout,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin", "PYTHONPATH": str(ROOT)},
    )


def rehearse(cell: str, timeout: float) -> str:
    """The output of ``benchmark.rehearse --workload <cell> --seconds 1`` (build,
    the whole reference check, warm-up and measure at the rehearsal's sizes),
    which must end with ``correct`` true."""
    done = run_module("benchmark.rehearse", "--workload", cell, "--seconds", "1", timeout=timeout)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    assert '"correct": true' in done.stdout and "rehearsal finished" in done.stdout
    return done.stdout


def rehearsal_cell(name: str):
    """``(job, engine, state, tokens)``: the cell built in-process as
    ``benchmark.rehearse`` builds it, and the first sequence of its first node."""
    _, cell, cfg, traffic = run.resolve(run.load_json(run.ROOT / "BENCHMARK.json"), name, rehearsal=True)
    job = run.Job(name, cell, cfg, traffic, 1, False, devices=jax.devices(), checks=ck.Checks(), say=lambda msg: None)
    engine = engines.load(cell["engine"])
    state = engine.build(job)
    return job, engine, state, jnp.asarray(state["shards"][0]["x"][:1])


# ---- one federation per engine ----------------------------------------------------


def mlp_federation():
    from p2pfl_tpu.models import mlp

    data = FederatedDataset.synthetic_mnist(n_train=256, n_test=64)
    return SpmdFederation.from_dataset(mlp(), data, n_nodes=4, batch_size=16, vote=False)


def _lm_federation(cfg):
    model = tiny_transformer(seq_len=128, cfg=cfg, attn="flash")  # interpret mode off-TPU
    data = FederatedDataset.synthetic_lm(vocab_size=256, seq_len=128, n_train=64, n_test=16)
    return SpmdLoraFederation.from_dataset(model, data, n_nodes=4, batch_size=2, vote=False, node_chunk=2)


def lora_federation():
    return _lm_federation(TransformerConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, ffn_hidden=128,
        lora_rank=4, lora_mlp=True, remat=True, scan_layers=True, remat_policy="mlp_qkv",
    ))


def hybrid_federation():
    return _lm_federation(TransformerConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=1, ffn_hidden=128, rope_theta=None,
        layer_pattern=("mamba", "attention"), ssm_dt_rank=8, lora_rank=4, lora_mlp=True, remat=True,
        scan_layers=True, remat_policy="ssm",
    ))


def expert_federation():
    return _lm_federation(TransformerConfig(
        vocab_size=256, dim=64, n_layers=3, n_heads=4, n_kv_heads=4, ffn_hidden=160, rope_theta=1e6,
        layer_pattern=("mla_dense", "mla_experts", "mla_experts"), lora_rank=4, lora_mlp=True, remat=True,
        scan_layers=True, norm_eps=1e-5, q_lora_rank=24, kv_lora_rank=16, qk_nope_dim=12, qk_rope_dim=4,
        v_head_dim=16, routed_experts=8, experts_per_token=2, expert_hidden=32, shared_experts=1,
        routed_scale=1.8, expert_tile_m=8,
    ))


def conv_expert_federation():
    return _lm_federation(TransformerConfig(
        vocab_size=256, dim=64, n_layers=6, n_heads=4, n_kv_heads=2, ffn_hidden=160, rope_theta=1e6,
        leading_pattern=("conv_dense", "conv_dense"), layer_pattern=("attention_experts", "conv_experts"), qk_norm=True,
        lora_rank=4, lora_mlp=True, remat=True, scan_layers=True, norm_eps=1e-5, routed_experts=8,
        experts_per_token=2, expert_hidden=32, expert_tile_m=8,
    ))


def window_expert_federation():
    return _lm_federation(TransformerConfig(
        vocab_size=256, dim=64, n_layers=6, n_heads=4, n_kv_heads=2, head_dim=32, ffn_hidden=160, rope_theta=1e4,
        leading_pattern=("swa_dense", "swa_dense"), layer_pattern=("swa_experts", "full_experts"), qk_norm=True,
        attn_window=32, attn_gate=True, post_norms=True, embed_scale=8.0, tie_head=False,
        lora_rank=4, lora_mlp=True, remat=True, scan_layers=True, norm_eps=1e-5, routed_experts=8, experts_held=4,
        first_expert=4, experts_per_token=2, expert_hidden=32, shared_experts=1, expert_tile_m=8,
    ))


def linear_expert_federation():
    return _lm_federation(TransformerConfig(
        vocab_size=256, dim=64, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=16, ffn_hidden=160, rope_theta=None,
        layer_pattern=("full_experts", "kda_experts", "kda_experts", "kda_experts"), attn_gate=True, tie_head=False,
        lora_rank=4, lora_mlp=True, remat=True, scan_layers=True, norm_eps=1e-5, routed_experts=8, experts_held=4,
        experts_per_token=2, expert_hidden=32, shared_experts=1, expert_tile_m=8, conv_taps=4, kda_chunk=32,
    ))


# ---- seeded weights ---------------------------------------------------------------


def draw(params, seed):
    """``lora_b`` perturbed (at its zero start every ``lora_a`` gradient is
    exactly zero), a router bias that changes choices, norm scales off one."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 512))

    def one(path, a):
        name = jax.tree_util.keystr(path)
        if "lora_b" in name:
            return 0.05 * jax.random.normal(next(keys), a.shape, a.dtype)
        if "router_bias" in name:
            return 0.02 * jax.random.normal(next(keys), a.shape, a.dtype)
        if "norm" in name:
            return 1.0 + 0.1 * jax.random.normal(next(keys), a.shape, a.dtype)
        return a

    return jax.tree_util.tree_map_with_path(one, params)


def perturbed_layer(layer, *args, seed=1):
    """(adapters, base) of ``layer`` initialised on ``args``, drawn as :func:`draw` does."""
    return split_lora(draw(layer.init(jax.random.PRNGKey(seed), *args)["params"], seed + 1))


def same_gradients(grads, want, tol=2e-4):
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(ref))) > 0, jax.tree_util.keystr(path)  # every adapter is reached
        assert ck.rel_l2(got, ref) < tol, jax.tree_util.keystr(path)


# ---- Trinity: sliding and full attention, a held share (``afmoe_lm``) -------------

TRINITY_SEQ = 48
TRINITY_WINDOW = 16
TRINITY_LEADING = ("swa_dense", "swa_dense")
TRINITY_PATTERN = ("swa_experts", "full_experts", "swa_experts", "swa_experts")
# the reference reads Hugging Face's keys: two dense sliding layers, then two periods s f s s;
# 4 heads of 32 on a residual of 64 (heads x head_dim = 2 x hidden, as published); experts 2..5 of 8 held
TRINITY_REF = {
    "hidden_size": 64, "intermediate_size": 160, "moe_intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "rms_norm_eps": 1e-5, "rope_theta": 1e4, "sliding_window": TRINITY_WINDOW,
    "layer_types": ["sliding_attention"] * 2 + ["sliding_attention", "full_attention", "sliding_attention", "sliding_attention"] * 2,
    "num_hidden_layers": 10, "num_dense_layers": 2, "num_experts": 4, "num_experts_per_tok": 2, "num_shared_experts": 1,
    "route_scale": 2.826, "mup_enabled": True, "tie_word_embeddings": False, "vocab_size": 256,
    "share": {"first_expert": 2, "router_experts": 8},
}


def trinity_config(**kw):
    base = dict(
        vocab_size=256, dim=64, n_layers=10, n_heads=4, n_kv_heads=2, head_dim=32, ffn_hidden=160, rope_theta=1e4,
        leading_pattern=TRINITY_LEADING, layer_pattern=TRINITY_PATTERN, qk_norm=True, attn_window=TRINITY_WINDOW,
        attn_gate=True, post_norms=True, embed_scale=8.0, tie_head=False, lora_rank=4, lora_alpha=8.0, lora_mlp=True,
        dtype=jnp.float32, remat=True, scan_layers=True, remat_policy=None, norm_eps=1e-5, routed_experts=8,
        experts_held=4, first_expert=2, experts_per_token=2, expert_hidden=32, shared_experts=1, routed_scale=2.826,
        expert_tile_m=8,
    )
    base.update(kw)
    return TransformerConfig(**base)


def trinity_seeded(cfg, seed=0, attn="dense"):
    model = tiny_transformer(seq_len=TRINITY_SEQ, seed=seed, cfg=cfg, attn=attn)
    model.params = draw(model.params, seed + 1)
    return (model, *split_lora(model.params))


# ---- Solar: the delta rule 3 : 1 with gated NoPE GQA (``solar_open2_lm``) ---------

SOLAR_SEQ = 40  # not a whole number of chunks: the op pads
SOLAR_PATTERN = ("full_experts", "kda_experts", "kda_experts", "kda_experts")
# Hugging Face's keys: one period GQA, KDA, KDA, KDA; 4 heads of 16 each kind on a residual of 64; experts 2..5 of 8
SOLAR_REF = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "rms_norm_eps": 1e-5,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4, "num_kv_heads": None},
    "kda_allow_neg_eigval": True, "gqa_layers": [0], "num_hidden_layers": 4, "first_k_dense_replace": 0,
    "moe_intermediate_size": 32, "n_routed_experts": 4, "num_experts_per_tok": 2, "n_shared_experts": 1,
    "routed_scaling_factor": 1.0, "vocab_size": 256, "share": {"first_expert": 2, "router_experts": 8},
}


def solar_config(**kw):
    base = dict(
        vocab_size=256, dim=64, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=16, ffn_hidden=160, rope_theta=None,
        layer_pattern=SOLAR_PATTERN, attn_gate=True, tie_head=False, lora_rank=4, lora_alpha=8.0, lora_mlp=True,
        dtype=jnp.float32, remat=True, scan_layers=True, norm_eps=1e-5, routed_experts=8, experts_held=4,
        first_expert=2, experts_per_token=2, expert_hidden=32, shared_experts=1, routed_scale=1.0, expert_tile_m=8,
        conv_taps=4, kda_chunk=16,
    )
    base.update(kw)
    return TransformerConfig(**base)


def _decay_up(params, seed):
    """``A_log`` moved so that decays span weak to strong (a head's rate 1..16 at init)."""
    key = jax.random.PRNGKey(seed)
    return jax.tree_util.tree_map_with_path(
        lambda p, a: a + jax.random.uniform(key, a.shape, a.dtype, -1.0, 2.0) if "A_log" in jax.tree_util.keystr(p) else a,
        params,
    )


@pytest.fixture(scope="module")
def solar():
    model = tiny_transformer(seq_len=SOLAR_SEQ, seed=0, cfg=solar_config())
    model.params = _decay_up(draw(model.params, 1), 2)
    return (model, *split_lora(model.params))


def solar_batch(seed=0, n=2):
    x = jax.random.randint(jax.random.PRNGKey(seed), (n, SOLAR_SEQ + 1), 0, 256)
    return x[:, :-1], x[:, 1:]
