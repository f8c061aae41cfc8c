"""A period of unlike layers (``TransformerConfig.layer_pattern``: Mamba-1 and
attention) through ``CausalLM`` and ``SpmdLoraFederation``, against the plain
reference ``benchmark/reference/jamba_lm.py`` on seeded weights — and the guard
that the default pattern still compiles the parent's round."""

import copy
import json
import re
import subprocess
import sys
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import checks as ck
from benchmark.reference import fedavg, jamba_lm
from p2pfl_tpu.learning.dataset import FederatedDataset
from p2pfl_tpu.learning.lora import _lm_loss, split_lora
from p2pfl_tpu.models.transformer import CausalLM, TransformerConfig, layer_runs, tiny_transformer
from p2pfl_tpu.parallel import SpmdLoraFederation
from p2pfl_tpu.parallel.spmd import draw_node_perms

ROOT = Path(__file__).resolve().parent.parent
PATTERN = ("mamba", "mamba", "attention", "mamba")
SEQ = 80  # two scan chunks (the op's default is 64 steps), the second padded
# the reference reads Hugging Face's keys
REF = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 1, "head_dim": 16, "intermediate_size": 128,
    "mamba_expand": 2, "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_dt_rank": 8, "rms_norm_eps": 1e-6,
    "attn_layer_period": 4, "attn_layer_offset": 2, "num_hidden_layers": 8, "vocab_size": 256,
}


def config(**kw):
    base = dict(
        vocab_size=256, dim=64, n_layers=8, n_heads=4, n_kv_heads=1, ffn_hidden=128, rope_theta=None,
        layer_pattern=PATTERN, ssm_dt_rank=8, lora_rank=4, lora_alpha=8.0, lora_mlp=True, dtype=jnp.float32,
        remat=True, scan_layers=True, remat_policy="mlp_ssm",
    )
    base.update(kw)
    return TransformerConfig(**base)


def seeded(cfg, seed=0):
    """Seeded weights with lora_b perturbed: at its zero start every lora_a
    gradient is exactly zero and half of each adapter would go unchecked."""
    model = tiny_transformer(seq_len=SEQ, seed=seed, cfg=cfg)
    lora, base = split_lora(model.params)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    lora = jax.tree_util.tree_map_with_path(
        lambda p, a: 0.05 * jax.random.normal(next(keys), a.shape, a.dtype) if "lora_b" in jax.tree_util.keystr(p) else a,
        lora,
    )
    return model, lora, base


def batch(seed=0, n=2):
    x = jax.random.randint(jax.random.PRNGKey(seed), (n, SEQ + 1), 0, 256)
    return x[:, :-1], x[:, 1:]


@pytest.fixture(scope="module")
def hybrid():
    return seeded(config())


def test_layer_runs_of_the_jamba_period():
    jamba = tuple("attention" if i == 7 else "mamba" for i in range(14))
    assert layer_runs(jamba) == [("mamba", 7), ("attention", 1), ("mamba", 6)]
    assert layer_runs(("attention",)) == [("attention", 1)]
    assert jamba_lm.layer_kinds(dict(REF, attn_layer_period=14, attn_layer_offset=7, num_hidden_layers=14)) == list(jamba)


def test_loss_and_every_adapter_gradient_match_the_reference(hybrid):
    model, lora, base = hybrid
    x, y = batch()
    (loss, _), grads = jax.value_and_grad(_lm_loss, has_aux=True)(lora, base, model.module, x, y)
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(jamba_lm.loss)(lora, base, x, y, REF, lora_scale=2.0)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(ref))) > 0, jax.tree_util.keystr(path)  # every adapter is reached
        assert ck.rel_l2(got, ref) < 1e-4, jax.tree_util.keystr(path)


def test_bfloat16_compute_meets_the_benchmarks_tolerances():
    """The cell's comparison at toy size: bfloat16 matmuls, float32 scan state,
    against the float32 reference, under ``checks.py``'s constants."""
    model, lora, base = seeded(config(dtype=jnp.bfloat16))
    x, y = batch()
    (loss, _), grads = jax.value_and_grad(_lm_loss, has_aux=True)(lora, base, model.module, x, y)
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(jamba_lm.loss)(lora, base, x, y, REF, lora_scale=2.0)
    assert abs(float(loss) - float(want_loss)) <= ck.LOSS_REL * float(want_loss)
    assert ck.cosine(grads, want) >= ck.GRAD_COS and ck.rel_l2(grads, want) <= ck.GRAD_REL


def test_one_federated_round_matches_reference_trained_nodes(hybrid):
    model, _, _ = hybrid
    lora, base = split_lora(model.params)
    data = FederatedDataset.synthetic_lm(vocab_size=256, seq_len=SEQ, n_train=16, n_test=4)
    fed = SpmdLoraFederation.from_dataset(model, data, n_nodes=2, batch_size=2, vote=False, seed=0, node_chunk=1)
    x_all, y_all = np.asarray(fed.x_all), np.asarray(fed.y_all)
    start = jax.tree.map(np.asarray, lora)
    # the batches the round is about to draw, from a copy of the federation's stream
    perm = draw_node_perms(copy.deepcopy(fed._rng), fed._sizes, fed._nb, fed.batch_size, 1)
    fed.run_round(epochs=1)
    got = jax.tree.map(lambda a: np.asarray(a[0]), fed.params)
    assert all(np.array_equal(np.asarray(leaf[0]), np.asarray(leaf[1])) for leaf in jax.tree.leaves(fed.params))

    grad = jax.jit(lambda lo, b, x, y: jax.value_and_grad(jamba_lm.loss)(lo, b, x, y, REF, lora_scale=2.0))
    step = fedavg.adam_step(grad)
    trained = []
    with jax.default_matmul_precision("highest"):
        for node in range(2):
            batches = [(base, jnp.asarray(x_all[node][i]), jnp.asarray(y_all[node][i])) for i in perm[node, 0]]
            out, _ = fedavg.adam_train(lora, batches, step, {"name": "adam", "schedule": "constant", "learning_rate": 1e-3})
            trained.append(jax.tree.map(np.asarray, out))
    want = fedavg.weighted_mean(trained, [x_all.shape[1]] * 2)
    assert ck.cosine(ck.tree_sub(got, start), ck.tree_sub(want, start)) > 0.999


def test_split_lora_leaves_the_state_space_leaves_in_the_base(hybrid):
    model, _, _ = hybrid
    lora, base = split_lora(model.params)
    names = {jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(base)}
    for leaf in ("A_log", "D", "conv_kernel", "conv_bias", "dt_bias", "dt_norm", "b_norm", "c_norm", "dt_proj"):
        assert any(leaf in n for n in names), leaf
    lora_names = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(lora)]
    assert lora_names and all("lora_" in n for n in lora_names)
    assert not any("dt_proj" in n for n in lora_names)
    for proj in ("in_proj", "x_proj", "out_proj", "wq", "wk", "wv", "wo", "w1", "w2", "w3"):
        assert any(f"'{proj}'" in n for n in lora_names), proj


def test_mamba_initialisation_is_mambas_own(hybrid):
    model, _, _ = hybrid
    mamba = model.params["layers"]["run0_mamba"]["block"]["mamba"]
    np.testing.assert_allclose(np.exp(np.asarray(mamba["A_log"]))[0, 0, 0], np.arange(1, 17), rtol=1e-6)
    assert np.all(np.asarray(mamba["D"]) == 1.0)
    dt = np.log1p(np.exp(np.asarray(mamba["dt_bias"])))  # softplus
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 1e-1 * 1.001 and dt.std() > 0.01


def unrolled_from(cfg, params):
    """The same weights laid out for ``scan_layers=False``: ``layer_<i>``."""
    periods = cfg.n_layers // len(cfg.layer_pattern)
    out, i = {k: v for k, v in params.items() if k != "layers"}, 0
    for p in range(periods):
        for r, (kind, count) in enumerate(layer_runs(cfg.layer_pattern)):
            tree = params["layers"][f"run{r}_{kind}"]
            for j in range(count):
                out[f"layer_{i}"] = (
                    jax.tree.map(lambda a: a[p, j], tree["block"]) if count > 1 else jax.tree.map(lambda a: a[p], tree)
                )
                i += 1
    return out


@pytest.mark.parametrize("policy", [None, "ssm", "mlp_ssm", "mlp_ssm_in"])
def test_period_scan_equals_the_unrolled_stack(hybrid, policy):
    model, lora, base = hybrid
    x, y = batch(seed=2)
    cfg = config(remat_policy=policy)
    flat = config(scan_layers=False, remat=False, remat_policy=None)
    lora_u, base_u = unrolled_from(cfg, lora), unrolled_from(cfg, base)
    (l_s, _), g_s = jax.value_and_grad(_lm_loss, has_aux=True)(lora, base, CausalLM(cfg), x, y)
    (l_u, _), g_u = jax.value_and_grad(_lm_loss, has_aux=True)(lora_u, base_u, CausalLM(flat), x, y)
    assert float(l_s) == pytest.approx(float(l_u), rel=1e-6)
    assert ck.rel_l2(unrolled_from(cfg, g_s), g_u) < 1e-5


def test_saved_scan_output_means_no_scan_in_the_reforward():
    """Under the ``ssm`` policies remat keeps the op's output and boundary
    states by name, so the backward re-runs the projections but no scan: the
    gradient program holds fewer loops than under full per-block remat."""
    x, y = batch()

    def loops(policy):
        cfg = config(remat_policy=policy, n_layers=4)
        model, lora, base = seeded(cfg)
        text = jax.jit(jax.grad(lambda lo: _lm_loss(lo, base, model.module, x, y)[0])).lower(lora).as_text()
        return len(re.findall(r"stablehlo\.while\b", text))

    assert loops("ssm") < loops(None)


def test_pattern_must_divide_depth_and_name_known_kinds():
    with pytest.raises(ValueError, match="periods"):
        config(n_layers=6)
    with pytest.raises(ValueError, match="layer_pattern"):
        config(layer_pattern=("mamba", "rwkv"))
    assert hash(config(layer_pattern=list(PATTERN))) == hash(config())  # a list is made a tuple


def test_no_rotation_without_rope_theta():
    cfg = config(layer_pattern=("attention",), n_layers=2, scan_layers=False, remat=False, remat_policy=None)
    model = tiny_transformer(seq_len=SEQ, cfg=cfg)
    text = jax.jit(lambda p, x: model.module.apply({"params": p}, x)).lower(model.params, batch()[0]).as_text()
    assert "stablehlo.cosine" not in text and "stablehlo.sine" not in text


def test_default_pattern_keeps_the_parents_round():
    """The guard for the dense LoRA cells: parameter tree paths and the lowered
    round's operation counts of a default-pattern ``scan_layers`` LoRA model
    equal what the parent commit gave (recorded before ``transformer.py`` was
    edited: ``tests/fixtures/lora_round_parent.json``; its ``note`` names the
    three counts that moved when the not-kept optimizer state left the round)."""
    want = json.loads((ROOT / "tests" / "fixtures" / "lora_round_parent.json").read_text())
    cfg = TransformerConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, ffn_hidden=128,
        lora_rank=4, lora_mlp=True, remat=True, scan_layers=True, remat_policy="mlp_qkv",
    )
    model = tiny_transformer(seq_len=128, cfg=cfg, attn="flash")
    data = FederatedDataset.synthetic_lm(vocab_size=256, seq_len=128, n_train=64, n_test=16)
    fed = SpmdLoraFederation.from_dataset(model, data, n_nodes=4, batch_size=2, vote=False, node_chunk=2)
    text = fed.lower_round(epochs=1).as_text()
    ops = Counter(re.findall(r"\b(stablehlo\.[a-z_]+|func\.call|sdy\.[a-z_]+)\b", text))
    paths = sorted(
        jax.tree_util.keystr(p) + str(tuple(leaf.shape)) for p, leaf in jax.tree_util.tree_leaves_with_path(model.params)
    )
    assert paths == want["paths"]
    for op in ("stablehlo.dot_general", "stablehlo.while", "stablehlo.custom_call", "stablehlo.all_reduce"):
        assert ops.get(op, 0) == want["ops"].get(op, 0), op
    assert dict(sorted(ops.items())) == want["ops"]


def test_moe_under_the_layer_scan_is_still_refused():
    cfg = config(n_experts=4, remat_policy=None)
    with pytest.raises(NotImplementedError, match="period scan"):
        CausalLM(cfg).init(jax.random.PRNGKey(0), batch()[0])


@pytest.mark.parametrize("module", ["benchmark.selfcheck", "benchmark.rehearse"])
def test_benchmark_files_resolve_and_the_cell_rehearses(module):
    args = [] if module.endswith("selfcheck") else ["--workload", "jamba_silo4_seq4096", "--seconds", "1"]
    done = subprocess.run(
        [sys.executable, "-m", module, *args], cwd=ROOT, capture_output=True, text=True, timeout=900,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin", "PYTHONPATH": str(ROOT)},
    )
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    if module.endswith("rehearse"):
        assert '"correct": true' in done.stdout and "rehearsal finished" in done.stdout
