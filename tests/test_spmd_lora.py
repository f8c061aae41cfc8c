"""SPMD LoRA federation + TP sharding rules tests."""

import jax
import pytest
import jax.numpy as jnp
import numpy as np

from p2pfl_tpu.learning.dataset import FederatedDataset
from p2pfl_tpu.models.transformer import TransformerConfig, tiny_transformer
from p2pfl_tpu.parallel import SpmdLoraFederation

CFG = TransformerConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, ffn_hidden=128)


def _data():
    return FederatedDataset.synthetic_lm(vocab_size=CFG.vocab_size, seq_len=32, n_train=512, n_test=64)


@pytest.mark.slow
def test_spmd_lora_learns_and_diffuses():
    # wider adapters + higher lr: the frozen base is random (not pretrained),
    # so the adapters carry all the learning in this test
    cfg = TransformerConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        ffn_hidden=128, lora_rank=16, lora_mlp=True,
    )
    model = tiny_transformer(seq_len=32, cfg=cfg)
    fed = SpmdLoraFederation.from_dataset(
        model, _data(), n_nodes=4, batch_size=8, vote=False, learning_rate=1e-2
    )
    before = fed.evaluate()["test_acc"]
    fed.run(rounds=4, epochs=1)
    after = fed.evaluate()["test_acc"]
    assert after > max(before, 0.1)
    # all nodes hold the same adapters after diffusion
    a = jax.tree.leaves(fed.node_params(0))
    b = jax.tree.leaves(fed.node_params(3))
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x, np.float32), np.asarray(y, np.float32), atol=1e-6)


def test_spmd_lora_state_is_adapters_only():
    model = tiny_transformer(seq_len=32, cfg=CFG)
    fed = SpmdLoraFederation.from_dataset(model, _data(), n_nodes=4, batch_size=8, vote=False)
    stacked = sum(x.size for x in jax.tree.leaves(fed.params))
    base = sum(x.size for x in jax.tree.leaves(fed.base))
    full = sum(x.size for x in jax.tree.leaves(model.params))
    assert stacked == 4 * (full - base)  # adapters only, stacked N times
    assert stacked < base  # federation state is smaller than one base model


@pytest.mark.slow
def test_tp_sharding_rules():
    from p2pfl_tpu.parallel.mesh import federation_mesh
    from p2pfl_tpu.parallel.sharding import partition_spec_for, transformer_shardings
    from jax.sharding import PartitionSpec as P

    assert partition_spec_for("layer_0/attn/wq/kernel") == P(None, "model")
    assert partition_spec_for("layer_0/attn/wo/kernel") == P("model", None)
    assert partition_spec_for("layer_1/mlp/w2/kernel") == P("model", None)
    assert partition_spec_for("layer_0/attn/wq/lora_a") == P()
    assert partition_spec_for("final_norm/scale") == P()

    mesh = federation_mesh(model_parallel=4, devices=jax.devices()[:4])
    model = tiny_transformer(seq_len=16, cfg=CFG)
    shardings = transformer_shardings(mesh, model.params)
    wq = shardings["layer_0"]["attn"]["wq"]["kernel"]
    assert wq.spec == P(None, "model")


def test_tp_sharded_forward_matches_replicated():
    """Forward pass with TP-sharded base == replicated base."""
    from p2pfl_tpu.parallel.mesh import federation_mesh
    from p2pfl_tpu.parallel.sharding import shard_transformer

    mesh = federation_mesh(model_parallel=4, devices=jax.devices()[:4])
    model = tiny_transformer(seq_len=16, cfg=CFG)
    toks = jnp.arange(16, dtype=jnp.int32)[None] % CFG.vocab_size
    want = model.apply(model.params, toks)
    sharded = shard_transformer(mesh, model.params)
    got = jax.jit(lambda p, t: model.module.apply({"params": p}, t))(sharded, toks)
    # bf16 matmuls accumulate in a different order when sharded
    np.testing.assert_allclose(np.asarray(want), np.asarray(got), atol=2e-2)


@pytest.mark.slow
def test_lora_fused_matches_sequential():
    """run_fused(R) must produce the same adapters as R run_round calls
    with the same seed (one dispatch vs R dispatches)."""
    import numpy as np

    from p2pfl_tpu.learning.dataset import FederatedDataset
    from p2pfl_tpu.models.transformer import TransformerConfig, tiny_transformer
    from p2pfl_tpu.parallel import SpmdLoraFederation

    cfg = TransformerConfig(vocab_size=64, dim=32, n_layers=1, n_heads=2, n_kv_heads=2, ffn_hidden=64)
    data = FederatedDataset.synthetic_lm(vocab_size=64, seq_len=16, n_train=4 * 32, n_test=16)

    def build():
        return SpmdLoraFederation.from_dataset(
            tiny_transformer(seq_len=16, cfg=cfg), data, n_nodes=4,
            batch_size=8, vote=False, seed=5,
        )

    seq = build()
    for _ in range(3):
        seq.run_round(epochs=1)
    fused = build()
    fused.run_fused(3, epochs=1)

    for a, b in zip(jax.tree.leaves(seq.params), jax.tree.leaves(fused.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    assert fused.round == 3


def test_node_chunk_matches_unchunked():
    """``node_chunk`` reorders the node axis from one vmap into a scan of
    vmapped chunks — identical round results, and a non-dividing chunk
    size is rejected."""
    a, b = _small(False, node_chunk=0), _small(False, node_chunk=2)
    ea, eb = a.run_round(epochs=1), b.run_round(epochs=1)
    assert float(ea["train_loss"]) == pytest.approx(float(eb["train_loss"]), abs=1e-6)
    for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-6)

    bad = _small(False, node_chunk=3)
    with pytest.raises(ValueError, match="node_chunk"):
        bad.run_round(epochs=1)


# --- keep_opt_state: what a round carries --------------------------------


def _small(keep, n_nodes=4, node_chunk=2):
    cfg = TransformerConfig(
        vocab_size=64, dim=32, n_layers=2, n_heads=2, n_kv_heads=2, ffn_hidden=64, lora_rank=2, remat=True,
        scan_layers=True,
    )
    data = FederatedDataset.synthetic_lm(vocab_size=64, seq_len=16, n_train=8 * n_nodes, n_test=16)
    return SpmdLoraFederation.from_dataset(
        tiny_transformer(seq_len=16, seed=0, cfg=cfg), data, n_nodes=n_nodes, batch_size=4, vote=False, seed=3,
        node_chunk=node_chunk, keep_opt_state=keep,
    )


def _bit_equal(a, b) -> bool:
    return all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def test_fresh_adam_in_the_node_is_fresh_adam_from_outside():
    """Round 1 starts from zero moments either way, so kept and not kept
    agree to the bit; from round 2 on the kept moments differ from zeros."""
    kept, fresh = _small(True), _small(False)
    assert fresh.opt_state is None and kept.opt_state is not None
    ek, ef = kept.run_round(epochs=1), fresh.run_round(epochs=1)
    assert fresh.opt_state is None
    assert float(ek["train_loss"]) == float(ef["train_loss"])
    assert _bit_equal(kept.params, fresh.params)
    kept.run_round(epochs=1), fresh.run_round(epochs=1)
    assert not _bit_equal(kept.params, fresh.params)


def test_not_kept_fused_rounds_match_sequential_rounds():
    seq, fused = _small(False), _small(False)
    losses = [float(seq.run_round(epochs=1)["train_loss"]) for _ in range(2)]
    entries = fused.run_fused(2, epochs=1)
    assert fused.opt_state is None and fused.round == 2
    np.testing.assert_allclose([float(e["train_loss"]) for e in entries], losses, atol=1e-6)
    for a, b in zip(jax.tree.leaves(seq.params), jax.tree.leaves(fused.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def _main_signature(lowered):
    """(operand types, result types) of the lowered module's entry function."""
    import re

    head = re.search(r"func\.func public @main\((.*?)\) -> \((.*?)\) \{", lowered.as_text(), re.S)
    tensor = re.compile(r"tensor<([0-9x]*)x?([a-z]+[0-9]+)>")
    return tensor.findall(head.group(1)), tensor.findall(head.group(2))


def test_not_kept_round_has_no_node_wide_optimizer_operand_or_result():
    """The only ``[N, ...]`` float arrays that enter or leave the round's
    program are the adapters — and an optimizer tree in second place (how
    ``benchmark/compile_check.py`` calls it) is pruned: the same program."""
    from p2pfl_tpu.parallel.spmd_lora import spmd_lora_round

    n = 6  # no other dimension of the model, the data or the optimizer is 6
    fed = _small(False, n_nodes=n)
    args, statics = fed._round_call(1)
    lowered = spmd_lora_round.lower(*args, **statics)

    def node_wide(types):  # float, rank two or more (the mask and the weights are [N] floats)
        return [t for t in types if t[0].startswith(f"{n}x") and t[0].count("x") > 1 and t[1].startswith(("f", "bf"))]

    operands, results = _main_signature(lowered)
    leaves = len(jax.tree.leaves(fed.params))
    assert len(node_wide(operands)) == leaves and len(node_wide(results)) == leaves
    assert len(results) == leaves + 1  # and the loss

    opt = jax.vmap(fed.tx.init)(fed.params)
    with_tree = spmd_lora_round.lower(args[0], opt, *args[2:], **statics)
    assert with_tree.as_text() == lowered.as_text()

    kept = _small(True, n_nodes=n)
    k_args, k_statics = kept._round_call(1)
    k_operands, k_results = _main_signature(spmd_lora_round.lower(*k_args, **k_statics))
    assert len(node_wide(k_operands)) == len(node_wide(k_results)) == 3 * leaves  # adapters + two moments


def test_kept_round_is_the_program_it_was():
    """With ``keep_opt_state=True`` the round traces to the program of the
    commit before the optimizer state left the not-kept round (ba2aefb: 939
    equations, nested ones counted, primitives with their result types hashing
    to 12552ce8600d1968) but for its loss: recorded again at PR 32, when
    ``_lm_forward`` took the cross-entropy from ``ops/head_loss.py`` — 901
    equations, bdd0d24d90a122cb; operands and results as they were."""
    import hashlib

    from p2pfl_tpu.parallel.spmd_lora import spmd_lora_round

    def walk(jaxpr, out):
        for eqn in jaxpr.eqns:
            out.append(eqn.primitive.name + ":" + ",".join(str(v.aval) for v in eqn.outvars))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, out)
        return out

    args, statics = _small(True)._round_call(1)
    closed = jax.make_jaxpr(lambda *a: spmd_lora_round(*a, **statics))(*args)
    eqns = walk(closed.jaxpr, [])
    assert len(eqns) == 901
    assert hashlib.sha256("\n".join(eqns).encode()).hexdigest()[:16] == "bdd0d24d90a122cb"
    assert (len(closed.jaxpr.invars), len(closed.jaxpr.outvars)) == (42, 26)


def test_recovery_after_a_consumed_donation_restages_without_optimizer_state():
    fed = _small(False)
    for leaf in jax.tree.leaves(fed.params):
        leaf.delete()
    fed._recover_donated_state()
    assert fed.opt_state is None
    fed.run_round(epochs=1)
    assert np.isfinite(float(fed.history[-1]["train_loss"]))


def test_a_model_that_sows_no_statistics_adds_nothing_to_the_round():
    """The round returns the model's sown statistics in fourth place: an empty
    tree for a dense model — no result, no history key, the program it was."""
    from p2pfl_tpu.parallel.spmd_lora import spmd_lora_round

    fed = _small(False)
    args, statics = fed._round_call(1)
    out = jax.eval_shape(lambda *a: spmd_lora_round(*a, **statics), *args)
    assert len(out) == 4 and out[3] == {} and out[1] is None
    entry = fed.run_round(epochs=1)
    assert sorted(entry) == ["round", "train_loss"]


def test_an_expert_models_round_carries_its_load_statistic_without_a_fetch():
    cfg = TransformerConfig(
        vocab_size=256, dim=64, n_layers=3, n_heads=4, n_kv_heads=4, ffn_hidden=160, rope_theta=1e6,
        layer_pattern=("mla_dense", "mla_experts", "mla_experts"), lora_rank=4, lora_mlp=True, remat=True,
        scan_layers=True, norm_eps=1e-5, q_lora_rank=24, kv_lora_rank=16, qk_nope_dim=12, qk_rope_dim=4,
        v_head_dim=16, routed_experts=8, experts_per_token=2, expert_hidden=32, shared_experts=1,
        routed_scale=1.8, expert_tile_m=8,
    )
    model = tiny_transformer(seq_len=32, cfg=cfg)
    fed = SpmdLoraFederation.from_dataset(model, _data(), n_nodes=4, batch_size=8, vote=False, node_chunk=2)
    first, second = fed.run_round(epochs=1), fed.run_round(epochs=1)
    for entry in (first, second):
        load = entry["moe_load_max_over_mean"]
        assert isinstance(load, jax.Array) and load.shape == ()  # a device scalar: nobody fetched it
        assert 1.0 <= float(load) <= 8 / 2
    fed.reset(0)
    assert fed.history == []
