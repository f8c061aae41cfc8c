"""No ``jax.checkpoint`` of a whole loss directly under the ``value_and_grad``
of the same scan step: it ran the forward twice and saved no memory (PR 25).

The federations keep accepting ``remat=``; it must not reach the program.
Rematerialisation that lowers memory lives in the model
(``TransformerConfig.remat``), which the positive control keeps visible.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from p2pfl_tpu.learning.dataset import FederatedDataset
from p2pfl_tpu.learning.learner import _loss
from p2pfl_tpu.models import mlp
from p2pfl_tpu.models.transformer import TransformerConfig, tiny_transformer
from p2pfl_tpu.models.vision import ResNet
from p2pfl_tpu.parallel import ChunkedFederation, SpmdFederation, SpmdLmFederation, SpmdLoraFederation
from p2pfl_tpu.parallel import chunked, spmd, spmd_lm

REMAT_MARKS = ("checkpoint/", "rematted_computation")  # as the name stack spells them


def _lm_model(block_remat: bool, lora_rank: int):
    cfg = TransformerConfig(
        vocab_size=64, dim=32, n_layers=2, n_heads=2, n_kv_heads=2, ffn_hidden=64,
        lora_rank=lora_rank, remat=block_remat, scan_layers=block_remat,
    )
    return tiny_transformer(seq_len=16, cfg=cfg)


def _lm_data():
    return FederatedDataset.synthetic_lm(vocab_size=64, seq_len=16, n_train=64, n_test=16)


def _mnist():
    return FederatedDataset.synthetic_mnist(n_train=256, n_test=64)


def _spmd(remat, block_remat=False):
    return SpmdFederation.from_dataset(mlp(), _mnist(), n_nodes=4, batch_size=16, vote=False, seed=5, remat=remat)


def _spmd_lora(remat, block_remat=False):
    return SpmdLoraFederation.from_dataset(
        _lm_model(block_remat, lora_rank=2), _lm_data(), n_nodes=4, batch_size=4, vote=False, seed=5, remat=remat
    )


def _spmd_lm(remat, block_remat=False):
    return SpmdLmFederation.from_dataset(
        _lm_model(block_remat, lora_rank=0), _lm_data(), n_nodes=4, batch_size=4, vote=False, seed=5, remat=remat
    )


def _chunked(remat, block_remat=False):
    return ChunkedFederation.from_dataset(
        mlp(), _mnist(), n_nodes=4, chunk_size=2, batch_size=16, vote=False, seed=5, remat=remat
    )


def _lower_spmd(fed):
    perm, mask, sel_idx = fed._round_inputs(1)
    return spmd.spmd_round.lower(
        fed.params, fed.opt_state, fed.x_all, fed.y_all, perm, mask, fed._samples, sel_idx,
        module=fed.module, tx=fed.tx, agg=fed.aggregator, trim=fed.trim, clip_tau=fed.clip_tau,
        out_sharding=fed._shard, keep_opt_state=fed.keep_opt_state,
        dp_keys=fed._dp_round_keys(), **fed._algo_kwargs(0),
    )


def _lower_spmd_lm(fed):
    perm, mask, sel_idx = fed._round_inputs(1)
    return spmd_lm.spmd_lm_round.lower(
        fed.params, fed.opt_state, fed.x_all, fed.y_all, perm, mask, fed._samples, sel_idx,
        module=fed.module, tx=fed.tx, agg=fed.aggregator, trim=fed.trim,
        out_sharding=fed._out_sharding_static(), keep_opt_state=fed.keep_opt_state,
    )


def _lower_chunked(fed):
    c = fed.chunk_size
    perm = np.zeros((c, 1, fed._nb, fed.batch_size), np.int32)
    return chunked._chunk_round.lower(
        fed.params, fed.opt_state, fed.x_chunks[0], fed.y_chunks[0], perm,
        jnp.ones((c,), jnp.float32), jnp.ones((c,), jnp.float32), module=fed.module, tx=fed.tx,
    )


ENGINES = {
    "spmd": (_spmd, _lower_spmd),
    "spmd_lora": (_spmd_lora, lambda fed: fed.lower_round(epochs=1)),
    "spmd_lm": (_spmd_lm, _lower_spmd_lm),
    "chunked": (_chunked, _lower_chunked),
}


def _text(lowered) -> str:
    """The lowered round with its ``op_name`` paths: where JAX writes
    ``checkpoint`` / ``rematted_computation``."""
    return lowered.as_text(debug_info=True)


def _final_params(fed):
    for _ in range(2):
        fed.run_round(epochs=1)
    return [np.asarray(leaf) for leaf in jax.tree.leaves(fed.params)]


@pytest.mark.parametrize("engine", list(ENGINES))
def test_remat_argument_does_not_reach_the_program(engine):
    build, lower = ENGINES[engine]
    on, off = build(True), build(False)
    assert on.remat is True and off.remat is False  # still accepted, still kept
    assert _text(lower(on)) == _text(lower(off))
    for a, b in zip(_final_params(on), _final_params(off)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("engine", list(ENGINES))
def test_step_lowers_without_a_checkpoint(engine):
    build, lower = ENGINES[engine]
    text = _text(lower(build(True)))
    assert not [mark for mark in REMAT_MARKS if mark in text]


@pytest.mark.parametrize("engine", ["spmd_lora", "spmd_lm"])
def test_the_models_block_remat_still_shows(engine):
    """Positive control: ``TransformerConfig(remat=True)`` wraps each block
    in ``nn.remat``, and that is what the marks above would have caught."""
    build, lower = ENGINES[engine]
    text = _text(lower(build(False, block_remat=True)))
    assert all(mark in text for mark in REMAT_MARKS)


def _conv_groupnorm_epoch(wrap: bool):
    """Two steps of batch 8 through a conv + GroupNorm residual net, as
    ``_local_epoch`` runs them; ``wrap`` reinstates the old whole-loss
    checkpoint under the step's own gradient."""
    module = ResNet(stage_sizes=(1, 1), bottleneck=True, num_classes=10)
    tx = optax.adam(1e-3)
    params = jax.eval_shape(lambda k: module.init(k, jnp.zeros((1, 16, 16, 3)))["params"], jax.random.PRNGKey(0))
    opt = jax.eval_shape(tx.init, params)
    xs = jax.ShapeDtypeStruct((2, 8, 16, 16, 3), jnp.float32)
    ys = jax.ShapeDtypeStruct((2, 8), jnp.int32)

    def old_epoch(p, o, xs_, ys_):
        def step(carry, batch):
            p_, o_ = carry
            loss_fn = jax.checkpoint(lambda q: _loss(q, module, *batch)[0])
            loss, grads = jax.value_and_grad(loss_fn)(p_)
            updates, o_ = tx.update(grads, o_, p_)
            return (optax.apply_updates(p_, updates), o_), loss

        (p, o), losses = jax.lax.scan(step, (p, o), (xs_, ys_))
        return p, o, jnp.mean(losses)

    def epoch(p, o, xs_, ys_):
        return spmd._local_epoch(p, o, xs_, ys_, module, tx)

    return jax.jit(old_epoch if wrap else epoch).lower(params, opt, xs, ys).compile()


def test_the_wrap_bought_no_memory_and_cost_a_forward():
    now, old = _conv_groupnorm_epoch(wrap=False), _conv_groupnorm_epoch(wrap=True)
    assert "rematted_computation" in old.as_text() and "rematted_computation" not in now.as_text()
    assert now.memory_analysis().temp_size_in_bytes <= old.memory_analysis().temp_size_in_bytes
    assert now.cost_analysis()["flops"] < 0.9 * old.cost_analysis()["flops"]
