"""An expert layer that holds a share of the experts, at its edges (PR 38): the
worst case the layout's static rows are kept for, and what a round lowers to —
beside ``tests/test_trinity_model.py`` (whose file is the suite's longest: these
run on another worker), against ``benchmark/reference/afmoe_lm.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import checks as ck
from benchmark.reference import afmoe_lm
from p2pfl_tpu.learning.dataset import FederatedDataset
from p2pfl_tpu.learning.lora import merge_params
from p2pfl_tpu.models.transformer import ExpertFFN
from p2pfl_tpu.ops.grouped_matmul import n_row_tiles
from p2pfl_tpu.parallel import SpmdLoraFederation
from tests.test_trinity_model import REF, SEQ, _perturbed_layer, _same_gradients, config, seeded


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("head_rows", [None, 96])
def test_every_assignment_on_a_held_expert_is_computed_the_layer_is_still_dropless(head_rows, impl, monkeypatch):
    """The worst case the layout's static rows are kept for: a bias sends EVERY
    assignment to the held experts 2..5 — nothing is absent, ``held_share`` is 1,
    every row tile but the spare one may be in use — value and gradients against
    the reference under the same share. ``head_rows``: the slab sums' source is
    over the limit of a copied head (128 rows against 96): seeded routing sums
    out of the copy, this routing's rows in use pass it and come straight from
    the source (``_slab_sum``)."""
    from p2pfl_tpu.models import transformer as tf

    cfg = config(expert_impl=impl)
    if head_rows:
        monkeypatch.setattr(tf, "_GATHER_HEAD_BYTES", head_rows * cfg.dim * 4)
    layer = ExpertFFN(cfg)
    h = jax.random.normal(jax.random.PRNGKey(0), (1, SEQ, cfg.dim), jnp.float32)
    lora, base = _perturbed_layer(layer, h)
    probe = jax.random.normal(jax.random.PRNGKey(3), h.shape)
    for routing, bias in (("seeded", base["router_bias"]), ("all_held", base["router_bias"] + jnp.array([0, 0, 9.0, 9.0, 9.0, 9.0, 0, 0]))):
        mine = dict(base, router_bias=bias)
        ours = lambda lo, h_: jnp.sum(layer.apply({"params": merge_params(mine, lo)}, h_) * probe)  # noqa: E731
        theirs = lambda lo, h_: jnp.sum(afmoe_lm.experts(h_[0], merge_params(mine, lo), REF, 2.0)[0] * probe[0])  # noqa: E731
        with jax.default_matmul_precision("highest"):
            want, want_grads = jax.value_and_grad(theirs, (0, 1))(lora, h)
            got, grads = jax.jit(jax.value_and_grad(ours, (0, 1)))(lora, h)
            _, mut = layer.apply({"params": merge_params(mine, lora)}, h, mutable=["moe_stats", "moe_routing"])
        assert float(got) == pytest.approx(float(want), rel=1e-4)
        _same_gradients(grads[0], want_grads[0])
        assert ck.rel_l2(grads[1], want_grads[1]) < 2e-4
        share, used = float(mut["moe_stats"]["held_share"][0]), float(mut["moe_stats"]["rows_used_share"][0])
        rows = 8 * (n_row_tiles(SEQ * 2, 4, 8) + 1)
        if routing == "all_held":
            chosen = np.asarray(mut["moe_routing"]["chosen"][0])
            assert ((chosen >= 2) & (chosen < 6)).all() and share == 1.0 and SEQ * 2 / rows <= used <= (rows - 8) / rows
            assert used * rows > 96 - 8  # past a head of 96 rows: the cond's other side
        else:
            assert 0.3 < share < 0.7 and used * rows <= 96 - 8  # inside it


def test_a_trinity_like_round_lowers_the_grouped_matmul_where_the_parent_did_and_no_new_kernel(monkeypatch):
    """Host-only lowering for a TPU of one round of the tiny federation with the
    kernel compiled (not interpreted): ``p2pfl_gmm`` at 18 call sites — three
    scan bodies with expert layers x (forward, re-forward, backward) x two
    products, the parent's count and the cell's ``expect.kernels_in_round`` —
    and no other Mosaic kernel (attention is the dense path here): what PR 38
    changed is inside that kernel and in the XLA ops around it."""
    from benchmark.engines.spmd_lora_moe import kernels_in
    from p2pfl_tpu.ops import grouped_matmul as gmm_ops
    from p2pfl_tpu.parallel.spmd_lora import spmd_lora_round

    model, _, _ = seeded(config(lora_rank=2))  # a program of its own: nothing cached from the interpreted rounds
    data = FederatedDataset.synthetic_lm(vocab_size=256, seq_len=SEQ, n_train=16, n_test=4)
    fed = SpmdLoraFederation.from_dataset(model, data, n_nodes=2, batch_size=2, vote=False, seed=0, node_chunk=1)
    args, statics = fed._round_call(1)
    monkeypatch.setattr(gmm_ops, "_on_tpu", lambda: True)  # what the chip's backend would answer (the weights were made interpreted)
    text = spmd_lora_round.trace(*args, **statics).lower(lowering_platforms=("tpu",)).as_text()
    assert kernels_in(text) == {"p2pfl_gmm": 18}
