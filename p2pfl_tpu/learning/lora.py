"""Federated LoRA: train and exchange only the adapters.

BASELINE config 5. The full model stays frozen and node-resident; the round
payload (and the aggregator's algebra) sees only the ``lora_*`` subtree —
for the default tiny config that's <1% of the parameters, and for a
TinyLlama-scale model it turns a ~2 GB gossip payload into a few MB.

Works with any module whose adapter params carry the ``lora_`` name prefix
(:class:`~p2pfl_tpu.models.transformer.LoRADense`). Whatever carries none is
base: frozen, node-resident and in no payload — the expert banks, a router,
convolution taps, and an untied output head (``lm_head``, which
``CausalLM(head=False)`` hands to the loss in the embedding's place). An
attention output gate (``wg``) is a ``LoRADense`` like the four projections
beside it, so it is adapted and exchanged with them.
"""

from __future__ import annotations

import threading
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax

from p2pfl_tpu.learning.dataset import FederatedDataset
from p2pfl_tpu.learning.learner import NodeLearner, adam, ce_eval
from p2pfl_tpu.management.logger import logger
from p2pfl_tpu.management.profiling import scope
from p2pfl_tpu.models.base import FlaxModel
from p2pfl_tpu.models.transformer import tied_logits
from p2pfl_tpu.ops.head_loss import head_loss

Pytree = Any


def split_lora(params: Pytree) -> tuple[dict, dict]:
    """Split a nested-dict param tree into (lora_subtree, base_subtree)."""

    def walk(node):
        lora, base = {}, {}
        for key, val in node.items():
            if isinstance(val, dict):
                sub_l, sub_b = walk(val)
                if sub_l:
                    lora[key] = sub_l
                if sub_b:
                    base[key] = sub_b
            elif key.startswith("lora_"):
                lora[key] = val
            else:
                base[key] = val
        return lora, base

    return walk(params)


def merge_params(base: dict, overlay: dict) -> dict:
    """Recursively overlay one nested dict onto another (pure, trace-safe)."""
    out = dict(base)
    for key, val in overlay.items():
        if key in out and isinstance(out[key], dict) and isinstance(val, dict):
            out[key] = merge_params(out[key], val)
        else:
            out[key] = val
    return out


def _mean_over_layers(leaves: list):
    """Mean over every layer that sowed into ``leaves`` — one leaf a run of
    expert layers, stacked along its scans; runs of unlike length weigh by
    their layers (equal runs, one run among them, keep the plain mean of means:
    the program every single-run model has always lowered to)."""
    means = jnp.stack([jnp.mean(leaf) for leaf in leaves])
    sizes = [leaf.size for leaf in leaves]
    if len(set(sizes)) == 1:
        return jnp.mean(means)
    return jnp.sum(means * (jnp.asarray(sizes, means.dtype) / sum(sizes)))


def _lm_forward(lora, base, module, x, y):
    """(training loss, logits, statistics, routing): CE + any sown auxiliary
    losses (MoE router balance) — the CE from :func:`head_loss`, which holds no
    ``[T, vocab]`` array whole; the logits the plain way beside it, a dead value
    that the compiler removes from a program that drops them (every training
    round does); what the model sowed into ``"moe_stats"`` —
    each name's mean over the layers that sowed it, ``{}`` for a model that sows none
    (an expert layer's ``load_max_over_mean``, ``rows_used_share``, ``held_share``) and into
    ``"kda_stats"`` (a delta-rule layer's ``neg_beta_share``), named ``moe_<name>`` /
    ``kda_<name>``; and the
    ``"moe_routing"`` collection as sown (the experts each row chose, from THIS
    forward — a comparison must not take them from another program: a TPU
    rounds a near-tie differently from one compiled program to the next)."""
    params = merge_params(base, lora)
    # `embedding`: the head's matrix, the embedding itself or an untied `lm_head`
    (hidden, embedding), mut = module.apply(
        {"params": params}, x, head=False, mutable=["moe_losses", "moe_stats", "moe_routing", "kda_stats"]
    )
    leaves = jax.tree.leaves(mut.get("moe_losses", {}))
    aux = sum(leaves) if leaves else jnp.zeros((), jnp.float32)
    ce = head_loss(hidden, embedding, y)
    with scope("head"):
        logits = tied_logits(hidden, embedding)
    found: dict[str, list] = {}
    for collection in ("moe_stats", "kda_stats"):
        for path, leaf in jax.tree_util.tree_leaves_with_path(mut.get(collection, {})):
            name = next(k.key for k in reversed(path) if isinstance(k, jax.tree_util.DictKey))
            found.setdefault(collection.split("_")[0] + "_" + name, []).append(leaf)
    stats = {name: _mean_over_layers(leaves) for name, leaves in found.items()}
    return ce + aux, logits, stats, mut.get("moe_routing", {})


def _lm_loss(lora, base, module, x, y):
    """Training loss: CE + any sown auxiliary losses (MoE router balance)."""
    loss, logits, _, _ = _lm_forward(lora, base, module, x, y)
    return loss, logits


@partial(jax.jit, static_argnames=("module", "tx"), donate_argnums=(1,))
def lora_train_epoch(lora, opt_state, base, xs, ys, module, tx):
    """Epoch scan updating only the adapter subtree (frozen base is an input)."""

    def step(carry, batch):
        lo, o = carry
        x, y = batch
        (loss, _), grads = jax.value_and_grad(_lm_loss, has_aux=True)(lo, base, module, x, y)
        updates, o = tx.update(grads, o, lo)
        lo = optax.apply_updates(lo, updates)
        return (lo, o), loss

    (lora, opt_state), losses = jax.lax.scan(step, (lora, opt_state), (xs, ys))
    return lora, opt_state, jnp.mean(losses)


@partial(jax.jit, static_argnames=("module",))
def lora_eval(lora, base, x, y, module):
    loss, logits = ce_eval(merge_params(base, lora), module, x, y)
    acc = jnp.mean((jnp.argmax(logits, axis=-1) == y).astype(jnp.float32))
    return loss, acc


class LoRALearner(NodeLearner):
    """JaxLearner variant whose exchanged parameters are the LoRA subtree only.

    ``get_parameters`` / ``set_parameters`` / ``get_model_update`` all speak
    the adapter subtree — aggregators, the weights codec, and both gossip and
    SPMD modes work unchanged on the smaller tree.
    """

    def __init__(
        self,
        model: FlaxModel,
        data: FederatedDataset,
        addr: str = "",
        epochs: int = 1,
        batch_size: int = 16,
        learning_rate: float = 1e-3,
        seed: int = 0,
    ) -> None:
        self.model = model
        self.data = data
        self.addr = addr
        self.epochs = epochs
        self.batch_size = batch_size
        self.tx = adam(learning_rate)
        self.lora, self.base = split_lora(model.params)
        if not jax.tree.leaves(self.lora):
            raise ValueError("model has no lora_* params — use JaxLearner instead")
        self.opt_state = self.tx.init(self.lora)
        self._rng = np.random.default_rng(seed)
        self._interrupt = threading.Event()
        self._steps_done = 0

    # ---- exchanged params = adapters only ----

    def set_parameters(self, params: Pytree) -> None:
        if jax.tree.structure(params) != jax.tree.structure(self.lora):
            from p2pfl_tpu.exceptions import ModelNotMatchingError

            raise ModelNotMatchingError("incoming params do not match LoRA structure")
        self.lora = params
        self.opt_state = self.tx.init(params)
        # the payload cache keys encoded bytes on model_version: skipping
        # the bump would replay the PREVIOUS adapters' bytes for these
        self.bump_model_version()

    def get_parameters(self) -> Pytree:
        return self.lora

    def full_parameters(self) -> Pytree:
        return merge_params(self.base, self.lora)

    def set_epochs(self, epochs: int) -> None:
        self.epochs = epochs

    # ---- training ----

    def fit(self) -> None:
        self._interrupt.clear()
        for _ in range(self.epochs):
            if self._interrupt.is_set():
                logger.info(self.addr, "Training interrupted")
                return
            xs, ys = self.data.epoch_batches(self.batch_size, self._rng)
            self.lora, self.opt_state, loss = lora_train_epoch(
                self.lora,
                self.opt_state,
                self.base,
                jnp.asarray(xs),
                jnp.asarray(ys),
                self.model.module,
                self.tx,
            )
            self._steps_done += xs.shape[0]
            logger.log_metric(self.addr, "train_loss", float(loss), step=self._steps_done)
        # trained adapters are new payload content (encode-once cache key)
        self.bump_model_version()

    def interrupt_fit(self) -> None:
        self._interrupt.set()

    def evaluate(self) -> dict[str, float]:
        x, y = self.data.test_arrays()
        if len(y) == 0:
            return {}
        loss, acc = lora_eval(
            self.lora, self.base, jnp.asarray(x), jnp.asarray(y), self.model.module
        )
        return {"test_loss": float(loss), "test_acc": float(acc)}

    def get_num_samples(self) -> int:
        return self.data.num_samples
