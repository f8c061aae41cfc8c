"""SPMD federated LoRA: BASELINE config 5 at mesh scale.

Node-stacked state is ONLY the adapter subtree ``[N, ...]``; the frozen base
model is stored once and replicated (or tensor-parallel over the ``model``
axis via ``parallel/sharding.py``) — N nodes' federation state costs
``N × adapter_size + 1 × model_size`` instead of ``N × model_size``, which is
what makes 32-node TinyLlama-scale federations fit a slice. The FedAvg
all-reduce moves only adapters.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from p2pfl_tpu.learning.dataset import FederatedDataset
from p2pfl_tpu.learning.learner import adam, ce_eval
from p2pfl_tpu.learning.lora import lora_train_epoch as _node_lora_epoch  # noqa: F401 (shared math)
from p2pfl_tpu.learning.lora import _lm_forward, _lm_loss, merge_params, split_lora
from p2pfl_tpu.management.profiling import dispatch_span, note_placed, scope, setup_span
from p2pfl_tpu.models.base import FlaxModel
from p2pfl_tpu.parallel.spmd import SpmdFederation, _aggregate

Pytree = Any


def _on_own_nodes(fn, sharding: Optional[NamedSharding], n_stacked: int):
    """``fn`` over node-stacked arguments, run by each device on its OWN nodes.

    ``fn``'s first ``n_stacked`` arguments and all its results carry the node
    axis in front; its last argument is shared. Per-node work needs no
    communication, and ``shard_map`` over the nodes axis says so: under plain
    ``jit`` GSPMD has to partition whatever ``fn`` contains, and it refuses
    Mosaic kernels ("cannot be automatically partitioned" — the flash
    kernels, first seen on four chips). Mosaic lowers only with EVERY mesh
    axis manual, so the other axes join when they are trivial; when they are
    not (a tensor-parallel base) they stay automatic, which XLA ops accept.
    """
    if sharding is None:
        return fn
    mesh, axis = sharding.mesh, sharding.spec[0]
    if mesh.shape[axis] == 1:
        return fn
    others = [a for a in mesh.axis_names if a != axis]
    manual = {axis} if any(mesh.shape[a] > 1 for a in others) else {axis, *others}
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(axis),) * n_stacked + (P(),),
        out_specs=P(axis),
        axis_names=manual,
        check_vma=False,  # pallas_call results carry no varying-axes typing
    )


def _lora_round_core(
    stacked_lora,  # [N, ...] adapters
    opt_states,  # [N, ...] when keep_opt_state, else not read
    base,  # shared frozen params (no node axis)
    x_all,  # [N, S, T] int tokens
    y_all,  # [N, S, T]
    perm,  # [N, epochs, nb, bs]
    mask,  # [N]
    weights,  # [N]
    sel_idx,  # [K] int32 indices of mask==1 rows
    *,
    module,
    tx,
    agg: str = "fedavg",
    trim: int = 0,
    out_sharding=None,
    keep_opt_state: bool = False,
    node_chunk: int = 0,
):
    """Trace-time body shared by the one-round and fused-round programs.

    ``node_chunk``: train the N nodes ``node_chunk`` at a time via a
    ``lax.scan`` of vmapped chunks instead of one N-wide vmap. Activation
    memory scales with nodes-in-flight, so chunking buys HBM headroom for
    a richer selective-remat policy (``TransformerConfig.remat_policy``) —
    the 0.98B bench row trades 4× fewer nodes in flight for skipping the
    FFN recompute entirely, a net model-MFU win. 0 = single vmap. On a
    mesh of k devices each device scans its own N/k nodes, ``node_chunk //
    k`` at a time (at least one), so the count in flight is the same.

    ``keep_opt_state``: whether the optimizer state outlives the round. True:
    ``opt_states`` ``[N, ...]`` is scanned over with the adapters and the
    trained state comes back in second place. False: every node starts from
    ``tx.init`` of its own adapters inside the vmapped chunk (the state of
    the nodes in flight is all that exists), ``opt_states`` is not read
    (whatever is passed there is an unused operand) and ``None`` comes back
    in its place. N nodes' fresh moments as an argument and a result are
    zeros that cost memory: at 32 nodes of a 7B block's adapters, 2 x 1.34 GB
    that the TPU compiler fitted by running a backward matmul twice.
    """
    n = mask.shape[0]
    if node_chunk and node_chunk < n and n % node_chunk:
        raise ValueError(f"node_chunk {node_chunk} must divide n_nodes {n}")
    if not keep_opt_state:
        opt_states = None

    def node_fn(lora, opt_state, x, y, idx, base):
        if not keep_opt_state:
            opt_state = tx.init(lora)

        def epoch_body(carry, ep_idx):
            lo, o = carry
            xs = jnp.take(x, ep_idx, axis=0)
            ys = jnp.take(y, ep_idx, axis=0)

            def step(c, batch):
                lo_, o_ = c
                bx, by = batch

                def loss_of(lo__, bx_, by_):
                    loss, _, stats, _ = _lm_forward(lo__, base, module, bx_, by_)
                    return loss, stats

                with scope("grad"):
                    (loss, stats), grads = jax.value_and_grad(loss_of, has_aux=True)(
                        lo_, bx, by
                    )
                with scope("optimizer"):
                    updates, o_ = tx.update(grads, o_, lo_)
                    lo_ = optax.apply_updates(lo_, updates)
                return (lo_, o_), (loss, stats)

            (lo, o), (losses, stats) = jax.lax.scan(step, (lo, o), (xs, ys))
            return (lo, o), (jnp.mean(losses), jax.tree.map(jnp.mean, stats))

        (lora, opt_state), (losses, stats) = jax.lax.scan(epoch_body, (lora, opt_state), idx)
        stats = jax.tree.map(jnp.mean, stats)  # {} unless the model sows statistics
        return lora, opt_state if keep_opt_state else None, jnp.mean(losses), stats

    def train(lora, opt, x, y, idx, base):
        """Every node on the leading axis — all N, or one device's share of
        them, then with the same share of ``node_chunk`` in flight."""
        here = idx.shape[0]
        in_flight = max(1, node_chunk * here // n)
        vmapped = jax.vmap(node_fn, in_axes=(0, 0, 0, 0, 0, None))
        if not node_chunk or in_flight >= here:
            return vmapped(lora, opt, x, y, idx, base)
        chunk = next(c for c in range(in_flight, 0, -1) if here % c == 0)

        def chunked(tree):
            return jax.tree.map(
                lambda a: a.reshape(here // chunk, chunk, *a.shape[1:]), tree
            )

        def chunk_body(_, args):
            return None, vmapped(*args, base)

        _, out = jax.lax.scan(chunk_body, None, chunked((lora, opt, x, y, idx)))
        return jax.tree.map(lambda a: a.reshape(here, *a.shape[2:]), out)

    trained, trained_opt, losses, stats = _on_own_nodes(train, out_sharding, 5)(
        stacked_lora, opt_states, x_all, y_all, perm, base
    )

    def sel(new, old):
        m = mask.reshape((n,) + (1,) * (new.ndim - 1)).astype(new.dtype)
        return new * m + old * (1 - m)

    with scope("fold"):  # everything after the last local step
        used = jax.tree.map(sel, trained, stacked_lora)
        agg_lora = _aggregate(used, mask, weights, sel_idx, agg, trim)
        out = jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n, *a.shape)), agg_lora)
        if out_sharding is not None:
            out = jax.tree.map(lambda a: jax.lax.with_sharding_constraint(a, out_sharding), out)
        if out_sharding is not None:  # trained_opt is None unless it is kept
            trained_opt = jax.tree.map(
                lambda a: jax.lax.with_sharding_constraint(a, out_sharding), trained_opt
            )
        trained_nodes = mask.astype(bool)
        stats = {name: jnp.mean(per_node, where=trained_nodes) for name, per_node in stats.items()}
        return out, trained_opt, jnp.mean(losses, where=trained_nodes), stats


_LORA_STATICS = (
    "module", "tx", "agg", "trim", "out_sharding", "keep_opt_state", "node_chunk",
)


@partial(jax.jit, static_argnames=_LORA_STATICS, donate_argnums=(0, 1))
def spmd_lora_round(
    stacked_lora, opt_states, base, x_all, y_all, perm, mask, weights, sel_idx,
    *, remat=None, **kw,
):
    # ``remat`` is ignored (an unused operand, pruned from the program):
    # ``benchmark/compile_check.py`` still passes it — and a full optimizer
    # tree in second place, which is pruned likewise unless it is kept
    return _lora_round_core(
        stacked_lora, opt_states, base, x_all, y_all, perm, mask, weights, sel_idx, **kw
    )


@partial(jax.jit, static_argnames=_LORA_STATICS, donate_argnums=(0, 1))
def spmd_lora_rounds_fused(
    stacked_lora, opt_states, base, x_all, y_all, perms, mask, weights, sel_idx, **kw
):
    """R LoRA federated rounds as ONE device dispatch (``lax.scan``).

    ``perms``: [R, N, epochs, nb, bs]. Adapters are tiny (config 5:
    57 k params/node), so a round is dispatch-dominated — fusing amortizes
    the host↔device round-trip R×, same as :func:`spmd_rounds_fused`.
    Returns (adapters', opt', losses [R]); ``opt'`` is ``None`` and the scan
    carries the adapters alone unless ``keep_opt_state``.
    """
    if not kw.get("keep_opt_state"):
        opt_states = None

    def body(carry, perm):
        p, o = carry
        out_p, out_o, loss, _ = _lora_round_core(
            p, o, base, x_all, y_all, perm, mask, weights, sel_idx, **kw
        )
        return (out_p, out_o), loss

    (p, o), losses = jax.lax.scan(body, (stacked_lora, opt_states), perms)
    return p, o, losses


@partial(jax.jit, static_argnames=("module", "sharding"))
def spmd_lora_eval(stacked_lora, base, x_test, y_test, *, module, sharding=None):
    def node_eval(lora, x, y, base):
        loss, logits = ce_eval(merge_params(base, lora), module, x, y)
        acc = jnp.mean((jnp.argmax(logits, axis=-1) == y).astype(jnp.float32))
        return loss, acc

    return _on_own_nodes(jax.vmap(node_eval, in_axes=(0, 0, 0, None)), sharding, 3)(
        stacked_lora, x_test, y_test, base
    )


class SpmdLoraFederation(SpmdFederation):
    """SPMD federation over adapter subtrees; frozen base stored once.

    Round-carried state is the adapters ``[N, ...]`` and, with
    ``keep_opt_state=True``, the optimizer state ``[N, ...]``. Without it
    ``opt_state`` is ``None``: each node makes its fresh state inside the
    round's program (:func:`_lora_round_core`), so no N-wide tree of zeros
    is staged, passed in, or written back.
    """

    def __init__(
        self,
        model: FlaxModel,
        datasets: list[FederatedDataset],
        mesh: Optional[Mesh] = None,
        model_parallel_base: bool = False,
        node_chunk: int = 0,
        **kwargs,
    ) -> None:
        lora0, base0 = split_lora(model.params)
        if not jax.tree.leaves(lora0):
            raise ValueError("model has no lora_* params")
        self._lora_template = lora0
        self._base_template = base0
        self._mp_base = model_parallel_base
        self.node_chunk = node_chunk
        super().__init__(model, datasets, mesh=mesh, **kwargs)

    # node-stacked state = adapters only; base placed separately
    @setup_span("stage_state")
    def _stage_state(self) -> None:
        n, keep = self.n, self.keep_opt_state

        @partial(jax.jit, out_shardings=(self._shard, self._shard))
        def stage(tree):
            stacked = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n, *x.shape)), tree)
            return stacked, jax.vmap(self.tx.init)(stacked) if keep else None

        self.params, self.opt_state = stage(self._lora_template)
        if self._mp_base:
            from p2pfl_tpu.parallel.sharding import shard_transformer

            self.base = shard_transformer(self.mesh, self._base_template)
        else:
            self.base = jax.device_put(self._base_template, self._repl)
        note_placed(n, self.params, self.opt_state, self.base)

    def _round_call(self, epochs: int) -> tuple[tuple, dict]:
        """(args, static kwargs) of the :func:`spmd_lora_round` dispatch for
        the next round — draws the round's batch permutation."""
        perm, mask, sel_idx = self._round_inputs(epochs)
        args = (
            self.params, self.opt_state, self.base, self.x_all, self.y_all,
            perm, mask, self._samples, sel_idx,
        )
        statics = dict(
            module=self.module, tx=self.tx, agg=self.aggregator, trim=self.trim,
            out_sharding=self._shard, keep_opt_state=self.keep_opt_state,
            node_chunk=self.node_chunk,
        )
        return args, statics

    def run_round(self, epochs: int = 1) -> dict:
        from p2pfl_tpu.settings import Settings

        if self._vote and (self.round == 0 or Settings.VOTE_EVERY_ROUND):
            self.train_mask = self.elect_train_set()
        args, statics = self._round_call(epochs)
        with dispatch_span("spmd_lora_round", "spmd", nodes=self.n, epochs=epochs, fed=id(self)):
            self.params, self.opt_state, loss, stats = spmd_lora_round(*args, **statics)
        self.round += 1
        # ``stats``: device scalars the model sowed, averaged over the round's steps and trained nodes (an
        # expert model's ``moe_load_max_over_mean``, ``moe_rows_used_share`` and, of a held share,
        # ``moe_held_share``); ``{}`` otherwise. Nothing here fetches them.
        entry = {"round": self.round, "train_loss": loss, **stats}
        self.history.append(entry)
        return entry

    def lower_round(self, epochs: int = 1) -> jax.stages.Lowered:
        """The program :meth:`run_round` dispatches, lowered but not run —
        for inspecting what the round compiles to (``chip_smoke.py`` looks
        for the Mosaic kernels in it). State is untouched apart from the
        batch-order rng draw a round would also make."""
        args, statics = self._round_call(epochs)
        return spmd_lora_round.lower(*args, **statics)

    def run_fused(self, rounds: int, epochs: int = 1, eval: bool = False) -> list[dict]:  # noqa: A002
        """R adapter-federation rounds as ONE device dispatch.

        Same contract as :meth:`SpmdFederation.run_fused` (fixed train set
        for the span; no per-round voting). ``eval`` is not fused here —
        adapters are tiny, call :meth:`evaluate` where a curve is needed.
        """
        if eval:
            raise ValueError("SpmdLoraFederation.run_fused has no fused eval; call evaluate()")
        perms, mask, sel_idx = self._fused_inputs(rounds, epochs)
        with dispatch_span("spmd_lora_rounds_fused", "spmd", nodes=self.n, rounds=rounds, fed=id(self)):
            self.params, self.opt_state, losses = spmd_lora_rounds_fused(
                self.params, self.opt_state, self.base, self.x_all, self.y_all,
                perms, mask, self._samples, sel_idx,
                module=self.module, tx=self.tx, agg=self.aggregator, trim=self.trim,
                out_sharding=self._shard, keep_opt_state=self.keep_opt_state,
                node_chunk=self.node_chunk,
            )
        entries = []
        for r in range(rounds):
            self.round += 1
            entry = {"round": self.round, "train_loss": losses[r]}
            self.history.append(entry)
            entries.append(entry)
        return entries

    def evaluate(self) -> dict:
        loss, acc = spmd_lora_eval(
            self.params, self.base, self.x_test, self.y_test, module=self.module,
            sharding=self._shard,
        )
        return {
            "test_loss": float(jnp.mean(loss)),
            "test_acc": float(jnp.mean(acc)),
            "per_node_acc": np.asarray(acc).tolist(),
        }

    def round_flops(self, epochs: int = 1) -> Optional[float]:
        """FLOPs of one LoRA round (scan-trip-count aware, VERDICT r2 #2).

        The base class's version lowers the FULL-model ``spmd_round``
        program, which is not what this federation runs. A LoRA round is
        step-dominated (the adapter aggregation is tiny next to the
        transformer fwd/bwd through the frozen base), so: one node's ONE
        SGD step from the shared scan-free probe × every step the round
        executes.
        """

        def loss_fn(lo, bx, by):
            return _lm_loss(lo, self.base, self.module, bx, by)[0]

        step = self._probe_step_flops(loss_fn)
        if step is None:
            return None
        return self.n * epochs * self._nb * step
