"""Time-shared federation: N logical nodes streamed through one chip in chunks.

BASELINE config 3's nameplate is 64 ResNet-50 nodes — 64 × (params + 2
Adam moments) ≈ 19.6 GB of node-stacked state, over a single v5e's HBM.
:class:`SpmdFederation` holds all N nodes resident, so it can only fold the
node count down (round 3 measured a 16-node proxy). This module runs the
STATED node count by time-sharing the chip instead — the same pattern
:class:`~p2pfl_tpu.parallel.spmd_lm.PipelineFederation` uses for stages,
applied to the federated-node axis:

- nodes process in chunks of ``chunk_size``; each chunk's jitted program
  broadcasts the round-start aggregate to its C slots, runs the vmapped
  local epochs, and reduces the trained models to a weighted partial sum
  ON DEVICE;
- FedAvg becomes a running (partial-sum, weight) accumulation across
  chunks, so the resident set is one aggregate + one chunk's workspace —
  nothing per-node ever leaves the device or lands in host RAM;
- the running sums ride DONATED accumulator arguments through the chunk
  program (``Settings.CHUNK_FUSED_REDUCE`` / ``CHUNK_DONATE_BUFFERS``):
  chunk k's partial sum updates in place inside the same dispatch that
  trains the chunk, instead of the host issuing 2×leaf-count eager adds
  between chunks — the serialization the round-5 ``gap_attribution``
  measured behind "broadcast + fp32 reduce";
- chunk inputs are staged ``Settings.CHUNK_STAGING_DEPTH`` chunks ahead
  (double buffering at the default 2): chunk k+1's host→device copies
  (perm indices, and x/y when ``resident=False`` streams the dataset
  from host RAM) overlap chunk k's compute instead of following it;
- optimizer moments are AGGREGATED with the same weighted mean as the
  params ("federated moment averaging"). Per-node moments would need
  N × 2 × params of storage — exactly the state that doesn't fit — and
  host-swapping them every round costs more than the round's
  compute. Every node therefore starts a round from (aggregate params,
  aggregate moments); step counts (integer optax leaves) pass through
  unchanged so warmup-cosine schedules keep ticking across rounds.
  This is a documented DIVERGENCE from :class:`SpmdFederation`'s
  per-node ``keep_opt_state``; config 3's convergence curve is the
  evidence it trains (the round-2 lesson — fresh moments every round —
  flatlined; averaged moments preserve the schedule and the moment
  scale).

FedAvg only: one streaming pass cannot compute coordinate-wise medians or
Krum distances, which need all K models simultaneously (use
:class:`SpmdFederation` at a node count that fits for those).

The reference has no analogue (its scale ceiling is one process per node,
SURVEY §2.9); this exists so the v4-128-sized configs EXECUTE on one chip,
slower, instead of shrinking to a proxy (VERDICT r3 #3).
"""

from __future__ import annotations

import random
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax

from p2pfl_tpu.learning.dataset import FederatedDataset
from p2pfl_tpu.learning.learner import _loss, adam
from p2pfl_tpu.models.base import FlaxModel
from p2pfl_tpu.parallel.spmd import _local_epoch, elect_train_set_mask
from p2pfl_tpu.settings import Settings

Pytree = Any


def _is_inexact(x) -> bool:
    return jnp.issubdtype(x.dtype, jnp.inexact)


def _chunk_contrib(agg_params, agg_opt, x, y, perm, mask, weights, module, tx):
    """One chunk's round contribution (trace-time body).

    Broadcast the aggregate to C slots, run each slot's scan-epochs, and
    reduce to (weighted param sum, weighted opt sum, total weight, loss).
    Masked slots train but contribute zero weight (static shapes; the
    host skips fully-masked chunks entirely).
    """
    c = mask.shape[0]
    stacked = jax.tree.map(lambda a: jnp.broadcast_to(a[None], (c, *a.shape)), agg_params)
    opts = jax.tree.map(lambda a: jnp.broadcast_to(a[None], (c, *a.shape)), agg_opt)

    def node_fn(p, o, x_, y_, idx):
        def epoch(carry, ep_idx):
            p_, o_ = carry
            xs = jnp.take(x_, ep_idx, axis=0)
            ys = jnp.take(y_, ep_idx, axis=0)
            p_, o_, loss = _local_epoch(p_, o_, xs, ys, module, tx)
            return (p_, o_), loss

        (p, o), losses = lax.scan(epoch, (p, o), idx)
        return p, o, jnp.mean(losses)

    trained, t_opt, losses = jax.vmap(node_fn)(stacked, opts, x, y, perm)
    w = (mask * weights).astype(jnp.float32)
    psum = jax.tree.map(
        lambda t: jnp.tensordot(w, t.astype(jnp.float32), axes=(0, 0)), trained
    )
    osum = jax.tree.map(
        lambda t: jnp.tensordot(w, t.astype(jnp.float32), axes=(0, 0))
        if _is_inexact(t)
        else t[0],
        t_opt,
    )
    denom = jnp.maximum(jnp.sum(w), 1e-9)
    loss = jnp.sum(losses * w) / denom
    return psum, osum, jnp.sum(w), loss


@partial(jax.jit, static_argnames=("module", "tx"))
def _chunk_round(agg_params, agg_opt, x, y, perm, mask, weights, *, module, tx):
    """Serial-path chunk program: contribution only, reduce on host.

    Kept verbatim as the reference semantics — the overlapped path's
    bit-parity test (tests/test_chunked.py) compares against it.
    """
    return _chunk_contrib(agg_params, agg_opt, x, y, perm, mask, weights, module, tx)


def _chunk_round_acc_impl(
    psum, osum, wsum, loss_sum, agg_params, agg_opt, x, y, perm, mask, weights,
    *, module, tx,
):
    """Fused-reduce chunk program: train the chunk AND fold its weighted
    contribution into the running accumulators in the same dispatch.

    fp32 zero-init + in-program adds keep the accumulation order identical
    to the host-side serial reduce (0 + x ≡ x in fp32), so the overlapped
    path stays numerically exact against it. Integer opt leaves (schedule
    step counts) are identical across chunks; the chunk's own value passes
    through.
    """
    p_c, o_c, w_c, l_c = _chunk_contrib(
        agg_params, agg_opt, x, y, perm, mask, weights, module, tx
    )
    psum = jax.tree.map(jnp.add, psum, p_c)
    osum = jax.tree.map(
        lambda a, b: jnp.add(a, b) if _is_inexact(b) else b, osum, o_c
    )
    return psum, osum, wsum + w_c, loss_sum + l_c * w_c


# donated variant: XLA writes each chunk's updated sums into the same HBM
# buffers (no fresh full-model allocation per chunk); the plain variant is
# the CHUNK_DONATE_BUFFERS=False debugging path
_chunk_round_acc_donated = partial(
    jax.jit, static_argnames=("module", "tx"), donate_argnums=(0, 1, 2, 3)
)(_chunk_round_acc_impl)
_chunk_round_acc_plain = partial(
    jax.jit, static_argnames=("module", "tx")
)(_chunk_round_acc_impl)


@jax.jit
def _zero_acc(params, opt_state):
    """Fresh on-device accumulators (fp32 sums, zero weight/loss)."""
    psum = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    osum = jax.tree.map(
        lambda a: jnp.zeros(a.shape, jnp.float32 if _is_inexact(a) else a.dtype),
        opt_state,
    )
    return psum, osum, jnp.float32(0.0), jnp.float32(0.0)


def _finalize_impl(psum, osum, wsum, params_ref, opt_ref, *, tx, keep_opt):
    """Divide the accumulated sums into the new aggregate (one dispatch)."""
    params = jax.tree.map(lambda s, ref: (s / wsum).astype(ref.dtype), psum, params_ref)
    if keep_opt:
        opt = jax.tree.map(
            lambda s, ref: (s / wsum).astype(ref.dtype) if _is_inexact(ref) else s,
            osum,
            opt_ref,
        )
    else:
        opt = tx.init(params)
    return params, opt


# keep_opt reads osum (donate both sums); the fresh-opt variant leaves osum
# untouched, so donating it would only emit an unused-donation warning
_finalize_keep = partial(
    jax.jit, static_argnames=("tx", "keep_opt"), donate_argnums=(0, 1)
)(_finalize_impl)
_finalize_fresh = partial(
    jax.jit, static_argnames=("tx", "keep_opt"), donate_argnums=(0,)
)(_finalize_impl)


@partial(jax.jit, static_argnames=("module",))
def _chunk_eval(agg_params, x_t, y_t, *, module):
    def one(x, y):
        loss, logits = _loss(agg_params, module, x, y)
        acc = jnp.mean((jnp.argmax(logits, axis=-1) == y).astype(jnp.float32))
        return loss, acc

    return jax.vmap(one)(x_t, y_t)


class ChunkedFederation:
    """N-node FedAvg federation streamed through the chip ``chunk_size``
    nodes at a time. Same round semantics as :class:`SpmdFederation`
    (reference round loop, §3.3) except the moment-averaging divergence
    documented in the module docstring. ``remat`` is accepted and has no
    effect on the step (see :class:`SpmdFederation`)."""

    def __init__(
        self,
        model: FlaxModel,
        datasets: list[FederatedDataset],
        chunk_size: int,
        batch_size: int = 128,
        learning_rate: float = 1e-3,
        keep_opt_state: bool = False,
        remat: bool = False,
        vote: bool = False,
        seed: int = 0,
        tx: Optional[optax.GradientTransformation] = None,
        resident: bool = True,
    ) -> None:
        self.model = model
        self.module = model.module
        self._resident = resident
        self.n = len(datasets)
        if self.n % chunk_size != 0:
            raise ValueError(f"{self.n} nodes not divisible into chunks of {chunk_size}")
        self._chunk_size = chunk_size
        self.datasets = datasets
        self.batch_size = batch_size
        self.tx = tx if tx is not None else adam(learning_rate)
        self.keep_opt_state = keep_opt_state
        self.remat = remat
        self._vote = vote
        self._rng = np.random.default_rng(seed)
        self._py_rng = random.Random(seed)

        sizes = [d.num_samples for d in datasets]
        tr_min, tr_max = min(sizes), max(sizes)
        if tr_min < batch_size:
            raise ValueError(f"smallest shard ({tr_min}) < batch size ({batch_size})")
        te_min = min(len(d.y_test) for d in datasets)

        # whole-federation data stays on device (config 3: ~200 MB — it's
        # the PER-NODE STATE that doesn't fit, not the data), PRE-SPLIT
        # into per-chunk arrays: slicing a device array per round per chunk
        # materializes a fresh copy every time (measured as pure HBM-copy
        # overhead on the round path); staging the slices once removes it
        self._tr_max = tr_max
        self._stage_chunks()
        self.x_test = jax.device_put(np.stack([d.x_test[:te_min] for d in datasets]))
        self.y_test = jax.device_put(np.stack([d.y_test[:te_min] for d in datasets]))
        self._sizes = sizes
        self._samples = np.asarray(sizes, np.float32)
        self._nb = tr_min // batch_size

        self.train_mask = np.ones(self.n, dtype=np.float32)
        self.active_mask = np.ones(self.n, dtype=np.float32)
        self.round = 0
        self.history: list[dict] = []
        self._stage_state()

    def _stage_chunks(self) -> None:
        # resident: rebuilt from the datasets each time (only at init and on
        # a chunk_size change) so no whole-federation numpy copy lives in
        # host RAM for the object's lifetime. resident=False keeps the
        # per-chunk numpy stacks IN host RAM instead — the mode for datasets
        # that don't fit HBM next to the model workspace; the round loop
        # streams them chunk-by-chunk, CHUNK_STAGING_DEPTH ahead of compute.
        c = self._chunk_size

        def wrap(a: np.ndarray) -> np.ndarray:
            if len(a) == self._tr_max:
                return a
            reps = -(-self._tr_max // len(a))
            return np.concatenate([a] * reps, axis=0)[: self._tr_max]

        xs = [
            np.stack([wrap(d.x_train) for d in self.datasets[c0 : c0 + c]])
            for c0 in range(0, self.n, c)
        ]
        ys = [
            np.stack([wrap(d.y_train) for d in self.datasets[c0 : c0 + c]])
            for c0 in range(0, self.n, c)
        ]
        if self._resident:
            self.x_chunks = [jax.device_put(x) for x in xs]
            self.y_chunks = [jax.device_put(y) for y in ys]
            self._x_np = self._y_np = None
        else:
            self._x_np, self._y_np = xs, ys
            self.x_chunks = self.y_chunks = None

    @property
    def chunk_size(self) -> int:
        return self._chunk_size

    @chunk_size.setter
    def chunk_size(self, value: int) -> None:
        # re-splitting the pre-staged per-chunk data keeps the round path
        # copy-free while letting callers retune the chunk size
        if self.n % value != 0:
            raise ValueError(f"{self.n} nodes not divisible into chunks of {value}")
        if value != self._chunk_size:
            self._chunk_size = value
            self._stage_chunks()

    def _stage_state(self) -> None:
        self.params = jax.device_put(self.model.params)
        self.opt_state = jax.jit(self.tx.init)(self.params)

    def reset(self, seed: int = 0) -> None:
        self._rng = np.random.default_rng(seed)
        self._py_rng = random.Random(seed)
        self.train_mask = np.ones(self.n, dtype=np.float32)
        self.active_mask = np.ones(self.n, dtype=np.float32)
        self.round = 0
        self.history = []
        self._stage_state()

    def drop_node(self, i: int) -> None:
        self.active_mask[i] = 0.0

    def restore_node(self, i: int) -> None:
        self.active_mask[i] = 1.0

    def elect_train_set(self) -> np.ndarray:
        """Reference vote semantics — delegates to
        :func:`~p2pfl_tpu.parallel.spmd.elect_train_set_mask`."""
        return elect_train_set_mask(self.n, self._py_rng)

    def _make_perm_np(self, epochs: int) -> np.ndarray:
        take = self._nb * self.batch_size
        return np.stack(
            [
                np.stack(
                    [
                        self._rng.permutation(self._sizes[i])[:take].reshape(
                            self._nb, self.batch_size
                        )
                        for _ in range(epochs)
                    ]
                )
                for i in range(self.n)
            ]
        ).astype(np.int32)

    def _stage_chunk_inputs(self, ci: int, perm_np: np.ndarray):
        """Start chunk ``ci``'s host→device transfers (async device_put)."""
        c, c0 = self._chunk_size, ci * self._chunk_size
        perm_d = jax.device_put(perm_np[c0 : c0 + c])
        if self._resident:
            return perm_d, self.x_chunks[ci], self.y_chunks[ci]
        return perm_d, jax.device_put(self._x_np[ci]), jax.device_put(self._y_np[ci])

    def run_round(self, epochs: int = 1, eval: bool = False) -> dict:  # noqa: A002
        if self._vote and (self.round == 0 or Settings.VOTE_EVERY_ROUND):
            self.train_mask = self.elect_train_set()
        perm_np = self._make_perm_np(epochs)
        eff = self.train_mask * self.active_mask
        if eff.sum() == 0:
            raise RuntimeError("no active train-set nodes left")

        c = self.chunk_size
        # fully-masked chunks contribute nothing: never staged, never dispatched
        live = [ci for ci in range(self.n // c) if eff[ci * c : ci * c + c].sum() > 0]
        # overlapped staging: keep DEPTH chunks' inputs in flight so chunk
        # k+1's host→device copies (perm indices; x/y when streaming
        # non-resident data) run while chunk k's program computes. Depth 1
        # reproduces the serial order (stage → dispatch → stage → ...).
        depth = max(1, int(Settings.CHUNK_STAGING_DEPTH))
        staged = {ci: self._stage_chunk_inputs(ci, perm_np) for ci in live[:depth]}

        def chunk_args(ci):
            c0 = ci * c
            perm_d, x_d, y_d = staged.pop(ci)
            return (
                x_d, y_d, perm_d,
                jnp.asarray(eff[c0 : c0 + c]),
                jnp.asarray(self._samples[c0 : c0 + c]),
            )

        # loss/weight accumulate ON DEVICE: a float() per chunk would block
        # the host until that chunk's whole jitted program finishes,
        # serializing chunk k+1's staging behind chunk k's compute and
        # defeating the async dispatch pipeline this class exists for
        if Settings.CHUNK_FUSED_REDUCE:
            # overlapped path: partial sums ride donated accumulator args
            # through the chunk program — one dispatch per chunk, no
            # host-side per-leaf adds between chunks
            step = (
                _chunk_round_acc_donated
                if Settings.CHUNK_DONATE_BUFFERS
                else _chunk_round_acc_plain
            )
            acc = _zero_acc(self.params, self.opt_state)
            for i, ci in enumerate(live):
                acc = step(
                    *acc, self.params, self.opt_state, *chunk_args(ci),
                    module=self.module, tx=self.tx,
                )
                if i + depth < len(live):
                    staged[live[i + depth]] = self._stage_chunk_inputs(
                        live[i + depth], perm_np
                    )
            psum, osum, wsum, loss_acc = acc
            fin = _finalize_keep if self.keep_opt_state else _finalize_fresh
            self.params, self.opt_state = fin(
                psum, osum, wsum, self.params, self.opt_state,
                tx=self.tx, keep_opt=self.keep_opt_state,
            )
        else:
            # serial reference path (CHUNK_FUSED_REDUCE=False): host-side
            # tree adds after every chunk — the bit-parity baseline
            psum = osum = None
            wsum = jnp.float32(0.0)
            loss_acc = jnp.float32(0.0)
            for i, ci in enumerate(live):
                p_c, o_c, w_c, l_c = _chunk_round(
                    self.params, self.opt_state, *chunk_args(ci),
                    module=self.module, tx=self.tx,
                )
                if i + depth < len(live):
                    staged[live[i + depth]] = self._stage_chunk_inputs(
                        live[i + depth], perm_np
                    )
                if psum is None:
                    psum, osum = p_c, o_c
                else:
                    psum = jax.tree.map(jnp.add, psum, p_c)
                    osum = jax.tree.map(
                        lambda a, b: jnp.add(a, b) if _is_inexact(a) else a, osum, o_c
                    )
                wsum = wsum + w_c
                loss_acc = loss_acc + l_c * w_c

            self.params = jax.tree.map(
                lambda s, ref: (s / wsum).astype(ref.dtype), psum, self.params
            )
            if self.keep_opt_state:
                self.opt_state = jax.tree.map(
                    lambda s, ref: (s / wsum).astype(ref.dtype) if _is_inexact(ref) else s,
                    osum,
                    self.opt_state,
                )
            else:
                self.opt_state = jax.jit(self.tx.init)(self.params)
        self.round += 1
        entry: dict = {"round": self.round, "train_loss": float(loss_acc / wsum)}
        if eval:
            entry.update(self.evaluate())
        self.history.append(entry)
        return entry

    def evaluate(self) -> dict:
        losses, accs = [], []
        for c0 in range(0, self.n, self.chunk_size):
            loss, acc = _chunk_eval(
                self.params,
                self.x_test[c0 : c0 + self.chunk_size],
                self.y_test[c0 : c0 + self.chunk_size],
                module=self.module,
            )
            losses.append(np.asarray(loss))
            accs.append(np.asarray(acc))
        return {
            "test_loss": float(np.mean(np.concatenate(losses))),
            "test_acc": float(np.mean(np.concatenate(accs))),
        }

    def round_flops(self, epochs: int = 1, hw: bool = False) -> Optional[float]:
        """Scan-aware FLOPs of one full round (all N nodes).

        The step recomputes nothing, so the executed FLOPs are the model's:
        ``hw`` is accepted and changes nothing.
        """
        from p2pfl_tpu.management.profiling import compiled_flops

        def one_step(p, o, bx, by):
            def loss_fn(p_):
                return _loss(p_, self.module, bx, by)[0]

            loss, grads = jax.value_and_grad(loss_fn)(p)
            updates, o = self.tx.update(grads, o, p)
            return optax.apply_updates(p, updates), o, loss

        x0 = self.x_chunks[0] if self._resident else self._x_np[0]
        y0 = self.y_chunks[0] if self._resident else self._y_np[0]
        bx = jnp.asarray(x0[0, : self.batch_size])
        by = jnp.asarray(y0[0, : self.batch_size])
        step = compiled_flops(jax.jit(one_step), self.params, self.opt_state, bx, by)
        if step is None:
            return None
        return self.n * epochs * self._nb * step

    @classmethod
    def from_dataset(
        cls,
        model: FlaxModel,
        dataset: FederatedDataset,
        n_nodes: int,
        chunk_size: int,
        strategy: str = "iid",
        alpha: float = 0.5,
        **kwargs,
    ) -> "ChunkedFederation":
        shards = [dataset.partition(i, n_nodes, strategy, alpha) for i in range(n_nodes)]
        return cls(model, shards, chunk_size, **kwargs)
