"""One-program SPMD federation: the ICI "transport".

The reference moves weights between nodes as pickled gRPC payloads and
aggregates them in Python (``p2pfl/learning/aggregators/fedavg.py:43-60``,
``grpc_client.py:142-179``). Here an entire federated round is ONE jitted
SPMD program over a ``(nodes, model)`` mesh:

- node-stacked params/opt-state/data arrays ``[N, ...]`` are sharded over
  the ``nodes`` axis — each chip owns its nodes' replicas;
- local training is a per-node ``lax.scan`` epoch, vectorized over the node
  axis (XLA partitions it across the mesh — zero communication);
- FedAvg is a masked, sample-weighted reduction over the node axis that XLA
  lowers to a single fp32 all-reduce over ICI, and the broadcast back is the
  reference's "diffusion" stage;
- election (the reference's vote protocol, ``vote_train_set_stage.py``) runs
  on host — it's a few hundred bytes — and enters the program as a ``[N]``
  mask.

Nothing touches the host inside a round: data lives device-resident across
rounds, per-round shuffles enter as ``[N, take]`` int32 index arrays.

Semantics preserved from the reference round (SURVEY §3.3): train-set
election in round 0 only, sample-weighted FedAvg over the train set,
aggregated model diffused to every node, optimizer state reset on
aggregation (the reference's ``set_parameters`` builds a fresh ``Trainer``
each round, ``lightning_learner.py:180-198``). Trades the reference's
asynchronous gossip for bulk-synchronous collectives — same round outcome,
orders of magnitude less overhead (SURVEY §7 "gossip semantics on
collectives").
"""

from __future__ import annotations

import math
import random
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from p2pfl_tpu.learning.dataset import FederatedDataset
from p2pfl_tpu.learning.learner import _loss, _prox_term, adam, ce_eval, sgd
from p2pfl_tpu.management.profiling import dispatch_span, host_annotation, note_placed, scope, setup_span
from p2pfl_tpu.models.base import FlaxModel
from p2pfl_tpu.settings import Settings

Pytree = Any


# ---- pure round program (module-level => one jit cache for all federations) ----


def _local_epoch(
    params, opt_state, xs, ys, module, tx,
    prox_mu: float = 0.0, anchor=None, corr=None,
    dp_clip: float = 0.0, dp_noise: float = 0.0, key=None,
    accumulate_grads: bool = False,
):
    """One node's epoch: scan of SGD steps (identical math to JaxLearner).

    The gradient is taken inside each scan step, so no activation outlives
    its step: memory is traded in the model (``TransformerConfig.remat``),
    never by a checkpoint around the loss here.

    ``prox_mu``/``anchor``: FedProx proximal pull toward the round's global
    model. ``corr``: SCAFFOLD control-variate correction ``c − c_i`` added
    to every step's gradient (pre-cast to the param dtype by the caller —
    the per-step ``astype`` is a no-op then). ``dp_clip > 0``: DP-SGD —
    per-example clipped grads + Gaussian noise (multiplier ``dp_noise``,
    rng ``key``). ``accumulate_grads=True`` additionally carries the fp32
    sum of RAW step gradients (pre-correction) through the scan and returns
    it as a fourth output — the SCAFFOLD fused-ci path derives each node's
    new control variate from it without retaining the round-start params.
    """
    import optax

    gsum0 = (
        jax.tree.map(lambda p_: jnp.zeros(p_.shape, jnp.float32), params)
        if accumulate_grads
        else None
    )

    if dp_clip > 0.0:
        from p2pfl_tpu.learning.privacy import dp_grads

        def loss_one(p_, xi, yi):
            loss = _loss(p_, module, xi[None], yi[None])[0]
            if prox_mu > 0.0:
                loss = loss + _prox_term(p_, anchor, prox_mu)
            return loss

        def dp_step(carry, batch):
            p, o, k, gs = carry
            x, y = batch
            k, sub = jax.random.split(k)
            with scope("grad"):
                grads, loss = dp_grads(loss_one, p, x, y, dp_clip, dp_noise, sub)
            with scope("optimizer"):
                if accumulate_grads:
                    gs = jax.tree.map(lambda s, g: s + g.astype(jnp.float32), gs, grads)
                if corr is not None:
                    grads = jax.tree.map(lambda g, c: g + c.astype(g.dtype), grads, corr)
                updates, o = tx.update(grads, o, p)
                p = optax.apply_updates(p, updates)
            return (p, o, k, gs), loss

        (params, opt_state, _, gsum), losses = jax.lax.scan(
            dp_step, (params, opt_state, key, gsum0), (xs, ys)
        )
        if accumulate_grads:
            return params, opt_state, jnp.mean(losses), gsum
        return params, opt_state, jnp.mean(losses)

    def step(carry, batch):
        p, o, gs = carry
        x, y = batch

        def loss_fn(p_):
            loss = _loss(p_, module, x, y)[0]  # CE + sown aux (canonical definition)
            if prox_mu > 0.0:
                loss = loss + _prox_term(p_, anchor, prox_mu)
            return loss

        with scope("grad"):
            loss, grads = jax.value_and_grad(loss_fn)(p)
        with scope("optimizer"):
            if accumulate_grads:
                gs = jax.tree.map(lambda s, g: s + g.astype(jnp.float32), gs, grads)
            if corr is not None:
                grads = jax.tree.map(lambda g, c: g + c.astype(g.dtype), grads, corr)
            updates, o = tx.update(grads, o, p)
            p = optax.apply_updates(p, updates)
        return (p, o, gs), loss

    (params, opt_state, gsum), losses = jax.lax.scan(
        step, (params, opt_state, gsum0), (xs, ys)
    )
    if accumulate_grads:
        return params, opt_state, jnp.mean(losses), gsum
    return params, opt_state, jnp.mean(losses)


def _node_round_core(
    params,
    opt_state,
    xs,  # [E, nb, bs, ...] all local epochs' batches
    ys,  # [E, nb, bs]
    weight,  # fp32 scalar sample count (traced: reweighting never retraces)
    x_test=None,
    y_test=None,
    *,
    module,
    tx,
    prox_mu: float = 0.0,
    with_acc: bool = True,
    agg_dtype: str = "float32",
):
    """Trace-time body of :func:`fused_node_round` — one node's round.

    Shared by the overlay fused round (single-chip dispatch) and the
    submesh federation's per-slice dispatch
    (``parallel/submesh.py submesh_node_round``), so the two paths cannot
    drift: at ``model_parallel=1`` the sharded program IS this program,
    which is the bit-parity contract.
    """
    out = {}
    if x_test is not None:
        e_loss, logits = ce_eval(params, module, x_test, y_test)
        out["eval_loss"] = e_loss
        out["eval_acc"] = jnp.mean(
            (jnp.argmax(logits, axis=-1) == y_test).astype(jnp.float32)
        )
    anchor = params if prox_mu > 0.0 else None

    def epoch(carry, batch):
        p, o = carry
        exs, eys = batch
        p, o, loss = _local_epoch(
            p, o, exs, eys, module, tx, prox_mu=prox_mu, anchor=anchor
        )
        return (p, o), loss

    (params, opt_state), losses = jax.lax.scan(epoch, (params, opt_state), (xs, ys))
    out["params"] = params
    out["opt_state"] = opt_state
    # [E] per-epoch mean losses — the caller logs the same per-epoch
    # series the staged fit() produces (one metric point per epoch)
    out["train_losses"] = losses
    if with_acc:
        # weighted fold in Settings.AGG_DTYPE (the same accumulate dtype
        # the staged fedavg kernel uses), zero-init order identical to the
        # staged aggregate's ``w·p`` term (0 + w·p ≡ w·p) — the bit-parity
        # anchor for tests/test_fused_round.py
        out["psum"] = jax.tree.map(
            lambda p: p.astype(agg_dtype) * weight.astype(agg_dtype), params
        )
        out["wsum"] = weight.astype(agg_dtype)
    return out


@partial(
    jax.jit,
    static_argnames=("module", "tx", "prox_mu", "with_acc", "agg_dtype"),
    donate_argnums=(1,),
)
def fused_node_round(
    params,
    opt_state,
    xs,  # [E, nb, bs, ...] all local epochs' batches
    ys,  # [E, nb, bs]
    weight,  # fp32 scalar sample count (traced: reweighting never retraces)
    x_test=None,
    y_test=None,
    *,
    module,
    tx,
    prox_mu: float = 0.0,
    with_acc: bool = True,
    agg_dtype: str = "float32",
):
    """ONE overlay node's whole round compute as ONE donated dispatch.

    The overlay (gossip Node) round used to cross the host at every stage
    boundary: an eval dispatch, one ``train_epoch`` dispatch per epoch with
    a blocking ``float(loss)`` between each, then host-side re-weighting at
    aggregation time. This program fuses all of it — the eval forward of
    the INCOMING params (TrainStage evaluates before training, pure-CE
    :func:`~p2pfl_tpu.learning.learner.ce_eval` so the metric stays
    comparable with the staged path), the epoch ``lax.scan`` (shared
    :func:`_local_epoch` math — bit-comparable to ``train_epoch``), and the
    node's own partial-aggregation fold ``psum = weight × params'`` in fp32
    (the chunked-federation accumulator algebra from ``parallel/chunked.py``
    applied at the Train→Aggregate seam) — so ``TrainStage`` issues exactly
    one device dispatch and nothing on the model plane syncs to host.

    ``opt_state`` is donated (round-carried state, exactly like
    ``train_epoch``); ``params`` is NOT — with the zero-copy in-memory
    transport other nodes' aggregators may hold references to these exact
    buffers. Returns a dict of device values: ``params``, ``opt_state``,
    ``train_losses`` (the [E] per-epoch mean-loss vector — the same series
    the staged path logs point by point), ``psum``/``wsum`` when
    ``with_acc`` (the :class:`~p2pfl_tpu.learning.weights.ModelUpdate.
    partial_acc` payload, accumulated in ``agg_dtype`` exactly like the
    staged fedavg kernel), ``eval_loss``/``eval_acc`` when test data was
    passed. All metrics stay device values — the caller batches their D2H
    into one flush per round instead of one sync per step.
    """
    return _node_round_core(
        params, opt_state, xs, ys, weight, x_test, y_test,
        module=module, tx=tx, prox_mu=prox_mu, with_acc=with_acc,
        agg_dtype=agg_dtype,
    )


def _aggregate(p_used, mask, weights, sel_idx, agg: str, trim: int, center=None, clip_tau: float = 1.0):
    """Combine node-stacked params [N, ...] into one model (fp32 accumulate).

    ``sel_idx`` is the [K] array of train-set ∩ active node indices
    (host-computed, K static per trace). The robust aggregators operate on
    the gathered [K, ...] stack only — non-elected / dropped slots hold
    stale copies of the previous aggregate and would otherwise dominate the
    coordinate-wise median and win Krum's distance score, silently freezing
    training (mirrors host Node mode, where robust aggregators only ever
    see train-set models).
    """
    from p2pfl_tpu.ops import aggregation as ops

    if agg == "fedavg":
        w = (mask * weights).astype(jnp.float32)
        wn = w / jnp.sum(w)
        return jax.tree.map(
            lambda x: jnp.tensordot(wn, x.astype(jnp.float32), axes=(0, 0)).astype(x.dtype),
            p_used,
        )
    k = sel_idx.shape[0]
    p_sel = jax.tree.map(lambda x: jnp.take(x, sel_idx, axis=0), p_used)
    if agg == "median":
        return jax.tree.map(
            lambda x: jnp.median(x.astype(jnp.float32), axis=0).astype(x.dtype), p_sel
        )
    if agg == "trimmed_mean":
        # clamp like the host-side TrimmedMean class: 2*trim must leave >=1 row
        t = min(trim, (k - 1) // 2)

        def tm(x):
            xs = jnp.sort(x.astype(jnp.float32), axis=0)
            kept = jax.lax.slice_in_dim(xs, t, k - t, axis=0)
            return jnp.mean(kept, axis=0).astype(x.dtype)

        return jax.tree.map(tm, p_sel)
    if agg == "krum":
        idx = ops.krum_select(p_sel, n_byzantine=trim, multi=1)

        def pick(x):
            return jnp.take(x, idx, axis=0).astype(jnp.float32).mean(axis=0).astype(x.dtype)

        return jax.tree.map(pick, p_sel)
    if agg == "bulyan":
        # iterated Krum selection (θ = K − 2f picks, re-scored each pick)
        # then β = f trimmed mean — all static shapes: the removal keeps
        # K−i−1 rows via an index shift around the traced Krum pick
        f = trim
        if k < 4 * f + 3:
            raise ValueError(f"Bulyan needs K >= 4f + 3 (K={k}, f={f})")
        theta = k - 2 * f
        cur = p_sel
        orig = jnp.arange(k, dtype=jnp.int32)
        chosen = []
        for i in range(theta):
            m = k - i
            idx = ops.krum_select(cur, n_byzantine=f, multi=1)[0]
            chosen.append(orig[idx])
            pos = jnp.arange(m - 1, dtype=jnp.int32)
            keep = jnp.where(pos < idx, pos, pos + 1)  # skip the pick
            cur = jax.tree.map(lambda x: jnp.take(x, keep, axis=0), cur)
            orig = jnp.take(orig, keep)
        sel = jnp.stack(chosen)
        sel_tree = jax.tree.map(lambda x: jnp.take(x, sel, axis=0), p_sel)
        return ops.trimmed_mean(sel_tree, trim=f)
    if agg == "clip":
        # centered clipping (Karimireddy et al. 2021): center = previous
        # round's global (every slot held it identically pre-training)
        return ops.centered_clip(p_sel, center, clip_tau)
    raise ValueError(f"unknown aggregator {agg}")


def _round_core(
    stacked_params,  # [N, ...] pytree
    opt_states,  # [N, ...] pytree
    x_all,  # [N, S, ...] node-resident datasets
    y_all,  # [N, S]
    perm,  # [N, epochs, nb, bs] int32 shuffle indices (host-generated)
    mask,  # [N] 1.0 = in train set
    weights,  # [N] sample counts
    sel_idx,  # [K] int32 indices of mask==1 rows (robust aggregation support)
    *,
    module,
    tx,
    agg: str = "fedavg",
    trim: int = 0,
    clip_tau: float = 1.0,
    out_sharding=None,
    keep_opt_state: bool = False,
    prox_mu: float = 0.0,
    scaffold: bool = False,
    scaffold_fused_ci: bool = True,  # ci⁺ from the scan's grad mean (fast path)
    local_lr: float = 1e-3,
    c_global=None,  # SCAFFOLD server control variate (replicated pytree)
    c_local=None,  # SCAFFOLD per-node control variates [N, ...]
    server_opt: str = "",  # FedOpt: "adam" | "yogi" | "adagrad" ("" = plain)
    server_lr: float = 0.1,
    opt_m=None,  # FedOpt server first/second moments (replicated pytrees)
    opt_v=None,
    opt_t=None,  # FedOpt server step count (scalar, 1-based)
    dp_clip: float = 0.0,  # DP-SGD clip norm (0 = off)
    dp_noise: float = 0.0,  # DP-SGD noise multiplier
    dp_keys=None,  # [N, 2] uint32 per-node rng keys (required when dp_clip > 0)
):
    """One federated round's device program (train → aggregate → diffuse).

    Pure trace-time function shared by :func:`spmd_round` (one jitted round)
    and :func:`spmd_rounds_fused` (many rounds in one dispatch). Returns
    ``(out_params, out_opt, mean_loss, scaffold_state, fedopt_state,
    agg_params)`` where the two state tuples are ``()`` when the feature is
    off. ``prox_mu`` enables FedProx; ``scaffold`` threads SCAFFOLD control
    variates through local steps (Karimireddy et al. 2020); ``server_opt``
    applies a FedOpt server step to the aggregate (Reddi et al. 2021).
    """
    n = mask.shape[0]

    # SCAFFOLD correction c − c_i: materialized ONCE for all nodes outside
    # ``node_fn``, pre-cast to the param compute dtype — under vmap the
    # per-node closure re-derived it from the replicated fp32 ``c_global``
    # inside the batched program (an N-way broadcast of the full variate
    # plus a per-step astype); hoisted, it is one subtraction + cast whose
    # result the epoch scans consume directly.
    corr_all = (
        jax.tree.map(
            lambda c, cl, p: (c[None] - cl).astype(p.dtype),
            c_global, c_local, stacked_params,
        )
        if scaffold
        else None
    )

    # gather per-epoch batches: idx [epochs, nb, bs] → x[idx] [epochs, nb, bs, ...]
    def node_fn(params, opt_state, x, y, idx, ci, corr, dp_key):
        fused_ci = scaffold and scaffold_fused_ci
        # the anchor (round-start params) is retained across the epoch scan
        # only when something still needs it afterwards — the fused-ci path
        # doesn't, which releases two full-model fp32 buffers per node
        anchor = params if (prox_mu > 0.0 or (scaffold and not fused_ci)) else None

        def epoch_body(carry, ep_idx):
            p, o, k, gs = carry
            xs = jnp.take(x, ep_idx, axis=0)  # [nb, bs, ...]
            ys = jnp.take(y, ep_idx, axis=0)
            sub = None
            if dp_clip > 0.0:
                k, sub = jax.random.split(k)
            out = _local_epoch(
                p, o, xs, ys, module, tx,
                prox_mu=prox_mu, anchor=anchor, corr=corr,
                dp_clip=dp_clip, dp_noise=dp_noise, key=sub,
                accumulate_grads=fused_ci,
            )
            if fused_ci:
                p, o, loss, g_ep = out
                gs = jax.tree.map(jnp.add, gs, g_ep)
            else:
                p, o, loss = out
            return (p, o, k, gs), loss

        k0 = dp_key if dp_clip > 0.0 else jnp.zeros((2,), jnp.uint32)
        gs0 = (
            jax.tree.map(lambda p_: jnp.zeros(p_.shape, jnp.float32), params)
            if fused_ci
            else None
        )
        (params, opt_state, _, gsum), losses = jax.lax.scan(
            epoch_body, (params, opt_state, k0, gs0), idx
        )
        k_steps = idx.shape[0] * idx.shape[1]
        if fused_ci:
            # under plain SGD, y_i = x − η·Σ(g_t + c − c_i), so option II's
            # c_i⁺ = c_i − c + (x − y_i)/(K·η) reduces EXACTLY to mean_t(g_t):
            # the scan's fp32 grad mean IS the new variate — no round-start
            # params retained, no large-magnitude cancellation
            ci_new = jax.tree.map(lambda gs_: gs_ / k_steps, gsum)
        elif scaffold:
            # c_i⁺ = c_i − c + (x_global − y_i)/(K·η)  (SCAFFOLD option II)
            ci_new = jax.tree.map(
                lambda cl, c, a, p: cl
                - c
                + (a.astype(jnp.float32) - p.astype(jnp.float32)) / (k_steps * local_lr),
                ci, c_global, anchor, params,
            )
        else:
            ci_new = ci
        return params, opt_state, jnp.mean(losses), ci_new

    key_ax = 0 if dp_clip > 0.0 else None
    keys = dp_keys if dp_clip > 0.0 else None
    if scaffold:
        trained_p, trained_o, losses, ci_new = jax.vmap(
            node_fn, in_axes=(0, 0, 0, 0, 0, 0, 0, key_ax)
        )(stacked_params, opt_states, x_all, y_all, perm, c_local, corr_all, keys)
    else:
        trained_p, trained_o, losses, _ = jax.vmap(
            node_fn, in_axes=(0, 0, 0, 0, 0, None, None, key_ax)
        )(stacked_params, opt_states, x_all, y_all, perm, None, None, keys)

    # everything after the last local step — selection, aggregation, the
    # server step, diffusion, SCAFFOLD's variates — is the round's fold
    with scope("fold"):
        # non-train-set nodes contribute their previous params (they don't train)
        def sel(new, old):
            m = mask.reshape((n,) + (1,) * (new.ndim - 1)).astype(new.dtype)
            return new * m + old * (1 - m)

        p_used = jax.tree.map(sel, trained_p, stacked_params)
        # clip center = the round's shared starting model. Under normal
        # diffusion every slot holds it identically; the coordinate-wise median
        # over the elected rows recovers it exactly in that case AND stays
        # robust if a slot's incoming copy was tampered with (taking row 0
        # verbatim would let a poisoned slot choose the center).
        center = (
            jax.tree.map(
                lambda x: jnp.median(
                    jnp.take(x, sel_idx, axis=0).astype(jnp.float32), axis=0
                ),
                stacked_params,
            )
            if agg == "clip"
            else None
        )
        agg_params = _aggregate(
            p_used, mask, weights, sel_idx, agg, trim, center=center, clip_tau=clip_tau
        )

        fedopt_state = ()
        if server_opt:
            # FedOpt server step on the pseudo-gradient prev_global − aggregate
            # (node slot 0's incoming params ARE the previous global — diffusion
            # left every slot identical)
            from p2pfl_tpu.ops.aggregation import fedopt_update

            prev_global = jax.tree.map(lambda x: x[0], stacked_params)
            agg_params, opt_m_out, opt_v_out = fedopt_update(
                prev_global, agg_params, opt_m, opt_v, opt_t,
                opt=server_opt, lr=server_lr,
            )
            fedopt_state = (opt_m_out, opt_v_out)

        # diffusion: every node receives the aggregate
        out_params = jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n, *a.shape)), agg_params)
        if out_sharding is not None:
            # pin the node-stacked layout so round k+1 reuses round k's executable
            # (otherwise the broadcast's replicated layout forces a relayout+retrace)
            out_params = jax.tree.map(
                lambda a: jax.lax.with_sharding_constraint(a, out_sharding), out_params
            )
        if keep_opt_state:
            # documented improvement over the reference: carry Adam moments
            # across rounds (the reference rebuilds its Trainer per round,
            # losing them — slower convergence)
            out_opt = trained_o
        else:
            out_opt = jax.vmap(tx.init)(out_params)
        if out_sharding is not None:
            # vmap(tx.init) outputs otherwise come back replicated, flipping the
            # opt-state layout between rounds and forcing a recompile per variant
            out_opt = jax.tree.map(
                lambda a: jax.lax.with_sharding_constraint(a, out_sharding), out_opt
            )
        mean_loss = jnp.mean(losses, where=mask.astype(bool))

        scaffold_state = ()
        if scaffold:
            # only train-set nodes commit their new control variates; the server
            # variate moves by |S|/N times the mean train-set delta
            def selc(new, old):
                m_ = mask.reshape((n,) + (1,) * (new.ndim - 1)).astype(new.dtype)
                return new * m_ + old * (1 - m_)

            c_local_out = jax.tree.map(selc, ci_new, c_local)
            n_train = jnp.maximum(jnp.sum(mask), 1.0)
            frac = n_train / n

            def upd(c, cn, co):
                m_ = mask.reshape((n,) + (1,) * (cn.ndim - 1))
                delta = jnp.sum((cn - co) * m_, axis=0) / n_train
                return c + frac * delta

            c_global_out = jax.tree.map(upd, c_global, ci_new, c_local)
            if out_sharding is not None:
                c_local_out = jax.tree.map(
                    lambda a: jax.lax.with_sharding_constraint(a, out_sharding), c_local_out
                )
            scaffold_state = (c_global_out, c_local_out)

    return out_params, out_opt, mean_loss, scaffold_state, fedopt_state, agg_params


def _agg_acc(module, agg_params, x_test, y_test):
    """Mean accuracy of the aggregated model over node-stacked test shards."""

    def node_acc(x, y):
        logits = module.apply({"params": agg_params}, x)
        return jnp.mean((jnp.argmax(logits, axis=-1) == y).astype(jnp.float32))

    return jnp.mean(jax.vmap(node_acc)(x_test, y_test))


_ROUND_STATICS = (
    # clip_tau is deliberately NOT static: it traces as a scalar operand
    # (ops.centered_clip takes tau traced), so tuning it never recompiles
    "module", "tx", "agg", "trim", "out_sharding", "keep_opt_state",
    "prox_mu", "scaffold", "scaffold_fused_ci", "local_lr", "server_opt",
    "server_lr", "dp_clip", "dp_noise",
)

# SCAFFOLD variates and FedOpt moments are round-carried state exactly like
# params/opt — donating them lets XLA write each round's new variates into
# the old buffers (the fused span otherwise keeps two copies of the fp32
# [N, model] c_local alive across the whole scan)
_ROUND_DONATED_STATE = ("c_global", "c_local", "opt_m", "opt_v")


@partial(
    jax.jit, static_argnames=_ROUND_STATICS, donate_argnums=(0, 1),
    donate_argnames=_ROUND_DONATED_STATE,
)
def spmd_round(
    stacked_params, opt_states, x_all, y_all, perm, mask, weights, sel_idx,
    *, c_global=None, c_local=None, opt_m=None, opt_v=None,
    x_test=None, y_test=None, remat=None, **kw,
):
    """One federated round for all N nodes.

    Returns (params', opt', mean loss[, c_global', c_local'][, opt_m',
    opt_v'][, test acc]) — the accuracy of the aggregated model is fused
    into the same program when test data is given (one device dispatch for
    train + aggregate + diffuse + eval). See :func:`_round_core` for the
    algorithm knobs. ``remat`` is ignored (an unused operand, pruned from
    the program): ``benchmark/compile_check.py`` still passes it.
    """
    out_params, out_opt, mean_loss, scaffold_state, fedopt_state, agg_params = _round_core(
        stacked_params, opt_states, x_all, y_all, perm, mask, weights, sel_idx,
        c_global=c_global, c_local=c_local, opt_m=opt_m, opt_v=opt_v, **kw,
    )
    if x_test is None:
        return (out_params, out_opt, mean_loss, *scaffold_state, *fedopt_state)
    acc = _agg_acc(kw["module"], agg_params, x_test, y_test)
    return (out_params, out_opt, mean_loss, *scaffold_state, *fedopt_state, acc)


@partial(
    jax.jit, static_argnames=_ROUND_STATICS, donate_argnums=(0, 1),
    donate_argnames=_ROUND_DONATED_STATE,
)
def spmd_rounds_fused(
    stacked_params, opt_states, x_all, y_all, perms, mask, weights, sel_idx,
    *,
    c_global=None, c_local=None, opt_m=None, opt_v=None, opt_t=None,
    dp_keys=None, x_test=None, y_test=None, **kw,
):
    """R federated rounds as ONE device dispatch: ``lax.scan`` over rounds.

    ``perms``: [R, N, epochs, nb, bs] per-round shuffle indices. The mask
    (train set) is fixed for the whole span — exactly the reference's
    round semantics, where voting happens only in round 0
    (``round_finished_stage.py:69-70``). At small model scale a federated
    round is dispatch-dominated; fusing R rounds amortizes the host↔device
    round-trip R×. With test data, each round's aggregated model is
    evaluated in-program → accs [R] (an on-device convergence curve).

    Returns (params', opt', losses [R][, c_global', c_local'][, opt_m',
    opt_v'][, accs [R]]).
    """
    scaffold = kw.get("scaffold", False)
    server_opt = kw.get("server_opt", "")
    if opt_t is None:
        opt_t = jnp.float32(0.0)

    def body(carry, xsi):
        perm, kk = xsi
        p, o, cg, cl, m_, v_, t_ = carry
        t_next = t_ + 1.0
        out_p, out_o, loss, sstate, fstate, agg_params = _round_core(
            p, o, x_all, y_all, perm, mask, weights, sel_idx,
            c_global=cg, c_local=cl, opt_m=m_, opt_v=v_, opt_t=t_next,
            dp_keys=kk, **kw,
        )
        cg, cl = sstate if scaffold else (cg, cl)
        m_, v_ = fstate if server_opt else (m_, v_)
        ys = (loss,) if x_test is None else (loss, _agg_acc(kw["module"], agg_params, x_test, y_test))
        return (out_p, out_o, cg, cl, m_, v_, t_next), ys

    carry0 = (stacked_params, opt_states, c_global, c_local, opt_m, opt_v, opt_t)
    (p, o, cg, cl, m_, v_, _), ys = jax.lax.scan(body, carry0, (perms, dp_keys))
    scaffold_state = (cg, cl) if scaffold else ()
    fedopt_state = (m_, v_) if server_opt else ()
    if x_test is None:
        return (p, o, ys[0], *scaffold_state, *fedopt_state)
    return (p, o, ys[0], *scaffold_state, *fedopt_state, ys[1])


@partial(jax.jit, static_argnames=("module",))
def spmd_eval(stacked_params, x_test, y_test, *, module):
    """Per-node eval over node-stacked test shards. Returns ([N] loss, [N] acc)."""
    import optax

    def node_eval(params, x, y):
        logits = module.apply({"params": params}, x)
        loss = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
        acc = jnp.mean((jnp.argmax(logits, axis=-1) == y).astype(jnp.float32))
        return loss, acc

    return jax.vmap(node_eval)(stacked_params, x_test, y_test)


# ---- host-side driver ----


def tree_has_deleted(tree) -> bool:
    """True if any jax leaf of ``tree`` was consumed by a donated dispatch."""
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array):
            try:
                if leaf.is_deleted():
                    return True
            except Exception:  # noqa: BLE001 — backends without the probe
                continue
    return False


def stage_node_shards(datasets, batch_size: int) -> dict:
    """Host-side shard staging policy shared by every node-stacked driver.

    Pads each node's train shard to the common max by wrap-around, clips
    test shards to the common min, and sizes the per-round batch count
    from the common MIN shard (every node's per-round shuffle draws from
    its OWN sample range — see :func:`draw_node_perms`). One
    implementation, because the bit-parity contract between
    :class:`SpmdFederation` and
    :class:`~p2pfl_tpu.parallel.submesh.ShardedNodeFederation` depends on
    both drivers sizing ``nb`` and padding shards identically — a policy
    change here reaches both or neither.

    Returns ``{"x": [N x np [S, ...]], "y": ..., "x_test": ..., "y_test":
    ..., "sizes": [N], "nb": int}``.
    """
    sizes = [d.num_samples for d in datasets]
    tr_min, tr_max = min(sizes), max(sizes)
    te_min = min(len(d.y_test) for d in datasets)
    if tr_min < batch_size:
        raise ValueError(f"smallest shard ({tr_min}) < batch size ({batch_size})")

    def wrap(a: np.ndarray, target: int) -> np.ndarray:
        if len(a) == target:
            return a
        reps = -(-target // len(a))
        return np.concatenate([a] * reps, axis=0)[:target]

    return {
        "x": [wrap(d.x_train, tr_max) for d in datasets],
        "y": [wrap(d.y_train, tr_max) for d in datasets],
        "x_test": [d.x_test[:te_min] for d in datasets],
        "y_test": [d.y_test[:te_min] for d in datasets],
        "sizes": sizes,
        "nb": tr_min // batch_size,
    }


def draw_node_perms(
    rng: np.random.Generator, sizes: list[int], nb: int, batch_size: int, epochs: int
) -> np.ndarray:
    """Per-node per-epoch shuffle indices ``[N, epochs, nb, bs]`` (int32).

    Single source of the round's batch-draw rng stream: node-major, then
    epoch-major, each draw one ``rng.permutation`` over the node's OWN
    sample range. Shared by :class:`SpmdFederation` and
    :class:`~p2pfl_tpu.parallel.submesh.ShardedNodeFederation` so the two
    drivers consume identical rng state — the bit-parity tests compare
    them round for round on one seed.
    """
    take = nb * batch_size  # always <= min shard size
    return np.stack(
        [
            np.stack(
                [
                    rng.permutation(sizes[i])[:take].reshape(nb, batch_size)
                    for _ in range(epochs)
                ]
            )
            for i in range(len(sizes))
        ]
    ).astype(np.int32)


def elect_train_set_mask(n: int, py_rng) -> np.ndarray:
    """Round-0 election: every node casts weighted random votes
    (``vote_train_set_stage.py:78-81``); top ``TRAIN_SET_SIZE`` win.

    Shared by every federation runtime (SpmdFederation, ChunkedFederation)
    so the reference vote semantics have exactly one implementation.
    """
    names = list(range(n))
    tally: dict[int, int] = {}
    k = min(Settings.TRAIN_SET_SIZE, n)
    for _voter in names:
        picks = py_rng.sample(names, k)
        for i, cand in enumerate(picks):
            tally[cand] = tally.get(cand, 0) + math.floor(py_rng.randint(0, 1000) / (i + 1))
    ranked = sorted(tally.items(), key=lambda kv: (kv[1], kv[0]), reverse=True)
    mask = np.zeros(n, dtype=np.float32)
    for cand, _ in ranked[:k]:
        mask[cand] = 1.0
    return mask


class SpmdFederation:
    """N federated nodes as one SPMD program over a device mesh.

    The drop-in high-throughput alternative to running N ``Node`` objects:
    same round semantics, same aggregators, none of the per-message overhead.

    ``remat`` is accepted and has no effect on the step: the gradient is
    taken inside each scan step, where a checkpoint around the whole loss
    only ran the forward twice. Activation memory is traded in the model
    (``TransformerConfig.remat`` / ``remat_policy``).
    """

    @setup_span("fed_init")
    def __init__(
        self,
        model: FlaxModel,
        datasets: list[FederatedDataset],
        mesh: Optional[Mesh] = None,
        batch_size: int = 128,
        learning_rate: float = 1e-3,
        aggregator: str = "fedavg",
        trim: int = 0,
        clip_tau: float = 1.0,
        vote: bool = True,
        keep_opt_state: bool = False,
        remat: bool = False,
        participation: float = 1.0,
        seed: int = 0,
        prox_mu: float = 0.0,
        scaffold: bool = False,
        optimizer: str = "adam",
        server_opt: str = "",
        server_lr: float = 0.1,
        dp_clip: float = 0.0,
        dp_noise: float = 0.0,
        tx: Optional[optax.GradientTransformation] = None,
    ) -> None:
        self.model = model
        self.module = model.module
        self.n = len(datasets)
        if self.n < 1:
            raise ValueError("need at least one dataset shard")
        if Settings.SECURE_AGGREGATION:
            # secagg is a gossip-plane protocol: pairwise masks exist to hide
            # individual updates from the PEERS that relay them. An SPMD
            # federation is one program on one mesh — a single trust domain
            # where every "node" already shares an address space, so masking
            # would add cost while protecting against nobody. Refuse loudly
            # instead of silently training unmasked (docs/design.md,
            # "Secure aggregation and the SPMD runtime").
            raise ValueError(
                "SECURE_AGGREGATION=True has no effect inside SpmdFederation: "
                "the SPMD mesh is one trust domain (one program, one address "
                "space). Use gossip Node mode for secure aggregation, or set "
                "Settings.SECURE_AGGREGATION=False for mesh runs."
            )
        self.datasets = datasets
        self.batch_size = batch_size
        if scaffold and (optimizer != "sgd" or tx is not None):
            # the (x − y_i)/(K·η) variate update assumes η-scaled SGD steps;
            # adaptive local steps break the correction's variance-reduction
            raise ValueError("scaffold=True requires optimizer='sgd'")
        if tx is not None:
            # explicit optax transform — e.g. adam(warmup_cosine_schedule):
            # with keep_opt_state=True the schedule's step count survives
            # round boundaries, giving federated LR schedules (config 2)
            self.tx = tx
        else:
            self.tx = sgd(learning_rate) if optimizer == "sgd" else adam(learning_rate)
        self.learning_rate = learning_rate
        # FedProx proximal strength (0 = plain FedAvg local steps)
        self.prox_mu = float(prox_mu)
        self.scaffold = scaffold
        # FedOpt server optimizer ("" = plain aggregation result)
        if server_opt and server_opt not in ("adam", "yogi", "adagrad"):
            raise ValueError(f"unknown server_opt {server_opt!r}")
        self.server_opt = server_opt
        self.server_lr = server_lr
        # DP-SGD per-node local steps (clip norm + noise multiplier)
        self.dp_clip = float(dp_clip)
        self.dp_noise = float(dp_noise)
        if self.dp_noise > 0.0 and self.dp_clip <= 0.0:
            raise ValueError("dp_noise > 0 requires dp_clip > 0")
        if aggregator not in ("fedavg", "median", "trimmed_mean", "krum", "bulyan", "clip"):
            raise ValueError(f"unknown aggregator {aggregator!r}")
        self.aggregator = aggregator
        self.trim = trim
        if aggregator == "clip" and clip_tau <= 0:
            # tau <= 0 zeroes every clip factor: the aggregate would never
            # leave the center and training silently freezes
            raise ValueError(f"clip_tau must be > 0 (got {clip_tau})")
        self.clip_tau = float(clip_tau)
        self.keep_opt_state = keep_opt_state
        self.remat = remat
        if not 0.0 < participation <= 1.0:
            raise ValueError("participation must be in (0, 1]")
        self.participation = participation
        self._rng = np.random.default_rng(seed)
        self._py_rng = random.Random(seed)

        self.mesh = mesh if mesh is not None else self._default_mesh()
        axis = Settings.MESH_NODES_AXIS
        self._shard = NamedSharding(self.mesh, P(axis))  # shard axis 0 over nodes
        self._repl = NamedSharding(self.mesh, P())

        # device-resident data, truncated to common per-node sizes
        self._stage_data()
        # per-node (ε, δ) tracking: every node runs the same mechanism on
        # its own shard, so one accountant describes each node's guarantee
        self.accountant = None
        if self.dp_clip > 0.0 and self.dp_noise > 0.0:
            from p2pfl_tpu.learning.privacy import PrivacyAccountant

            q = min(1.0, self.batch_size / min(self._sizes))
            self.accountant = PrivacyAccountant(self.dp_noise, q)
        # node-stacked state: every node starts from the same params
        # (reference: initiator's weights seed the network, §3.3)
        self._stage_state()

        # election state (round-0 vote, reused thereafter — reference quirk)
        self.train_mask = np.ones(self.n, dtype=np.float32)
        self._vote = vote
        # failure semantics on a mesh (SURVEY §7 "failure semantics on a
        # pod"): chips don't crash independently, so node failure is modeled
        # by masking slots out of training AND aggregation — the collective
        # analogue of heartbeat eviction
        self.active_mask = np.ones(self.n, dtype=np.float32)
        self.round = 0
        self.history: list[dict] = []

    @setup_span("reset")
    def reset(self, seed: int = 0) -> None:
        """Back to round 0 with fresh state, keeping mesh/data/executables.

        Use this (not a new federation) to measure or restart: a new object
        builds a new Mesh and misses every jit cache.
        """
        self._rng = np.random.default_rng(seed)
        self._py_rng = random.Random(seed)
        self.train_mask = np.ones(self.n, dtype=np.float32)
        self.active_mask = np.ones(self.n, dtype=np.float32)
        self.round = 0
        self.history = []
        self._stage_state()

    @setup_span("stage_state")
    def _stage_state(self) -> None:
        # jitted with out_shardings: the broadcast + init run ON DEVICE and
        # land directly in the mesh layout (a host-side device_put would
        # re-upload N x model_size through the host link)
        n = self.n

        @partial(jax.jit, out_shardings=(self._shard, self._shard))
        def stage(tree):
            stacked = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n, *x.shape)), tree)
            return stacked, jax.vmap(self.tx.init)(stacked)

        self.params, self.opt_state = stage(self.model.params)
        self._server_t = 0  # FedOpt server step count (stays 0 without server_opt)
        if self.scaffold:
            # control variates start at zero (Karimireddy et al. 2020 §3);
            # the global variate replicates on the MESH (a device-0-committed
            # array would clash with the sharded args under jit)
            self.c_global = jax.device_put(
                jax.tree.map(
                    lambda x: jnp.zeros(x.shape, jnp.float32), self.model.params
                ),
                self._repl,
            )
            self.c_local = jax.device_put(
                jax.tree.map(
                    lambda x: jnp.zeros((n, *x.shape), jnp.float32), self.model.params
                ),
                self._shard,
            )
        if self.server_opt:
            zeros = jax.device_put(
                jax.tree.map(
                    lambda x: jnp.zeros(x.shape, jnp.float32), self.model.params
                ),
                self._repl,
            )
            self.opt_m = zeros
            self.opt_v = jax.tree.map(jnp.copy, zeros)
        note_placed(n, self.params, self.opt_state)

    def _default_mesh(self) -> Mesh:
        from p2pfl_tpu.parallel.mesh import federation_mesh

        devices = jax.devices()
        slots = min(self.n, len(devices))
        while self.n % slots != 0:  # fold nodes evenly onto mesh slots
            slots -= 1
        return federation_mesh(n_nodes=slots, devices=devices[:slots])

    @setup_span("data_put")
    def _stage_data(self) -> None:
        # node shards are padded (wrap-around) to a common static length so
        # they stack into one [N, S, ...] array, but each node's per-round
        # shuffle indices are drawn from its OWN sample range (``_make_perm_np``)
        # — so the FedAvg sample-count weights match the data each node
        # actually trains on (over rounds, every node covers its full shard).
        # Policy (padding/clipping/nb sizing) lives in the shared
        # :func:`stage_node_shards` — the submesh driver consumes the same
        # helper, which is what keeps the two drivers' rng streams parity.
        staged = stage_node_shards(self.datasets, self.batch_size)
        self.x_all = jax.device_put(np.stack(staged["x"]), self._shard)
        self.y_all = jax.device_put(np.stack(staged["y"]), self._shard)
        self.x_test = jax.device_put(np.stack(staged["x_test"]), self._shard)
        self.y_test = jax.device_put(np.stack(staged["y_test"]), self._shard)
        self._samples = jax.device_put(
            jnp.asarray([float(s) for s in staged["sizes"]]), self._shard
        )
        self._sizes = staged["sizes"]
        self._tr_size = len(staged["x"][0])
        self._nb = staged["nb"]
        note_placed(self.n, self.x_all, self.y_all, self.x_test, self.y_test, self._samples)

    # ---- election (host control plane — reference vote semantics) ----

    def elect_train_set(self) -> np.ndarray:
        """Reference vote semantics — delegates to
        :func:`elect_train_set_mask`."""
        return elect_train_set_mask(self.n, self._py_rng)

    # ---- round driver ----

    def _make_perm_np(self, epochs: int) -> np.ndarray:
        return draw_node_perms(self._rng, self._sizes, self._nb, self.batch_size, epochs)

    def _round_inputs(self, epochs: int) -> tuple:
        """``(perm, mask, sel_idx)`` of the next round, on the device: the
        host work a round does before its dispatch, in two phases a profiler
        trace can tell apart (``p2pfl:round_perm`` draws, ``p2pfl:round_put``
        transfers). Robust aggregators see only the [K] selected rows; K is
        static per mask pattern, so the executable is reused as long as K
        is stable."""
        with host_annotation("round_perm"):
            perm = self._make_perm_np(epochs)
            eff = self._effective_mask()
            sel = np.flatnonzero(eff).astype(np.int32)
        with host_annotation("round_put"):
            return (
                jax.device_put(perm, self._shard),
                jax.device_put(jnp.asarray(eff), self._shard),
                jax.device_put(sel, self._repl),
            )

    def _effective_mask(self) -> np.ndarray:
        """Train-set ∩ active nodes, optionally client-sampled per round."""
        effective = self.train_mask * self.active_mask
        if self.participation < 1.0:
            # FedAvg-style client sampling: each round a random fraction of
            # the eligible nodes trains (McMahan et al. 2017 C-fraction)
            eligible = np.flatnonzero(effective)
            k = max(1, round(self.participation * len(eligible)))
            chosen = self._rng.choice(eligible, size=k, replace=False)
            effective = np.zeros_like(effective)
            effective[chosen] = 1.0
        if effective.sum() == 0:
            raise RuntimeError("no active train-set nodes left")
        return effective

    def drop_node(self, i: int) -> None:
        """Mark a logical node failed: it stops training and contributing
        (the reference's heartbeat-eviction outcome, ``heartbeater.py:91-101``)."""
        self.active_mask[i] = 0.0

    def restore_node(self, i: int) -> None:
        self.active_mask[i] = 1.0

    def _algo_kwargs(self, opt_t: float) -> dict:
        """The ``_round_core`` algorithm knobs — single source of truth for
        run_round / run_fused / round_flops. A missed copy would silently
        change the compiled program (e.g. MFU counting the wrong FLOPs).
        ``opt_t`` is the FedOpt server step the program should use: the
        1-based step for a single round, the 0-based starting counter for a
        fused span (the scan body pre-increments)."""
        return dict(
            prox_mu=self.prox_mu,
            scaffold=self.scaffold,
            # static (traced-program) knob: read per call so flipping the
            # Setting reaches the next round's executable, never a stale one
            scaffold_fused_ci=bool(Settings.SCAFFOLD_FUSED_CI),
            local_lr=self.learning_rate,
            server_opt=self.server_opt,
            server_lr=self.server_lr,
            c_global=self.c_global if self.scaffold else None,
            c_local=self.c_local if self.scaffold else None,
            opt_m=self.opt_m if self.server_opt else None,
            opt_v=self.opt_v if self.server_opt else None,
            opt_t=jnp.float32(opt_t) if self.server_opt else None,
            dp_clip=self.dp_clip,
            dp_noise=self.dp_noise,
        )

    def _dp_round_keys(self, rounds: int = 0) -> Optional[jax.Array]:
        """Per-node DP rng keys: [N, 2] for one round, [R, N, 2] fused."""
        if self.dp_clip <= 0.0:
            return None
        root = jax.random.PRNGKey(int(self._rng.integers(2**31)))
        if rounds:
            keys = jax.random.split(root, rounds * self.n).reshape(rounds, self.n, 2)
            return jax.device_put(
                keys, NamedSharding(self.mesh, P(None, Settings.MESH_NODES_AXIS))
            )
        return jax.device_put(jax.random.split(root, self.n), self._shard)

    def run_round(self, epochs: int = 1, eval: bool = False) -> dict:  # noqa: A002
        if self._vote and (self.round == 0 or Settings.VOTE_EVERY_ROUND):
            self.train_mask = self.elect_train_set()
        perm, mask, sel_idx = self._round_inputs(epochs)
        try:
            with dispatch_span("spmd_round", "spmd", nodes=self.n, epochs=epochs, fed=id(self)):
                result = spmd_round(
                    self.params,
                    self.opt_state,
                    self.x_all,
                    self.y_all,
                    perm,
                    mask,
                    self._samples,
                    sel_idx,
                    module=self.module,
                    tx=self.tx,
                    agg=self.aggregator,
                    trim=self.trim,
                    clip_tau=self.clip_tau,
                    out_sharding=self._shard,
                    keep_opt_state=self.keep_opt_state,
                    x_test=self.x_test if eval else None,
                    y_test=self.y_test if eval else None,
                    dp_keys=self._dp_round_keys(),
                    **self._algo_kwargs(self._server_t + 1 if self.server_opt else 0),
                )
        except Exception:
            self._recover_donated_state()
            raise
        self.params, self.opt_state, loss = result[:3]
        i = 3
        if self.scaffold:
            self.c_global, self.c_local = result[i:i + 2]
            i += 2
        if self.server_opt:
            self.opt_m, self.opt_v = result[i:i + 2]
            self._server_t += 1
        if self.accountant is not None:
            self.accountant.step(epochs * self._nb)
        self.round += 1
        # keep the loss as a device scalar: rounds pipeline back-to-back with
        # no host sync; it coerces to float lazily (e.g. when printed)
        entry = {"round": self.round, "train_loss": loss}
        if eval:
            entry["test_acc"] = result[-1]  # acc is last (scaffold adds outputs)
        self.history.append(entry)
        return entry

    def _recover_donated_state(self) -> None:
        """Failed round dispatch: drop and rebuild any consumed donated state.

        ``spmd_round`` / ``spmd_rounds_fused`` donate params, opt state and
        the SCAFFOLD/FedOpt carries. A dispatch that dies mid-execution may
        already have consumed those buffers — leaving them in place poisons
        EVERY later round with "array has been deleted" deep inside jit
        argument processing (the exact failure mode PR 4 fixed for the
        encode path's EF store). Same remedy: drop and rebuild. Rebuilt
        state is the round-0 init (the consumed training progress is gone
        with the buffers — recorded loudly), which keeps the federation
        object usable for a retry/diagnosis instead of bricked.
        """
        from p2pfl_tpu.management.logger import logger

        donated = [self.params, self.opt_state]  # a None opt_state has no leaves
        if self.scaffold:
            donated += [self.c_global, self.c_local]
        if self.server_opt:
            donated += [self.opt_m, self.opt_v]
        if not any(tree_has_deleted(t) for t in donated):
            return
        logger.warning(
            "spmd",
            "Round dispatch failed after consuming donated buffers — "
            "rebuilding federation state from the round-0 init (training "
            "progress in the consumed buffers is lost)",
        )
        self._stage_state()

    def run(self, rounds: int, epochs: int = 1, eval_every: int = 0) -> list[dict]:
        for r in range(rounds):
            entry = self.run_round(epochs)
            if eval_every and (r + 1) % eval_every == 0:
                entry.update(self.evaluate())
        return self.history

    def _fused_inputs(self, rounds: int, epochs: int):
        """Guards + staged device inputs shared by every fused-span runner.

        Elects the round-0 train set if needed, rejects per-round
        voting/client sampling (a fused span needs one fixed mask), and
        returns ``(perms [R,N,epochs,nb,bs], mask, sel_idx)`` device-put
        with the span's shardings.
        """
        if self._vote and self.round == 0:
            self.train_mask = self.elect_train_set()
        if (self._vote and Settings.VOTE_EVERY_ROUND) or self.participation < 1.0:
            raise ValueError(
                "run_fused needs a fixed mask: per-round voting/client "
                "sampling re-elects between rounds — use run_round"
            )
        perms = jax.device_put(
            np.stack([self._make_perm_np(epochs) for _ in range(rounds)]),
            NamedSharding(self.mesh, P(None, Settings.MESH_NODES_AXIS)),
        )
        eff = self._effective_mask()
        mask = jax.device_put(jnp.asarray(eff), self._shard)
        sel_idx = jax.device_put(np.flatnonzero(eff).astype(np.int32), self._repl)
        return perms, mask, sel_idx

    def run_fused(self, rounds: int, epochs: int = 1, eval: bool = False) -> list[dict]:  # noqa: A002
        """Run ``rounds`` rounds as ONE device dispatch (``lax.scan``).

        At small model scale a round is dispatch-dominated — fusing
        amortizes the host↔device round-trip. The train set is fixed for
        the span (the reference's own semantics: voting happens only in
        round 0); per-round voting or client sampling needs
        :meth:`run_round`. With ``eval=True`` the per-round accuracy curve
        is computed on-device and returned in the history entries.
        """
        perms, mask, sel_idx = self._fused_inputs(rounds, epochs)
        try:
            with dispatch_span("spmd_rounds_fused", "spmd", nodes=self.n, rounds=rounds, fed=id(self)):
                result = spmd_rounds_fused(
                    self.params, self.opt_state, self.x_all, self.y_all, perms, mask,
                    self._samples, sel_idx,
                    module=self.module, tx=self.tx, agg=self.aggregator, trim=self.trim, clip_tau=self.clip_tau,
                    out_sharding=self._shard, keep_opt_state=self.keep_opt_state,
                    x_test=self.x_test if eval else None,
                    y_test=self.y_test if eval else None,
                    dp_keys=self._dp_round_keys(rounds),
                    **self._algo_kwargs(self._server_t),
                )
        except Exception:
            self._recover_donated_state()
            raise
        self.params, self.opt_state, losses = result[:3]
        i = 3
        if self.scaffold:
            self.c_global, self.c_local = result[i:i + 2]
            i += 2
        if self.server_opt:
            self.opt_m, self.opt_v = result[i:i + 2]
            self._server_t += rounds
            i += 2
        if self.accountant is not None:
            self.accountant.step(rounds * epochs * self._nb)
        accs = result[i] if eval else None
        entries = []
        for r in range(rounds):
            self.round += 1
            entry = {"round": self.round, "train_loss": losses[r]}
            if eval:
                entry["test_acc"] = accs[r]
            self.history.append(entry)
            entries.append(entry)
        return entries

    def round_flops(self, epochs: int = 1) -> Optional[float]:
        """FLOPs of one no-eval round, scan-trip-count aware.

        XLA's ``cost_analysis`` counts a ``lax.scan`` body ONCE regardless
        of trip count, so the whole-round program's figure misses
        ``epochs × nb − 1`` of every node's SGD steps (a ~16× undercount at
        nb=16 — this made round-1's MFU look 1.7% when the chip was really
        running ~10×+ that). Corrected here: the whole-round analysis (which
        counts aggregation/diffusion plus exactly one step per node) plus a
        scan-free single-step probe times the steps the analysis missed.
        """
        from p2pfl_tpu.management.profiling import compiled_flops

        perm, mask, sel_idx = self._round_inputs(epochs)
        # algorithm knobs change the compiled program — MFU must count the
        # program that actually runs
        base = compiled_flops(
            spmd_round,
            self.params, self.opt_state, self.x_all, self.y_all, perm, mask,
            self._samples, sel_idx,
            module=self.module, tx=self.tx, agg=self.aggregator, trim=self.trim, clip_tau=self.clip_tau,
            out_sharding=self._shard, keep_opt_state=self.keep_opt_state,
            dp_keys=self._dp_round_keys(),
            **self._algo_kwargs(self._server_t + 1 if self.server_opt else 0),
        )
        if base is None:
            return None
        step = self._single_step_flops()
        if step is None:
            return base
        return base + self.n * (epochs * self._nb - 1) * step

    def _probe_step_flops(self, loss_fn) -> Optional[float]:
        """Compiled FLOPs of ONE node's ONE SGD step, from shape-only probes.

        ``loss_fn(params, bx, by) -> scalar``. Shared by the LoRA and
        full-LM federations' ``round_flops`` (scan-trip-count pitfall: the
        probe is scan-free, so cost analysis counts it exactly once).
        """
        import optax

        from p2pfl_tpu.management.profiling import compiled_flops

        p1 = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), self.params
        )
        o1 = jax.eval_shape(self.tx.init, p1)  # ``self.opt_state`` may be None (LoRA, not kept)
        bx = jax.ShapeDtypeStruct(
            (self.batch_size,) + tuple(self.x_all.shape[2:]), self.x_all.dtype
        )
        by = jax.ShapeDtypeStruct(
            (self.batch_size,) + tuple(self.y_all.shape[2:]), self.y_all.dtype
        )

        def one_step(p, o, bx_, by_):
            _loss, grads = jax.value_and_grad(loss_fn)(p, bx_, by_)
            updates, o = self.tx.update(grads, o, p)
            return optax.apply_updates(p, updates), o

        return compiled_flops(jax.jit(one_step), p1, o1, bx, by)

    def _single_step_flops(self) -> Optional[float]:
        """Compiled FLOPs of ONE node's ONE SGD step (trip-count-1 scan, so
        the cost analysis counts it exactly once). Mirrors the round's
        per-step math including FedProx/DP variants."""
        from p2pfl_tpu.management.profiling import compiled_flops

        def one(a):
            return jax.ShapeDtypeStruct(a.shape[1:], a.dtype)

        p1 = jax.tree.map(one, self.params)
        o1 = jax.tree.map(one, self.opt_state)
        xs = jax.ShapeDtypeStruct(
            (1, self.batch_size) + tuple(self.x_all.shape[2:]), self.x_all.dtype
        )
        ys = jax.ShapeDtypeStruct(
            (1, self.batch_size) + tuple(self.y_all.shape[2:]), self.y_all.dtype
        )
        dp = self.dp_clip > 0.0

        def one_epoch(p, o, xs_, ys_, key=None):
            anchor = p if (self.prox_mu > 0.0 or self.scaffold) else None
            return _local_epoch(
                p, o, xs_, ys_, self.module, self.tx,
                prox_mu=self.prox_mu, anchor=anchor,
                dp_clip=self.dp_clip, dp_noise=self.dp_noise, key=key,
            )

        args = [p1, o1, xs, ys]
        if dp:
            args.append(jax.ShapeDtypeStruct((2,), jnp.uint32))
        return compiled_flops(jax.jit(one_epoch), *args)

    def evaluate(self) -> dict:
        loss, acc = spmd_eval(self.params, self.x_test, self.y_test, module=self.module)
        return {
            "test_loss": float(jnp.mean(loss)),
            "test_acc": float(jnp.mean(acc)),
            "per_node_acc": np.asarray(acc).tolist(),
        }

    # ---- checkpoint / resume (absent in the reference; SURVEY §5) ----

    def save(self, directory: str) -> None:
        from p2pfl_tpu.learning.checkpoint import save_federation

        save_federation(directory, self)

    def restore(self, directory: str, step: Optional[int] = None) -> None:
        from p2pfl_tpu.learning.checkpoint import restore_federation

        restore_federation(directory, self, step)

    # ---- interop ----

    def node_params(self, i: int) -> Pytree:
        """Extract one node's params (for parity checks with Node mode)."""
        return jax.tree.map(lambda x: x[i], self.params)

    @classmethod
    def from_dataset(
        cls,
        model: FlaxModel,
        dataset: FederatedDataset,
        n_nodes: int,
        strategy: str = "iid",
        alpha: float = 0.5,
        **kwargs,
    ) -> "SpmdFederation":
        shards = [dataset.partition(i, n_nodes, strategy, alpha) for i in range(n_nodes)]
        return cls(model, shards, **kwargs)
