"""Pipeline parallelism: GPipe-style microbatched stage pipeline over a mesh axis.

A stack of identical layers (e.g. transformer blocks) is split into
``P = mesh.shape[axis]`` contiguous stages; layer params stack on a leading
``[L, ...]`` axis that shards over the pipeline axis, so each device holds
``L/P`` layers. Microbatches stream through the stages: device ``s``
processes microbatch ``m`` at step ``s + m`` and hands its activation to
stage ``s+1`` via ``lax.ppermute`` — the classic fill/steady/drain schedule
with ``P - 1`` bubble steps on each side.

Everything is a single SPMD program under ``shard_map``: one ``lax.scan``
over ``M + P - 1`` steps, one ``ppermute`` per step riding ICI. Autodiff
goes straight through (``ppermute``'s transpose is the reverse permute), so
``jax.grad`` of a pipelined loss just works — the backward pass replays the
schedule in reverse.

The reference has no pipeline (or any tensor) parallelism anywhere
(SURVEY §2.9); this provides the PP axis of the multi-chip design, composing
with the ``nodes`` (federated DP), ``model`` (TP/SP) and expert (EP) axes.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

Pytree = Any


def pipeline_mesh(n_stages: int, devices=None, axis: str = "pipe") -> Mesh:
    """A 1-axis mesh of ``n_stages`` devices for pipeline tests/dryruns."""
    devices = list(devices if devices is not None else jax.devices())[:n_stages]
    if len(devices) < n_stages:
        raise ValueError(f"need {n_stages} devices, have {len(devices)}")
    return Mesh(np.array(devices), (axis,))


def stack_layers(per_layer_params: list[Pytree]) -> Pytree:
    """Stack per-layer param pytrees into one ``[L, ...]`` pytree."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer_params)


def _varying(x, axis: str):
    """shard_map typing: scan carries must be device-varying to match the
    values produced by axis_index/ppermute."""
    return lax.pcast(x, (axis,), to="varying")


def _pipeline_body(stage_params, xs, apply_layer: Callable, axis: str, n_stages: int):
    """Per-device body. stage_params: ``[L/P, ...]``; xs: ``[M, mb, ...]``
    (replicated). ``apply_layer(p_layer, act) -> (act, aux_scalar)``.
    Returns ``([M, mb, ...], aux)`` replicated (psum off the last stage);
    aux = sum over layers, mean over microbatches."""
    sid = lax.axis_index(axis)
    m_micro = xs.shape[0]
    total = m_micro + n_stages - 1
    perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]

    def apply_stage(p_stage, act):
        def one(act, p_layer):
            act, aux = apply_layer(p_layer, act)
            return act, aux

        act, auxs = lax.scan(one, act, p_stage)
        return act, jnp.sum(auxs)

    def step_fn(carry, step):
        act_in, ys, aux_acc = carry
        # stage 0 consumes the next microbatch; everyone else consumes the
        # activation handed over by the previous stage last step
        feed = xs[jnp.clip(step, 0, m_micro - 1)]
        inp = jnp.where(sid == 0, _varying(feed, axis), act_in)
        out, aux = apply_stage(stage_params, inp)
        # stage s holds real data only during steps [s, s + M): outside that
        # window it is chewing on fill/drain garbage whose aux must not count
        valid = jnp.logical_and(step >= sid, step < sid + m_micro)
        aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
        # the last stage emits microbatch step-(P-1) during drain
        oidx = jnp.clip(step - (n_stages - 1), 0, m_micro - 1)
        collect = jnp.logical_and(sid == n_stages - 1, step >= n_stages - 1)
        ys = ys.at[oidx].set(jnp.where(collect, out, ys[oidx]))
        act_next = lax.ppermute(out, axis, perm)
        return (act_next, ys, aux_acc), None

    act0 = _varying(jnp.zeros_like(xs[0]), axis)
    ys0 = _varying(jnp.zeros_like(xs), axis)
    aux0 = _varying(jnp.zeros((), jnp.float32), axis)
    (_, ys, aux_acc), _ = lax.scan(step_fn, (act0, ys0, aux0), jnp.arange(total))
    # only the last stage holds real outputs; psum replicates them to all.
    # aux: every stage contributes its layers' sum; normalize microbatches.
    ys = lax.psum(jnp.where(sid == n_stages - 1, ys, jnp.zeros_like(ys)), axis)
    aux = lax.psum(aux_acc, axis) / m_micro
    return ys, aux


def pipeline_apply(
    stacked_params: Pytree,
    x_microbatches: jax.Array,
    apply_layer: Callable[[Pytree, jax.Array], jax.Array],
    mesh: Mesh,
    axis: str = "pipe",
    with_aux: bool = False,
) -> jax.Array:
    """Run ``[M, mb, ...]`` microbatches through pipelined stacked layers.

    ``stacked_params``: pytree with leading layer axis ``[L, ...]``,
    ``L`` divisible by ``mesh.shape[axis]``; sharded over ``axis`` (each
    device keeps its own stage's slice — pass it pre-sharded or let
    ``shard_map`` split it). ``apply_layer(p_layer, act) -> act`` applies a
    single layer — or, with ``with_aux=True``, returns ``(act, aux_scalar)``
    and the call returns ``(out, aux)`` where aux is summed over layers and
    averaged over microbatches (how MoE balance losses ride the pipeline).
    Differentiable end to end.
    """
    n_stages = mesh.shape[axis]
    n_layers = jax.tree.leaves(stacked_params)[0].shape[0]
    if n_layers % n_stages != 0:
        raise ValueError(f"{n_layers} layers not divisible by {n_stages} stages")
    if with_aux:
        layer_fn = apply_layer
    else:
        def layer_fn(p_layer, act):
            return apply_layer(p_layer, act), jnp.zeros((), jnp.float32)

    fn = jax.shard_map(
        partial(_pipeline_body, apply_layer=layer_fn, axis=axis, n_stages=n_stages),
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=(P(), P()),
    )
    out, aux = fn(stacked_params, x_microbatches)
    return (out, aux) if with_aux else out


def pipelined_lm_apply(
    params: Pytree,
    tokens: jax.Array,
    cfg,
    mesh: Mesh,
    axis: str = "pipe",
    n_micro: int = 0,
    attn_fn: Callable | None = None,
    return_aux: bool = False,
) -> jax.Array:
    """Forward a :class:`~p2pfl_tpu.models.transformer.CausalLM` param tree
    with its block stack pipelined over ``mesh[axis]``.

    Embedding, final norm and the tied head are cheap and stay replicated;
    only the ``layer_i`` blocks stream through stages. ``n_micro`` defaults
    to the stage count (the minimum that fills the pipeline). The batch must
    divide into ``n_micro`` microbatches. Same modules and params as
    ``CausalLM.apply`` — forward ``attn_fn`` if the model was built with a
    non-default attention backend. A ``cfg.flash_config`` kernel schedule
    needs NO threading: the stages build their Blocks from ``cfg``, so the
    statically-keyed Pallas flash schedule rides into every stage program
    (and into any enclosing jit's cache key) through the config itself.

    MoE blocks (``cfg.n_experts > 0``): sown router losses are collected
    per stage and returned when ``return_aux=True`` (sum over layers, mean
    over microbatches — per-microbatch balance fractions, vs the monolithic
    model's full-batch fractions). Training an MoE pipeline MUST use
    ``return_aux=True`` and add the aux term, or routers never learn to
    balance.
    """
    from p2pfl_tpu.models.transformer import Block, RMSNorm

    if cfg.n_experts > 0 and not return_aux:
        raise ValueError(
            "MoE pipeline: pass return_aux=True and add the aux loss "
            "(silently dropping router balance losses breaks routing)"
        )
    n_stages = mesh.shape[axis]
    n_micro = n_micro or n_stages
    b = tokens.shape[0]
    if b % n_micro != 0:
        raise ValueError(f"batch {b} not divisible into {n_micro} microbatches")

    emb = params["embed"]
    x = emb[tokens].astype(cfg.dtype)  # [B, T, D]
    xm = x.reshape(n_micro, b // n_micro, *x.shape[1:])
    stacked = stack_layers([params[f"layer_{i}"] for i in range(cfg.n_layers)])
    block = Block(cfg, attn_fn)

    def apply_layer(p_layer, act):
        out, mut = block.apply({"params": p_layer}, act, mutable=["moe_losses"])
        leaves = jax.tree.leaves(mut)
        return out, (sum(leaves) if leaves else jnp.zeros((), jnp.float32))

    y, aux = pipeline_apply(stacked, xm, apply_layer, mesh, axis, with_aux=True)
    y = y.reshape(b, *x.shape[1:])
    y = RMSNorm(cfg.dtype, cfg.norm_eps).apply({"params": params["final_norm"]}, y)
    logits = jnp.dot(y, emb.T.astype(cfg.dtype)).astype(jnp.float32)
    return (logits, aux) if return_aux else logits
