"""Reference local training and FedAvg: Adam written out (Kingma & Ba 2015,
the bias-corrected form, epsilon outside the root — what ``optax.adam``
computes), the learning-rate schedules the cells use, and the sample-weighted
mean in numpy. Independent of optax and of the program."""

from __future__ import annotations

import math

import jax
import numpy as np


def learning_rate(opt: dict, step: int) -> float:
    """``opt`` is a cell's ``optimizer`` entry; ``step`` counts from 0."""
    if opt["schedule"] == "constant":
        return opt["learning_rate"]
    if opt["schedule"] == "warmup_cosine":
        warm, total = opt["warmup_steps"], opt["decay_steps"]
        if step < warm:
            return opt["init_value"] + (opt["peak_value"] - opt["init_value"]) * step / warm
        frac = min(1.0, (step - warm) / (total - warm))
        cosine = 0.5 * (1.0 + math.cos(math.pi * frac))
        return opt["end_value"] + (opt["peak_value"] - opt["end_value"]) * cosine
    raise ValueError(f"unknown schedule {opt['schedule']!r}")


def adam_step(grad_fn, b1=0.9, b2=0.999, eps=1e-8):
    """One local step — gradient, moments, bias-corrected update — as one
    jitted call; ``grad_fn(params, *batch) -> (loss, grads)``. Build it once
    and hand it to :func:`adam_train` for every node."""

    @jax.jit
    def step(params, m, v, t, lr, *batch):
        loss, g = grad_fn(params, *batch)
        m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        params = jax.tree.map(
            lambda p, m_, v_: p - lr * (m_ / (1 - b1**t)) / ((v_ / (1 - b2**t)) ** 0.5 + eps),
            params, m, v,
        )
        return params, m, v, loss

    return step


def adam_train(params, batches, step, opt: dict):
    """Local steps over ``batches`` (argument tuples after the parameters)
    with a :func:`adam_step`. Returns (params, losses)."""
    m = jax.tree.map(lambda p: 0.0 * p, params)
    v = jax.tree.map(lambda p: 0.0 * p, params)
    losses = []
    for i, batch in enumerate(batches):
        params, m, v, loss = step(params, m, v, float(i + 1), learning_rate(opt, i), *batch)
        losses.append(float(loss))
    return params, losses


def weighted_mean(trees: list, weights: list[float]):
    """Sample-weighted mean of parameter trees, in float64 numpy."""
    w = np.asarray(weights, np.float64) / float(np.sum(weights))
    return jax.tree.map(
        lambda *leaves: sum(wi * np.asarray(leaf, np.float64) for wi, leaf in zip(w, leaves)),
        *trees,
    )
