"""Differential privacy: DP-SGD local steps + an RDP accountant.

The reference has no privacy mechanism anywhere (grep for clip/noise/dp
finds nothing); in federated learning DP-SGD (Abadi et al. 2016) is the
standard defense against gradient-leakage of client data, so the rebuild
ships it as a first-class learner knob.

Mechanics (``dp_train_epoch`` / the ``dp_clip``/``dp_noise`` knobs):

- per-example gradients via ``jax.vmap`` of a single-example loss grad —
  on TPU this is a batched program, not a Python loop;
- each example's gradient is clipped to L2 norm ``clip``;
- Gaussian noise ``N(0, (noise · clip)² / B²)`` is added to the mean.

Accounting (``PrivacyAccountant``): Rényi differential privacy of the
subsampled Gaussian mechanism, the analytical moments-accountant bound for
integer orders α (Abadi et al. 2016 lemma 3 / Mironov 2017):

    RDP(α) ≤ 1/(α−1) · log Σ_{k=0..α} C(α,k)(1−q)^{α−k} q^k e^{k(k−1)/2σ²}

composed linearly over steps, converted to (ε, δ) by
``ε = min_α RDP(α)·T + log(1/δ)/(α−1)``. Pure numpy, no dependencies.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

Pytree = object


def clip_by_global_norm(grads: Pytree, clip: float) -> Pytree:
    """Scale ``grads`` so its global L2 norm is at most ``clip``."""
    norm = jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
    )
    scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), grads)


def dp_grads(loss_one, params, x, y, clip: float, noise: float, key):
    """Per-example clipped + noised mean gradient (the DP-SGD estimator).

    ``loss_one(params, x_i, y_i) -> scalar`` is the single-example loss;
    ``x``/``y`` carry the batch dim. Returns
    ``(grads, mean_loss)`` — the pre-update loss comes free from the grad
    pass, matching what the non-DP paths report.
    """
    batch = x.shape[0]

    def one(xi, yi):
        loss, g = jax.value_and_grad(loss_one)(params, xi, yi)
        return clip_by_global_norm(g, clip), loss

    per_ex, losses = jax.vmap(one)(x, y)  # [B, ...] pytrees, [B] losses
    mean_g = jax.tree.map(lambda g: jnp.mean(g.astype(jnp.float32), axis=0), per_ex)
    leaves, tdef = jax.tree.flatten(mean_g)
    keys = jax.random.split(key, len(leaves))
    sigma = noise * clip / batch
    noised = [
        (g + sigma * jax.random.normal(k, g.shape, jnp.float32)).astype(p.dtype)
        for g, k, p in zip(leaves, keys, jax.tree.leaves(params))
    ]
    return tdef.unflatten(noised), jnp.mean(losses)


@partial(jax.jit, static_argnames=("module", "tx", "clip", "noise", "prox_mu"))
def dp_train_epoch(
    params, opt_state, xs, ys, key, module, tx, clip: float, noise: float,
    prox_mu: float = 0.0, anchor=None,
):
    """One DP-SGD epoch: scan over [nb, bs, ...] batches (counterpart of
    ``learner.train_epoch`` with the DP estimator instead of the batch
    gradient; ``prox_mu`` keeps FedProx active under DP, same as the SPMD
    path)."""
    import optax

    from p2pfl_tpu.learning.learner import _loss, _prox_term

    def loss_one(p, xi, yi):
        loss = _loss(p, module, xi[None], yi[None])[0]
        if prox_mu > 0.0:
            loss = loss + _prox_term(p, anchor, prox_mu)
        return loss

    def step(carry, batch):
        p, o, k = carry
        x, y = batch
        k, sub = jax.random.split(k)
        grads, loss = dp_grads(loss_one, p, x, y, clip, noise, sub)
        updates, o = tx.update(grads, o, p)
        p = optax.apply_updates(p, updates)
        return (p, o, k), loss

    (params, opt_state, _), losses = jax.lax.scan(step, (params, opt_state, key), (xs, ys))
    return params, opt_state, jnp.mean(losses)


class PrivacyAccountant:
    """(ε, δ) tracking for the subsampled Gaussian mechanism.

    ``q`` = batch/shard sampling rate, ``noise`` = noise multiplier σ.
    ``step(n)`` records n mechanism invocations (one per DP-SGD step).
    """

    ORDERS = tuple(range(2, 65))

    def __init__(self, noise: float, q: float) -> None:
        if noise <= 0 or not 0 < q <= 1:
            raise ValueError("need noise > 0 and 0 < q <= 1")
        self.noise = noise
        self.q = q
        self.steps = 0
        self._rdp_per_step = [self._rdp_one(a) for a in self.ORDERS]

    def _rdp_one(self, alpha: int) -> float:
        """RDP of ONE subsampled-Gaussian step at integer order ``alpha``."""
        q, sigma = self.q, self.noise
        if q == 1.0:
            return alpha / (2.0 * sigma**2)
        # log Σ_k C(α,k) (1−q)^{α−k} q^k exp(k(k−1)/2σ²), stable in log-space
        log_terms = [
            math.lgamma(alpha + 1)
            - math.lgamma(k + 1)
            - math.lgamma(alpha - k + 1)
            + (alpha - k) * math.log1p(-q)
            + k * math.log(q)
            + (k * (k - 1)) / (2.0 * sigma**2)
            for k in range(alpha + 1)
        ]
        m = max(log_terms)
        return (m + math.log(sum(math.exp(t - m) for t in log_terms))) / (alpha - 1)

    def step(self, n: int = 1) -> None:
        self.steps += n

    def epsilon(self, delta: float = 1e-5) -> float:
        """Smallest ε over the tracked orders for the given δ."""
        if self.steps == 0:
            return 0.0
        return min(
            r * self.steps + math.log(1.0 / delta) / (a - 1)
            for a, r in zip(self.ORDERS, self._rdp_per_step)
        )
