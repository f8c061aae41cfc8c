"""The chunked gated delta rule (``ops/delta_rule.py``): the XLA path and both
Pallas kernels (interpreted) against the recurrence one token at a time, forward
and VJP, with β across (0, 2), at strong decay, at one chunk and at several."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import checks as ck
from benchmark.reference import solar_open2_lm
from p2pfl_tpu.ops.delta_rule import (
    chunk_backward, chunk_forward, delta_rule_reference, gated_delta_rule, unit_lower_inverse,
)


def inputs(seed, t=64, heads=2, d=32, decay=1.0, batch=1):
    """Flat ``[B, T, H · d]`` q, k, v, log-decay and ``[B, T, H]`` β as the mixer
    makes them: unit q / k (q scaled), silu'd keys (they correlate), decays a
    channel, β spanning (0, 2)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (batch, t, heads, d)
    q = jax.random.normal(ks[0], shape)
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / d ** 0.5
    k = jax.nn.silu(jax.random.normal(ks[1], shape))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], shape)
    log_alpha = -decay * jax.nn.softplus(jax.random.normal(ks[3], shape))
    beta = 2 * jax.nn.sigmoid(2 * jax.random.normal(ks[4], (batch, t, heads)))
    flat = lambda x: x.reshape(batch, t, heads * d)  # noqa: E731
    return flat(q), flat(k), flat(v), flat(log_alpha), beta


def _value_and_vjp(fn, args, seed=9):
    probe = jax.random.normal(jax.random.PRNGKey(seed), args[2].shape)
    out, pull = jax.vjp(fn, *args)
    return out, pull(probe)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("t,chunk", [(32, 32), (80, 16)])  # one chunk; several, the last padded
def test_the_op_matches_the_token_recurrence_forward_and_gradient(impl, t, chunk):
    args = inputs(0, t=t)
    assert float(jnp.min(args[4])) < 0.5 and float(jnp.max(args[4])) > 1.5  # β across (0, 2)
    with jax.default_matmul_precision("highest"):
        want, want_grads = _value_and_vjp(delta_rule_reference, args)
        got, grads = _value_and_vjp(lambda *a: gated_delta_rule(*a, chunk=chunk, impl=impl), args)
    assert got.shape == want.shape == args[2].shape and got.dtype == jnp.float32
    assert ck.rel_l2(got, want) < 2e-6
    for name, g, w in zip(("q", "k", "v", "log_alpha", "beta"), grads, want_grads):
        assert ck.rel_l2(g, w) < 5e-6, name


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_strong_decay_stays_finite_and_exact(impl):
    """A chunk decays by thousands of nats (``e^{-g}`` would be inf): the pairwise
    decays keep the output and every gradient finite and exact; the log-decay's
    own gradient is float32 cancellation of O(1) terms, so it is held to the
    scale of the value gradient."""
    args = inputs(1, t=64, decay=200.0)
    la = np.asarray(args[3])
    assert la.reshape(1, 2, 32, -1).sum(axis=2).min() < -1000  # a chunk's cumulative decay
    with jax.default_matmul_precision("highest"):
        want, want_grads = _value_and_vjp(delta_rule_reference, args)
        got, grads = _value_and_vjp(lambda *a: gated_delta_rule(*a, chunk=32, impl=impl), args)
    assert all(bool(jnp.isfinite(x).all()) for x in (got, *grads))
    assert ck.rel_l2(got, want) < 2e-6
    for name, g, w in zip(("q", "k", "v", "beta"), (grads[0], grads[1], grads[2], grads[4]), (want_grads[0], want_grads[1], want_grads[2], want_grads[4])):
        assert ck.rel_l2(g, w) < 5e-6, name
    assert float(jnp.max(jnp.abs(grads[3] - want_grads[3]))) < 1e-6 * float(jnp.max(jnp.abs(want_grads[2])))


def test_the_reference_recurrences_agree_and_a_bfloat16_state_is_far_from_them():
    """``delta_rule_reference`` and the benchmark's blocked
    ``solar_open2_lm.recurrence`` are the same definition; a bfloat16 state
    lands three orders of magnitude further from it than the chunked op does."""
    q, k, v, la, beta = inputs(2, t=64)
    heads = lambda x: x[0].reshape(64, 2, -1)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = delta_rule_reference(q, k, v, la, beta)
        blocked = solar_open2_lm.recurrence(heads(q), heads(k), heads(v), heads(la), beta[0]).reshape(1, 64, -1)
        low = delta_rule_reference(q, k, v, la, beta, state_dtype=jnp.bfloat16)
        chunked = gated_delta_rule(q, k, v, la, beta, chunk=16, impl="xla")
    assert ck.rel_l2(blocked, want) < 1e-6
    assert ck.rel_l2(low, want) > 1000 * ck.rel_l2(chunked, want)


def test_one_chunk_forward_and_backward_against_autodiff():
    """``chunk_backward`` is the VJP of ``chunk_forward`` (state in and out)."""
    c, d = 32, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 8)
    q, k = (jax.random.normal(key, (c, d)) / 4 for key in ks[:2])
    v = jax.random.normal(ks[2], (c, d))
    g = jnp.cumsum(-jax.nn.softplus(jax.random.normal(ks[3], (c, d))), axis=0)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (c, 1)))
    st = jax.random.normal(ks[5], (d, d))
    do, dst = jax.random.normal(ks[6], (c, d)), jax.random.normal(ks[7], (d, d))
    with jax.default_matmul_precision("highest"):
        _, pull = jax.vjp(chunk_forward, st, q, k, v, g, beta)
        want = pull((do, dst))
        got = chunk_backward(st, q, k, v, g, beta, do, dst)
    for name, a, b in zip(("state", "q", "k", "v", "g", "beta"), got, want):
        assert ck.rel_l2(a, b) < 1e-5, name


def test_the_unit_lower_inverse():
    n = 0.1 * jnp.tril(jax.random.normal(jax.random.PRNGKey(4), (64, 64)), -1)
    with jax.default_matmul_precision("highest"):
        got = unit_lower_inverse(n)
    np.testing.assert_allclose(got @ (jnp.eye(64) + n), jnp.eye(64), atol=1e-5)
    assert bool((jnp.triu(got, 1) == 0).all()) and bool((jnp.diag(got) == 1).all())


def test_the_op_refuses_a_chunk_that_is_no_whole_number_of_sub_blocks():
    with pytest.raises(ValueError, match="sub-block"):
        gated_delta_rule(*inputs(0, t=32), chunk=24)
