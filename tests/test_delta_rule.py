"""The chunked gated delta rule (``ops/delta_rule.py``): the XLA path and both
Pallas kernels (interpreted) against the recurrence one token at a time, forward
and VJP, with β across (0, 2), at strong decay, at one chunk and at several; the
kernels at every number of heads a grid step the shape rule takes, against the
XLA path too."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import checks as ck
from benchmark.reference import solar_open2_lm
from p2pfl_tpu.ops.delta_rule import (
    chunk_backward, chunk_forward, delta_rule_reference, gated_delta_rule, heads_per_step, unit_lower_inverse,
)

# (impl, heads, the heads a stack holds — a kernel step, or a stack of the XLA
# path): both at n = 2 and 4, at 6 heads (4 does not divide them) and at 3
# (nothing does)
IMPLS = [
    ("xla", 2, 2), ("pallas", 2, 2), ("pallas", 4, 4), ("pallas", 6, 2), ("pallas", 3, 1),
    ("xla", 4, 4), ("xla", 6, 2), ("xla", 3, 1),
]


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


def _against_the_xla_path(impl, n, chunk, args, got, grads):
    """The kernels at ``n`` heads a step run the XLA path's chunk functions on
    stacks of the same ``n``: equal to it to float32 rounding, forward and all
    five gradients."""
    assert heads_per_step(args[4].shape[-1]) == n
    if impl == "xla":
        return
    xla, xla_grads = _value_and_vjp(lambda *a: gated_delta_rule(*a, chunk=chunk, impl="xla"), args)
    assert ck.rel_l2(got, xla) < 1e-6
    for name, g, w in zip(("q", "k", "v", "log_alpha", "beta"), grads, xla_grads):
        assert ck.rel_l2(g, w) < 1e-6, name


@pytest.mark.parametrize(
    "impl,heads,n,t,chunk",
    [(impl, 2, n, 32, 32) for impl, _, n in IMPLS[:2]]  # one chunk
    + [(impl, heads, n, 80, 16) for impl, heads, n in IMPLS],  # several, the last padded
)
def test_the_op_matches_the_token_recurrence_forward_and_gradient(impl, heads, n, t, chunk):
    args = inputs(0, t=t, heads=heads)
    assert float(jnp.min(args[4])) < 0.5 and float(jnp.max(args[4])) > 1.5  # β across (0, 2)
    with jax.default_matmul_precision("highest"):
        want, want_grads = _value_and_vjp(delta_rule_reference, args)
        got, grads = _value_and_vjp(lambda *a: gated_delta_rule(*a, chunk=chunk, impl=impl), args)
        _against_the_xla_path(impl, n, chunk, args, got, grads)
    assert got.shape == want.shape == args[2].shape and got.dtype == jnp.float32
    assert ck.rel_l2(got, want) < 2e-6
    for name, g, w in zip(("q", "k", "v", "log_alpha", "beta"), grads, want_grads):
        assert ck.rel_l2(g, w) < 5e-6, name


@pytest.mark.parametrize("impl,heads,n", IMPLS)
def test_strong_decay_stays_finite_and_exact(impl, heads, n):
    """A chunk decays by thousands of nats (``e^{-g}`` would be inf): the pairwise
    decays keep the output and every gradient finite and exact; the log-decay's
    own gradient is float32 cancellation of O(1) terms, so it is held to the
    scale of the value gradient."""
    args = inputs(1, t=64, decay=200.0, heads=heads)
    la = np.asarray(args[3])
    assert la.reshape(1, 2, 32, -1).sum(axis=2).min() < -1000  # a chunk's cumulative decay
    with jax.default_matmul_precision("highest"):
        want, want_grads = _value_and_vjp(delta_rule_reference, args)
        got, grads = _value_and_vjp(lambda *a: gated_delta_rule(*a, chunk=32, impl=impl), args)
        _against_the_xla_path(impl, n, 32, args, got, grads)
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


@pytest.mark.parametrize("heads", [None, 2, 3])  # one block; a stack: two blocks a 128-lane row, and a row of one
def test_the_unit_lower_inverse(heads):
    n = 0.1 * jnp.tril(jax.random.normal(jax.random.PRNGKey(4), (heads or 1, 64, 64)), -1)
    with jax.default_matmul_precision("highest"):
        got = unit_lower_inverse(n if heads else n[0])
        one = jnp.stack([unit_lower_inverse(block) for block in n])
    got = got if heads else got[None]
    for block, inv in zip(n, got):
        np.testing.assert_allclose(inv @ (jnp.eye(64) + block), jnp.eye(64), atol=1e-5)
        assert bool((jnp.triu(inv, 1) == 0).all()) and bool((jnp.diag(inv) == 1).all())
    assert bool((got == one).all())  # side by side, the sweep is each block's element for element


def test_a_stack_of_heads_is_each_head_computed_alone():
    """The chunk functions on a stack ``[m, …]`` (what a kernel step of ``m``
    heads and the XLA path run) against each head's call alone, forward and
    backward."""
    m, c, d = 3, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 8)
    q, k = (jax.random.normal(key, (m, c, d)) / 4 for key in ks[:2])
    v = jax.random.normal(ks[2], (m, c, d))
    g = jnp.cumsum(-jax.nn.softplus(jax.random.normal(ks[3], (m, c, d))), axis=1)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (m, c, 1)))
    st = jax.random.normal(ks[5], (m, d, d))
    do, dst = jax.random.normal(ks[6], (m, c, d)), jax.random.normal(ks[7], (m, d, d))
    with jax.default_matmul_precision("highest"):
        stacked = (*chunk_forward(st, q, k, v, g, beta), *chunk_backward(st, q, k, v, g, beta, do, dst))
        alone = [(*chunk_forward(*x[:6]), *chunk_backward(*x)) for x in zip(st, q, k, v, g, beta, do, dst)]
    for i, name in enumerate(("o", "end", "state", "q", "k", "v", "g", "beta")):
        want = jnp.stack([a[i] for a in alone])
        assert stacked[i].shape == want.shape
        assert ck.rel_l2(stacked[i], want) < 1e-6, name


@pytest.mark.parametrize(
    "heads,n",
    [
        (64, 4),  # the Solar cell: 16 steps a chunk
        (8, 4),
        (6, 2),  # 4 does not divide 6
        (2, 2),
        (3, 1),  # nothing does
        (1, 1),
    ],
)
def test_the_heads_a_stack_holds(heads, n):
    assert heads_per_step(heads) == n


def test_the_op_refuses_a_chunk_that_is_no_whole_number_of_sub_blocks():
    with pytest.raises(ValueError, match="sub-block"):
        gated_delta_rule(*inputs(0, t=32), chunk=24)
