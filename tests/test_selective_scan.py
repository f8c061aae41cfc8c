"""The chunked selective scan (``ops/selective_scan.py``) against the
recurrence one token at a time: forward and all seven gradients, whatever the
chunk size; the Pallas kernels (interpreted) against the plain-XLA path;
and a precision control — the same scan with a bfloat16 state misses the
tolerance the float32 one meets."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2pfl_tpu.ops import selective_scan as ss
from p2pfl_tpu.ops import selective_scan_kernel as kernel

NAMES = ("u", "delta", "A", "B", "C", "D", "z")
T = 50
TOL = 2e-5  # float32 sums in another order: a few ulps of O(10) values


def inputs(seed=0, b=2, t=T, dm=24, n=4, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    u = jax.random.normal(ks[0], (b, t, dm))
    delta = jax.nn.softplus(jax.random.normal(ks[1], (b, t, dm)) - 1.0)
    a = -jnp.exp(0.5 * jax.random.normal(ks[2], (dm, n)))
    bb, c = jax.random.normal(ks[3], (b, t, n)), jax.random.normal(ks[4], (b, t, n))
    d, z = jax.random.normal(ks[5], (dm,)), jax.random.normal(ks[6], (b, t, dm))
    return tuple(x.astype(dtype) for x in (u, delta, a, bb, c, d, z))


def weighted(fn, args, w):
    return jax.value_and_grad(lambda *xs: jnp.sum(fn(*xs) * w), argnums=tuple(range(7)))(*args)


def rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / (jnp.max(jnp.abs(want)) + 1e-30))


@pytest.fixture(scope="module")
def loop():
    args = inputs()
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    y = ss.selective_scan_reference(*args)
    _, grads = weighted(ss.selective_scan_reference, args, w)
    return args, w, y, grads


@pytest.mark.parametrize("chunk", [1, 7, 10, 16, 25, 50, 64])  # 7, 16, 64 do not divide T = 50
def test_chunked_scan_equals_the_per_token_loop(loop, chunk):
    args, w, y, grads = loop
    fn = lambda *xs: ss.selective_scan(*xs, chunk=chunk, impl="xla")  # noqa: E731
    assert rel(fn(*args), y) < TOL
    _, got = weighted(fn, args, w)
    for name, g, want in zip(NAMES, got, grads):
        assert g.shape == want.shape and g.dtype == want.dtype, name
        assert rel(g, want) < TOL, name


def test_chunk_size_does_not_change_the_answer():
    args = inputs(seed=3)
    outs = [ss.selective_scan(*args, chunk=c, impl="xla") for c in (5, 16, 50)]
    for other in outs[1:]:
        assert rel(other, outs[0]) < TOL


def test_state_crosses_chunk_boundaries():
    """An input only in the first chunk still shows in the last chunk's output."""
    u, delta, a, b, c, d, z = inputs(seed=4)
    u = u.at[:, 10:].set(0.0)
    y = ss.selective_scan(u, 0.05 * delta, a, b, c, jnp.zeros_like(d), z, chunk=10, impl="xla")
    assert float(jnp.max(jnp.abs(y[:, 40:]))) > 1e-4


def as_the_mixer_passes_them(args):
    """``u``, ``B``, ``C``, ``z`` in bfloat16; ``Δ``, ``A``, ``D`` float32."""
    return tuple(x.astype(jnp.bfloat16) if i in (0, 3, 4, 6) else x for i, x in enumerate(args))


@pytest.mark.parametrize(
    "dm,chunk,mixed",
    [
        (128, 16, False), (256, 16, False), (1024, 25, False), (2048, 64, False),
        (1024, 16, True), (2048, 64, True),
    ],
)
def test_pallas_kernels_equal_the_xla_path(dm, chunk, mixed):
    """Interpret mode: 128 and 256 channels are one narrow block, 1024 one whole
    [8, 128] tile, 2048 two blocks; chunk 16 and 64 do not divide T = 50, which
    is no multiple of 8 either. ``mixed``: the dtypes a Mamba layer passes."""
    args = inputs(seed=5, dm=dm)
    if mixed:
        args = as_the_mixer_passes_them(args)
    w = jax.random.normal(jax.random.PRNGKey(1), args[0].shape)
    run = lambda impl: weighted(  # noqa: E731
        lambda *xs: ss.selective_scan(*xs, chunk=chunk, impl=impl).astype(jnp.float32), args, w
    )
    (y_x, g_x), (y_p, g_p) = run("xla"), run("pallas")
    # a bfloat16 result may round the other way on a float32 difference of an ulp
    assert abs(float(y_x - y_p)) <= (2e-3 if mixed else TOL) * abs(float(y_x)) + 1e-3
    for name, got, want in zip(NAMES, g_p, g_x):
        assert got.dtype == want.dtype, name
        tol = 2.0**-7 if got.dtype == jnp.bfloat16 else TOL
        assert rel(got.astype(jnp.float32), want.astype(jnp.float32)) < tol, name
    y, starts = kernel.scan_fwd(*args[:5], chunk, interpret=True)
    y_ref, starts_ref = ss._scan_xla(*args[:5], chunk)
    assert y.dtype == jnp.float32 and rel(y, y_ref) < TOL and rel(starts, starts_ref) < TOL
    gy = jax.random.normal(jax.random.PRNGKey(2), y.shape)
    ys, grads = kernel.scan_bwd(*args[:5], starts_ref, gy, chunk, interpret=True)
    ys_ref, grads_ref = ss._scan_bwd_xla(*args[:5], starts_ref, gy, chunk)
    assert rel(ys, ys_ref) < TOL
    for name, got, want in zip(NAMES, grads, grads_ref):
        assert got.shape == want.shape and rel(got, want) < TOL, name


def equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations carry, the
    inside of a ``pallas_call`` left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from equations(sub)


@pytest.mark.parametrize("dm", [1024, 2048])
def test_nothing_stands_between_the_mixer_and_the_kernels(dm):
    """The kernels' HBM interface: outside the two ``pallas_call``s no array has
    the tiled shape ``[B, T', Dm / 128, 128]`` — ``u``, ``Δ``, ``gy`` go in and
    ``y``, ``du``, ``dΔ`` come out as ``[B, T', Dm]`` in the dtype they have —
    and dB / dC leave the backward kernel as ``[B, T', N, 128]`` at most."""
    chunk, n = 16, 4
    args = as_the_mixer_passes_them(inputs(seed=11, dm=dm, n=n))
    bsz, padded = args[0].shape[0], -(-T // chunk) * chunk

    def fn(*xs):
        return jnp.sum(ss.selective_scan(*xs, chunk=chunk, impl="pallas").astype(jnp.float32))

    for traced in (fn, jax.grad(fn, argnums=tuple(range(7)))):
        eqns = list(equations(jax.make_jaxpr(traced)(*args).jaxpr))
        calls = [e for e in eqns if e.primitive.name == "pallas_call"]
        assert [e.params["name"] for e in calls] == (
            ["p2pfl_ssm_scan_fwd"] if traced is fn else ["p2pfl_ssm_scan_fwd", "p2pfl_ssm_scan_bwd"]
        )
        for e in eqns:
            if e.primitive.name != "pallas_call":
                assert all(v.aval.shape != (bsz, padded, dm // 128, 128) for v in e.outvars), e
        for call in calls:
            wide = [v.aval for v in call.invars if v.aval.shape == (bsz, padded, dm)]
            assert jnp.bfloat16 in [a.dtype for a in wide], "u enters in its own dtype"
            for out in call.outvars:
                shape = out.aval.shape
                if len(shape) > 3 and shape[1:3] == (padded, n):  # dB, dC
                    assert out.aval.size <= bsz * padded * n * 128, shape


def test_a_compiled_kernel_wants_whole_sublane_tiles_of_time():
    args = inputs(dm=128)
    with pytest.raises(ValueError, match="multiple of 16"):
        kernel.scan_fwd(*args[:5], 25, interpret=False)


def test_pallas_kernel_under_vmap_as_the_federation_calls_it():
    args = inputs(seed=6, dm=256)
    stacked = tuple(jnp.stack([x, 0.5 * x]) if i != 2 else jnp.stack([x, x]) for i, x in enumerate(args))
    run = lambda impl: jax.vmap(lambda *xs: ss.selective_scan(*xs, chunk=16, impl=impl))(*stacked)  # noqa: E731
    assert rel(run("pallas"), run("xla")) < TOL


def test_kernel_lowers_for_mosaic_at_the_jamba_widths():
    """Host-only Pallas->Mosaic lowering (no chip, no libtpu): 4096 x 5120 x 16,
    under the vmap the federation puts around a node's step."""
    t, dm, n = 4096, 5120, 16
    wide = jax.ShapeDtypeStruct((1, 1, t, dm), jnp.bfloat16)
    narrow = jax.ShapeDtypeStruct((1, 1, t, n), jnp.bfloat16)

    def fn(u, delta, a, b, c, d, z):
        one = lambda u_, dl_, b_, c_, z_: ss.selective_scan(u_, dl_, a, b_, c_, d, z_, impl="pallas")  # noqa: E731
        return jnp.sum(jax.vmap(one)(u, delta, b, c, z).astype(jnp.float32))

    real = ss._on_tpu
    ss._on_tpu = lambda: True  # interpret=False, as on the chip
    try:
        text = jax.jit(jax.grad(fn, argnums=(0, 1, 3, 4, 6))).trace(
            wide, jax.ShapeDtypeStruct((1, 1, t, dm), jnp.float32), jax.ShapeDtypeStruct((dm, n), jnp.float32),
            narrow, narrow, jax.ShapeDtypeStruct((dm,), jnp.float32), wide,
        ).lower(lowering_platforms=("tpu",)).as_text()
    finally:
        ss._on_tpu = real
    assert text.count("tpu_custom_call") == 2
    assert 'kernel_name = "p2pfl_ssm_scan_fwd"' in text and 'kernel_name = "p2pfl_ssm_scan_bwd"' in text


def test_bfloat16_state_fails_the_tolerance_float32_meets():
    """The precision control. 400 steps with slow decays: a bfloat16 state
    (8 mantissa bits) rounds every step's small increment away."""
    u, delta, a, b, c, _, _ = inputs(seed=7, b=1, t=400, dm=32, n=8)
    delta = 0.02 * delta
    want = jnp.moveaxis(
        jax.lax.scan(
            lambda h, xs: ss._advance(h, a.T, xs[1], xs[1] * xs[0], xs[2], xs[3]),
            jnp.zeros((1, 8, 32)), tuple(jnp.moveaxis(x, 1, 0) for x in (u, delta, b, c)),
        )[1], 0, 1,
    )
    tol = 1e-3
    assert rel(ss._scan_xla(u, delta, a, b, c, 50)[0], want) < tol
    assert rel(ss._scan_xla(u, delta, a, b, c, 50, jnp.bfloat16)[0], want) > 10 * tol


def test_bfloat16_inputs_give_bfloat16_out_and_input_dtype_gradients():
    args = list(inputs(seed=8, dm=16))
    for i in (0, 3, 4, 6):
        args[i] = args[i].astype(jnp.bfloat16)
    y, grads = weighted(lambda *xs: ss.selective_scan(*xs, chunk=16, impl="xla").astype(jnp.float32), args, 1.0)
    assert [g.dtype for g in grads] == [a.dtype for a in args]
    assert np.isfinite(float(y))


def test_rejects_unknown_impl_and_chunk():
    args = inputs()
    with pytest.raises(ValueError, match="impl"):
        ss.selective_scan(*args, impl="cuda")
    with pytest.raises(ValueError, match="chunk"):
        ss.selective_scan(*args, chunk=0)
