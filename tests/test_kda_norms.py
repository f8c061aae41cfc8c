"""The KDA mixer's per-head norms (``_l2_heads``, ``gated_head_norm``) over the
view that splits ``T`` at the float32 sublane tile: the same values and
gradients as the ``[B, T, H, d]`` formulation, and — compiled for a described
v5e, forward, re-forward and backward — no relayout copy of an 8192-wide
float32 array into that formulation's order."""

import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from p2pfl_tpu.models import transformer
from p2pfl_tpu.models.transformer import KDAMixer, TransformerConfig, _head_view, _l2_heads, gated_head_norm


def _l2_heads_bthd(x, heads, eps=1e-6):
    b, t, width = x.shape
    xh = x.astype(jnp.float32).reshape(b, t, heads, width // heads)
    return (xh * jax.lax.rsqrt(jnp.sum(xh * xh, axis=-1, keepdims=True) + eps)).reshape(b, t, width)


def _gated_head_norm_bthd(o, gate, scale, eps):
    b, t, width = o.shape
    oh = o.astype(jnp.float32).reshape(b, t, -1, scale.shape[0])
    oh = oh * jax.lax.rsqrt(jnp.mean(oh * oh, axis=-1, keepdims=True) + eps) * scale
    return oh.reshape(b, t, width) * jax.nn.sigmoid(gate)


def _close(got, want, terms: int = 1):
    """Equal to float32 rounding: a few roundings of the largest element, times the depth of a
    summation tree over ``terms`` (``scale``'s gradient sums every token and head; the two views
    may add them in another order)."""
    want = np.asarray(want)
    atol = 4 * np.finfo(np.float32).eps * np.abs(want).max() * max(1.0, np.log2(terms))
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=atol)


@pytest.mark.parametrize("heads, d", [(4, 16), (64, 128)])
@pytest.mark.parametrize("t", [40, 64, 37])  # 37: not a multiple of 8, today's view
@pytest.mark.parametrize("b", [1, 2])
@pytest.mark.parametrize("helper", ["l2_heads", "gated_head_norm"])
def test_the_split_view_gives_the_bthd_values_and_gradients(helper, b, t, heads, d):
    keys = jax.random.split(jax.random.PRNGKey(b * 1000 + t * 10 + heads), 5)
    x = jax.random.normal(keys[0], (b, t, heads * d), jnp.float32) * 3.0
    ct = jax.random.normal(keys[1], x.shape, jnp.float32)
    assert _head_view(x, d).shape == ((b, t // 8, 8, heads, d) if t % 8 == 0 else (b, t, heads, d))
    if helper == "l2_heads":
        args, got_fn, want_fn = (x,), lambda x: _l2_heads(x, heads), lambda x: _l2_heads_bthd(x, heads)
    else:
        gate = jax.random.normal(keys[2], x.shape, jnp.float32)
        scale = 1.0 + 0.1 * jax.random.normal(keys[3], (d,), jnp.float32)
        args = (x, gate, scale)
        got_fn = lambda o, g, s: gated_head_norm(o, g, s, 1e-5)  # noqa: E731
        want_fn = lambda o, g, s: _gated_head_norm_bthd(o, g, s, 1e-5)  # noqa: E731
    got, got_vjp = jax.vjp(jax.jit(got_fn), *args)
    want, want_vjp = jax.vjp(jax.jit(want_fn), *args)
    _close(got, want)
    for g, w in zip(got_vjp(ct), want_vjp(ct), strict=True):
        assert float(jnp.max(jnp.abs(w))) > 0
        _close(g, w, terms=x.size // w.size)


@pytest.fixture(scope="module")
def one_v5e():
    """One chip of a described ``v5e:2x2``: the TPU compiler runs here without the chip."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever stops libtpu from describing the chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A program compiled for a described chip is written to the persistent cache and cannot
    be read back without one: keep the cache off around such compiles."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _relayouts_of_head_views(one_chip, t: int) -> int:
    """Copies of an ``[T, H · d]`` float32 array into a per-head order (shape ending in
    ``H, d``) in the compiled gradient of ``KDAMixer`` at Solar's widths — 64 heads of
    128, bf16 compute, the delta rule's Pallas kernels, under ``jax.checkpoint`` as the
    blocks run it — compiled for ``one_chip``."""
    heads, d = 64, 128
    cfg = TransformerConfig(
        vocab_size=256, dim=4096, n_layers=1, n_heads=heads, n_kv_heads=8, head_dim=d, ffn_hidden=128,
        dtype=jnp.bfloat16, conv_taps=4, kda_chunk=64, lora_rank=8, lora_alpha=16.0, norm_eps=1e-5,
    )
    mixer = KDAMixer(cfg)
    x = jax.ShapeDtypeStruct((1, t, cfg.dim), jnp.bfloat16)
    params = jax.eval_shape(lambda x: mixer.init(jax.random.PRNGKey(0), x)["params"], x)

    def loss(p, x):
        return jnp.sum(jax.checkpoint(lambda p, x: mixer.apply({"params": p}, x))(p, x).astype(jnp.float32))

    spec = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)  # noqa: E731
    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(spec(params), spec(x)).compile().as_text()
    shapes = collections.Counter(re.findall(r"= f32\[([\d,]+)\]\{[^}]*\} copy\(", hlo))
    per_head = lambda dims: dims[-2:] == [heads, d] and np.prod(dims) == t * heads * d  # noqa: E731
    return sum(n for s, n in shapes.items() if per_head([int(v) for v in s.split(",")]))


@pytest.mark.parametrize("view", ["split", "bthd"])
def test_the_mixers_gradient_compiles_for_v5e_without_relayouts_of_the_head_views(one_v5e, no_compile_cache, monkeypatch, view):
    """The tree's view compiles to no such copy; the ``[B, T, H, d]`` view, put back
    for the count, to several (seven at 4096 tokens), so the count sees them."""
    from p2pfl_tpu.ops import delta_rule

    monkeypatch.setattr(delta_rule, "_on_tpu", lambda: True)
    if view == "bthd":
        monkeypatch.setattr(transformer, "_head_view", lambda x, d: x.reshape(*x.shape[:2], -1, d))
        assert _relayouts_of_head_views(one_v5e, 256) > 0
    else:
        assert _relayouts_of_head_views(one_v5e, 256) == 0
