"""Selective scan (the Mamba-1 state-space recurrence), forward and backward,
chunked.

For every channel ``d`` of ``Dm`` and state index ``n`` of ``N``::

    h_t = exp(Δ_t[d] · A[d, n]) · h_{t-1} + Δ_t[d] · u_t[d] · B_t[n]      (h_0 = 0)
    y_t[d] = (Σ_n h_t[d, n] · C_t[n] + D[d] · u_t[d]) · silu(z_t[d])

The state over one sequence, ``T × Dm × N`` float32, is 1.34 GB at ``T`` = 4096,
``Dm`` = 5120, ``N`` = 16, so nothing here ever holds it whole: the sequence is
cut into chunks of ``chunk`` steps, the state crosses chunk boundaries in
float32, and the ``custom_vjp`` keeps the inputs and the CHUNK-BOUNDARY states
only (``T / chunk × Dm × N``); the backward recomputes inside a chunk.

How the chunks run side by side (the plain-XLA path, every backend). The
recurrence is linear in ``h``, and its decays multiply: the state a chunk ends
with is ``exp(A · ΣΔ) · h_start + Σ_j exp(A · (ΣΔ − cumΔ_j)) · ΔuB_j``. Both
factors are reductions over the chunk's steps with no recurrence in them (every
exponent is ≤ 0, so they are stable), hence

1. one fused reduction gives each chunk's contribution from a zero start;
2. a recurrence over the ``T / chunk`` chunks (tiny) gives every boundary state;
3. ONE ``lax.scan`` over the ``chunk`` positions advances ALL chunks together
   from their boundary states — ``chunk`` large steps over ``[T / chunk, N, Dm]``
   instead of ``T`` small ones, the shape a TPU wants from XLA.

The backward mirrors it: the gradient that reaches a chunk's boundary state
from the chunk's own outputs is again a fused reduction
(``Σ_j exp(A · cumΔ_j) · C_j gy_j``), a reverse recurrence over chunks makes it
the full gradient at every boundary, and then each chunk's inner gradients
follow from its start state and its end gradient alone — so chunks run side by
side again, a few (``_BWD_STEPS`` steps' worth) at a time, their per-step
states held only that long.

On a TPU the recurrence runs in two Pallas kernels instead
(``ops/selective_scan_kernel.py``: ``p2pfl_ssm_scan_fwd`` and
``p2pfl_ssm_scan_bwd``, time inside the kernel, one channel block's state in
VMEM across the chunk axis); chosen by backend the way
``models/transformer.Attention`` picks flash, not by a setting. Same chunks,
same boundary states, same residuals. The kernels read ``u``, ``Δ`` and the
cotangent as ``[B, T, Dm]`` in the dtype the mixer holds them and write their
results the same way, so between the mixer and the two Mosaic calls stands
only the gate arithmetic of ``_fwd`` / ``_bwd`` below. ``impl`` is for tests
and for the chip comparison of the two paths (4096 × 5120 × 16 on a v5e:
forward 4.8 ms and forward + backward 16.4 ms in XLA, PR 27; the two kernel
calls with all they need around them 1.06 and 2.86 ms, PR 28).

State layout is ``[..., N, Dm]`` throughout (channels on the lanes): with
``N`` = 16 minor a TPU tile would be seven eighths padding.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from p2pfl_tpu.management.profiling import scope

DEFAULT_CHUNK = 64
# steps' worth of per-step states the backward holds at a time (× N × Dm × 4
# bytes: 168 MB at the Jamba widths, an eighth of a 4096-token sequence's)
_BWD_STEPS = 512


def _to_chunks(x: jax.Array, chunk: int) -> jax.Array:
    """``[B, T, ...] -> float32 [chunk, B, ⌈T / chunk⌉, ...]``, zero-padded to
    whole chunks (a Δ = 0 step leaves the state alone): position-in-chunk
    leads, so a ``lax.scan`` over it steps every chunk at once."""
    b, t = x.shape[:2]
    x = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, -t % chunk), (0, 0)))
    return jnp.moveaxis(x.reshape(b, -1, chunk, *x.shape[2:]), 2, 0)


def _from_chunks(x: jax.Array) -> jax.Array:
    """Inverse of :func:`_to_chunks`."""
    x = jnp.moveaxis(x, 0, 2)
    return x.reshape(x.shape[0], x.shape[1] * x.shape[2], *x.shape[3:])


def _advance(h, a_t, delta_j, du_j, b_j, c_j):
    """One step of every chunk: ``h`` is ``[B, nc, N, Dm]``; returns the new
    state and ``Σ_n h · C``."""
    h = jnp.exp(delta_j[..., None, :] * a_t) * h + b_j[..., :, None] * du_j[..., None, :]
    return h, jnp.sum(c_j[..., :, None] * h, axis=-2)


def _run_chunks(h_start, a_t, delta_c, du_c, b_c, c_c, dtype=jnp.float32):
    """All chunks from their start states: ``([chunk, B, nc, Dm]`` outputs,
    ``[B, nc, N, Dm]`` end states). ``dtype`` is the state's — float32; tests
    pass bfloat16 to show that it is needed."""

    def step(h, xs):
        h, y = _advance(h.astype(jnp.float32), a_t, *xs)
        return h.astype(dtype), y

    h_end, ys = lax.scan(step, h_start.astype(dtype), (delta_c, du_c, b_c, c_c))
    return ys, h_end.astype(jnp.float32)


def _boundary_states(a_t, delta_c, du_c, b_c, dtype=jnp.float32):
    """``[B, nc, N, Dm]``: the state each chunk starts from (chunk 0: zero)."""
    cum = jnp.cumsum(delta_c, axis=0)
    total = cum[-1]
    # each chunk's end state from a zero start: a reduction, no recurrence
    decay = jnp.exp((total[None] - cum)[..., None, :] * a_t)
    local = jnp.sum(decay * b_c[..., :, None] * du_c[..., None, :], axis=0)
    through = jnp.exp(total[..., None, :] * a_t)  # a whole chunk's decay

    def across(h, xs):
        keep, add = xs
        return (keep * h.astype(jnp.float32) + add).astype(dtype), h

    _, starts = lax.scan(
        across, jnp.zeros_like(local[:, 0], dtype), (jnp.moveaxis(through, 1, 0), jnp.moveaxis(local, 1, 0))
    )
    return jnp.moveaxis(starts, 0, 1).astype(jnp.float32)


def _scan_xla(u, delta, a, b, c, chunk: int, state_dtype=jnp.float32):
    """``(Σ_n h_t C_t`` as ``[B, T, Dm]`` float32, boundary states)``."""
    a_t = a.astype(jnp.float32).T  # [N, Dm]
    u_c, delta_c, b_c, c_c = (_to_chunks(x, chunk) for x in (u, delta, b, c))
    du_c = delta_c * u_c
    starts = _boundary_states(a_t, delta_c, du_c, b_c, state_dtype)
    ys, _ = _run_chunks(starts, a_t, delta_c, du_c, b_c, c_c, state_dtype)
    return _from_chunks(ys)[:, : u.shape[1]], starts


def _scan_bwd_xla(u, delta, a, b, c, starts, gy, chunk: int):
    """``Σ_n h_t C_t`` again (the gate's backward needs it, and the chunks are
    re-run anyway) and its gradients with respect to ``u, Δ, A, B, C``, given
    its cotangent ``gy`` (float32 ``[B, T, Dm]``) and the saved boundary states."""
    t = u.shape[1]
    a_t = a.astype(jnp.float32).T
    u_c, delta_c, b_c, c_c, gy_c = (_to_chunks(x, chunk) for x in (u, delta, b, c, gy))
    cum = jnp.cumsum(delta_c, axis=0)
    # what a chunk's own outputs send back to its start state: a reduction
    own = jnp.sum(jnp.exp(cum[..., None, :] * a_t) * c_c[..., :, None] * gy_c[..., None, :], axis=0)
    through = jnp.exp(cum[-1][..., None, :] * a_t)

    def across(g_end, xs):  # g_end: gradient at this chunk's END state
        keep, add = xs
        return keep * g_end + add, g_end

    _, g_ends = lax.scan(
        across, jnp.zeros_like(own[:, 0]), (jnp.moveaxis(through, 1, 0), jnp.moveaxis(own, 1, 0)), reverse=True
    )
    g_ends = jnp.moveaxis(g_ends, 0, 1)  # [B, nc, N, Dm]

    # inside the chunks: start state and end gradient are known for every
    # chunk, so chunks are independent; a group of them at a time, the group's
    # per-step states kept by autodiff of the (checkpointed) step only
    nc = starts.shape[1]
    group = max(1, min(nc, _BWD_STEPS // chunk))
    while nc % group:
        group -= 1

    def grouped(x, axis):  # nc -> (nc / group, group), the group index leading
        x = x.reshape(*x.shape[:axis], nc // group, group, *x.shape[axis + 1:])
        return jnp.moveaxis(x, axis, 0)

    def inner(h0, a_t_, delta_g, u_g, b_g, c_g):
        def step(h, xs):
            d_j, u_j, b_j, c_j = xs
            return _advance(h, a_t_, d_j, d_j * u_j, b_j, c_j)

        h_end, ys = lax.scan(jax.checkpoint(step), h0, (delta_g, u_g, b_g, c_g))
        return ys, h_end

    def one_group(args):
        h0, g_end, delta_g, u_g, b_g, c_g, gy_g = args
        (ys_g, _), pull = jax.vjp(partial(inner, h0), a_t, delta_g, u_g, b_g, c_g)
        return ys_g, pull((gy_g, g_end))

    ys, (da_t, d_delta, d_u, d_b, d_c) = lax.map(
        one_group,
        (grouped(starts, 1), grouped(g_ends, 1), *(grouped(x, 2) for x in (delta_c, u_c, b_c, c_c, gy_c))),
    )

    def ungrouped(x):  # [groups, chunk, B, group, ...] -> [B, T, ...]
        x = jnp.moveaxis(x, 0, 2)
        x = x.reshape(*x.shape[:2], nc, *x.shape[4:])
        return _from_chunks(x)[:, :t]

    grads = ungrouped(d_u), ungrouped(d_delta), jnp.sum(da_t, axis=0).T, ungrouped(d_b), ungrouped(d_c)
    return ungrouped(ys), grads


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_kernel(impl: Optional[str], dm: int) -> bool:
    if impl not in (None, "xla", "pallas"):
        raise ValueError(f"selective_scan impl {impl!r} (None|xla|pallas)")
    if impl == "pallas":
        return True
    return impl is None and _on_tpu() and dm % 128 == 0  # the kernels tile channels by 128 lanes


@partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _selective_scan(u, delta, a, b, c, d, z, chunk, impl):
    return _fwd(u, delta, a, b, c, d, z, chunk, impl)[0]


def _gated(y_scan, u, d, z):
    """``(Σ_n h C + D u) · silu(z)``, float32."""
    return _with_skip(y_scan, u, d) * jax.nn.silu(z.astype(jnp.float32))


def _with_skip(y_scan, u, d):
    return y_scan + d.astype(jnp.float32) * u.astype(jnp.float32)


def _fwd(u, delta, a, b, c, d, z, chunk, impl):
    with scope("ssm_scan_fwd"):
        if _use_kernel(impl, u.shape[-1]):
            from p2pfl_tpu.ops.selective_scan_kernel import scan_fwd

            y_scan, starts = scan_fwd(u, delta, a, b, c, chunk, interpret=not _on_tpu())
        else:
            y_scan, starts = _scan_xla(u, delta, a, b, c, chunk)
        y = _gated(y_scan, u, d, z).astype(u.dtype)
    # named so that a remat policy may keep the op's output and its one
    # residual that is not an input, and then re-run no scan in its re-forward
    starts = checkpoint_name(starts, "ssm_state")
    return y, (u, delta, a, b, c, d, z, starts)


def _bwd(chunk, impl, res, g):
    u, delta, a, b, c, d, z, starts = res
    with scope("ssm_scan_bwd"):
        gf, zf = g.astype(jnp.float32), z.astype(jnp.float32)
        sig = jax.nn.sigmoid(zf)
        gy = gf * zf * sig  # cotangent of Σ_n h C + D u
        if _use_kernel(impl, u.shape[-1]):
            from p2pfl_tpu.ops.selective_scan_kernel import scan_bwd

            y_scan, (du, d_delta, da, db, dc) = scan_bwd(u, delta, a, b, c, starts, gy, chunk, interpret=not _on_tpu())
        else:
            y_scan, (du, d_delta, da, db, dc) = _scan_bwd_xla(u, delta, a, b, c, starts, gy, chunk)
        dz = gf * _with_skip(y_scan, u, d) * sig * (1.0 + zf * (1.0 - sig))
        dd = jnp.sum(gy * u.astype(jnp.float32), axis=(0, 1))
        du = du + gy * d.astype(jnp.float32)
    cast = lambda x, like: x.astype(like.dtype)  # noqa: E731
    return (
        cast(du, u), cast(d_delta, delta), cast(da, a), cast(db, b), cast(dc, c), cast(dd, d), cast(dz, z)
    )


_selective_scan.defvjp(_fwd, _bwd)


def selective_scan(
    u: jax.Array,  # [B, T, Dm] the mixer's (convolved, activated) input
    delta: jax.Array,  # [B, T, Dm] step sizes, > 0 (after softplus)
    a: jax.Array,  # [Dm, N] negative decay rates (−exp(A_log))
    b: jax.Array,  # [B, T, N] input projection of the step
    c: jax.Array,  # [B, T, N] output projection of the step
    d: jax.Array,  # [Dm] skip
    z: jax.Array,  # [B, T, Dm] gate
    *,
    chunk: int = DEFAULT_CHUNK,
    impl: Optional[str] = None,
) -> jax.Array:
    """``y [B, T, Dm]`` in ``u``'s dtype; the state and all arithmetic in
    float32. Differentiable in all seven arguments. ``impl``: ``None`` picks by
    backend (the Pallas kernels on a TPU, plain XLA elsewhere), ``"xla"`` /
    ``"pallas"`` force one (the kernels interpreted off-TPU)."""
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    return _selective_scan(u, delta, a, b, c, d, z, chunk, impl)


def selective_scan_reference(u, delta, a, b, c, d, z):
    """The recurrence one token at a time, differentiated by JAX (what the
    tests hold the chunked op to)."""
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    a_t = f32(a).T

    def step(h, xs):
        u_t, d_t, b_t, c_t = xs
        return _advance(h, a_t, d_t, d_t * u_t, b_t, c_t)

    h0 = jnp.zeros((u.shape[0], *a_t.shape), jnp.float32)
    time_major = lambda x: jnp.moveaxis(f32(x), 1, 0)  # noqa: E731
    _, ys = lax.scan(step, h0, tuple(time_major(x) for x in (u, delta, b, c)))
    return _gated(jnp.moveaxis(ys, 0, 1), u, d, z).astype(u.dtype)
