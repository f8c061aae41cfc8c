"""Grouped matrix multiplication: every row times ITS OWN group's matrix.

The expert layer of a sparse model sorts its ``tokens × top_k`` assignments by
expert and multiplies each expert's rows by that expert's ``[K, N]`` matrix::

    out[r] = lhs[r] @ rhs[group_of(r)]            rhs: [G, K, N], frozen

Rows arrive in the *tile-aligned grouped layout* of :func:`group_layout`:
group ``g`` owns ``ceil(size_g / tile_m)`` whole row tiles, its rows first and
zero rows after them, so a row tile never straddles two groups. That costs at
most ``G`` partly-filled tiles (what a kernel that lets tiles straddle pays as
well: a straddled tile is multiplied once per group) and buys a kernel body
that is one plain matmul with nothing to mask.

On a TPU the product is the Mosaic kernel ``p2pfl_gmm``: ``tile_group[i]`` (the
group of row tile ``i``, from ``group_sizes``) and the number of tiles in use
are prefetched as scalars, the block index map picks ``rhs[tile_group[i]]`` for
step ``i``, and because the row tiles of one group are consecutive the
pipeline fetches each group's matrix ONCE a pass — the bank is read once, the
rows once. Tiles past the used count are not multiplied (they are written as
zeros) and fetch nothing new. Everywhere else (the CPU tests, ``impl="xla"``)
the product is ``lax.ragged_dot`` over the same layout.

A stack of banks ``[L, G, K, N]`` — the layers of a scanned run — is read in
place as well: ``layer`` is a third prefetched scalar and the index map picks
``rhs[layer, tile_group[i]]``. The scan body takes the whole stack as a loop
constant; nothing slices it (a Mosaic call cannot read a slice of an operand in
place: XLA would copy the layer's bank before every call).

The product runs under the scope ``p2pfl.moe_gmm`` (forward, re-forward and
backward; the kernel with the few scalar ops that make its prefetched
arguments), so a trace tells its time from the gathers around it.

The bank is frozen: the ``custom_vjp`` has an input cotangent
(``dlhs = dout @ rhs[g]ᵀ`` — the SAME kernel contracting the matrix's other
axis, no transposed copy of the bank is made) and none for ``rhs``. Two kernel
faces, not three.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from p2pfl_tpu.management.profiling import scope

DEFAULT_TILE_M = 128
# one matrix block of the bank may take this much VMEM (it is double-buffered);
# the GLM expert matrices (2048 x 3072 and 1536 x 2048 bf16: 12.6 and 6.3 MB)
# go in whole, so the rows are read once a pass
_RHS_BLOCK_BYTES = 16 * 1024 * 1024
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


class GroupLayout(NamedTuple):
    """Where each assignment's row lives in the tile-aligned grouped layout."""

    group_sizes: jax.Array  # [G] int32: assignments per group
    slot_of_assignment: jax.Array  # [M] int32: row of assignment a
    assignment_of_slot: jax.Array  # [rows] int32: assignment in row r, M where the row is padding
    rows: int  # static: tile_m * n_tiles


def n_row_tiles(n_assignments: int, n_groups: int, tile_m: int) -> int:
    """Row tiles that hold ANY split of ``n_assignments`` over ``n_groups``:
    ``Σ ceil(s_g / tile_m) <= (M + G (tile_m - 1)) / tile_m``."""
    return max(1, (n_assignments + n_groups * (tile_m - 1)) // tile_m)


def _tiles_per_group(group_sizes: jax.Array, tile_m: int) -> jax.Array:
    return (group_sizes + (tile_m - 1)) // tile_m


def group_layout(group_of: jax.Array, n_groups: int, tile_m: int = DEFAULT_TILE_M) -> GroupLayout:
    """Lay ``group_of`` (``[M]`` int: the group of each assignment) out by group:
    a stable sort (assignments of one group keep their order), each group
    padded to whole row tiles. Deterministic: the same ``group_of`` gives the
    same rows, which is what lets remat's re-forward repeat the forward."""
    m = group_of.shape[0]
    rows = tile_m * n_row_tiles(m, n_groups, tile_m)
    group_of = group_of.astype(jnp.int32)
    sizes = jnp.zeros((n_groups,), jnp.int32).at[group_of].add(1)
    tiles = _tiles_per_group(sizes, tile_m)
    padded_start = tile_m * (jnp.cumsum(tiles) - tiles)
    sorted_start = jnp.cumsum(sizes) - sizes
    order = jnp.argsort(group_of, stable=True).astype(jnp.int32)
    sorted_group = group_of[order]
    slot_sorted = padded_start[sorted_group] + jnp.arange(m, dtype=jnp.int32) - sorted_start[sorted_group]
    slot_of_assignment = jnp.zeros((m,), jnp.int32).at[order].set(slot_sorted)
    assignment_of_slot = jnp.full((rows,), m, jnp.int32).at[slot_sorted].set(order)
    return GroupLayout(sizes, slot_of_assignment, assignment_of_slot, rows)


def _tile_groups(group_sizes: jax.Array, n_tiles: int, tile_m: int) -> tuple[jax.Array, jax.Array]:
    """(``[n_tiles]`` group of each row tile, ``[1]`` tiles in use). A tile past
    the used count names the last used tile's group: it fetches no matrix."""
    ends = jnp.cumsum(_tiles_per_group(group_sizes, tile_m))
    used = ends[-1]
    tile = jnp.minimum(jnp.arange(n_tiles, dtype=jnp.int32), jnp.maximum(used - 1, 0))
    group = jnp.searchsorted(ends, tile, side="right").astype(jnp.int32)
    return jnp.minimum(group, group_sizes.shape[0] - 1), used.reshape(1).astype(jnp.int32)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _gmm_kernel(layer_ref, tile_group_ref, used_ref, lhs_ref, rhs_ref, out_ref, *, transpose_rhs: bool):
    del layer_ref, tile_group_ref  # read by the index maps
    i = pl.program_id(1)

    @pl.when(i < used_ref[0])
    def _():
        contract = (((1,), (1,)), ((), ())) if transpose_rhs else (((1,), (0,)), ((), ()))
        # the block is multiplied in the rows' dtype (a float32 test model over
        # the bf16 bank widens ONE block in VMEM; bf16 rows: no cast at all)
        out_ref[...] = lax.dot_general(
            lhs_ref[...], rhs_ref[...].astype(lhs_ref.dtype), contract, preferred_element_type=jnp.float32
        ).astype(out_ref.dtype)

    @pl.when(i >= used_ref[0])
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)


def _block_n(contract: int, out: int, itemsize: int) -> int:
    """The widest output block (a divisor of ``out``, a multiple of 128 lanes
    unless it is ``out`` itself) whose matrix block fits ``_RHS_BLOCK_BYTES``."""
    for parts in range(1, out + 1):
        if out % parts:
            continue
        bn = out // parts
        if parts > 1 and bn % 128:
            continue
        if contract * bn * itemsize <= _RHS_BLOCK_BYTES:
            return bn
    return out


def _gmm_pallas(lhs, rhs, layer, group_sizes, tile_m: int, transpose_rhs: bool, interpret: bool):
    rows, contract = lhs.shape
    _, _, k, n = rhs.shape
    out = k if transpose_rhs else n
    n_tiles = rows // tile_m
    bn = _block_n(contract, out, rhs.dtype.itemsize)
    tile_group, used = _tile_groups(group_sizes, n_tiles, tile_m)

    def lhs_map(j, i, layer_ref, tile_group_ref, used_ref):
        # an unused tile re-names the last used one: no new fetch
        return jnp.minimum(i, jnp.maximum(used_ref[0] - 1, 0)), 0

    if transpose_rhs:
        rhs_spec = pl.BlockSpec((None, None, bn, n), lambda j, i, layer_ref, tg, used_ref: (layer_ref[0], tg[i], j, 0))
    else:
        rhs_spec = pl.BlockSpec((None, None, k, bn), lambda j, i, layer_ref, tg, used_ref: (layer_ref[0], tg[i], 0, j))
    return pl.pallas_call(
        partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            # the row tiles run innermost and in order: consecutive tiles of
            # one group name the same matrix block, which is then not fetched again
            grid=(out // bn, n_tiles),
            in_specs=[pl.BlockSpec((tile_m, contract), lhs_map), rhs_spec],
            out_specs=pl.BlockSpec((tile_m, bn), lambda j, i, layer_ref, tg, used_ref: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, out), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
        name="p2pfl_gmm",
    )(layer.reshape(1), tile_group, used, lhs, rhs)


@jax.custom_batching.custom_vmap
def _ragged_dot(lhs, rhs, sizes):
    return lax.ragged_dot(lhs, rhs, sizes, preferred_element_type=jnp.float32).astype(lhs.dtype)


@_ragged_dot.def_vmap
def _ragged_dot_vmap(axis_size, in_batched, lhs, rhs, sizes):
    """``lax.ragged_dot`` has no batching rule for batched group sizes: the
    mapped elements run one after the other (the bank is not broadcast)."""
    pick = lambda i: [x[i] if mapped else x for x, mapped in zip((lhs, rhs, sizes), in_batched)]  # noqa: E731
    return lax.map(lambda i: _ragged_dot(*pick(i)), jnp.arange(axis_size)), True


def _gmm_xla(lhs, rhs, layer, group_sizes, tile_m: int, transpose_rhs: bool):
    padded = tile_m * _tiles_per_group(group_sizes, tile_m)  # rows past their sum come out zero
    rhs = lax.dynamic_index_in_dim(rhs, layer, 0, keepdims=False)  # this path copies the layer's bank
    if transpose_rhs:
        rhs = jnp.swapaxes(rhs, 1, 2)
    return _ragged_dot(lhs, rhs.astype(lhs.dtype), padded)


def _use_kernel(impl: Optional[str]) -> bool:
    if impl not in (None, "xla", "pallas"):
        raise ValueError(f"grouped_matmul impl {impl!r} (None|xla|pallas)")
    return impl == "pallas" or (impl is None and _on_tpu())


def _gmm(lhs, rhs, layer, group_sizes, tile_m, transpose_rhs, impl):
    if lhs.shape[0] % tile_m:
        raise ValueError(f"grouped_matmul: {lhs.shape[0]} rows are no whole number of tiles of {tile_m}")
    with scope("moe_gmm"):
        if _use_kernel(impl):
            return _gmm_pallas(lhs, rhs, layer, group_sizes, tile_m, transpose_rhs, interpret=not _on_tpu())
        return _gmm_xla(lhs, rhs, layer, group_sizes, tile_m, transpose_rhs)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _grouped_matmul(lhs, rhs, layer, group_sizes, tile_m, transpose_rhs, impl):
    return _gmm(lhs, rhs, layer, group_sizes, tile_m, transpose_rhs, impl)


def _fwd(lhs, rhs, layer, group_sizes, tile_m, transpose_rhs, impl):
    return _gmm(lhs, rhs, layer, group_sizes, tile_m, transpose_rhs, impl), (rhs, layer, group_sizes)


def _bwd(tile_m, transpose_rhs, impl, res, g):
    rhs, layer, group_sizes = res
    # the frozen bank has no cotangent (None); the rows' is the same product
    # against the matrix's other axis
    return _grouped_matmul(g, rhs, layer, group_sizes, tile_m, not transpose_rhs, impl), None, None, None


_grouped_matmul.defvjp(_fwd, _bwd)


def grouped_matmul(
    lhs: jax.Array,
    rhs: jax.Array,
    group_sizes: jax.Array,
    *,
    layer: Optional[jax.Array] = None,
    tile_m: int = DEFAULT_TILE_M,
    transpose_rhs: bool = False,
    impl: Optional[str] = None,
) -> jax.Array:
    """``out[r] = lhs[r] @ rhs[g(r)]`` (``@ rhs[g(r)]ᵀ`` with ``transpose_rhs``)
    for rows in the layout of :func:`group_layout` (same ``tile_m``); padding
    rows and unused tiles come out zero. ``lhs``: ``[rows, K]`` (``[rows, N]``
    transposed), ``rhs``: ``[G, K, N]`` in any float dtype — it is read as it
    is stored, never cast — or a stack ``[L, G, K, N]`` with ``layer`` (an int32
    scalar, traced or not) naming the bank to use. Differentiable in ``lhs``
    only. ``impl``: ``None`` picks the Mosaic kernel on a TPU and
    ``lax.ragged_dot`` elsewhere; ``"xla"`` / ``"pallas"`` force one (the kernel
    interpreted off-TPU)."""
    if (rhs.ndim == 4) != (layer is not None):
        raise ValueError("grouped_matmul: a stack of banks [L, G, K, N] comes with `layer`, one bank [G, K, N] without")
    if layer is None:
        rhs, layer = rhs[None], 0  # a bitcast: one bank is a stack of one
    return _grouped_matmul(lhs, rhs, jnp.asarray(layer, jnp.int32), group_sizes, tile_m, transpose_rhs, impl)
