"""Grouped matrix multiplication: every row times ITS OWN group's matrix.

The expert layer of a sparse model groups its ``tokens × top_k`` assignments by
expert and multiplies each expert's rows by that expert's ``[K, N]`` matrix::

    out[r] = lhs[r] @ rhs[group_of(r)]            rhs: [G, K, N], frozen

Rows arrive in the *tile-aligned grouped layout* of :func:`group_layout`:
group ``g`` owns ``ceil(size_g / tile_m)`` whole row tiles, its rows first and
zero rows after them, so a row tile never straddles two groups. That costs at
most ``G`` partly-filled tiles (what a kernel that lets tiles straddle pays as
well: a straddled tile is multiplied once per group) and buys a kernel body
that is one plain matmul with nothing to mask.

On a TPU the product is the Mosaic kernel ``p2pfl_gmm``: the row tiles run in
order over the grid, the rows and the output through the grid's own pipeline,
and because the row tiles of one group are consecutive the kernel fetches each
group's matrix ONCE a pass — the bank is read once, the rows once. The bank
itself stays in HBM and the kernel copies it, a matrix block a group, into a
ring of two VMEM slots: when a group's FIRST row tile starts, the copy of the
next group that has rows is issued into the slot the group before it has just
left, so a fetch has the whole of a group's tiles to arrive behind. (Until
PR 34 the matrix was a block of the grid's pipeline, which asks for step
``i + 1``'s block when step ``i`` starts — at a group's LAST tile: one 128-row
tile, 6-12 µs, against a 7-15 MB copy of 9-18 µs at 819 GB/s, so every group
boundary stalled, 10-13 % of a call at both expert cells' shapes.) Which fetch
each tile multiplies, the group of each fetch and the counts of both
(:func:`tiles_and_fetches`) are prefetched scalars. Tiles past the used count
are not multiplied, fetch nothing and are NOT WRITTEN: every grid step past the
count names the call's last tile, one resident block that is zeroed once and
flushed once — the one place past the used tiles that anyone reads (a held
share's absent assignments: :func:`group_layout`). Until PR 38 each was written
as zeros: 400 of 544 tiles a call where a chip holds a quarter of the experts,
a quarter of the kernel's time (PERF.md). A call's first block is the one fetch
nothing hides. Everywhere else (the CPU tests, ``impl="xla"``) the product is
``lax.ragged_dot`` over the same layout, which leaves every row past the groups
zero.

A stack of banks ``[L, G, K, N]`` — the layers of a scanned run — is read in
place as well: ``layer`` is one more prefetched scalar and the copy's source is
``rhs[layer, group]``. The scan body takes the whole stack as a loop
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
# one matrix block of the bank may take this much VMEM (the ring holds
# `_RING_SLOTS` of them); the GLM and LFM2 expert matrices (2048 x 3072 / 1536 x 2048 and
# 2048 x 3584 / 1792 x 2048 bf16: 12.6 / 6.3 and 14.7 / 7.3 MB) go in whole, so
# the rows are read once a pass
_RHS_BLOCK_BYTES = 16 * 1024 * 1024
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# matrix blocks in the kernel's ring: the one being multiplied and the next
# group's. With a third (the block two groups ahead in flight as well) the
# product read 0.5-1.7 % SLOWER at both expert cells' shapes (PERF.md, PR 34)
_RING_SLOTS = 2


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


def group_layout(
    group_of: jax.Array,
    n_groups: int,
    tile_m: int = DEFAULT_TILE_M,
    first_group: int = 0,
    spare_tile: bool = False,
    per_token: int = 1,
) -> GroupLayout:
    """Lay ``group_of`` (``[M]`` int: the group of each assignment) out by group,
    assignments of one group in their order (a stable sort's answer), each group
    padded to whole row tiles. Nothing is sorted — the layout is COUNTED: an
    assignment's row is its group's first row plus the number of earlier
    assignments of that group, a running count along the one-hot ``[G, M]`` of
    ``group_of`` (the long axis in the lanes). No gather and one scatter, with
    unique indices: ``assignment_of_slot``. (The sorted layout's three gathers
    of ``M`` scalars and its three scatters cost 0.74 ms a call on the chip, its
    sort 0.08: PERF.md, PR 36.) Deterministic: the same ``group_of`` gives the
    same rows, which is what lets remat's re-forward repeat the forward.

    A HELD SHARE: the ``n_groups`` groups laid out are ``first_group ..
    first_group + n_groups - 1`` of a larger numbering, and an assignment to any
    other group is ABSENT — it belongs to no group, counts in no size and owns
    no row. ``spare_tile`` appends one row tile that no group owns; an absent
    assignment's ``slot_of_assignment`` is a row of THAT tile, picked by its
    token (assignment ``a`` is token ``a // per_token``'s: consecutive tokens
    read consecutive rows, ``tile_m`` tokens apart the same one). Every row of
    the spare tile is padding whatever the split (``assignment_of_slot`` says
    ``M`` there): zero going in, and — the call's last tile — zero coming out of
    the matmul. (Until PR 38 every absent assignment read the tile's last row:
    three quarters of a slab's indices at one 4 KB address cost the gather a
    fifth of its time on the chip, PERF.md.) Without absent assignments none of
    the three arguments is needed and the layout is what it has been."""
    m = group_of.shape[0]
    rows = tile_m * (n_row_tiles(m, n_groups, tile_m) + int(spare_tile))
    groups = jnp.arange(n_groups, dtype=jnp.int32)
    if first_group:
        groups = first_group + groups
    mine = groups[:, None] == group_of.astype(jnp.int32)[None, :]
    through = jnp.cumsum(mine, axis=1, dtype=jnp.int32)  # [g, a]: assignments of g through a
    sizes = through[:, -1]
    tiles = _tiles_per_group(sizes, tile_m)
    padded_start = tile_m * (jnp.cumsum(tiles) - tiles)
    slot_of_assignment = jnp.sum(jnp.where(mine, padded_start[:, None] + through - 1, 0), axis=0)
    if not spare_tile:
        assignment_of_slot = jnp.full((rows,), m, jnp.int32).at[slot_of_assignment].set(
            jnp.arange(m, dtype=jnp.int32), unique_indices=True, mode="promise_in_bounds"
        )
        return GroupLayout(sizes, slot_of_assignment, assignment_of_slot, rows)
    present = jnp.any(mine, axis=0)
    # an absent assignment scatters nowhere (past the rows, each to an index of its
    # own: dropped, and still unique) and reads its token's row of the spare tile
    every = jnp.arange(m, dtype=jnp.int32)
    assignment_of_slot = jnp.full((rows,), m, jnp.int32).at[jnp.where(present, slot_of_assignment, rows + every)].set(
        every, unique_indices=True, mode="drop"
    )
    spare_row = rows - tile_m + (every // per_token) % tile_m
    return GroupLayout(sizes, jnp.where(present, slot_of_assignment, spare_row), assignment_of_slot, rows)


def tiles_and_fetches(group_sizes: jax.Array, tile_m: int) -> tuple[jax.Array, jax.Array]:
    """(row tiles in use, matrix blocks fetched) by one call over ``group_sizes``:
    ``Σ ceil(s_g / tile_m)`` and the number of groups with a row. Their ratio is
    the arithmetic a fetch has to hide behind (a column-split call fetches that
    many blocks a column block)."""
    tiles = _tiles_per_group(group_sizes, tile_m)
    return tiles.sum().astype(jnp.int32), (tiles > 0).sum().astype(jnp.int32)


def _fetch_plan(group_sizes: jax.Array, n_tiles: int, tile_m: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The kernel's prefetched scalars: ``[n_tiles]`` the fetch each row tile
    multiplies (its group's rank among the groups WITH rows; a tile past the
    used count names the last used tile's), ``[G]`` the group of each fetch
    (the groups with rows, in order) and ``[2]`` (tiles in use, fetches).
    Counted by comparing every tile, and every fetch, with every group: a few
    small fusions, where running sums, a search and a scatter were a loop and
    a dozen launches (10-15 µs a call on the chip: PERF.md, PR 34)."""
    tiles = _tiles_per_group(group_sizes, tile_m)
    used, fetches = tiles_and_fetches(group_sizes, tile_m)
    has = tiles > 0
    group = jnp.arange(group_sizes.shape[0], dtype=jnp.int32)
    upto = group[:, None] >= group[None, :]  # [g, h]: group h is g or before it
    ends = jnp.sum(jnp.where(upto, tiles[None, :], 0), axis=1)  # row tiles through group g
    rank_end = jnp.sum(upto & has[None, :], axis=1)  # groups with rows through group g
    tile = jnp.minimum(jnp.arange(n_tiles, dtype=jnp.int32), jnp.maximum(used - 1, 0))
    # the groups with rows that END at or before a tile are those before its own
    tile_fetch = jnp.sum(has[None, :] & (ends[None, :] <= tile[:, None]), axis=1)
    # fetch r is the first group through which r + 1 groups have rows
    fetch_group = jnp.minimum(jnp.sum(rank_end[None, :] <= group[:, None], axis=1), group[-1])
    return tile_fetch.astype(jnp.int32), fetch_group.astype(jnp.int32), jnp.stack([used, fetches])


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _gmm_kernel(
    layer_ref, tile_fetch_ref, fetch_group_ref, counts_ref, lhs_ref, rhs_hbm, out_ref, ring, sems,
    *, transpose_rhs: bool, n_cols: int,
):  # fmt: skip
    j, i = pl.program_id(0), pl.program_id(1)
    used, fetches = counts_ref[0], counts_ref[1]
    bn = out_ref.shape[1]
    blocks = fetches * n_cols  # of the whole call

    def fetch(f):
        """The copy of the call's ``f``-th matrix block — column block
        ``f // fetches`` of group ``fetch_group[f % fetches]`` — into its slot."""
        if n_cols == 1:  # the whole matrix is one block
            src = rhs_hbm.at[layer_ref[0], fetch_group_ref[f]]
        else:
            g, col = fetch_group_ref[lax.rem(f, fetches)], pl.multiple_of(lax.div(f, fetches) * bn, bn)
            cut = (pl.ds(col, bn), slice(None)) if transpose_rhs else (slice(None), pl.ds(col, bn))
            src = rhs_hbm.at[(layer_ref[0], g, *cut)]
        slot = lax.rem(f, _RING_SLOTS)
        return pltpu.make_async_copy(src, ring.at[slot], sems.at[slot])

    @pl.when(i < used)
    def _():
        f = j * fetches + tile_fetch_ref[i]

        @pl.when((i == 0) | (tile_fetch_ref[i] != tile_fetch_ref[jnp.maximum(i - 1, 0)]))
        def _():  # a group's first tile
            @pl.when(f == 0)
            def _():  # the call's first block: nothing hides it
                fetch(0).start()

            fetch(f).wait()

            # every tile of the block before this one is done, so its slot is
            # free for the next, which has the whole of this group's tiles to
            # arrive behind
            @pl.when(f + 1 < blocks)
            def _():
                fetch(f + 1).start()

        contract = (((1,), (1,)), ((), ())) if transpose_rhs else (((1,), (0,)), ((), ()))
        # the block is multiplied in the rows' dtype (a float32 test model over
        # the bf16 bank widens ONE block in VMEM; bf16 rows: no cast at all)
        out_ref[...] = lax.dot_general(
            lhs_ref[...], ring[lax.rem(f, _RING_SLOTS)].astype(lhs_ref.dtype), contract, preferred_element_type=jnp.float32
        ).astype(out_ref.dtype)

    # every step past the used count names the call's LAST tile (`out_map`): one
    # resident block, zeroed once and flushed once; the tiles between are not written
    @pl.when(i == used)
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

    def lhs_map(j, i, layer_ref, tile_fetch_ref, fetch_group_ref, counts_ref):
        # an unused tile re-names the last used one: no new fetch
        return jnp.minimum(i, jnp.maximum(counts_ref[0] - 1, 0)), 0

    def out_map(j, i, layer_ref, tile_fetch_ref, fetch_group_ref, counts_ref):
        # a tile nobody owns is not written: the run of unused steps holds the call's last tile
        return jnp.where(i < counts_ref[0], i, n_tiles - 1), j

    return pl.pallas_call(
        partial(_gmm_kernel, transpose_rhs=transpose_rhs, n_cols=out // bn),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            # the row tiles run innermost and in order: the tiles of one group
            # are consecutive, and the ring's fetches are issued in that order
            grid=(out // bn, n_tiles),
            # the bank stays in HBM: the kernel copies a matrix block a group into the ring
            in_specs=[pl.BlockSpec((tile_m, contract), lhs_map), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile_m, bn), out_map),
            scratch_shapes=[
                pltpu.VMEM((_RING_SLOTS, bn, n) if transpose_rhs else (_RING_SLOTS, k, bn), rhs.dtype),
                pltpu.SemaphoreType.DMA((_RING_SLOTS,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, out), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            # the ring carries blocks from one grid step to the next, over both axes
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
        name="p2pfl_gmm",
    )(layer.reshape(1), *_fetch_plan(group_sizes, n_tiles, tile_m), lhs, rhs)


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
    rows inside a used tile and the call's last tile come out zero; other
    unused tiles are not written (the kernel; ``lax.ragged_dot`` writes zeros
    there) and, going in, are not read — whoever consumes the result reads an
    assignment's row or the last tile, or keeps what it reads of a row inside
    that row. ``lhs``: ``[rows, K]`` (``[rows, N]``
    transposed), ``rhs``: ``[G, K, N]`` in any float dtype — it is read as it
    is stored, never cast — or a stack ``[L, G, K, N]`` with ``layer`` (an int32
    scalar, traced or not) naming the bank to use. Differentiable in ``lhs``
    only. ``impl``: ``None`` picks the Mosaic kernel on a TPU and
    ``lax.ragged_dot`` elsewhere; ``"xla"`` / ``"pallas"`` force one (the kernel
    interpreted off-TPU: the interpreter shows an unwritten tile as NaN)."""
    if (rhs.ndim == 4) != (layer is not None):
        raise ValueError("grouped_matmul: a stack of banks [L, G, K, N] comes with `layer`, one bank [G, K, N] without")
    if layer is None:
        rhs, layer = rhs[None], 0  # a bitcast: one bank is a stack of one
    return _grouped_matmul(lhs, rhs, jnp.asarray(layer, jnp.int32), group_sizes, tile_m, transpose_rhs, impl)
