"""Shard-to-shard transfer glue for the ICI weights plane.

The primitive behind ``communication/ici.py``: move a pytree that lives on
one node's device slice onto the matching devices of a peer's slice —
device ``p`` of the source slice copies its block directly to device ``p``
of the destination slice — without the data ever visiting the host.

Mechanics (the zero-copy "pair mesh" idiom):

1. Source and destination slices are described by :class:`SliceInfo`
   (slice mesh + per-leaf partition specs), derived from the live arrays
   by :func:`slice_info_of`. A single-chip node is the degenerate
   one-device slice.
2. For a transfer, the two slices' device arrays stack into one
   ``(2, *slice_shape)`` **pair mesh**. Each leaf is wrapped into a
   ``(2, *leaf.shape)`` pair-global array sharded ``P('ici_pair', *spec)``
   — pure metadata assembly (``make_array_from_single_device_arrays``
   over the *existing* shards plus the receiver-side filler blocks; the
   only per-shard work is a device-local leading-axis reshape).
3. One jitted ``shard_map`` program over the pair mesh exchanges the two
   blocks along ``ici_pair``: the pure-XLA backend is a
   ``lax.ppermute`` collective (CPU-runnable — the bit-parity fallback
   tier-1 and the chaos suite exercise on the virtual device mesh); the
   TPU backend is a Pallas remote-DMA kernel
   (``pltpu.make_async_remote_copy`` — each device RDMAs its block
   straight into the partner chip's HBM, the SNIPPETS right-permute
   shape specialized to a pair). Both backends move the same shards, so
   backend choice can never change what the receiver decodes.
4. The output's destination-side blocks re-wrap under the receiver's own
   shardings — metadata assembly again — so the delivered tree is
   *already placed* exactly where the receiver's jits expect it and
   ``ops/tree.tree_align_devices`` is an asserted no-op downstream.

This module is inside the ``no-host-gather`` analyzer scope
(:mod:`p2pfl_tpu.analysis`): no ``np.asarray``/``jax.device_get``/
``.tobytes()`` may appear here — the zero-host-bytes contract is enforced
statically, not by prose.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Pytree = Any

#: leading axis of the transfer pair mesh (block 0 = sender's slice,
#: block 1 = receiver's)
PAIR_AXIS = "ici_pair"
#: synthesized sub-axis name for the degenerate single-device slice
_SUB_AXIS = "ici_sub"

#: compiled exchange programs, keyed on (pair device ids, gspecs, backend)
#: — jax.jit handles per-shape caching under each entry
_programs: dict = {}


@dataclass(frozen=True)
class SliceInfo:
    """Where a pytree lives: the slice mesh + per-leaf partition specs.

    ``mesh`` is the node's ``(data, model)`` submesh for placed learners,
    or a synthesized one-device mesh for single-chip nodes; ``specs`` is
    one :class:`~jax.sharding.PartitionSpec` per leaf in
    ``jax.tree.leaves`` order. Equality of ``specs`` + mesh layout is
    what makes two slices shard-compatible.
    """

    mesh: Mesh
    specs: tuple

    @property
    def device_ids(self) -> frozenset:
        return frozenset(d.id for d in self.mesh.devices.flat)

    @property
    def shape(self) -> tuple:
        """The slice's devices-array shape — the wire ``sp`` handshake's
        first element."""
        return tuple(self.mesh.devices.shape)


def _single_device_mesh(device) -> Mesh:
    arr = np.empty((1,), dtype=object)
    arr[0] = device
    return Mesh(arr, (_SUB_AXIS,))


def slice_info_of(tree: Pytree) -> Optional[SliceInfo]:
    """Derive the :class:`SliceInfo` of a live pytree, or ``None``.

    Eligible trees: every leaf a committed ``jax.Array``, either all on
    ONE device (single-chip node — synthesized one-device mesh, all
    specs replicated) or all ``NamedSharding`` over one common mesh
    (submesh-placed learner). Anything mixed — host numpy leaves, leaves
    scattered across meshes — returns ``None`` and the caller falls back
    to the byte path.
    """
    leaves = jax.tree.leaves(tree)
    if not leaves or not all(isinstance(x, jax.Array) for x in leaves):
        return None
    shardings = [x.sharding for x in leaves]
    if all(isinstance(s, NamedSharding) for s in shardings):
        mesh = shardings[0].mesh
        if any(s.mesh is not mesh and s.mesh != mesh for s in shardings[1:]):
            return None
        return SliceInfo(mesh=mesh, specs=tuple(s.spec for s in shardings))
    device_sets = [s.device_set for s in shardings]
    first = device_sets[0]
    if len(first) == 1 and all(ds == first for ds in device_sets[1:]):
        (dev,) = first
        return SliceInfo(
            mesh=_single_device_mesh(dev), specs=tuple(P() for _ in leaves)
        )
    return None


def same_devices(src: SliceInfo, dst: SliceInfo) -> bool:
    """True when the two slices are the SAME devices with the SAME
    per-leaf layout — the degenerate co-residency case where a transfer
    is a zero-copy handoff (the shards are already where the receiver
    wants them)."""
    return (
        src.device_ids == dst.device_ids
        and src.shape == dst.shape
        and src.specs == dst.specs
    )


def transfer_compatible(src: SliceInfo, dst: SliceInfo) -> bool:
    """True when a shard-to-shard pair transfer between the slices is
    well-defined: same slice topology (devices-array shape + axis
    names), identical per-leaf specs (device ``p`` holds the same block
    on both sides), and disjoint device sets (each chip belongs to one
    side of the pair)."""
    return (
        src.shape == dst.shape
        and src.mesh.axis_names == dst.mesh.axis_names
        and src.specs == dst.specs
        and not (src.device_ids & dst.device_ids)
    )


def tree_device_bytes(tree: Pytree) -> int:
    """Payload size moved over the interconnect (metadata only — reads
    shapes/dtypes, never the buffers)."""
    return sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)
        if isinstance(x, jax.Array)
    )


# ---- the exchange program ----


def _pallas_exchange(v, sub_axes: tuple):
    """Pair exchange of one leaf block as a Pallas TPU remote DMA.

    Each device RDMAs its local block straight into the HBM of the
    partner device — same sub-axis coordinates, opposite side of the
    pair (the SNIPPETS [2] ``right_permute`` shape specialized to a
    2-cycle). Refs live in ``ANY`` memory space so arbitrarily large
    parameter blocks stream HBM→HBM without a VMEM bound; the DMA
    semaphore pair is scratch. Only lowers on real TPU hardware — the
    ``ppermute`` backend is the everywhere-else fallback.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(x_ref, o_ref, send_sem, recv_sem):
        my_pair = jax.lax.axis_index(PAIR_AXIS)
        partner = (1 - my_pair, *(jax.lax.axis_index(a) for a in sub_axes))
        rdma = pltpu.make_async_remote_copy(
            src_ref=x_ref,
            dst_ref=o_ref,
            send_sem=send_sem,
            recv_sem=recv_sem,
            device_id=partner,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        rdma.wait()

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(v.shape, v.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=0
        ),
    )(v)


def _exchange_program(pair_mesh: Mesh, gspecs: tuple, backend: str):
    key = (
        tuple(d.id for d in pair_mesh.devices.flat),
        pair_mesh.axis_names,
        gspecs,
        backend,
    )
    prog = _programs.get(key)
    if prog is not None:
        return prog
    sub_axes = pair_mesh.axis_names[1:]

    if backend == "pallas":

        def body(*leaves):
            return tuple(_pallas_exchange(v, sub_axes) for v in leaves)

    else:

        def body(*leaves):
            # a 2-cycle: both blocks swap sides in one collective, so the
            # kernel stays uniform across the pair (no masked sender) and
            # the discarded source-side block costs nothing extra
            return tuple(
                jax.lax.ppermute(v, PAIR_AXIS, perm=((0, 1), (1, 0)))
                for v in leaves
            )

    # pallas_call's out_shape carries no vma typing — check off for it
    prog = jax.jit(
        jax.shard_map(
            body, mesh=pair_mesh, in_specs=gspecs, out_specs=gspecs,
            check_vma=backend != "pallas",
        )
    )
    _programs[key] = prog
    return prog


def _pair_global(leaf_src, leaf_fill, gsharding: NamedSharding):
    """Wrap the two slices' existing shards into one pair-global array.

    Metadata assembly: the only per-shard work is the device-local
    leading-axis reshape (no transfer, no host)."""
    gshape = (2,) + tuple(leaf_src.shape)
    dmap = {}
    for s in leaf_src.addressable_shards:
        dmap[s.device] = s.data.reshape((1,) + s.data.shape)
    for s in leaf_fill.addressable_shards:
        dmap[s.device] = s.data.reshape((1,) + s.data.shape)
    arrs = [dmap[d] for d in gsharding.addressable_devices_indices_map(gshape)]
    return jax.make_array_from_single_device_arrays(gshape, gsharding, arrs)


def _dst_view(out_leaf, dst_sharding: NamedSharding, shape: tuple, dst_devs: set):
    """The receiver-side block of an exchanged pair-global, re-wrapped
    under the receiver's own sharding (metadata assembly again)."""
    omap = {
        s.device: s.data.reshape(s.data.shape[1:])
        for s in out_leaf.addressable_shards
        if s.device in dst_devs
    }
    arrs = [omap[d] for d in dst_sharding.addressable_devices_indices_map(shape)]
    return jax.make_array_from_single_device_arrays(shape, dst_sharding, arrs)


def shard_transfer(
    tree: Pytree,
    filler: Pytree,
    src: SliceInfo,
    dst: SliceInfo,
    backend: str = "ppermute",
) -> Pytree:
    """Move ``tree`` from slice ``src`` onto slice ``dst``, shard to shard.

    ``filler`` is a structurally-identical pytree already resident on
    ``dst`` (the receiver's current parameters, or cached zero buffers
    for codec payloads) — its shards complete the pair-global's
    receiver-side blocks; its VALUES are discarded by the exchange.
    Returns the tree placed under ``dst``'s shardings. One jitted
    dispatch for the whole tree; everything else is metadata.
    """
    leaves = jax.tree.leaves(tree)
    fillers = jax.tree.leaves(filler)
    treedef = jax.tree.structure(tree)
    pair_devices = np.stack([src.mesh.devices, dst.mesh.devices])
    pair_mesh = Mesh(pair_devices, (PAIR_AXIS, *src.mesh.axis_names))
    gspecs = tuple(P(PAIR_AXIS, *spec) for spec in src.specs)
    gshardings = [NamedSharding(pair_mesh, gs) for gs in gspecs]
    pair_globals = tuple(
        _pair_global(a, b, gs) for a, b, gs in zip(leaves, fillers, gshardings)
    )
    prog = _exchange_program(pair_mesh, gspecs, backend)
    outs = prog(*pair_globals)
    dst_devs = set(dst.mesh.devices.flat)
    new_leaves = [
        _dst_view(
            o,
            NamedSharding(dst.mesh, spec),
            tuple(x.shape),
            dst_devs,
        )
        for o, spec, x in zip(outs, dst.specs, leaves)
    ]
    return jax.tree.unflatten(treedef, new_leaves)


def conform_specs(tree: Pytree, mesh: Mesh, specs: tuple) -> tuple[Pytree, int]:
    """Re-lay out a pytree to ``specs`` on ``mesh``, counting moved leaves.

    A payload's producing program (an aggregation fold, an XLA-chosen
    output layout) may leave leaves on the sender's slice in a DIFFERENT
    per-leaf layout than the receiver's placement expects. Conforming at
    the SOURCE — one ``device_put`` per differing leaf, device-to-device
    within the slice — is what lets the subsequent pair transfer land
    every block exactly where the receiver's jits want it, keeping
    ``tree_align_devices`` a no-op downstream. Returns
    ``(conformed_tree, moved_leaf_count)``.
    """
    leaves = jax.tree.leaves(tree)
    treedef = jax.tree.structure(tree)
    moved = 0
    out = []
    for leaf, spec in zip(leaves, specs):
        target = NamedSharding(mesh, spec)
        if isinstance(leaf, jax.Array) and leaf.sharding == target:
            out.append(leaf)
        else:
            moved += 1
            out.append(jax.device_put(leaf, target))
    return jax.tree.unflatten(treedef, out), moved


def replicate_on_slice(tree: Pytree, info: SliceInfo) -> Pytree:
    """Re-place a pytree replicated over a slice's devices (D2D within
    the slice — used to give codec buffers a deterministic layout before
    a pair transfer). No-op for the single-device slice when the leaves
    already live there."""
    sharding = NamedSharding(info.mesh, P())
    slice_devices = set(info.mesh.devices.flat)

    def one(x):
        if isinstance(x, jax.Array):
            if x.sharding == sharding:
                return x
            # a one-device slice: anything already committed to that
            # device IS "replicated over the slice" — skip the copy
            if len(slice_devices) == 1 and x.sharding.device_set == slice_devices:
                return x
        return jax.device_put(x, sharding)

    return jax.tree.map(one, tree)
