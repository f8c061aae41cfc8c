"""Latent attention, the dropless sigmoid-routed expert layer and its grouped
matmul, and "one dense layer, then a scanned run of expert layers" through
``CausalLM`` and ``SpmdLoraFederation`` — against the plain reference
``benchmark/reference/glm_moe_lm.py`` on seeded weights."""

import copy
import dataclasses
import hashlib
import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import checks as ck
from benchmark.reference import fedavg, glm_moe_lm
from p2pfl_tpu.learning.dataset import FederatedDataset
from p2pfl_tpu.learning.lora import _lm_forward, _lm_loss, merge_params, split_lora
from p2pfl_tpu.models.transformer import (
    CausalLM, ExpertFFN, MLAttention, TransformerConfig, _from_expert_rows, _rows_at, _to_expert_rows, choose_experts,
    layer_runs, router_scores, routing_weights, sown_by_layer, tiny_transformer,
)
from p2pfl_tpu.ops import grouped_matmul as gmm_ops
from p2pfl_tpu.ops.grouped_matmul import group_layout, grouped_matmul, n_row_tiles, tiles_and_fetches
from p2pfl_tpu.parallel import SpmdLoraFederation
from p2pfl_tpu.parallel.spmd import draw_node_perms

ROOT = Path(__file__).resolve().parent.parent
SEQ = 32
PATTERN = ("mla_dense", "mla_experts", "mla_experts", "mla_experts")
# the reference reads Hugging Face's keys; unequal low-rank widths, a shared rotary head
REF = {
    "hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4, "intermediate_size": 160,
    "rope_theta": 1e6, "rms_norm_eps": 1e-5, "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 12,
    "qk_rope_head_dim": 4, "v_head_dim": 16, "n_routed_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "n_shared_experts": 1, "routed_scaling_factor": 1.8, "first_k_dense_replace": 1,
    "vocab_size": 256,
}


def config(**kw):
    base = dict(
        vocab_size=256, dim=64, n_layers=4, n_heads=4, n_kv_heads=4, ffn_hidden=160, rope_theta=1e6,
        layer_pattern=PATTERN, lora_rank=4, lora_alpha=8.0, lora_mlp=True, dtype=jnp.float32, remat=True,
        scan_layers=True, remat_policy=None, norm_eps=1e-5, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_dim=12, qk_rope_dim=4, v_head_dim=16, routed_experts=8, experts_per_token=2, expert_hidden=32,
        shared_experts=1, routed_scale=1.8, expert_tile_m=8,
    )
    base.update(kw)
    return TransformerConfig(**base)


def seeded(cfg, seed=0):
    """Seeded weights with ``lora_b`` perturbed (at its zero start every
    ``lora_a`` gradient is exactly zero) and a router bias that changes choices."""
    model = tiny_transformer(seq_len=SEQ, seed=seed, cfg=cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 128))

    def draw(path, a):
        name = jax.tree_util.keystr(path)
        if "lora_b" in name:
            return 0.05 * jax.random.normal(next(keys), a.shape, a.dtype)
        if "router_bias" in name:
            return 0.02 * jax.random.normal(next(keys), a.shape, a.dtype)
        return a

    model.params = jax.tree_util.tree_map_with_path(draw, model.params)
    lora, base = split_lora(model.params)
    return model, lora, base


def batch(seed=0, n=2):
    x = jax.random.randint(jax.random.PRNGKey(seed), (n, SEQ + 1), 0, 256)
    return x[:, :-1], x[:, 1:]


@pytest.fixture(scope="module")
def glm():
    return seeded(config())


# ---- the grouped matmul -------------------------------------------------------


def _groups(case: str, m: int, g: int):
    if case == "even":
        return jnp.arange(m, dtype=jnp.int32) % g
    if case == "one_group":
        return jnp.full((m,), 2, jnp.int32)
    if case == "empty_groups":
        return 2 * (jnp.arange(m, dtype=jnp.int32) % (g // 2))  # odd groups get no row
    if case == "empty_runs":  # groups 0 | 2, 3 | 5 in a row without a row: two fetches, as many as the ring has slots
        return jnp.where(jnp.arange(m) < 9, 1, 4).astype(jnp.int32)
    if case == "first_and_last_full":  # several tiles each, a run of four empty groups between them
        return jnp.where(jnp.arange(m) % 2 == 0, 0, g - 1).astype(jnp.int32)
    if case == "one_full_among_single_tiles":  # more fetches than slots, one group many tiles
        return jnp.where(jnp.arange(m) < 5, jnp.arange(m) + 1, 0).astype(jnp.int32)
    return jax.random.randint(jax.random.PRNGKey(3), (m,), 0, g)  # "ragged"


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize(
    "case",
    ["even", "one_group", "empty_groups", "ragged", "empty_runs", "first_and_last_full", "one_full_among_single_tiles"],
)
def test_grouped_matmul_matches_einsum_forward_and_input_cotangent(case, impl):
    """37 rows in tiles of 8 (no multiple), every routing shape: the product and
    its input cotangent against a per-row einsum; the bank gets no cotangent."""
    g, k, n, m, tile = 6, 32, 48, 37, 8
    rhs = jax.random.normal(jax.random.PRNGKey(0), (g, k, n), jnp.float32).astype(jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(1), (m, k), jnp.float32)
    group_of = _groups(case, m, g)
    layout = group_layout(group_of, g, tile)
    assert layout.rows == tile * n_row_tiles(m, g, tile) and int(layout.group_sizes.sum()) == m
    rows = jnp.take(x, layout.assignment_of_slot, axis=0, mode="fill", fill_value=0)
    dense = rhs.astype(jnp.float32)[group_of]

    def ours(rows_, rhs_):
        return grouped_matmul(rows_, rhs_, layout.group_sizes, tile_m=tile, impl=impl)

    out = ours(rows, rhs)
    np.testing.assert_allclose(out[layout.slot_of_assignment], jnp.einsum("mk,mkn->mn", x, dense), rtol=1e-5, atol=1e-5)
    padding = np.setdiff1d(np.arange(layout.rows), np.asarray(layout.slot_of_assignment))
    if impl == "pallas":  # the kernel writes the used tiles and the call's last tile; ragged_dot every row
        padding = padding[_defined_rows(layout.rows, np.asarray(layout.group_sizes), tile)[padding]]
    assert not np.asarray(out)[padding].any()  # padding rows of a used tile and the last tile are zeros, not garbage
    probe = jax.random.normal(jax.random.PRNGKey(2), out.shape, jnp.float32)
    d_rows, d_rhs = jax.grad(lambda r, w: jnp.sum(ours(r, w) * probe), argnums=(0, 1))(rows, rhs)
    want = jnp.einsum("mn,mkn->mk", probe[layout.slot_of_assignment], dense)
    np.testing.assert_allclose(d_rows[layout.slot_of_assignment], want, rtol=1e-5, atol=1e-5)
    assert not np.asarray(d_rhs.astype(jnp.float32)).any()  # frozen: no weight gradient exists


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_grouped_matmul_reads_the_named_layer_of_a_stack_of_banks(impl):
    """``[L, G, K, N]`` with a traced ``layer``: the product and the input
    cotangent are that layer's; one bank without ``layer`` is a stack of one."""
    g, k, n, m, tile = 4, 16, 24, 20, 8
    stack = jax.random.normal(jax.random.PRNGKey(0), (3, g, k, n), jnp.float32).astype(jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(1), (m, k), jnp.float32)
    layout = group_layout(jax.random.randint(jax.random.PRNGKey(2), (m,), 0, g), g, tile)
    rows = jnp.take(x, layout.assignment_of_slot, axis=0, mode="fill", fill_value=0)

    @jax.jit
    def stacked(rows_, layer):
        fn = lambda r: grouped_matmul(r, stack, layout.group_sizes, layer=layer, tile_m=tile, impl=impl)  # noqa: E731
        out, pull = jax.vjp(fn, rows_)
        return out, pull(jnp.ones_like(out))[0]

    for layer in range(3):
        fn = lambda r: grouped_matmul(r, stack[layer], layout.group_sizes, tile_m=tile, impl=impl)  # noqa: E731
        want, pull = jax.vjp(fn, rows)
        got, d_rows = stacked(rows, jnp.int32(layer))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(d_rows, pull(jnp.ones_like(want))[0], rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError, match="stack of banks"):
        grouped_matmul(rows, stack, layout.group_sizes, tile_m=tile, impl=impl)


@pytest.mark.parametrize("axis_size", [3, 1])
def test_grouped_matmul_under_vmap_keeps_each_elements_groups(axis_size):
    """Rows and group sizes mapped, the bank not — how every cell runs the
    kernel (the node-chunk ``vmap``; axis size 1 takes ``pallas_call``'s own
    batching rule, a larger one its loop over the elements)."""
    g, k, n, m, tile = 4, 16, 24, 20, 8
    rhs = jax.random.normal(jax.random.PRNGKey(0), (g, k, n), jnp.float32).astype(jnp.bfloat16)
    xs = jax.random.normal(jax.random.PRNGKey(1), (axis_size, m, k), jnp.float32)
    groups = jax.random.randint(jax.random.PRNGKey(2), (axis_size, m), 0, g)

    def one(x, group_of, impl):
        layout = group_layout(group_of, g, tile)
        rows = jnp.take(x, layout.assignment_of_slot, axis=0, mode="fill", fill_value=0)
        return grouped_matmul(rows, rhs, layout.group_sizes, tile_m=tile, impl=impl)[layout.slot_of_assignment]

    want = jnp.stack([one(x, gr, "xla") for x, gr in zip(xs, groups)])
    for impl in ("xla", "pallas"):
        np.testing.assert_allclose(jax.vmap(lambda x, gr: one(x, gr, impl))(xs, groups), want, rtol=1e-5, atol=1e-5)


# ---- the counted layout, and the gathers through it --------------------------------


def _layout_by_sorting(group_of, n_groups: int, tile: int):
    """numpy, a stable sort: what ``group_layout`` computed until PR 36."""
    group_of = np.asarray(group_of)
    m = group_of.shape[0]
    sizes = np.bincount(group_of, minlength=n_groups)
    tiles = -(-sizes // tile)
    padded_start, sorted_start = tile * (np.cumsum(tiles) - tiles), np.cumsum(sizes) - sizes
    order = np.argsort(group_of, kind="stable")
    slot_sorted = padded_start[group_of[order]] + np.arange(m) - sorted_start[group_of[order]]
    slot_of_assignment = np.zeros(m, np.int64)
    slot_of_assignment[order] = slot_sorted
    assignment_of_slot = np.full(tile * n_row_tiles(m, n_groups, tile), m)
    assignment_of_slot[slot_sorted] = order
    return sizes, slot_of_assignment, assignment_of_slot


LAYOUT_CASES = {  # (assignments, groups, tile): [elements, M] group ids, another routing each element
    "random": lambda key: jax.random.randint(key, (3, 1000), 0, 8),
    "all_in_one_group": lambda key: jnp.tile(jnp.array([[5], [0], [7]], jnp.int32), (1, 300)),
    "one_empty_group": lambda key: (jax.random.randint(key, (3, 300), 0, 7) + jnp.array([[1], [3], [7]])) % 8,
    "fewer_than_a_tile": lambda key: jax.random.randint(key, (3, 11), 0, 8),
}


@pytest.mark.parametrize("mapped", ["alone", "under_vmap"])
@pytest.mark.parametrize("case", list(LAYOUT_CASES))
def test_group_layout_is_the_stable_sorts_on_every_field(case, mapped):
    """The counted layout against a numpy stable sort, element for element —
    alone, and under ``vmap`` with each element's own groups (the node chunk)."""
    groups = LAYOUT_CASES[case](jax.random.PRNGKey(4)).astype(jnp.int32)
    tile = 16
    if mapped == "alone":
        got = [group_layout(g, 8, tile) for g in groups]
        sizes, slot_of, a_of = (np.stack([np.asarray(lay[i]) for lay in got]) for i in range(3))
        rows = got[0].rows
    else:
        sizes, slot_of, a_of = (np.asarray(f) for f in jax.vmap(lambda g: group_layout(g, 8, tile)[:3])(groups))
        rows = a_of.shape[1]
    assert rows == tile * n_row_tiles(groups.shape[1], 8, tile) == a_of.shape[1]
    assert sizes.dtype == slot_of.dtype == a_of.dtype == np.int32
    for i, g in enumerate(groups):
        for got_field, want in zip((sizes[i], slot_of[i], a_of[i]), _layout_by_sorting(g, 8, tile)):
            np.testing.assert_array_equal(got_field, want)
    if case == "one_empty_group":
        assert (sizes == 0).sum(axis=1).tolist() == [1, 1, 1]


def _dispatch_operands(k: int):
    """37 tokens x ``k`` assignments over 6 groups in tiles of 8 (padding rows in
    every group), the index arrays as ``ExpertFFN`` makes them."""
    s, d, g, tile = 37, 24, 6, 8
    keys = jax.random.split(jax.random.PRNGKey(k), 5)
    chosen = jnp.argsort(jax.random.uniform(keys[0], (s, g)), axis=-1)[:, :k].astype(jnp.int32)  # k distinct groups a token
    layout = group_layout(chosen.reshape(-1), g, tile)
    token_of_row = jnp.where(layout.assignment_of_slot < s * k, layout.assignment_of_slot // k, s)
    row_of = np.asarray(layout.slot_of_assignment).reshape(s, k)
    assert layout.rows > s * k and (np.asarray(layout.assignment_of_slot) == s * k).any()
    place = np.zeros((s, k, layout.rows), np.float32)  # place[s, j, r] = 1: row r holds assignment (s, j)
    np.put_along_axis(place, row_of[..., None], 1.0, axis=-1)
    return layout, token_of_row, jnp.asarray(row_of.T), place, keys, (s, d)


@pytest.mark.parametrize("k", [1, 2, 4])
def test_to_expert_rows_and_its_cotangent_match_the_dense_formulas(k):
    """``rows = placeᵀ x`` (padding rows zero) and ``dx[s] = Σ_j g[row of (s, j)]``
    as float32 einsums over the one-hot placement."""
    layout, token_of_row, row_of, place, keys, (s, d) = _dispatch_operands(k)
    x = jax.random.normal(keys[1], (s, d), jnp.float32)
    g = jax.random.normal(keys[2], (layout.rows, d), jnp.float32)
    rows, pull = jax.vjp(lambda x_: _to_expert_rows(x_, token_of_row, row_of), x)
    np.testing.assert_allclose(rows, np.einsum("sjr,sd->rd", place, np.asarray(x)), rtol=1e-6, atol=1e-6)
    assert not np.asarray(rows)[np.asarray(layout.assignment_of_slot) == s * k].any()
    np.testing.assert_allclose(pull(g)[0], np.einsum("sjr,rd->sd", place, np.asarray(g)), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("k", [1, 2, 4])
def test_from_expert_rows_and_its_cotangents_match_the_dense_formulas(k):
    """``y[s] = Σ_j w[s, j] rows[row of (s, j)]``, ``d_rows[r] = w(r) g[token of r]``
    (zero for padding rows) and ``d_w[s, j] = ⟨rows[row of (s, j)], g[s]⟩`` — the
    last read in ROW order by the code, by assignment here."""
    layout, token_of_row, row_of, place, keys, (s, d) = _dispatch_operands(k)
    rows = jax.random.normal(keys[1], (layout.rows, d), jnp.float32)  # padding rows NOT zero: nothing may read them
    weights = jax.random.uniform(keys[3], (s, k), jnp.float32, 0.1, 1.0)
    g = jax.random.normal(keys[4], (s, d), jnp.float32)
    y, pull = jax.vjp(lambda r, w: _from_expert_rows(r, w, row_of, token_of_row, layout.assignment_of_slot), rows, weights)
    d_rows, d_weights = pull(g)
    rows_, weights_, g_ = np.asarray(rows), np.asarray(weights), np.asarray(g)
    np.testing.assert_allclose(y, np.einsum("sjr,sj,rd->sd", place, weights_, rows_), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(d_rows, np.einsum("sjr,sj,sd->rd", place, weights_, g_), rtol=1e-5, atol=1e-5)
    assert not np.asarray(d_rows)[np.asarray(layout.assignment_of_slot) == s * k].any()
    np.testing.assert_allclose(d_weights, np.einsum("sjr,rd,sd->sj", place, rows_, g_), rtol=1e-5, atol=1e-5)


def _equations(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs (``pjit``, ``custom_vjp`` rules, loops) too."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


@pytest.mark.parametrize("mapped", ["rows_and_index", "index_only", "rows_only"])
@pytest.mark.parametrize("axis_size", [1, 3])
def test_row_gather_under_vmap_is_one_flat_gather_with_each_elements_rows(axis_size, mapped):
    """``_rows_at`` batches itself (one gather over ``[B · N, D]``, element ``b``'s
    index ``b · N`` further down — the node-chunk ``vmap`` of every cell): equal
    to the loop over the elements, whichever operand is mapped, ``[k, S]``
    indices included; and the batched jaxpr gathers from two dimensions, not three."""
    a = jax.random.normal(jax.random.PRNGKey(0), (axis_size, 40, 16), jnp.float32)
    index = jax.random.randint(jax.random.PRNGKey(1), (axis_size, 2, 37), 0, 40)
    axes = {"rows_and_index": (0, 0), "index_only": (None, 0), "rows_only": (0, None)}[mapped]
    pick = lambda x, axis, b: x[0] if axis is None else x[b]  # noqa: E731
    operands = tuple(x[0] if axis is None else x for x, axis in zip((a, index), axes))
    got = jax.vmap(_rows_at, in_axes=axes)(*operands)
    want = jnp.stack([pick(a, axes[0], b)[pick(index, axes[1], b)] for b in range(axis_size)])
    np.testing.assert_array_equal(got, want)
    gathers = [eqn for eqn in _equations(jax.make_jaxpr(jax.vmap(_rows_at, in_axes=axes))(*operands).jaxpr) if eqn.primitive.name == "gather"]
    assert [len(eqn.invars[0].aval.shape) for eqn in gathers] == [2]


def test_the_expert_layer_sorts_nothing_and_gathers_each_row_array_once():
    """Structure: the lowered layout and one ``ExpertFFN``'s lowered gradient
    hold no ``stablehlo.sort`` (the layout is counted), the layout ONE scatter
    (``assignment_of_slot``; the parent's three beside its sort) and no gather.
    The gradient gathers ``D``-wide rows four times — every row of the layout
    for the dispatch and for the combine's ``g_rows``, every assignment for the
    combine and for the dispatch's cotangent; the parent's combine read ``out``
    by assignment a SECOND time for the weights' cotangent, now a gather of
    scalars. No gathered array holds ``k`` between ``S`` and ``D``. Counted in
    the jaxpr: the lowered text holds one function however many calls share it."""
    layout = jax.jit(lambda g: group_layout(g, 8, 8)[:3]).lower(jnp.zeros((74,), jnp.int32)).as_text()
    assert "stablehlo.sort" not in layout and "stablehlo.gather" not in layout and layout.count('"stablehlo.scatter"') == 1
    cfg = config(expert_impl="xla", shared_experts=0)
    layer, params, h = _expert_layer(cfg)
    (s, k), rows = (h.shape[1], cfg.experts_per_token), 8 * n_row_tiles(37 * 2, 8, 8)
    grad = jax.grad(lambda p, h_: jnp.sum(layer.apply({"params": p}, h_)), argnums=(0, 1))
    assert "stablehlo.sort" not in jax.jit(grad).lower(params, h).as_text()
    gathered = [tuple(eqn.outvars[0].aval.shape) for eqn in _equations(jax.make_jaxpr(grad)(params, h).jaxpr) if eqn.primitive.name == "gather"]
    wide_rows = sum(int(np.prod(shape[:-1])) for shape in gathered if len(shape) > 1 and shape[-1] == cfg.dim)
    parents_wide_rows = 2 * rows + 3 * s * k
    assert wide_rows == 2 * rows + 2 * s * k < parents_wide_rows, gathered
    assert not any(len(shape) == 3 and shape[1] == k and shape[2] == cfg.dim for shape in gathered), gathered  # no [S, k, D]


# the kernel's ring of matrix blocks, case by case: group sizes in tiles of 8 over
# `n_row_tiles` tiles (so some tiles are past the used count)
RING_TILE = 8
RING_SIZES = {
    "empty_runs_first_and_last": [0, 0, 9, 0, 0, 20, 0],  # the ring skips to the next group WITH rows
    "one_group_every_tile": [0, 0, 0, 37, 0],  # one fetch, nothing to fetch ahead
    "every_group_one_tile": [8, 1, 5, 8, 3, 7],  # a fetch every grid step
    "as_many_groups_as_slots": [12, 30],
    "more_groups_than_slots": [17, 3, 0, 9, 8, 1, 0, 26, 2],
    "no_assignments": [0, 0, 0, 0],  # used = 0: every tile zeros, nothing fetched
}
RING_DIGESTS = json.loads((ROOT / "tests" / "fixtures" / "gmm_parent_outputs.json").read_text())["sha256"]


def _ring_operands(sizes, face: bool, layers=None, lead=()):
    """Small whole numbers in bf16 (every product and partial sum is exact in
    float32, so an output's bits do not depend on the machine's dot), rows in
    EVERY tile — the kernel masks nothing, a tile's rows times its group's matrix."""
    g, k, n = len(sizes), 256, 256
    rows = RING_TILE * n_row_tiles(sum(sizes), g, RING_TILE)
    whole = lambda key, shape: jax.random.randint(jax.random.PRNGKey(key), shape, -2, 3).astype(jnp.bfloat16)  # noqa: E731
    return whole(1, (*lead, rows, n if face else k)), whole(2, (g, k, n) if layers is None else (layers, g, k, n))


def _product_by_tile(lhs, rhs, sizes, face: bool):
    """numpy: tile after tile, each against its group's matrix; zeros past the used count."""
    lhs, rhs = np.asarray(lhs, np.float32), np.asarray(rhs, np.float32)
    out = np.zeros((lhs.shape[0], rhs.shape[1] if face else rhs.shape[2]), np.float32)
    tile_group = [g for g, size in enumerate(sizes) for _ in range(-(-size // RING_TILE))]
    for t, g in enumerate(tile_group):
        at = slice(t * RING_TILE, (t + 1) * RING_TILE)
        out[at] = lhs[at] @ (rhs[g].T if face else rhs[g])
    return out


def _defined_rows(rows: int, sizes, tile: int) -> np.ndarray:
    """``[rows]`` bool: the rows ``grouped_matmul``'s contract defines — the used
    tiles (each group's rows and the zero rows that pad its last tile) and the
    call's LAST tile (zeros where it is not a used one). From PR 38 the kernel
    does not write the tiles between: the interpreter leaves NaN there."""
    used = int(np.sum(-(-np.asarray(sizes) // tile)))
    defined = np.arange(rows) < used * tile
    defined[rows - tile:] = True
    return defined


def _defined(out, sizes, tile: int = RING_TILE):
    """``out`` (``[..., rows, N]``, one ``sizes`` for all or one an element) with
    the rows outside the contract put to zero — what the parent's kernel wrote there."""
    out = np.array(out, np.float32)
    many = np.ndim(sizes) == 2
    for b, each in enumerate(sizes if many else [sizes]):
        keep = _defined_rows(out.shape[-2], each, tile)
        (out[b] if many else out)[..., ~keep, :] = 0.0
    return out


def _digest(out) -> str:
    out = np.asarray(out)
    return hashlib.sha256(str((out.shape, out.dtype.name)).encode() + np.asarray(out, np.float32).tobytes()).hexdigest()


@pytest.mark.parametrize("blocks", ["whole_matrix", "column_split"])
@pytest.mark.parametrize("face", ["forward", "cotangent"])
@pytest.mark.parametrize("case", list(RING_SIZES))
def test_grouped_matmul_kernel_fetches_each_groups_matrix_for_its_tiles(case, face, blocks, monkeypatch):
    """The interpreted kernel against a tile-by-tile numpy product, and its
    output bit-equal to what the parent's step-ahead kernel gave on these
    operands (``tests/fixtures/gmm_parent_outputs.json``) over the rows the
    contract still defines: the used tiles — padding rows too — and the call's
    last tile. The tiles between, which that kernel wrote as zeros and this one
    does not write, are put to zero before the digest (``_defined``).
    ``column_split``: a matrix block limit of 64 KB, so two column blocks a matrix
    and the ring keyed by (column block, group)."""
    sizes, transposed = RING_SIZES[case], face == "cotangent"
    if blocks == "column_split":
        monkeypatch.setattr(gmm_ops, "_RHS_BLOCK_BYTES", 256 * 128 * 2)
        assert gmm_ops._block_n(256, 256, 2) == 128
    lhs, rhs = _ring_operands(sizes, transposed)
    out = grouped_matmul(lhs, rhs, jnp.asarray(sizes, jnp.int32), tile_m=RING_TILE, transpose_rhs=transposed, impl="pallas")
    assert out.dtype == lhs.dtype
    defined = _defined_rows(lhs.shape[0], sizes, RING_TILE)
    np.testing.assert_array_equal(np.asarray(out, np.float32)[defined], _product_by_tile(lhs, rhs, sizes, transposed)[defined])
    assert _digest(jnp.asarray(_defined(out, sizes), out.dtype)) == RING_DIGESTS[f"{case}.{face}.{blocks}"]


@pytest.mark.parametrize("face", ["forward", "cotangent"])
def test_grouped_matmul_kernel_in_a_scan_reads_the_traced_layers_bank(face):
    """A stacked bank with a traced ``layer`` inside ``lax.scan``: each step's
    ring holds that layer's matrices (bit-equal to the parent's kernel)."""
    sizes, transposed = RING_SIZES["more_groups_than_slots"], face == "cotangent"
    lhs, stack = _ring_operands(sizes, transposed, layers=3)
    group_sizes = jnp.asarray(sizes, jnp.int32)

    def step(_, layer):
        out = grouped_matmul(lhs, stack, group_sizes, layer=layer, tile_m=RING_TILE, transpose_rhs=transposed, impl="pallas")
        return None, out

    _, outs = jax.jit(lambda: jax.lax.scan(step, None, jnp.arange(3, dtype=jnp.int32)))()
    outs = jnp.asarray(_defined(outs, sizes), outs.dtype)  # the rows the contract defines
    for layer in range(3):
        np.testing.assert_array_equal(np.asarray(outs[layer], np.float32), _product_by_tile(lhs, stack[layer], sizes, transposed))
    assert _digest(outs) == RING_DIGESTS[f"scan.{face}"]


@pytest.mark.parametrize("axis_size", [1, 3])
@pytest.mark.parametrize("face", ["forward", "cotangent"])
def test_grouped_matmul_kernel_under_vmap_with_an_unmapped_bank(face, axis_size):
    """Every cell's case: rows and group sizes mapped over the node chunk, the
    bank shared. Each element has its own routing (bit-equal to the parent's kernel)."""
    cases, transposed = ["more_groups_than_slots", "every_group_one_tile", "empty_runs_first_and_last"][:axis_size], face == "cotangent"
    sizes = [(RING_SIZES[c] + [0] * 9)[:9] for c in cases]  # nine groups each; the first element's rows hold the others'
    lhs, rhs = _ring_operands(sizes[0], transposed, lead=(axis_size,))
    outs = jax.vmap(
        lambda rows, group_sizes: grouped_matmul(rows, rhs, group_sizes, tile_m=RING_TILE, transpose_rhs=transposed, impl="pallas")
    )(lhs, jnp.asarray(sizes, jnp.int32))
    outs = jnp.asarray(_defined(outs, np.asarray(sizes)), outs.dtype)  # the rows the contract defines, element by element
    for out, rows, group_sizes in zip(outs, lhs, sizes):
        np.testing.assert_array_equal(np.asarray(out, np.float32), _product_by_tile(rows, rhs, group_sizes, transposed))
    assert _digest(outs) == RING_DIGESTS[f"vmap{axis_size}.{face}"]


def test_tiles_and_fetches_counts_what_a_call_multiplies_and_fetches():
    """The counter behind PERF.md's "tiles over fetches": a numpy count, group
    by group, for every routing the ring's cases use and a drawn one."""
    drawn = np.random.default_rng(0).integers(0, 700, 64).tolist()
    for sizes in [*RING_SIZES.values(), drawn]:
        for tile in (8, 128):
            tiles = [-(-size // tile) for size in sizes]
            got = tiles_and_fetches(jnp.asarray(sizes, jnp.int32), tile)
            assert [int(v) for v in got] == [sum(tiles), sum(t > 0 for t in tiles)]


# what the kernel writes, case by case: (group sizes, row tiles of the call) in tiles of 8
WRITTEN_CASES = {
    "a_spare_tile_and_unused_tiles_before_it": ([9, 0, 20, 3], n_row_tiles(32, 4, 8) + 1),
    "no_spare_tile_and_unused_tiles": ([9, 0, 20, 3], n_row_tiles(32, 4, 8)),
    "every_tile_used": ([8, 8, 8, 8, 5], 5),  # used == n_tiles: the index map is the identity, nothing is zeroed
    "every_tile_used_but_the_spare": ([8, 8, 8, 8, 5], 6),  # used == n_tiles - 1: the one unused step IS the last tile
    "one_tile_used": ([0, 0, 3, 0], n_row_tiles(3, 4, 8) + 1),
    "every_group_empty": ([0, 0, 0, 0], 4),  # used == 0: every step names the last tile
}


@pytest.mark.parametrize("face", ["forward", "cotangent"])
@pytest.mark.parametrize("case", list(WRITTEN_CASES))
def test_grouped_matmul_kernel_writes_the_used_tiles_and_the_calls_last_tile(case, face):
    """The contract from PR 38: the used tiles (padding rows zero) and the call's
    LAST tile (zero) are ``lax.ragged_dot``'s rows; the tiles between are not
    written — the interpreter shows them as NaN, which is how this test knows
    that a step past the used count names the last tile and no other."""
    sizes, n_tiles = WRITTEN_CASES[case]
    transposed, tile, g, rows = face == "cotangent", RING_TILE, len(sizes), RING_TILE * n_tiles
    used = sum(-(-size // tile) for size in sizes)
    assert used == {"every_tile_used": n_tiles, "every_tile_used_but_the_spare": n_tiles - 1, "one_tile_used": 1, "every_group_empty": 0}.get(case, 6)
    whole = lambda key, shape: jax.random.randint(jax.random.PRNGKey(key), shape, -2, 3).astype(jnp.bfloat16)  # noqa: E731
    lhs, rhs = whole(1, (rows, 256)), whole(2, (g, 256, 256))
    group_sizes = jnp.asarray(sizes, jnp.int32)
    got = grouped_matmul(lhs, rhs, group_sizes, tile_m=tile, transpose_rhs=transposed, impl="pallas")
    want = grouped_matmul(lhs, rhs, group_sizes, tile_m=tile, transpose_rhs=transposed, impl="xla")
    defined = _defined_rows(rows, sizes, tile)
    assert defined.sum() == min(used + 1, n_tiles) * tile
    np.testing.assert_array_equal(np.asarray(got, np.float32)[defined], np.asarray(want, np.float32)[defined])
    assert not np.asarray(want, np.float32)[used * tile:].any()  # ragged_dot: every row past the used tiles is zero
    if used < rows // tile:
        assert not np.asarray(got, np.float32)[rows - tile:].any()  # the last tile: zeros
    assert np.isnan(np.asarray(got, np.float32)[~defined]).all()  # the tiles between: not written


def _poisoned(real, tile):
    """``grouped_matmul`` with NaN written into every row the contract does not
    define — of ``lhs`` going in, of the product coming out, and (the same
    function's derivative) of the cotangent on its way back, before and after
    the product's own rule."""

    @jax.custom_vjp
    def poison(x, used_rows):
        row = jnp.arange(x.shape[0])[:, None]
        return jnp.where((row >= used_rows) & (row < x.shape[0] - tile), jnp.nan, x)

    poison.defvjp(lambda x, used_rows: (poison(x, used_rows), used_rows), lambda used_rows, g: (poison(g, used_rows), None))

    def product(lhs, rhs, group_sizes, **kw):
        used_rows = tile * tiles_and_fetches(group_sizes, tile)[0]
        return poison(real(poison(lhs, used_rows), rhs, group_sizes, **kw), used_rows)

    return product


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("routing", ["seeded", "nothing_held_is_chosen"])
def test_no_reader_of_an_unwritten_row_under_a_held_share(routing, impl, monkeypatch):
    """Every row past the used tiles but the last tile's poisoned with NaN — the
    rows of the first product, ``h`` between the products, both cotangents —
    through ``ExpertFFN`` holding experts 2..5 of 8: the output, the router's
    gradient (through ``d_weights``) and the input's cotangent are finite and
    equal the clean run's (``ragged_dot``, zeros everywhere)."""
    cfg = config(experts_held=4, first_expert=2, expert_impl=impl, shared_experts=0)
    layer, params, h = _expert_layer(cfg)
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(9), (8,))
    if routing == "nothing_held_is_chosen":  # used == 0: every assignment reads the spare tile
        bias = jnp.array([9.0, 9.0, 0, 0, 0, 0, 0, 0])
    params = dict(params, router_bias=bias)
    probe = jax.random.normal(jax.random.PRNGKey(5), h.shape)

    def value_and_grads(layer_):
        loss = lambda p, h_: jnp.sum(layer_.apply({"params": p}, h_) * probe)  # noqa: E731
        return jax.value_and_grad(loss, argnums=(0, 1))(params, h)

    want, (want_params, want_h) = value_and_grads(ExpertFFN(dataclasses.replace(cfg, expert_impl="xla")))
    monkeypatch.setattr(gmm_ops, "grouped_matmul", _poisoned(gmm_ops.grouped_matmul, cfg.expert_tile_m))
    got, (got_params, got_h) = value_and_grads(layer)
    assert np.isfinite(float(got)) and float(got) == pytest.approx(float(want), rel=1e-5, abs=1e-6)
    assert np.isfinite(np.asarray(got_params["router"])).all()
    np.testing.assert_allclose(got_params["router"], want_params["router"], rtol=1e-4, atol=1e-6)
    assert np.isfinite(np.asarray(got_h)).all()
    np.testing.assert_allclose(got_h, want_h, rtol=1e-4, atol=1e-6)
    if routing == "seeded":
        assert np.asarray(want_params["router"]).any() and np.asarray(want_h).any()


@pytest.mark.parametrize("axis_size", [None, 1, 3])
@pytest.mark.parametrize("reach", ["the_head_holds_them", "rows_in_use_pass_the_head"])
def test_slab_sum_out_of_a_copied_head_is_the_plain_sum_to_the_bit(reach, axis_size, monkeypatch):
    """A source over ``_GATHER_HEAD_BYTES`` whose used rows and last tile fit
    inside it: the slabs come out of a copy of those two stretches — the same
    rows, the same order, so bit-equal to the straight gathers — and straight out
    of the source when they do not fit. Under ``vmap`` the choice is still ONE
    ``cond`` (a batched predicate would make it a select and run both sides)."""
    from p2pfl_tpu.models import transformer as tf

    s, k, d, tile, n = 37, 4, 16, 8, 160
    monkeypatch.setattr(tf, "_GATHER_HEAD_BYTES", 64 * d * 4)  # a head of 64 rows
    batch = axis_size or 1
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    in_use = jnp.full((batch,), 56 if reach == "the_head_holds_them" else 120, jnp.int32)
    low = jax.random.randint(keys[0], (batch, k, s), 0, in_use[0])
    last = n - tile + jax.random.randint(keys[1], (batch, k, s), 0, tile)
    index = jnp.where(jax.random.bernoulli(keys[2], 0.6, (batch, k, s)), last, low).astype(jnp.int32)  # most read the last tile
    rows = jax.random.normal(keys[1], (batch, n, d), jnp.float32)
    weights = jax.random.uniform(keys[2], (batch, k, s), jnp.float32, 0.1, 1.0)
    ours = lambda r, i, w, u: tf._slab_sum(r, i, w, u, tile)  # noqa: E731
    plain = lambda r, i, w: tf._slab_sum(r, i, w)  # noqa: E731
    if axis_size is None:  # both compiled: op by op the CPU rounds a product and a sum apart, a fusion together
        got, want = jax.jit(ours)(rows[0], index[0], weights[0], in_use[0]), jax.jit(plain)(rows[0], index[0], weights[0])
        jaxpr = jax.make_jaxpr(ours)(rows[0], index[0], weights[0], in_use[0])
    else:
        got, want = jax.jit(jax.vmap(ours))(rows, index, weights, in_use), jax.jit(jax.vmap(plain))(rows, index, weights)
        jaxpr = jax.make_jaxpr(jax.vmap(ours))(rows, index, weights, in_use)
    np.testing.assert_array_equal(got, want)
    names = [eqn.primitive.name for eqn in _equations(jaxpr.jaxpr)]
    assert names.count("cond") == 1
    # a source under the limit, or no word on where the indices point: no cond at all
    assert "cond" not in [eqn.primitive.name for eqn in _equations(jax.make_jaxpr(plain)(rows[0], index[0], weights[0]).jaxpr)]
    monkeypatch.setattr(tf, "_GATHER_HEAD_BYTES", n * d * 4)
    again = lambda r, i, w, u: tf._slab_sum(r, i, w, u, tile)  # noqa: E731 (a new function: a traced one is cached)
    assert "cond" not in [eqn.primitive.name for eqn in _equations(jax.make_jaxpr(again)(rows[0], index[0], weights[0], in_use[0]).jaxpr)]


# ---- the router ----------------------------------------------------------------


def test_router_chooses_with_the_bias_and_weighs_without_it():
    """Hand-written numpy: sigmoid scores, top-k of score + bias, weights from
    the scores alone, normalised over the chosen, times the scale — on a case
    where the bias changes the choice."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(16, 12)).astype(np.float32)
    router = rng.normal(scale=0.3, size=(12, 6)).astype(np.float32)
    bias = np.array([0.0, 0.4, 0.0, -0.4, 0.0, 0.0], np.float32)
    scores = router_scores(jnp.asarray(x), jnp.asarray(router))
    chosen = choose_experts(scores, jnp.asarray(bias), 2)
    weights = routing_weights(scores, chosen, 1.8)
    s = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ router.astype(np.float64))))
    want_chosen = np.argsort(-(s + bias), axis=-1)[:, :2]
    unbiased = np.argsort(-s, axis=-1)[:, :2]
    assert (np.sort(want_chosen, -1) != np.sort(unbiased, -1)).any()  # the bias matters here
    np.testing.assert_array_equal(np.sort(np.asarray(chosen), -1), np.sort(want_chosen, -1))
    picked = np.take_along_axis(s, np.asarray(chosen), axis=-1)
    np.testing.assert_allclose(weights, 1.8 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.8, rtol=1e-5)


# ---- the expert layer ----------------------------------------------------------


def _expert_layer(cfg, seed=0):
    layer = ExpertFFN(cfg)
    h = jax.random.normal(jax.random.PRNGKey(seed), (1, 37, cfg.dim), jnp.float32)  # 37 x 2 rows: no multiple of the tile
    params = layer.init(jax.random.PRNGKey(seed + 1), h)["params"]
    return layer, params, h


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("routing", ["seeded", "all_on_one_expert", "experts_without_rows"])
def test_expert_layer_drops_no_assignment(routing, impl):
    """Against "every expert on every row, masked" at any imbalance: the routed
    output, the sown load, and that the S x k assignments are all in the groups."""
    cfg = config(expert_impl=impl)
    layer, params, h = _expert_layer(cfg)
    bias = {
        "seeded": 0.05 * jax.random.normal(jax.random.PRNGKey(9), (8,)),
        "all_on_one_expert": jnp.array([9.0, 5.0, 0, 0, 0, 0, 0, 0]),  # everyone chooses experts 0 and 1
        "experts_without_rows": jnp.array([0, -9.0, 0, -9.0, 0, -9.0, 0, -9.0]),
    }[routing]
    params = dict(params, router_bias=bias)
    got, mut = layer.apply({"params": params}, h, mutable=["moe_stats", "moe_routing"])
    ref = dict(REF)
    with jax.default_matmul_precision("highest"):
        want, want_chosen = glm_moe_lm.experts(h[0], params, ref, 2.0)
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-5)
    chosen = np.asarray(mut["moe_routing"]["chosen"][0])
    np.testing.assert_array_equal(np.sort(chosen, -1), np.sort(np.asarray(want_chosen), -1))
    sizes = np.bincount(chosen.ravel(), minlength=8)
    assert sizes.sum() == 37 * 2  # dropless: every assignment has a row
    assert float(mut["moe_stats"]["load_max_over_mean"][0]) == pytest.approx(sizes.max() / (37 * 2 / 8))
    if routing == "all_on_one_expert":
        assert sizes[0] == sizes[1] == 37 and not sizes[2:].any()
    if routing == "experts_without_rows":
        assert not sizes[1::2].any()


def test_expert_layer_gradients_reach_the_input_through_experts_and_router():
    cfg = config()
    layer, params, h = _expert_layer(cfg)
    params = dict(params, router_bias=0.05 * jax.random.normal(jax.random.PRNGKey(9), (8,)))
    probe = jax.random.normal(jax.random.PRNGKey(5), h.shape)
    got = jax.grad(lambda h_: jnp.sum(layer.apply({"params": params}, h_) * probe))(h)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda h_: jnp.sum(glm_moe_lm.experts(h_, params, REF, 2.0)[0] * probe[0]))(h[0])
    assert ck.rel_l2(got[0], want) < 1e-4


def test_the_re_forward_uses_the_forwards_assignments(glm):
    """The choice is kept across remat (``moe_chosen``), so the backward's
    re-forward CANNOT choose anew: no second ``top_k`` in the differentiated
    program — one in the scanned run's body, the forward's. The sort is
    deterministic: the same choice lays out the same rows. And the gradient
    equals the un-rematted one."""
    model, lora, base = glm
    x, y = batch()

    def top_ks(module):
        jaxpr = jax.make_jaxpr(jax.grad(lambda lo: _lm_loss(lo, base, module, x, y)[0]))(lora)
        return str(jaxpr).count("top_k[")

    cfg = model.module.cfg
    plain = CausalLM(dataclasses.replace(cfg, remat=False))
    assert top_ks(plain) == top_ks(model.module) == 1  # ONE body for the run's three layers, and in it the forward's only
    routing = jax.tree.leaves(_lm_forward(lora, base, model.module, x, y)[3])
    chosen = routing[0].reshape(-1)
    one, two = group_layout(chosen, 8, 8), group_layout(chosen, 8, 8)
    np.testing.assert_array_equal(one.slot_of_assignment, two.slot_of_assignment)
    g_plain = jax.grad(lambda lo: _lm_loss(lo, base, plain, x, y)[0])(lora)
    g_remat = jax.grad(lambda lo: _lm_loss(lo, base, model.module, x, y)[0])(lora)
    assert ck.rel_l2(g_remat, g_plain) < 1e-6


@pytest.mark.parametrize("fault", ["none", "bf16_router", "dropped_assignment", "weigh_with_bias", "choose_without_bias"])
def test_the_benchmarks_layer_check_sees_each_planted_fault(fault):
    """``engines/spmd_lora_moe.check_expert_layer`` under its own limits: the
    sound layer passes every comparison, and a bfloat16 router, ONE dropped
    assignment of 8,192, weights taken with the bias, and a choice made without
    it each fail at least one. The input has a component every token shares, as
    a residual stream has, and the bias cancels each expert's mean score."""
    from types import SimpleNamespace

    from benchmark.engines import spmd_lora_moe as engine
    from benchmark.planted_faults import planted

    cfg = config(expert_impl="xla")
    tokens = 4096
    g = jax.random.normal(jax.random.PRNGKey(0), (1, tokens, cfg.dim)) + 3.0 * jax.random.normal(jax.random.PRNGKey(1), (cfg.dim,))
    h = g / jnp.sqrt(jnp.mean(g * g, axis=-1, keepdims=True))
    params = ExpertFFN(cfg).init(jax.random.PRNGKey(2), h[:, :8])["params"]
    mean = jnp.mean(router_scores(h[0], params["router"]), axis=0)
    assert float(jnp.std(mean)) > 0.02  # the shared component skews the seeded router, as in the cell
    mlp = {
        "router": params["router"], "router_bias": jnp.mean(mean) - mean, "bank_layer": 0,
        "experts_w13": params["experts_w13"][None], "experts_w2": params["experts_w2"][None],
    }
    job = SimpleNamespace(cfg=REF, checks=ck.Checks())
    with planted(fault):
        engine.check_expert_layer(job, cfg, mlp, h)
    failed = [row["check"] for row in job.checks.rows if not row["ok"]]
    assert len(job.checks.rows) == 3
    if fault == "none":
        assert not failed, job.checks.rows
    else:
        assert failed, job.checks.rows
    if fault in ("bf16_router", "choose_without_bias"):
        assert "layer.routing_agreement" in failed
    if fault in ("dropped_assignment", "weigh_with_bias"):
        assert "layer.worst_agreeing_token_rel" in failed


# ---- latent attention ----------------------------------------------------------


def test_latent_attention_forward_and_adapter_gradients_match_the_reference():
    cfg = config()
    layer = MLAttention(cfg)
    h = jax.random.normal(jax.random.PRNGKey(0), (1, SEQ, cfg.dim), jnp.float32)
    params = layer.init(jax.random.PRNGKey(1), h)["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 16))
    params = jax.tree_util.tree_map_with_path(
        lambda p, a: 0.05 * jax.random.normal(next(keys), a.shape) if "lora_b" in jax.tree_util.keystr(p) else a, params
    )
    assert params["q_a"]["kernel"].shape == (64, 24) and params["kv_a"]["kernel"].shape == (64, 16 + 4)  # unequal ranks, one rotary head
    lora, base = split_lora(params)
    probe = jax.random.normal(jax.random.PRNGKey(3), h.shape)
    ours = lambda lo: jnp.sum(layer.apply({"params": merge_params(base, lo)}, h) * probe)  # noqa: E731
    theirs = lambda lo: jnp.sum(glm_moe_lm.mla(h[0], merge_params(base, lo), REF, 2.0) * probe[0])  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.value_and_grad(theirs)(lora)
    got, grads = jax.value_and_grad(ours)(lora)
    assert float(got) == pytest.approx(float(want), rel=1e-4)
    assert sorted(lora) == ["kv_a", "kv_b", "o", "q_a", "q_b"]  # LoRA on all five projections
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want_grads)):
        assert float(jnp.max(jnp.abs(w))) > 0 and ck.rel_l2(g, w) < 1e-4, jax.tree_util.keystr(path)


# ---- the whole model -----------------------------------------------------------


def test_layer_runs_of_one_dense_then_expert_layers():
    assert layer_runs(PATTERN) == [("mla_dense", 1), ("mla_experts", 3)]
    assert glm_moe_lm.layer_kinds(REF) == list(PATTERN) and glm_moe_lm.runs(REF) == layer_runs(PATTERN)


def test_loss_and_every_adapter_gradient_match_the_reference(glm):
    model, lora, base = glm
    x, y = batch()
    (loss, _), grads = jax.value_and_grad(_lm_loss, has_aux=True)(lora, base, model.module, x, y)
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(glm_moe_lm.loss)(lora, base, x, y, REF, lora_scale=2.0)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(ref))) > 0, jax.tree_util.keystr(path)  # every adapter is reached
        assert ck.rel_l2(got, ref) < 2e-4, jax.tree_util.keystr(path)


def test_reference_held_to_given_assignments_reports_its_own_and_uses_the_given(glm):
    """The comparison holds the reference to the program's assignments: given
    its OWN choice nothing changes; given another, the loss does, and what it
    reports stays its own choice."""
    _, lora, base = glm
    x, y = batch()
    free, own = glm_moe_lm.loss_and_routing(lora, base, x, y, REF, lora_scale=2.0)
    assert own.shape == (2, 3, SEQ, 2)
    same, _ = glm_moe_lm.loss_and_routing(lora, base, x, y, REF, lora_scale=2.0, forced=own)
    assert float(same) == pytest.approx(float(free), rel=1e-6)
    other, reported = glm_moe_lm.loss_and_routing(lora, base, x, y, REF, lora_scale=2.0, forced=(own + 1) % 8)
    assert abs(float(other) - float(free)) > 1e-6
    np.testing.assert_array_equal(reported[:, 0], own[:, 0])  # the first expert layer sees the same input either way


def test_scanned_layers_equal_the_unrolled_layers_on_restacked_parameters(glm):
    """Under ``scan_layers`` the dense layer and the run of expert layers are two
    runs of one period, the expert run ONE scan body over stacked parameters —
    its banks stacked too, beside ``layers``, outside every scan (see ExpertFFN)."""
    model, lora, base = glm
    params = merge_params(base, lora)
    x, _ = batch()
    jaxpr = str(jax.make_jaxpr(lambda p: model.module.apply({"params": p}, x))(params))
    assert jaxpr.count("top_k[") == 1  # one expert body, whatever the depth
    assert params["experts_w13_run1"].shape == (3, 8, 64, 64) and params["experts_w2_run1"].shape == (3, 8, 32, 64)
    unrolled = CausalLM(dataclasses.replace(model.module.cfg, scan_layers=False))
    layers = params["layers"]
    restacked = {"embed": params["embed"], "final_norm": params["final_norm"]}
    restacked["layer_0"] = jax.tree.map(lambda a: a[0], layers["run0_mla_dense"])
    for i in range(3):
        layer = jax.tree.map(lambda a: a[0, i], layers["run1_mla_experts"]["block"])
        bank = {w: params[f"{w}_run1"][i] for w in ("experts_w13", "experts_w2")}
        restacked[f"layer_{i + 1}"] = dict(layer, mlp=dict(layer["mlp"], **bank))
    np.testing.assert_allclose(
        model.module.apply({"params": params}, x), unrolled.apply({"params": restacked}, x), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize(
    "pattern,n_layers",
    [(("mla_experts",), 2), (("mla_dense", "mla_experts"), 4), (("mla_dense", "mla_experts", "mla_experts"), 6)],
    ids=["experts_alone", "two_periods_runs_of_one", "two_periods_runs_of_two"],
)
def test_every_scanned_expert_layer_reads_its_own_bank(pattern, n_layers):
    """The banks lie outside the scans, stacked over ALL of a run's layers
    (``periods x count``); layer ``j`` of period ``p`` must read bank
    ``p * count + j`` — through the period scan, the run scan, a run of one, and
    a stack of expert layers alone. Against the unrolled layers."""
    cfg = config(layer_pattern=pattern, n_layers=n_layers)
    model, lora, base = seeded(cfg)
    params = merge_params(base, lora)
    x, _ = batch()
    periods = n_layers // len(pattern)
    restacked = {"embed": params["embed"], "final_norm": params["final_norm"]}
    at = 0
    for period in range(periods):
        for i, (kind, count) in enumerate(layer_runs(pattern)):
            for j in range(count):
                if len(pattern) == 1:
                    layer = jax.tree.map(lambda a: a[period], params["layers"]["block"])
                else:
                    run = jax.tree.map(lambda a: a[period], params["layers"][f"run{i}_{kind}"])
                    layer = jax.tree.map(lambda a: a[j], run["block"]) if count > 1 else run
                if kind == "mla_experts":
                    assert params[f"experts_w13_run{i}"].shape[0] == periods * count
                    bank = {w: params[f"{w}_run{i}"][period * count + j] for w in ("experts_w13", "experts_w2")}
                    layer = dict(layer, mlp=dict(layer["mlp"], **bank))
                restacked[f"layer_{at}"] = layer
                at += 1
    unrolled = CausalLM(dataclasses.replace(cfg, scan_layers=False))
    np.testing.assert_allclose(
        model.module.apply({"params": params}, x), unrolled.apply({"params": restacked}, x), rtol=1e-5, atol=1e-5
    )


def test_the_statistic_leaves_the_layer_scan_and_the_loss(glm):
    model, lora, base = glm
    x, y = batch()
    loss, _, stats, routing = _lm_forward(lora, base, model.module, x, y)
    (chosen,) = jax.tree.leaves(routing)
    assert chosen.shape == (1, 3, 2 * SEQ, 2)  # the experts each row chose, stacked along the period and the run
    _, mut = model.module.apply({"params": merge_params(base, lora)}, x, mutable=["moe_stats"])
    sown = sown_by_layer(model.module.cfg, mut["moe_stats"], "rows_used_share")
    per_layer = {path[-2].key: leaf for path, leaf in jax.tree_util.tree_leaves_with_path(mut)}  # .../<name>/0: sown values are tuples
    assert {name: leaf.shape for name, leaf in per_layer.items()} == {"load_max_over_mean": (1, 3), "rows_used_share": (1, 3)}  # one period, three expert layers
    assert sown.shape == (3,) and set(stats) == {"moe_load_max_over_mean", "moe_rows_used_share"}
    np.testing.assert_array_equal(sown, per_layer["rows_used_share"].reshape(-1))  # in layer order
    assert float(stats["moe_load_max_over_mean"]) == pytest.approx(float(per_layer["load_max_over_mean"].mean()))
    # the tiles a grouped-matmul call writes over the tiles it has: every assignment has a row, a group at most one part-filled tile
    rows = 8 * n_row_tiles(2 * SEQ * 2, 8, 8)
    assert 2 * SEQ * 2 / rows <= float(stats["moe_rows_used_share"]) <= 1.0
    assert float(stats["moe_rows_used_share"]) == pytest.approx(float(per_layer["rows_used_share"].mean()))
    assert float(loss) == pytest.approx(float(_lm_loss(lora, base, model.module, x, y)[0]))
    dense = tiny_transformer(seq_len=SEQ, cfg=TransformerConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, ffn_hidden=128))
    d_lora, d_base = split_lora(dense.params)
    assert _lm_forward(d_lora, d_base, dense.module, x, y)[2:] == ({}, {})  # a model that sows none


def test_bfloat16_compute_meets_the_benchmarks_tolerances():
    """The cell's comparison at toy size: bfloat16 matmuls and bank, float32
    router, against the float32 reference, under ``checks.py``'s constants."""
    model, lora, base = seeded(config(dtype=jnp.bfloat16))
    x, y = batch()
    (loss, _), grads = jax.value_and_grad(_lm_loss, has_aux=True)(lora, base, model.module, x, y)
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(glm_moe_lm.loss)(lora, base, x, y, REF, lora_scale=2.0)
    assert abs(float(loss) - float(want_loss)) <= ck.LOSS_REL * float(want_loss)
    # toy widths: 64 rows choose among 8 experts, so ONE near-tie that bfloat16
    # activations flip moves the gradient by what 250 flips move at the cell's size
    assert ck.cosine(grads, want) >= 0.95 and ck.rel_l2(grads, want) <= 0.35


def test_norm_eps_comes_from_the_configuration():
    x, _ = batch()
    small, large = config(norm_eps=1e-5), config(norm_eps=1e-1)
    model = tiny_transformer(seq_len=SEQ, cfg=small)
    a = CausalLM(small).apply({"params": model.params}, x)
    b = CausalLM(large).apply({"params": model.params}, x)
    assert float(jnp.max(jnp.abs(a - b))) > 1e-3
    assert TransformerConfig().norm_eps == 1e-6  # every other model's programs keep their constant


# ---- LoRA and the federation ---------------------------------------------------


def test_split_lora_leaves_the_bank_and_the_router_in_the_base_with_their_dtypes(glm):
    model, _, _ = glm
    lora, base = split_lora(model.params)
    names = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(lora)]
    assert names and all("lora_" in n for n in names)
    assert not any(word in n for n in names for word in ("experts_w", "router"))  # the FedAvg payload holds none of them
    mlp = base["layers"]["run1_mla_experts"]["block"]["mlp"]
    assert base["experts_w13_run1"].dtype == base["experts_w2_run1"].dtype == jnp.bfloat16
    assert mlp["router"].dtype == mlp["router_bias"].dtype == jnp.float32
    merged = merge_params(base, lora)
    assert jax.tree.structure(merged) == jax.tree.structure(model.params)
    assert all(a.dtype == b.dtype for a, b in zip(jax.tree.leaves(merged), jax.tree.leaves(model.params)))
    data = FederatedDataset.synthetic_lm(vocab_size=256, seq_len=SEQ, n_train=16, n_test=4)
    fed = SpmdLoraFederation.from_dataset(model, data, n_nodes=2, batch_size=2, vote=False, node_chunk=1)
    staged = fed.base
    assert staged["experts_w13_run1"].dtype == staged["experts_w2_run1"].dtype == jnp.bfloat16  # through _stage_state
    assert staged["experts_w13_run1"].shape == (3, 8, 64, 64)  # stored once, the run's layers stacked: no node axis
    assert all(leaf.dtype == jnp.float32 and leaf.shape[0] == 2 for leaf in jax.tree.leaves(fed.params))


def test_one_federated_round_matches_reference_trained_nodes(glm):
    model, _, _ = glm
    lora, base = split_lora(model.params)
    data = FederatedDataset.synthetic_lm(vocab_size=256, seq_len=SEQ, n_train=16, n_test=4)
    fed = SpmdLoraFederation.from_dataset(model, data, n_nodes=2, batch_size=2, vote=False, seed=0, node_chunk=1)
    x_all, y_all = np.asarray(fed.x_all), np.asarray(fed.y_all)
    start = jax.tree.map(np.asarray, lora)
    perm = draw_node_perms(copy.deepcopy(fed._rng), fed._sizes, fed._nb, fed.batch_size, 1)
    entry = fed.run_round(epochs=1)
    got = jax.tree.map(lambda a: np.asarray(a[0]), fed.params)
    assert all(np.array_equal(np.asarray(leaf[0]), np.asarray(leaf[1])) for leaf in jax.tree.leaves(fed.params))
    assert 1.0 <= float(entry["moe_load_max_over_mean"]) <= 8 / 2  # the round's entry carries the counter

    grad = jax.jit(lambda lo, b, x, y: jax.value_and_grad(glm_moe_lm.loss)(lo, b, x, y, REF, lora_scale=2.0))
    step = fedavg.adam_step(grad)
    trained = []
    with jax.default_matmul_precision("highest"):
        for node in range(2):
            batches = [(base, jnp.asarray(x_all[node][i]), jnp.asarray(y_all[node][i])) for i in perm[node, 0]]
            out, _ = fedavg.adam_train(lora, batches, step, {"name": "adam", "schedule": "constant", "learning_rate": 1e-3})
            trained.append(jax.tree.map(np.asarray, out))
    want = fedavg.weighted_mean(trained, [x_all.shape[1]] * 2)
    assert ck.cosine(ck.tree_sub(got, start), ck.tree_sub(want, start)) > 0.999


def test_capacity_based_moe_under_the_layer_scan_is_still_refused_and_experts_are_not():
    with pytest.raises(NotImplementedError, match="period scan"):
        CausalLM(TransformerConfig(n_experts=4, scan_layers=True)).init(jax.random.PRNGKey(0), batch()[0])
    CausalLM(config()).init(jax.random.PRNGKey(0), batch()[0])  # a router that sows no loss scans


def test_flops_moe_counts_the_published_model():
    import json

    from benchmark import flops_moe

    cfg = json.loads((ROOT / "benchmark" / "configs" / "glm47_flash_lora.json").read_text())
    assert sum(i * o for _, i, o in flops_moe.mla_matrices(cfg)) + 768 + 512 == 21_759_232
    assert flops_moe.layer_params(cfg, "mla_dense") == 84_677_888
    assert flops_moe.layer_params(cfg, "mla_experts") == 31_331_648
    assert flops_moe.bank_params(cfg) == 603_979_776 == 64 * 9_437_184
    assert cfg["rms_norm_eps"] == 1e-5 and cfg["vocab_size"] == 154_880 and cfg["num_experts_per_tok"] == 4
    ops, moved = flops_moe.gmm_pass(cfg, 4096)
    assert ops == 2.0 * 4096 * 4 * 9_437_184  # four experts a token, not executed tiles
    assert moved > 2 * 603_979_776  # the bfloat16 bank once, and the rows


@pytest.mark.parametrize("module", ["benchmark.selfcheck", "benchmark.rehearse", "benchmark.planted_faults"])
def test_benchmark_files_resolve_and_the_cell_rehearses(module):
    """The last case runs the cell's WHOLE reference check at the rehearsal's
    sizes with the expert layer choosing without its bias: exit 0 = it was seen."""
    args = {
        "benchmark.selfcheck": [],
        "benchmark.rehearse": ["--workload", "glm_silo4_seq4096", "--seconds", "1"],
        "benchmark.planted_faults": ["--workload", "glm_silo4_seq4096", "--seed", "1", "--fault", "choose_without_bias", "--rehearsal"],
    }[module]
    done = subprocess.run(
        [sys.executable, "-m", module, *args], cwd=ROOT, capture_output=True, text=True, timeout=900,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin", "PYTHONPATH": str(ROOT)},
    )
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    if module.endswith("rehearse"):
        assert '"correct": true' in done.stdout and "rehearsal finished" in done.stdout
    if module.endswith("planted_faults"):
        assert "'layer.routing_agreement'" in done.stdout.splitlines()[-1]
