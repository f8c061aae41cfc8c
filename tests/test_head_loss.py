"""``ops/head_loss.py``: the tied head and its cross-entropy as one blocked
function with its own derivative — value and both cotangents against
``optax.softmax_cross_entropy_with_integer_labels`` on float32 logits, in every
nesting the LoRA round puts it in, and what the lowered gradient of
``_lm_loss`` may and may not hold."""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from p2pfl_tpu.learning.lora import _lm_forward, _lm_loss, split_lora
from p2pfl_tpu.models.transformer import TransformerConfig, tiny_transformer
from p2pfl_tpu.ops import head_loss as hl
from p2pfl_tpu.ops.head_loss import block_rows, head_loss

VOCAB, DIM = 40, 12


def plain(hidden, embedding, labels):
    """The head and the loss as the parent wrote them."""
    logits = jnp.dot(hidden, embedding.T.astype(hidden.dtype)).astype(jnp.float32)
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()


def draw(shape, seed=0, dtype=jnp.float32):
    k_h, k_e, k_y = jax.random.split(jax.random.PRNGKey(seed), 3)
    hidden = jax.random.normal(k_h, (*shape, DIM), jnp.float32).astype(dtype)
    embedding = 0.5 * jax.random.normal(k_e, (VOCAB, DIM), jnp.float32)
    labels = jax.random.randint(k_y, shape, 0, VOCAB)
    return hidden, embedding, labels


@pytest.fixture
def blocks_of(monkeypatch):
    """Make the shapes of a test 'large': blocks of about ``rows`` rows."""

    def set_rows(rows):
        monkeypatch.setattr(hl, "_ONE_BLOCK_ELEMENTS", 0)
        monkeypatch.setattr(hl, "_BLOCK_ROWS", rows)

    return set_rows


def rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize(
    "shape, near, per",
    [
        ((16,), None, 16),  # one block: the constants as they are
        ((32,), 8, 8),  # four blocks
        ((24,), 16, 12),  # the constant does not divide the rows: two blocks of 12
        ((3, 8), 6, 6),  # [B, T] with B > 1, blocks that straddle sequences
    ],
    ids=["one_block", "several_blocks", "constant_not_a_divisor", "batch_of_sequences"],
)
@pytest.mark.parametrize("cotangent", [1.0, 2.5], ids=["unit", "times_2.5"])
def test_value_and_both_cotangents_match_optax(blocks_of, shape, near, per, cotangent):
    if near is not None:
        blocks_of(near)
    hidden, embedding, labels = draw(shape)
    assert block_rows(math.prod(shape), VOCAB) == per
    got = jax.value_and_grad(lambda h, e: cotangent * head_loss(h, e, labels), argnums=(0, 1))(hidden, embedding)
    want = jax.value_and_grad(lambda h, e: cotangent * plain(h, e, labels), argnums=(0, 1))(hidden, embedding)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-6)
    for g, w in zip(got[1], want[1]):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-7)


@pytest.mark.parametrize("near", [None, 4], ids=["one_block", "several_blocks"])
def test_under_vmap_over_nodes_with_a_shared_embedding(blocks_of, near):
    if near is not None:
        blocks_of(near)
    hidden, embedding, labels = draw((5, 16))  # 5 nodes of 16 rows

    def total(fn):
        return lambda h, e: jnp.sum(jax.vmap(fn, in_axes=(0, None, 0))(h, e, labels) * jnp.arange(1.0, 6.0))

    got = jax.value_and_grad(total(head_loss), argnums=(0, 1))(hidden, embedding)
    want = jax.value_and_grad(total(plain), argnums=(0, 1))(hidden, embedding)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-6)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-7)


@pytest.mark.parametrize("near", [None, 8], ids=["one_block", "several_blocks"])
def test_inside_a_scan_of_steps_under_value_and_grad(blocks_of, near):
    """The round's nesting: ``lax.scan`` over steps, each a ``value_and_grad``
    of the loss followed by an update of what it was taken for."""
    if near is not None:
        blocks_of(near)
    steps = 3
    hidden, embedding, labels = draw((steps, 2, 8))
    w0 = jnp.eye(DIM) + 0.1 * jax.random.normal(jax.random.PRNGKey(7), (DIM, DIM))

    def run(fn):
        def step(w, batch):
            loss, grad = jax.value_and_grad(lambda w_: fn(batch[0] @ w_, embedding, batch[1]))(w)
            return w - 0.5 * grad, loss

        return jax.jit(lambda w: jax.lax.scan(step, w, (hidden, labels)))(w0)

    (w_got, l_got), (w_want, l_want) = run(head_loss), run(plain)
    np.testing.assert_allclose(l_got, l_want, rtol=5e-6)
    np.testing.assert_allclose(w_got, w_want, rtol=5e-5, atol=1e-6)


@pytest.mark.parametrize("near", [None, 16], ids=["one_block", "several_blocks"])
def test_bf16_hidden_states(blocks_of, near):
    """bf16 carries 8 significant bits: a rounding is off by at most 2^-9 of
    the value. The VALUE is a function of logits rounded to bf16 on both sides
    (on a TPU the same ones; the CPU's bf16 product rounds by another route at
    another shape), so two sides differ by under 2^-9 of the largest logit — a
    row's loss moves by no more than its logits do. The COTANGENT of the hidden
    states rounds three times a
    side — ``softmax - onehot`` to bf16, the product, the scaled product; the
    plain path scales before its roundings, so they fall differently — which
    bounds the distance by 6 x 2^-9 = 1.2e-2 if every error lined up; taken in
    norm over 12 x 64 numbers they do not, and a few 2^-9 are what is read."""
    if near is not None:
        blocks_of(near)
    hidden, embedding, labels = draw((64,), dtype=jnp.bfloat16)
    got = jax.value_and_grad(head_loss, argnums=(0, 1))(hidden, embedding, labels)
    want = jax.value_and_grad(plain, argnums=(0, 1))(hidden, embedding, labels)
    assert got[1][0].dtype == jnp.bfloat16 and got[1][1].dtype == jnp.float32
    largest = float(jnp.max(jnp.abs(hidden.astype(jnp.float32) @ embedding.T)))
    assert abs(float(got[0]) - float(want[0])) < 2.0**-9 * largest
    assert rel(got[1][0], want[1][0]) < 6 * 2.0**-9
    assert rel(got[1][1], want[1][1]) < 6 * 2.0**-9
    # and against float32 hidden states: within bf16's noise on the logits
    exact = jax.value_and_grad(plain)(hidden.astype(jnp.float32), embedding, labels)
    assert abs(float(got[0]) - float(exact[0])) < 2.0**-7
    assert rel(got[1][0], exact[1]) < 3e-2


@pytest.mark.parametrize(
    "rows, vocab, per",
    [
        (512, 32768, 512),  # lora_fleet32_seq512, a node
        (4096, 32768, 4096),  # lora_silo4_seq4096
        (4096, 65536, 4096),  # jamba_silo4_seq4096
        (4096, 154880, 512),  # glm_silo4_seq4096
        (3000, 154880, 500),  # the constant does not divide the rows
        (8192, 154880, 512),
        (4099, 154880, 4099),  # a prime: the rows themselves are nearer than 1
    ],
)
def test_block_rows_follow_from_the_shapes(rows, vocab, per):
    assert block_rows(rows, vocab) == per
    assert rows % per == 0


# ---- the loss of the LoRA round ----

LM_VOCAB, LM_DIM, LM_SEQ = 320, 48, 32


def lm(seq=LM_SEQ, **kw):
    cfg = TransformerConfig(
        vocab_size=LM_VOCAB, dim=LM_DIM, n_layers=2, n_heads=4, n_kv_heads=2, ffn_hidden=96, lora_rank=4,
        lora_alpha=8.0, lora_mlp=True, **kw,
    )
    model = tiny_transformer(seq_len=seq, seed=0, cfg=cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    model.params = jax.tree_util.tree_map_with_path(  # at lora_b = 0 every lora_a gradient is zero
        lambda path, a: 0.05 * jax.random.normal(next(keys), a.shape, a.dtype)
        if "lora_b" in jax.tree_util.keystr(path) else a,
        model.params,
    )
    return model, *split_lora(model.params)


def tokens(seq=LM_SEQ, n=2):
    x = jax.random.randint(jax.random.PRNGKey(3), (n, seq + 1), 0, LM_VOCAB)
    return x[:, :-1], x[:, 1:]


def tensors(text):
    """Every tensor type in lowered text as (dims, element type)."""
    found = set()
    for dims, kind in re.findall(r"tensor<((?:\d+x)+)([a-z]+\d+)>", text):
        found.add((tuple(int(d) for d in dims.split("x") if d), kind))
    return found


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_lm_forward_keeps_its_four_results(blocks_of, dtype):
    """Loss = CE of the module's own logits; the logits in second place are the
    module's, float32; a dense model sows neither statistics nor routing."""
    blocks_of(16)
    model, lora, base = lm(dtype=dtype)
    x, y = tokens()
    loss, logits, stats, routing = _lm_forward(lora, base, model.module, x, y)
    want = model.module.apply({"params": model.params}, x)
    assert logits.dtype == jnp.float32 and logits.shape == (2, LM_SEQ, LM_VOCAB)
    np.testing.assert_array_equal(logits, want)
    # bf16: the CPU rounds a block's product by another route than the whole's (test_bf16_hidden_states)
    tolerance = 2e-6 * float(loss) if dtype == jnp.float32 else 2.0**-9 * float(jnp.max(jnp.abs(want)))
    assert abs(float(loss) - float(optax.softmax_cross_entropy_with_integer_labels(want, y).mean())) < tolerance
    assert (stats, routing) == ({}, {})
    assert float(_lm_loss(lora, base, model.module, x, y)[0]) == float(loss)


def test_gradient_of_lm_loss_holds_no_logits_whole_and_two_vocabulary_matmuls(blocks_of):
    """Lowered ``jax.grad`` of ``_lm_loss`` w.r.t. the adapters, 64 rows in
    four blocks of 16: no float32 value of rows x vocab elements; no gather or
    scatter over the vocabulary but the embedding lookup; and exactly two
    ``dot_general`` with the vocabulary among their dimensions — the logits'
    and dX's, each once: the embedding is frozen, so its cotangent's product is
    gone, and so are the plain logits ``_lm_loss`` hands out beside the loss."""
    blocks_of(16)
    model, lora, base = lm(dtype=jnp.bfloat16)
    x, y = tokens()
    rows = x.size
    text = jax.jit(jax.grad(lambda lo: _lm_loss(lo, base, model.module, x, y)[0])).lower(lora).as_text()
    whole = [t for t in tensors(text) if t[1] == "f32" and math.prod(t[0]) >= rows * LM_VOCAB]
    assert not whole, whole
    table = ((LM_VOCAB, LM_DIM), "f32")  # the embedding lookup gathers from this and nothing else
    for line in text.splitlines():
        if "stablehlo.gather" in line or "stablehlo.scatter" in line:
            over_vocab = [t for t in tensors(line) if LM_VOCAB in t[0] and t != table]
            assert not over_vocab, line
    dots = [line for line in text.splitlines() if "stablehlo.dot_general" in line]
    with_vocab = [line for line in dots if any(LM_VOCAB in t[0] for t in tensors(line))]
    assert len(with_vocab) == 2, with_vocab
    assert ((16, LM_VOCAB), "bf16") in tensors(text)  # a block's logits


def test_embedding_cotangent_is_there_for_who_reads_it(blocks_of):
    """The same program differentiated for the embedding too holds the third
    product, and its cotangent matches the plain loss's."""
    blocks_of(16)
    model, _, _ = lm(dtype=jnp.float32)
    x, y = tokens()
    rest = {k: v for k, v in model.params.items() if k != "embed"}
    embed = model.params["embed"]

    def loss(rest_, embed_, fn):
        hidden, table = model.module.apply({"params": {**rest_, "embed": embed_}}, x, head=False)
        return fn(hidden, table, y)

    text = jax.jit(jax.grad(loss, argnums=(0, 1)), static_argnums=2).lower(rest, embed, head_loss).as_text()
    dots = [line for line in text.splitlines() if "stablehlo.dot_general" in line]
    assert len([line for line in dots if any(LM_VOCAB in t[0] for t in tensors(line))]) == 3
    got = jax.grad(loss, argnums=1)(rest, embed, head_loss)
    want = jax.grad(loss, argnums=1)(rest, embed, plain)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-7)


def test_512_rows_lower_without_a_loop_from_the_head():
    """The fleet cell's node holds 512 rows: one block, so the head adds no
    ``while`` (layers unrolled here, so the program has none at all)."""
    model, lora, base = lm(seq=512)
    x, y = tokens(seq=512, n=1)
    assert block_rows(512, 32768) == 512
    text = jax.jit(jax.grad(lambda lo: _lm_loss(lo, base, model.module, x, y)[0])).lower(lora).as_text()
    assert "stablehlo.while" not in text
