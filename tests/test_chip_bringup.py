"""Chip bring-up guards that need no chip.

What ``chip_smoke.py`` proves on the TPU, pinned here as far as a host can:
the flash kernels carry their grid ``dimension_semantics`` and lower for
Mosaic at the shapes the shipped selection produces, an unknown TPU kind is
an error (not a guessed default), the compile cache is placed from outside,
and the smoke's phase functions run end to end at toy sizes.
"""

import base64
import re
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))  # chip_smoke.py: repo root

import chip_smoke  # noqa: E402
from p2pfl_tpu import compile_cache  # noqa: E402
from p2pfl_tpu.learning.dataset import FederatedDataset  # noqa: E402
from p2pfl_tpu.management import profiling  # noqa: E402
from p2pfl_tpu.ops import autotune  # noqa: E402
from p2pfl_tpu.ops.flash_attention import _compiler_params, flash_attention  # noqa: E402


def test_compiler_params_carry_dimension_semantics():
    params = _compiler_params("parallel", "parallel", "arbitrary")
    assert isinstance(params, pltpu.CompilerParams)
    assert tuple(params.dimension_semantics) == ("parallel", "parallel", "arbitrary")


@pytest.mark.parametrize("t,d", [(1024, 64), (4096, 128), (8192, 128), (4096, 256)])
def test_flash_fwd_bwd_lowers_for_mosaic(t, d):
    """Host-only Pallas→Mosaic lowering of forward + backward under the v5e
    defaults: catches Python-side lowering breaks before chip time is spent.
    Every kernel's serialized body must name its grid semantics, with the
    sequential ('arbitrary') dim the lse row and the dQ scratch rely on."""
    cfg = autotune.default_flash_config(t, d, kind="TPU v5 lite")

    def loss(q, k, v):
        out = flash_attention(q, k, v, True, cfg, False)
        return jnp.sum((out * out).astype(jnp.float32))

    x = jax.ShapeDtypeStruct((1, t, 2, d), jnp.bfloat16)
    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    text = grad.trace(x, x, x).lower(lowering_platforms=("tpu",)).as_text()
    bodies = re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', text)
    assert len(bodies) == text.count("tpu_custom_call") == 2  # fwd + fused bwd
    for body in bodies:
        raw = base64.b64decode(body)
        assert b"dimension_semantics" in raw and b"arbitrary" in raw


def test_wide_heads_ask_for_more_scoped_vmem_in_the_fused_backward_only():
    """D = 256 / T = 4096: the fused backward's resident blocks pass the 16 MiB
    default by 0.8 MiB (host-only compile, PR 31), and D = 128 / T = 8192 — the
    same bytes — by 1.0 MiB (PR 37); heads up to 128 at up to 4096 tokens keep
    the default, so their kernels are the ones they were."""
    from p2pfl_tpu.ops.flash_attention import _fused_vmem_limit

    assert _fused_vmem_limit(4096, 64) is None and _fused_vmem_limit(4096, 128) is None and _fused_vmem_limit(8192, 64) is None
    assert _fused_vmem_limit(4096, 256) == _fused_vmem_limit(8192, 128) == 32 * 1024 * 1024
    assert autotune.flash_config_source(4096, 256, kind="TPU v5 lite")[1] == "defaults"


@pytest.mark.parametrize("transpose_rhs", [False, True])
def test_grouped_matmul_lowers_for_mosaic(transpose_rhs):
    """Host-only Pallas→Mosaic lowering of ``p2pfl_gmm`` at the GLM expert
    widths over a stack of four layers' banks, both faces (the product and the
    input cotangent's); the layer is a traced scalar."""
    from p2pfl_tpu.ops import grouped_matmul as gm

    e, d, f, tile = 64, 2048, 1536, 128
    rows = tile * gm.n_row_tiles(4096 * 4, e, tile)
    lhs = jax.ShapeDtypeStruct((rows, 2 * f if transpose_rhs else d), jnp.bfloat16)
    rhs = jax.ShapeDtypeStruct((4, e, d, 2 * f), jnp.bfloat16)
    sizes = jax.ShapeDtypeStruct((e,), jnp.int32)
    layer = jax.ShapeDtypeStruct((), jnp.int32)
    fn = jax.jit(lambda a, b, l, c: gm._gmm_pallas(a, b, l, c, tile, transpose_rhs, interpret=False))
    text = fn.trace(lhs, rhs, layer, sizes).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 1 and 'kernel_name = "p2pfl_gmm"' in text


def _kda_body(direction: str) -> str:
    """The Mosaic body of ``p2pfl_kda_fwd`` / ``_bwd`` lowered host-only at the
    Solar widths (64 heads of 128, 4096 tokens, chunk 64), deserialized and
    printed without locations (the serialized body carries source lines)."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jaxlib.mlir import ir
    from jaxlib.mlir.passmanager import PassManager

    from p2pfl_tpu.ops import delta_rule_kernel as dk

    t, h, d, c = 4096, 64, 128, 64
    x = jax.ShapeDtypeStruct((1, t, h * d), jnp.float32)
    beta = jax.ShapeDtypeStruct((1, t, h), jnp.float32)
    if direction == "fwd":
        fn, args = jax.jit(lambda *a: dk.kda_fwd(*a, c)), (x, x, x, x, beta)
    else:
        starts = jax.ShapeDtypeStruct((1, t // c, h, d, d), jnp.float32)
        fn, args = jax.jit(lambda *a: dk.kda_bwd(*a, c)), (x, x, x, x, beta, starts, x)
    text = fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 1 and f'kernel_name = "p2pfl_kda_{direction}"' in text
    (body,) = re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', text)
    with mlir.make_ir_context() as ctx:
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True  # the serialized dialect's names, until mosaic-serde reads them back
        module = ir.Module.parse(base64.b64decode(body))
        PassManager.parse("builtin.module(mosaic-serde{serialize=false})").run(module.operation)
        return module.operation.get_asm(enable_debug_info=False)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_delta_rule_kernels_lower_for_mosaic(direction):
    """Host-only Pallas→Mosaic lowering of ``p2pfl_kda_fwd`` / ``p2pfl_kda_bwd``
    at the Solar widths: the chunk and head axes sequential (the states carried
    in VMEM scratch), and the grid ``(1, 64, 64 / n)`` for the ``n`` heads a
    step the shape rule picks (4)."""
    from p2pfl_tpu.ops.delta_rule import heads_per_step

    n = heads_per_step(64)
    assert n == 4
    body = _kda_body(direction)
    assert "#tpu.dimension_semantics<arbitrary>" in body
    assert f"iteration_bounds = array<i64: 1, 64, {64 // n}>" in body


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_delta_rule_kernels_at_one_head_a_step_lower_to_the_parents_text(direction, monkeypatch):
    """With the shape rule held to one head a step, the kernels are the ones
    there were, op for op: the body's text hash for hash what the commit before
    heads were grouped lowered (``tests/fixtures/kda_lowered_parent.json``)."""
    import hashlib
    import json

    from p2pfl_tpu.ops import delta_rule_kernel as dk

    want = json.loads((Path(__file__).parent / "fixtures" / "kda_lowered_parent.json").read_text())[direction]
    monkeypatch.setattr(dk, "heads_per_step", lambda heads: 1)
    body = _kda_body(direction)
    assert "iteration_bounds = array<i64: 1, 64, 64>" in body
    assert (hashlib.sha256(body.encode()).hexdigest(), len(body)) == (want["sha256"], want["chars"])


def test_lora_round_with_compiled_flash_lowers_on_a_four_device_mesh():
    """GSPMD refuses to partition a Mosaic kernel ("cannot be automatically
    partitioned"), which the interpreted kernels of the CPU mesh never show:
    the first four-chip run did. Host-only lowering for TPU does show it, so
    the per-node ``shard_map`` of the LoRA round and eval is pinned here."""
    from functools import partial

    from p2pfl_tpu.models.transformer import CausalLM, TransformerConfig, tiny_transformer
    from p2pfl_tpu.ops.flash_attention import FlashConfig
    from p2pfl_tpu.parallel import SpmdLoraFederation
    from p2pfl_tpu.parallel.spmd_lora import spmd_lora_eval, spmd_lora_round

    cfg = TransformerConfig(
        vocab_size=128, dim=256, n_layers=2, n_heads=2, n_kv_heads=1, ffn_hidden=256,
        lora_rank=4, lora_mlp=True, remat=True, scan_layers=True, remat_policy="mlp_qkv",
    )
    model = tiny_transformer(seq_len=256, cfg=cfg)  # dense init: same param tree
    compiled = partial(flash_attention, causal=True, config=FlashConfig(128, 128), interpret=False)
    model.module = CausalLM(cfg, compiled)
    data = FederatedDataset.synthetic_lm(vocab_size=128, seq_len=256, n_train=8, n_test=4)
    fed = SpmdLoraFederation.from_dataset(
        model, data, n_nodes=4, batch_size=1, vote=False, node_chunk=4
    )
    assert dict(fed.mesh.shape) == {"nodes": 4, "model": 1}
    args, statics = fed._round_call(1)
    text = spmd_lora_round.trace(*args, **statics).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 3  # forward, remat forward, fused backward
    text = (
        spmd_lora_eval.trace(
            fed.params, fed.base, fed.x_test, fed.y_test, module=fed.module, sharding=fed._shard
        )
        .lower(lowering_platforms=("tpu",))
        .as_text()
    )
    assert text.count("tpu_custom_call") == 1


def test_unknown_tpu_kind_raises():
    with pytest.raises(ValueError, match="TPU v9"):
        autotune._family("TPU v9")
    with pytest.raises(ValueError, match="TPU v9"):
        autotune.default_flash_config(1024, 64, kind="TPU v9")
    assert autotune._family("TPU v5 lite") == "v5e"
    assert autotune._family("cpu") == "cpu"
    unknown = types.SimpleNamespace(platform="tpu", device_kind="TPU v9")
    with pytest.raises(ValueError, match="TPU v9"):
        profiling.peak_flops(unknown)
    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert profiling.peak_flops(v5e) == 197e12
    assert profiling.peak_flops() is None  # the CPU test backend has no peak


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    updates = []
    monkeypatch.setattr(jax.config, "update", lambda *a: updates.append(a))
    keep_all = ("jax_persistent_cache_min_compile_time_secs", 0.0)
    by_name = ("jax_compilation_cache_include_metadata_in_key", True)  # scopes are part of the key
    # set: JAX reads the variable itself, the helper sets no directory in code
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.configure_compile_cache() == before
    assert updates == [keep_all, by_name]
    # unset: one fixed path under the checkout, the same on every call
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    del updates[:]
    compile_cache.configure_compile_cache()
    compile_cache.configure_compile_cache()
    placed = ("jax_compilation_cache_dir", str(compile_cache.DEFAULT_CACHE_DIR))
    assert updates == [placed, keep_all, by_name] * 2
    assert compile_cache.DEFAULT_CACHE_DIR == Path(chip_smoke.__file__).resolve().parent / ".jax_cache"


def test_chip_smoke_refuses_cpu(capsys, monkeypatch):
    # keep the rest of the test session off the persistent cache
    monkeypatch.setattr(compile_cache, "configure_compile_cache", lambda: "unused")
    assert chip_smoke.main() != 0
    assert capsys.readouterr().out == ""  # no result line without a TPU


def test_chip_smoke_phases_at_toy_size():
    """The smoke's control flow on the CPU mesh: tiny widths, interpreted
    kernels, same phase functions and checks ``main()`` runs on the chip."""
    compile_cache.install_compile_bridge()  # what configure_compile_cache() does in main(), without its cache
    flash = chip_smoke.check_flash(1, 256, 2, 32, interpret=True)
    assert flash["config_source"] == "defaults" and flash["mosaic_calls"] == 0
    scan = chip_smoke.check_scan(1, 600, 256, 4, interpret=True)
    assert scan["mosaic_calls"] == 0 and set(scan["rel_err"]) >= {"y", "dA", "dB", "dz", "xla_vs_loop"}

    a = chip_smoke.run_phase(
        "A", chip_smoke.phase_spmd,
        data=FederatedDataset.synthetic_mnist(n_train=2048, n_test=256),
        n_nodes=8, batch_size=32, chunk=2, min_acc=0.5,
    )
    assert a["mesh"] == {"nodes": 8, "model": 1} and len(a["accuracy_curve"]) == 4
    assert a["compile_s"] > 0 and a["run_s"] > 0

    b = chip_smoke.run_phase(
        "B", chip_smoke.phase_lora,
        widths=dict(vocab_size=128, dim=64, n_heads=2, n_kv_heads=1, n_layers=2, ffn_hidden=128),
        seq_len=128, n_nodes=8, node_chunk=4, steps_per_round=2, n_test=8,
        interpret=True,
    )
    assert b["mosaic_calls_in_round"] == 0 and b["train_loss"][2] < b["train_loss"][0]

    c = chip_smoke.run_phase(
        "C", chip_smoke.phase_nodes,
        data=FederatedDataset.synthetic_mnist(n_train=512, n_test=128),
        rounds=2, batch_size=64, timeout=90.0,
    )
    assert c["dispatch_counts"]["fused_round"] == 4
