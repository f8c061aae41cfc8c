"""Host-only compile of a cell's round program for a described ``v5e:2x2``,
at the real size, with no chip attached (the third rehearsal).

    JAX_PLATFORMS=cpu python -m benchmark.compile_check --workload <cell>

Prints ``memory_analysis()`` (bytes per device of the ONE program: arguments,
outputs, temporaries — not what else the process keeps resident), the number
of Mosaic kernels and the collectives in the compiled text. What the TPU
compiler refuses here (memory, an unpartitionable kernel) costs no chip time.
A compile that passes is not a chip run: nothing here is a device metric.
"""

from __future__ import annotations

import argparse
import os
import sys
from functools import partial

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from benchmark import run  # noqa: E402


def spec(tree, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)


def stacked(tree, n: int, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct((n, *a.shape), a.dtype, sharding=sharding), tree)


def lower_spmd_lora(job, mesh):
    from p2pfl_tpu.learning.learner import adam
    from p2pfl_tpu.learning.lora import split_lora
    from p2pfl_tpu.models.transformer import CausalLM
    from p2pfl_tpu.ops.autotune import default_flash_config
    from p2pfl_tpu.ops.flash_attention import flash_attention
    from p2pfl_tpu.parallel.spmd_lora import spmd_lora_round
    from p2pfl_tpu.settings import Settings

    from benchmark.engines.spmd_lora import _transformer_config

    cfg, tr, args = job.cfg, job.traffic, job.cell["engine_args"]
    tcfg = _transformer_config(cfg, args)
    seq, n = tr["seq_len"], tr["n_nodes"]
    attn = args["attn"]
    if attn == "auto":  # pick_attention asks the backend, which is the CPU here
        attn = "flash" if seq >= Settings.FLASH_MIN_SEQ_LEN else "dense"
    attn_fn = None
    if attn == "flash":
        config = default_flash_config(seq, cfg["head_dim"], kind="TPU v5 lite")
        attn_fn = partial(flash_attention, causal=True, config=config, interpret=False)
    module = CausalLM(tcfg, attn_fn)
    params = jax.eval_shape(
        lambda k: CausalLM(tcfg, None).init(k, jnp.zeros((1, 16), jnp.int32))["params"], jax.random.PRNGKey(0)
    )
    lora, base = split_lora(params)
    shard, repl = NamedSharding(mesh, P("nodes")), NamedSharding(mesh, P())
    tx = adam(args["optimizer"]["learning_rate"])
    opt = jax.eval_shape(jax.vmap(tx.init), stacked(lora, n, None))
    docs, steps, bs = tr["data"]["docs_per_node"], tr["local_steps"], tr["batch_size"]
    tokens = jax.ShapeDtypeStruct((n, docs, seq), jnp.int32, sharding=shard)
    return spmd_lora_round.lower(
        stacked(lora, n, shard), spec(opt, shard), spec(base, repl), tokens, tokens,
        jax.ShapeDtypeStruct((n, 1, steps, bs), jnp.int32, sharding=shard),
        jax.ShapeDtypeStruct((n,), jnp.float32, sharding=shard),
        jax.ShapeDtypeStruct((n,), jnp.float32, sharding=shard),
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=repl),
        module=module, tx=tx, agg="fedavg", trim=0, out_sharding=shard,
        keep_opt_state=args["keep_opt_state"], remat=False, node_chunk=args["node_chunk"],
    )


def _resnet(job):
    from p2pfl_tpu.models.vision import ResNet

    cfg = job.cfg
    module = ResNet(stage_sizes=tuple(cfg["stage_sizes"]), bottleneck=True, num_classes=cfg["num_classes"])
    shape = tuple(cfg["input_shape"])
    params = jax.eval_shape(
        lambda k: module.init(k, jnp.zeros((1, *shape), jnp.float32))["params"], jax.random.PRNGKey(0)
    )
    return module, params, shape


def lower_spmd(job, mesh):
    from p2pfl_tpu.parallel.spmd import spmd_round
    from p2pfl_tpu.settings import Settings

    from benchmark.engines.spmd import make_tx

    tr, args = job.traffic, job.cell["engine_args"]
    module, params, shape = _resnet(job)
    n, steps, bs = tr["n_nodes"], tr["local_steps"], tr["batch_size"]
    samples = tr["data"]["samples_per_node"]
    shard, repl = NamedSharding(mesh, P("nodes")), NamedSharding(mesh, P())
    tx = make_tx(args["optimizer"])
    opt = jax.eval_shape(jax.vmap(tx.init), stacked(params, n, None))
    return spmd_round.lower(
        stacked(params, n, shard), spec(opt, shard),
        jax.ShapeDtypeStruct((n, samples, *shape), jnp.float32, sharding=shard),
        jax.ShapeDtypeStruct((n, samples), jnp.int32, sharding=shard),
        jax.ShapeDtypeStruct((n, 1, steps, bs), jnp.int32, sharding=shard),
        jax.ShapeDtypeStruct((n,), jnp.float32, sharding=shard),
        jax.ShapeDtypeStruct((n,), jnp.float32, sharding=shard),
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=repl),
        module=module, tx=tx, agg="fedavg", trim=0, clip_tau=1.0, out_sharding=shard,
        keep_opt_state=args["keep_opt_state"], remat=args["remat"], x_test=None, y_test=None,
        dp_keys=None, prox_mu=0.0, scaffold=False, scaffold_fused_ci=bool(Settings.SCAFFOLD_FUSED_CI),
        local_lr=1e-3, server_opt="", server_lr=0.1, c_global=None, c_local=None, opt_m=None,
        opt_v=None, opt_t=None, dp_clip=0.0, dp_noise=0.0,
    )


def lower_nodes(job, mesh):
    from p2pfl_tpu.learning.learner import adam
    from p2pfl_tpu.parallel.spmd import fused_node_round
    from p2pfl_tpu.settings import Settings

    tr = job.traffic
    module, params, shape = _resnet(job)
    one = jax.sharding.SingleDeviceSharding(mesh.devices.flat[0])
    tx = adam(job.cell["engine_args"]["optimizer"]["learning_rate"])
    steps, bs, n_test = tr["local_steps"], tr["batch_size"], tr["data"]["test_samples_per_node"]
    return fused_node_round.lower(
        spec(params, one), spec(jax.eval_shape(tx.init, params), one),
        jax.ShapeDtypeStruct((1, steps, bs, *shape), jnp.float32, sharding=one),
        jax.ShapeDtypeStruct((1, steps, bs), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one),
        jax.ShapeDtypeStruct((n_test, *shape), jnp.float32, sharding=one),
        jax.ShapeDtypeStruct((n_test,), jnp.int32, sharding=one),
        module=module, tx=tx, prox_mu=0.0, with_acc=True, agg_dtype=Settings.AGG_DTYPE,
    )


LOWER = {"spmd_lora": lower_spmd_lora, "spmd": lower_spmd, "nodes": lower_nodes}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    parser.add_argument("--workload", required=True)
    args = parser.parse_args()
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    benchmark = run.load_json(run.ROOT / "BENCHMARK.json")
    entry, cell, cfg, traffic = run.resolve(benchmark, args.workload)
    job = run.Job(args.workload, cell, cfg, traffic, 0, False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chips = entry["chips"]
    mesh = Mesh(np.array(topo.devices[:chips]).reshape(chips, 1), ("nodes", "model"))
    lowered = LOWER[cell["engine"]](job, mesh)
    text = lowered.as_text()
    print(f"{args.workload}: lowered; tpu_custom_call in lowered text: {text.count('tpu_custom_call')}")
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    collectives = {op: hlo.count(f" {op}(") for op in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute", "all-reduce-start")}
    gb = lambda b: round(b / 1e9, 3)  # noqa: E731
    print(
        f"{args.workload}: compiled for v5e:2x2 on {chips} chip(s): arguments {gb(mem.argument_size_in_bytes)} GB, "
        f"outputs {gb(mem.output_size_in_bytes)} GB, aliased {gb(mem.alias_size_in_bytes)} GB, "
        f"temporaries {gb(mem.temp_size_in_bytes)} GB, program {gb(mem.generated_code_size_in_bytes)} GB per device; "
        f"Mosaic kernels in compiled text: {hlo.count('tpu_custom_call')}; collectives: {collectives}"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
