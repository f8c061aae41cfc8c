"""Host-only compile of a ``spmd_lora_window_moe`` cell (``compile_check_conv_moe.py``
under this engine's model: two flash schedules, the sliding layers' beside the
full layers') for a described
``v5e:2x2`` — ``compile_check.py`` dispatches through a fixed table of engines,
so this engine's lowerings are registered here:

    JAX_PLATFORMS=cpu python -m benchmark.compile_check_window_moe --workload trinity_silo4_seq8192 \
        [--what round|init|reference] [--layers N] [--remat POLICY|none] [--gmm pallas|xla] [--tile-m M]

``round`` (default) is the timed program; ``init`` the seeded weights' one
jitted call; ``reference`` the float32 reference's loss-and-gradient of the
check. ``--layers N`` keeps the leading dense layers and the first N expert
layers (whole periods: how the depth was chosen is in PERF.md section 4).
Besides ``compile_check``'s lines ``round`` prints the Mosaic kernels of the
lowered round by name, its scan bodies, and the largest arrays of the compiled
program, and fails if an expert bank shows anywhere but as the stacked
arguments: a bfloat16 array of ONE layer's bank (a copy the scan sliced out of
the stack) or a float32 array with the expert count among its dimensions. The
grouped matmul picks its kernel by backend, and the backend here is the CPU:
this script steers that, the program has no option for it.
"""

from __future__ import annotations

import argparse
import math
import re
import sys
from functools import partial

from benchmark import compile_check as cc  # pins JAX to the CPU before importing it
from benchmark.compile_check_hybrid import largest_arrays

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402


def _module_and_params(job, gmm: str, reference: bool = False):
    from p2pfl_tpu.models.transformer import CausalLM, _flash_attend
    from p2pfl_tpu.ops import grouped_matmul
    from p2pfl_tpu.ops.autotune import default_flash_config

    from benchmark.engines.spmd_lora_window_moe import _transformer_config, seeded_params

    grouped_matmul._on_tpu = lambda: gmm == "pallas"  # what the chip's backend would answer
    cfg, tr, args = job.cfg, job.traffic, job.cell["engine_args"]
    tcfg = _transformer_config(cfg, dict(args, gmm=gmm))
    if args["attn"] != "flash":
        raise SystemExit("compile_check_window_moe: the expert cells run flash attention")
    seq = job.cell["check"]["seq_len"] if reference else tr["seq_len"]
    config = default_flash_config(seq, cfg["head_dim"], kind="TPU v5 lite")
    sliding = default_flash_config(seq, cfg["head_dim"], kind="TPU v5 lite", window=cfg["sliding_window"])
    attend = partial(_flash_attend, config=config, window_config=sliding, interpret=False)
    print(f"flash schedules for v5e at T={seq}, D={cfg['head_dim']}: full layers {config}; window {cfg['sliding_window']}: {sliding}")
    module = CausalLM(tcfg, attend)
    init = lambda key: seeded_params(tcfg, key, cfg["router_bias_std"])  # noqa: E731
    return module, init, jax.eval_shape(init, jax.random.PRNGKey(0))


def lower_round(job, mesh, gmm: str = "pallas"):
    from p2pfl_tpu.learning.learner import adam
    from p2pfl_tpu.learning.lora import split_lora
    from p2pfl_tpu.parallel.spmd_lora import spmd_lora_round

    tr, args = job.traffic, job.cell["engine_args"]
    module, _, params = _module_and_params(job, gmm)
    seq, n = tr["seq_len"], tr["n_nodes"]
    lora, base = split_lora(params)
    shard, repl = NamedSharding(mesh, P("nodes")), NamedSharding(mesh, P())
    tx = adam(args["optimizer"]["learning_rate"])
    docs, steps, bs = tr["data"]["docs_per_node"], tr["local_steps"], tr["batch_size"]
    tokens = jax.ShapeDtypeStruct((n, docs, seq), jnp.int32, sharding=shard)
    return spmd_lora_round.lower(
        cc.stacked(lora, n, shard), None, cc.spec(base, repl), tokens, tokens,
        jax.ShapeDtypeStruct((n, 1, steps, bs), jnp.int32, sharding=shard),
        jax.ShapeDtypeStruct((n,), jnp.float32, sharding=shard),
        jax.ShapeDtypeStruct((n,), jnp.float32, sharding=shard),
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=repl),
        module=module, tx=tx, agg="fedavg", trim=0, out_sharding=shard,
        keep_opt_state=args["keep_opt_state"], node_chunk=args["node_chunk"],
    )


def lower_init(job, mesh, gmm: str = "pallas"):
    _, init, _ = _module_and_params(job, gmm)
    repl = NamedSharding(mesh, P())
    return jax.jit(init, out_shardings=repl).lower(jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=repl))


def lower_reference(job, mesh, gmm: str = "pallas"):
    from p2pfl_tpu.learning.lora import split_lora

    from benchmark.engines.spmd_lora_window_moe import _reference_grad

    _, _, params = _module_and_params(job, gmm, reference=True)
    lora, base = split_lora(params)
    repl = NamedSharding(mesh, P())
    seq, cfg = job.cell["check"]["seq_len"], job.cfg
    tokens = jax.ShapeDtypeStruct((1, seq), jnp.int32, sharding=repl)
    expert_layers = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    forced = jax.ShapeDtypeStruct((1, expert_layers, seq, cfg["num_experts_per_tok"]), jnp.int32, sharding=repl)
    with jax.default_matmul_precision("highest"):
        return _reference_grad(job).lower(cc.spec(lora, repl), cc.spec(base, repl), tokens, tokens, forced)


WHAT = {"round": lower_round, "init": lower_init, "reference": lower_reference}


def _dims(shape: str) -> list[int]:
    return [int(d) for d in shape.split("[")[1].rstrip("]").split(",")]


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--what", choices=sorted(WHAT), default="round")
    parser.add_argument("--layers", type=int, help="expert layers N after the leading dense ones (whole periods)")
    parser.add_argument("--remat", help="a remat policy's name, or 'none'")
    parser.add_argument("--gmm", choices=("pallas", "xla"), default="pallas")
    parser.add_argument("--tile-m", type=int)
    args = parser.parse_args()

    lower = partial(WHAT[args.what], gmm=args.gmm)

    def with_overrides(job, mesh):
        if args.layers is not None:
            job.cfg["num_hidden_layers"] = job.cfg["num_dense_layers"] + args.layers
            job.cfg["layer_types"] = job.cfg["layer_types"][:job.cfg["num_hidden_layers"]]
        if args.remat is not None:
            job.cell["engine_args"]["remat_policy"] = None if args.remat == "none" else args.remat
        if args.tile_m is not None:
            job.cell["engine_args"]["gmm_tile_m"] = args.tile_m
        print(
            f"{args.workload}: {args.what}, {job.cfg['num_hidden_layers']} layers, remat_policy "
            f"{job.cell['engine_args']['remat_policy']}, gmm {args.gmm}, tile_m {job.cell['engine_args']['gmm_tile_m']}"
        )
        return lower(job, mesh)

    cc.LOWER["spmd_lora_window_moe"] = with_overrides
    sys.argv = [sys.argv[0], "--workload", args.workload]

    # compile_check.main prints its lines and keeps nothing: take the lowered
    # and compiled texts from the one place they pass through
    texts = {}
    lowered_compile = jax.stages.Lowered.compile

    def compile_and_keep(self, *a, **kw):
        compiled = lowered_compile(self, *a, **kw)
        texts["lowered"], texts["compiled"] = self.as_text(), compiled.as_text()
        return compiled

    jax.stages.Lowered.compile = compile_and_keep
    try:
        rc = cc.main()
    finally:
        jax.stages.Lowered.compile = lowered_compile
    biggest = largest_arrays(texts["compiled"])
    print(f"{args.workload}: largest arrays in the compiled program: " + ", ".join(f"{s} {b / 1e9:.3f} GB ({n})" for b, s, n in biggest))
    print(f"{args.workload}: ops named *.remat* in the compiled text: {len(re.findall(r'%[\w.-]*remat[\w.-]* = ', texts['compiled']))}")
    if args.what != "round":
        return rc
    from benchmark.engines.spmd_lora_window_moe import kernels_in

    print(f"{args.workload}: Mosaic kernels in the lowered round: {kernels_in(texts['lowered'])}")
    bench = cc.run.load_json(cc.run.ROOT / "BENCHMARK.json")
    _, _, cfg, _ = cc.run.resolve(bench, args.workload)
    experts, d, f = cfg["num_experts"], cfg["hidden_size"], cfg["moe_intermediate_size"]
    one_bank = experts * d * f  # the smaller of a layer's two stacks
    # the banks: bf16 arrays with the expert count and a bank's two widths among their dimensions
    banks = sorted({
        s for s in re.findall(r"bf16\[[\d,]+\]", texts["compiled"])
        if experts in _dims(s) and math.prod(_dims(s)) >= one_bank
    })
    print(f"{args.workload}: bfloat16 arrays as large as one layer's expert stack in the compiled text: {banks}")
    sliced = [s for s in banks if len(_dims(s)) == 3]  # [E, K, N]: one layer's bank, cut out of the stack
    # a float32 copy of (a layer of) the bank has the expert count among its dimensions
    wide = [s for _, s, _ in biggest if s.startswith("f32") and experts in _dims(s) and math.prod(_dims(s)) >= one_bank]
    if sliced or wide:
        print(f"{args.workload}: FAILED: a copy of a layer's expert stack ({one_bank} elements): bf16 {sliced}, f32 {wide}")
        return 1
    print(f"{args.workload}: the banks appear only stacked and in bfloat16; no float32 array reaches one expert stack ({one_bank} elements)")
    return rc


if __name__ == "__main__":
    sys.exit(main())
