"""Host-only compile of a ``spmd_lora_linear_moe`` cell for a described
``v5e:2x2`` (``compile_check_window_moe.py`` under this engine's model) —
``compile_check.py`` dispatches through a fixed table of engines, so this
engine's lowerings are registered here:

    JAX_PLATFORMS=cpu python -m benchmark.compile_check_linear_moe --workload solar_silo4_seq4096 \
        [--what round|init|reference] [--seq T] [--remat POLICY|none] [--kda xla|pallas]

``round`` (default) is the timed program; ``init`` the seeded weights' one
jitted call; ``reference`` the float32 reference's loss-and-gradient of the
check. ``--seq T`` compiles at another sequence length (the cut's "does not
fit" at 8192). Besides ``compile_check``'s lines ``round`` prints the Mosaic
kernels of the lowered round by name, the largest arrays of the compiled
program and the count of ops named ``*.remat*``. The delta rule and the grouped
matmul pick their kernels by backend, and the backend here is the CPU: this
script steers that, the program has no option for it.
"""

from __future__ import annotations

import argparse
import re
import sys

from benchmark import compile_check as cc  # pins JAX to the CPU before importing it
from benchmark.compile_check_hybrid import largest_arrays

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402


def _module_and_params(job, kda: str, seq: int):
    from p2pfl_tpu.models.transformer import CausalLM
    from p2pfl_tpu.ops import delta_rule, grouped_matmul
    from p2pfl_tpu.ops.autotune import default_flash_config
    from p2pfl_tpu.ops.flash_attention import flash_attention

    from benchmark.engines.spmd_lora_linear_moe import _transformer_config, seeded_params

    grouped_matmul._on_tpu = lambda: True  # what the chip's backend would answer
    delta_rule._on_tpu = lambda: kda == "pallas"
    cfg, args = job.cfg, job.cell["engine_args"]
    tcfg = _transformer_config(cfg, args)
    config = default_flash_config(seq, cfg["head_dim"], kind="TPU v5 lite")
    print(f"flash schedule for v5e at T={seq}, D={cfg['head_dim']}: {config}; delta rule {kda}, chunk {tcfg.kda_chunk}")
    module = CausalLM(tcfg, lambda q, k, v: flash_attention(q, k, v, causal=True, config=config, interpret=False))
    init = lambda key: seeded_params(tcfg, key, cfg["router_bias_std"])  # noqa: E731
    return module, init, jax.eval_shape(init, jax.random.PRNGKey(0))


def lower_round(job, mesh, kda: str, seq: int):
    from p2pfl_tpu.learning.learner import adam
    from p2pfl_tpu.learning.lora import split_lora
    from p2pfl_tpu.parallel.spmd_lora import spmd_lora_round

    tr, args = job.traffic, job.cell["engine_args"]
    module, _, params = _module_and_params(job, kda, seq)
    n = tr["n_nodes"]
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


def lower_init(job, mesh, kda: str, seq: int):
    _, init, _ = _module_and_params(job, kda, seq)
    repl = NamedSharding(mesh, P())
    return jax.jit(init, out_shardings=repl).lower(jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=repl))


def lower_reference(job, mesh, kda: str, seq: int):
    from p2pfl_tpu.learning.lora import split_lora

    from benchmark.engines.spmd_lora_linear_moe import _reference_grad

    _, _, params = _module_and_params(job, kda, seq)
    lora, base = split_lora(params)
    repl = NamedSharding(mesh, P())
    cfg = job.cfg
    tokens = jax.ShapeDtypeStruct((1, seq), jnp.int32, sharding=repl)
    forced = jax.ShapeDtypeStruct((1, cfg["num_hidden_layers"], seq, cfg["num_experts_per_tok"]), jnp.int32, sharding=repl)
    with jax.default_matmul_precision("highest"):
        return _reference_grad(job).lower(cc.spec(lora, repl), cc.spec(base, repl), tokens, tokens, forced)


WHAT = {"round": lower_round, "init": lower_init, "reference": lower_reference}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--what", choices=sorted(WHAT), default="round")
    parser.add_argument("--seq", type=int, help="sequence length (default: the traffic's, or the check's for reference)")
    parser.add_argument("--remat", help="a remat policy's name, or 'none'")
    parser.add_argument("--kda", choices=("pallas", "xla"), default="pallas")
    args = parser.parse_args()

    def with_overrides(job, mesh):
        if args.remat is not None:
            job.cell["engine_args"]["remat_policy"] = None if args.remat == "none" else args.remat
        seq = args.seq or (job.cell["check"]["seq_len"] if args.what == "reference" else job.traffic["seq_len"])
        print(f"{args.workload}: {args.what} at {seq} tokens, remat_policy {job.cell['engine_args']['remat_policy']}, delta rule {args.kda}")
        return WHAT[args.what](job, mesh, args.kda, seq)

    cc.LOWER["spmd_lora_linear_moe"] = with_overrides
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
    if args.what == "round":
        from benchmark.engines.spmd_lora_moe import kernels_in

        print(f"{args.workload}: Mosaic kernels in the lowered round: {kernels_in(texts['lowered'])}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
