"""Host-only compile of a ``spmd_lora_hybrid`` cell's round for a described
``v5e:2x2`` — ``compile_check.py`` dispatches through a fixed table of
engines, so the hybrid engine's lowering is registered here:

    JAX_PLATFORMS=cpu python -m benchmark.compile_check_hybrid --workload jamba_silo4_seq4096 [--scan xla|pallas]

Besides ``compile_check``'s lines it prints the largest arrays of the compiled
program and fails if one is as large as a whole sequence's state
(``T x inner x N`` elements): the selective scan has to stay chunked.
``--scan`` picks the scan's path (the program picks by backend, and the
backend here is the CPU): the test steers, the program has no option for it.
"""

from __future__ import annotations

import argparse
import math
import re
import sys
from functools import partial

from benchmark import compile_check as cc  # pins JAX to the CPU before importing it

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

_ARRAY = re.compile(r"\b(f32|bf16|s32|f16|u32|s8|u8|pred)\[([0-9,]+)\]")
_BYTES = {"f32": 4, "bf16": 2, "s32": 4, "f16": 2, "u32": 4, "s8": 1, "u8": 1, "pred": 1}


def lower_spmd_lora_hybrid(job, mesh, scan: str = "xla"):
    from p2pfl_tpu.learning.learner import adam
    from p2pfl_tpu.learning.lora import split_lora
    from p2pfl_tpu.models.transformer import CausalLM
    from p2pfl_tpu.ops import selective_scan
    from p2pfl_tpu.ops.autotune import default_flash_config
    from p2pfl_tpu.ops.flash_attention import flash_attention
    from p2pfl_tpu.parallel.spmd_lora import spmd_lora_round

    from benchmark.engines.spmd_lora_hybrid import _transformer_config

    selective_scan._on_tpu = lambda: scan == "pallas"  # what the chip's backend would answer
    cfg, tr, args = job.cfg, job.traffic, job.cell["engine_args"]
    tcfg = _transformer_config(cfg, args)
    seq, n = tr["seq_len"], tr["n_nodes"]
    if args["attn"] != "flash":
        raise SystemExit("compile_check_hybrid: the hybrid cells run flash attention")
    config = default_flash_config(seq, cfg["head_dim"], kind="TPU v5 lite")
    module = CausalLM(tcfg, partial(flash_attention, causal=True, config=config, interpret=False))
    params = jax.eval_shape(
        lambda k: CausalLM(tcfg, None).init(k, jnp.zeros((1, 16), jnp.int32))["params"], jax.random.PRNGKey(0)
    )
    lora, base = split_lora(params)
    shard, repl = NamedSharding(mesh, P("nodes")), NamedSharding(mesh, P())
    tx = adam(args["optimizer"]["learning_rate"])
    opt = jax.eval_shape(jax.vmap(tx.init), cc.stacked(lora, n, None))
    docs, steps, bs = tr["data"]["docs_per_node"], tr["local_steps"], tr["batch_size"]
    tokens = jax.ShapeDtypeStruct((n, docs, seq), jnp.int32, sharding=shard)
    return spmd_lora_round.lower(
        cc.stacked(lora, n, shard), cc.spec(opt, shard), cc.spec(base, repl), tokens, tokens,
        jax.ShapeDtypeStruct((n, 1, steps, bs), jnp.int32, sharding=shard),
        jax.ShapeDtypeStruct((n,), jnp.float32, sharding=shard),
        jax.ShapeDtypeStruct((n,), jnp.float32, sharding=shard),
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=repl),
        module=module, tx=tx, agg="fedavg", trim=0, out_sharding=shard,
        keep_opt_state=args["keep_opt_state"], node_chunk=args["node_chunk"],
    )


def largest_arrays(hlo: str, top: int = 12) -> list[tuple[int, str, str]]:
    """(bytes, shape, an instruction that produces it) of the largest distinct
    arrays that instructions OUTSIDE fusion bodies produce — a shape inside a
    fused computation is never a buffer."""
    seen: dict[str, tuple[int, str]] = {}
    in_fusion = False
    for line in hlo.splitlines():
        if line and not line.startswith(" "):  # a computation's header or its closing brace
            in_fusion = "fused_computation" in line or line.startswith("%fused") or line.startswith("fused")
            continue
        if in_fusion or " = " not in line:
            continue
        name, _, rest = line.strip().partition(" = ")
        for dtype, dims in _ARRAY.findall(rest.split("(", 1)[0]):
            size = _BYTES[dtype] * math.prod(int(d) for d in dims.split(","))
            shape = f"{dtype}[{dims}]"
            if size > seen.get(shape, (0, ""))[0] - 1:
                seen[shape] = (size, name.lstrip("%").replace("ROOT ", ""))
    return sorted(((b, s, n) for s, (b, n) in seen.items()), reverse=True)[:top]


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--scan", choices=("xla", "pallas"), default="pallas")
    args, rest = parser.parse_known_args()
    cc.LOWER["spmd_lora_hybrid"] = partial(lower_spmd_lora_hybrid, scan=args.scan)
    sys.argv = [sys.argv[0], "--workload", args.workload, *rest]

    # compile_check.main prints its lines and keeps nothing: take the compiled
    # text from the one place it passes through
    texts = []
    lowered_compile = jax.stages.Lowered.compile

    def compile_and_keep(self, *a, **kw):
        compiled = lowered_compile(self, *a, **kw)
        texts.append(compiled.as_text())
        return compiled

    jax.stages.Lowered.compile = compile_and_keep
    try:
        rc = cc.main()
    finally:
        jax.stages.Lowered.compile = lowered_compile
    bench = cc.run.load_json(cc.run.ROOT / "BENCHMARK.json")
    _, _, cfg, traffic = cc.run.resolve(bench, args.workload)
    inner, n_state = cfg["mamba_expand"] * cfg["hidden_size"], cfg["mamba_d_state"]
    whole = traffic["seq_len"] * inner * n_state
    biggest = largest_arrays(texts[-1])
    print(f"{args.workload}: scan={args.scan}; largest arrays in the compiled program: " + ", ".join(f"{s} {b / 1e9:.3f} GB ({n})" for b, s, n in biggest))

    def is_state(shape: str) -> bool:  # a state has the inner width and the state size among its dimensions
        dims = [int(d) for d in shape.split("[")[1].rstrip("]").split(",")]
        return inner in dims and n_state in dims and math.prod(dims) >= whole // 2

    states = [s for _, s, _ in biggest if is_state(s)]
    if states:
        print(f"{args.workload}: FAILED: an array as large as a whole sequence's state ({whole} elements): {states}")
        return 1
    print(f"{args.workload}: no array reaches a whole sequence's state ({whole} elements, {whole * 4 / 1e9:.2f} GB in float32)")
    return rc


if __name__ == "__main__":
    sys.exit(main())
