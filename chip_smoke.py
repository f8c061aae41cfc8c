"""Chip smoke: the system's main paths, end to end, on the TPU it finds.

One process, no arguments, run from the repo root; it uses every chip
``jax.devices()`` shows. There is no flag, environment variable or handler
that lets it pass without a TPU: ``main()`` refuses any other platform
before doing work, and any failed check raises, so the exit code is
non-zero. Phases, each through the public entry points:

- **kernels** (untimed): flash-attention forward AND backward, compiled by
  Mosaic, against fp32 dense attention at the full-width model's shape and
  at the corners the shipped block selection can produce; the selective
  scan's forward and backward kernels against the op's plain-XLA path at
  4096 x 5120 channels x 16 states;
- **A** the ``bench.py`` path: 64-node MNIST ``SpmdFederation``, fused rounds;
- **B** the full-width model: TinyLlama-1.1B widths, all 22 layers, LoRA
  federation through ``SpmdLoraFederation`` with the compiled flash kernels;
- **C** the ``Node`` stack: two nodes over the in-memory transport, and the
  dispatch counters prove the fused round ran (not the staged fall-back).

Every phase prints wall seconds split into compile (JAX's own compile
events: trace + lowering + backend compile or cache retrieval) and run (the
rest), persistent-cache hits/misses, and ``peak_bytes_in_use`` per device.
The last stdout line is one JSON object naming the device.

The phase functions take their sizes as parameters so that
``tests/test_chip_bringup.py`` drives the same control flow at toy sizes on
the CPU mesh; only ``main()`` asserts the TPU.
"""

from __future__ import annotations

import json
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# the bench.py task (same difficulty, same seed): the sanity floor in phase A
# is only meaningful against the curve that task is known to produce
HARD_TASK = {"modes": 8, "noise": 0.7, "proto_scale": 0.5}

# TinyLlama-1.1B (TinyLlama/TinyLlama-1.1B-Chat-v1.0 config.json): every width
# as published, depth not cut. Weights are random, from a seed.
TINYLLAMA = dict(
    vocab_size=32000, dim=2048, n_heads=32, n_kv_heads=4, n_layers=22,
    ffn_hidden=5632,
)
SEQ_LEN = 1024

# (B, T, H, D) flash checks: the model's own shape — which is also the
# narrow-head q_span=2 corner — then the wide-head corners of the fused
# backward: (4096, 128) and (8192, 128), the largest T the ``auto`` rule
# sends to the fused kernel. Few heads there: VMEM use is per (b, h) program.
FLASH_SHAPES = ((1, SEQ_LEN, 32, 64), (1, 4096, 2, 128), (1, 8192, 2, 128))
# max |flash − ref| / max |ref| against fp32 dense attention on the same
# bf16-rounded inputs. bf16 carries 8 mantissa bits (2^-8 ≈ 0.4% per rounded
# operand); the kernels round P and dS to bf16 before the MXU.
FLASH_TOL_FWD = 2e-2
FLASH_TOL_BWD = 4e-2
# (B, T, channels, states) of the selective-scan check: one sequence at the
# widths of the benchmark's hybrid configuration (4096 x 5120 x 16)
SCAN_SHAPES = ((1, 4096, 5120, 16),)
# |kernels − XLA path| / |XLA path| (L2) of the output and each gradient: both
# compute in float32 and differ in summation order and in the bf16 rounding of
# outputs (read on the chip at PR 27: 1e-4 to 3e-4)
SCAN_TOL = 2e-3


def say(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


def device_memory(key: str) -> list:
    """``memory_stats()[key]`` per device (None where the backend reports none)."""
    return [(d.memory_stats() or {}).get(key) for d in jax.devices()]


def run_phase(name: str, fn, **kwargs) -> dict:
    """Run one phase and split its wall time into compile and run without a
    second, warm call: compile is the union of the flight recorder's
    ``"compile"`` spans inside the phase (``compile_cache``'s bridge, installed
    by ``configure_compile_cache()``); a jit traced inside another's trace
    reports both spans, so the per-kind sums keep the nesting. ``backend`` is
    XLA/Mosaic compilation or, on a persistent-cache hit, retrieval;
    ``cache_misses`` are entries written."""
    from p2pfl_tpu.management.telemetry import telemetry

    say(f"== phase {name} ==")
    t0_ns = time.monotonic_ns()
    out = fn(**kwargs)
    t1_ns = time.monotonic_ns()
    split = telemetry.startup_report(since_ns=t0_ns, until_ns=t1_ns)["compile"]
    wall, compile_s = (t1_ns - t0_ns) / 1e9, split.pop("all_s")
    out.update(
        wall_s=round(wall, 2),
        compile_s=round(compile_s, 2),
        run_s=round(wall - compile_s, 2),
        # short_*: the dropped traces are counted since the process started, not within a phase
        compile_split={k: round(v, 2) for k, v in split.items() if not k.startswith("short_")},
        peak_bytes_in_use=device_memory("peak_bytes_in_use"),
    )
    say(f"phase {name}: {json.dumps(out)}")
    return out


# ---- kernels: compiled flash fwd+bwd vs fp32 dense -------------------------


def check_flash(b: int, t: int, h: int, d: int, *, interpret: bool) -> dict:
    """Flash forward and backward at ``[b, t, h, d]`` bf16 under the config
    the shipped selection resolves, against ``causal_attention`` in fp32
    (highest matmul precision) on the same bf16-rounded inputs."""
    from p2pfl_tpu.ops.attention import causal_attention
    from p2pfl_tpu.ops.autotune import flash_config_source
    from p2pfl_tpu.ops.flash_attention import flash_attention

    cfg, source = flash_config_source(t, d, dtype=jnp.bfloat16)
    keys = jax.random.split(jax.random.PRNGKey(t + d), 4)
    q, k, v = (jax.random.normal(s, (b, t, h, d)).astype(jnp.bfloat16) for s in keys[:3])
    w = jax.random.normal(keys[3], (b, t, h, d), jnp.float32)

    def with_grads(attend):
        def loss(q, k, v):
            out = attend(q, k, v).astype(jnp.float32)
            return jnp.sum(out * w), out  # random cotangent: dO is data

        return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)

    flash = jax.jit(with_grads(partial(flash_attention, causal=True, config=cfg, interpret=interpret)))
    lowered = flash.lower(q, k, v)
    mosaic_calls = lowered.as_text().count("tpu_custom_call")
    # forward + backward kernels; zero only under the interpreter
    check((mosaic_calls >= 2) != interpret, f"{mosaic_calls} Mosaic calls, interpret={interpret}")
    (_, out), grads = lowered.compile()(q, k, v)

    with jax.default_matmul_precision("highest"):
        (_, ref_out), ref_grads = jax.jit(with_grads(causal_attention))(
            *(x.astype(jnp.float32) for x in (q, k, v))
        )

    def rel_err(got, want) -> float:
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        check(bool(np.isfinite(got).all()), "non-finite kernel output")
        return float(np.abs(got - want).max() / np.abs(want).max())

    errs = {"out": rel_err(out, ref_out)}
    errs.update({n: rel_err(g, r) for n, g, r in zip(("dq", "dk", "dv"), grads, ref_grads)})
    res = {
        "shape": [b, t, h, d],
        "config": {"block_q": cfg.block_q, "block_k": cfg.block_k, "q_span": cfg.q_span},
        "config_source": source,
        # forward + one fused backward kernel, or + the split dq/dkv pair
        "mosaic_calls": mosaic_calls,
        "bwd": {0: "interpreted", 2: "fused", 3: "split"}[mosaic_calls],
        "rel_err": {n: float(f"{e:.3g}") for n, e in errs.items()},
    }
    say(f"flash check: {json.dumps(res)}")
    check(errs["out"] <= FLASH_TOL_FWD, f"flash fwd error {errs['out']:.3g} > {FLASH_TOL_FWD}")
    worst = max(errs["dq"], errs["dk"], errs["dv"])
    check(worst <= FLASH_TOL_BWD, f"flash bwd error {worst:.3g} > {FLASH_TOL_BWD}")
    return res


def check_scan(b: int, t: int, dm: int, n: int, *, interpret: bool) -> dict:
    """The selective scan's Pallas kernels (forward and backward) at
    ``[b, t, dm]`` channels and ``n`` states against the op's plain-XLA path on
    the same inputs — output and all seven gradients — and the XLA path
    against the recurrence one token at a time on a 512-token prefix."""
    from p2pfl_tpu.ops import selective_scan as ss

    keys = jax.random.split(jax.random.PRNGKey(t + dm), 7)
    bf16 = lambda k, shape: jax.random.normal(k, shape).astype(jnp.bfloat16)  # noqa: E731
    u, z, w = bf16(keys[0], (b, t, dm)), bf16(keys[1], (b, t, dm)), bf16(keys[2], (b, t, dm))
    # step sizes as a seeded Mamba layer has them: log-uniform in [1e-3, 1e-1], jittered per token
    base = jnp.exp(jax.random.uniform(keys[3], (dm,), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    delta = jax.nn.softplus(jnp.log(jnp.expm1(base)) + 0.5 * jax.random.normal(keys[4], (b, t, dm)))
    a = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), (dm, n))
    args = (u, delta, a, bf16(keys[5], (b, t, n)), bf16(keys[6], (b, t, n)), jnp.ones((dm,)), z)

    def with_grads(impl):
        def loss(*xs):
            out = ss.selective_scan(*xs, impl=impl).astype(jnp.float32)
            return jnp.sum(out * w), out

        return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(7)), has_aux=True))

    kernels = with_grads("pallas")
    mosaic_calls = kernels.lower(*args).as_text().count("tpu_custom_call")
    check((mosaic_calls == 2) != interpret, f"{mosaic_calls} Mosaic calls, interpret={interpret}")
    (_, out), grads = kernels(*args)
    (_, ref_out), ref_grads = with_grads("xla")(*args)

    def rel_err(got, want) -> float:
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        check(bool(np.isfinite(got).all()), "non-finite scan output")
        return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))

    errs = {"y": rel_err(out, ref_out)}
    errs.update({f"d{name}": rel_err(g, r) for name, g, r in zip(("u", "delta", "A", "B", "C", "D", "z"), grads, ref_grads)})
    prefix = tuple(x[:, :512] if x.ndim == 3 else x for x in args)
    errs["xla_vs_loop"] = rel_err(ss.selective_scan(*prefix, impl="xla"), jax.jit(ss.selective_scan_reference)(*prefix))
    res = {"shape": [b, t, dm, n], "mosaic_calls": mosaic_calls, "rel_err": {k: float(f"{e:.3g}") for k, e in errs.items()}}
    say(f"scan check: {json.dumps(res)}")
    worst = max(errs.values())
    check(worst <= SCAN_TOL, f"selective scan error {worst:.3g} > {SCAN_TOL}")
    return res


def phase_kernels(shapes, *, interpret: bool, scan_shapes=()) -> dict:
    return {
        "checks": [check_flash(*shape, interpret=interpret) for shape in shapes],
        "scan_checks": [check_scan(*shape, interpret=interpret) for shape in scan_shapes],
    }


# ---- phase A: the bench.py path --------------------------------------------


def phase_spmd(data, *, n_nodes: int, batch_size: int, chunk: int, min_acc: float) -> dict:
    """``SpmdFederation`` as ``bench.py`` builds it: two fused chunks with the
    on-device accuracy curve, then ``evaluate()``."""
    from p2pfl_tpu.models import mlp
    from p2pfl_tpu.parallel import SpmdFederation

    fed = SpmdFederation.from_dataset(
        mlp(), data, n_nodes=n_nodes, batch_size=batch_size, vote=False, seed=3,
        keep_opt_state=True,
    )
    mesh_devices = set(fed.mesh.devices.flat)
    check(mesh_devices == set(jax.devices()), f"mesh {dict(fed.mesh.shape)} strands devices")
    curve, losses = [], []
    for _ in range(2):
        for entry in fed.run_fused(chunk, epochs=1, eval=True):
            curve.append(float(entry["test_acc"]))
            losses.append(float(entry["train_loss"]))
    final = fed.evaluate()
    check(bool(np.isfinite(curve + losses + [final["test_loss"]]).all()), "non-finite metrics")
    for leaf in jax.tree.leaves(fed.params):
        check(leaf.sharding.device_set == mesh_devices, "stacked params miss a mesh device")
        check(bool(jnp.isfinite(leaf).all()), "non-finite params")
    check(curve[-1] >= min_acc, f"accuracy {curve[-1]:.4f} after {len(curve)} rounds < {min_acc}")
    return {
        "mesh": dict(fed.mesh.shape),
        "nodes_per_device": n_nodes // len(mesh_devices),
        "accuracy_curve": [round(a, 6) for a in curve],
        "final_test_acc": round(final["test_acc"], 6),
        "bytes_in_use": device_memory("bytes_in_use"),
    }


# ---- phase B: the full-width model with compiled kernels -------------------


def phase_lora(
    widths: dict, *, seq_len: int, n_nodes: int, node_chunk: int,
    steps_per_round: int, n_test: int, interpret: bool,
) -> dict:
    """LoRA federation over the TinyLlama recipe exactly as
    ``bench_suite.config5_nameplate_1b`` configures it (rank 8 incl. MLP,
    scanned layers, ``mlp_qkv`` selective remat), random-initialised base."""
    from p2pfl_tpu.learning.dataset import FederatedDataset
    from p2pfl_tpu.models.transformer import TransformerConfig, tiny_transformer
    from p2pfl_tpu.parallel import SpmdLoraFederation

    cfg = TransformerConfig(
        **widths, lora_rank=8, lora_mlp=True, remat=True, scan_layers=True,
        remat_policy="mlp_qkv",
    )
    model = tiny_transformer(seq_len=seq_len, cfg=cfg, attn="flash")
    attend = model.module.attn_fn
    check(attend.keywords["interpret"] == interpret, f"attention built with {attend.keywords}")
    n_params = sum(x.size for x in jax.tree.leaves(model.params))
    say(
        f"model: {n_params / 1e9:.3f}B params, vocab {cfg.vocab_size}, {cfg.n_layers} layers, "
        f"seq {seq_len}, flash config {attend.keywords['config']}"
    )
    data = FederatedDataset.synthetic_lm(
        vocab_size=cfg.vocab_size, seq_len=seq_len,
        n_train=n_nodes * steps_per_round, n_test=n_test,
    )
    fed = SpmdLoraFederation.from_dataset(
        model, data, n_nodes=n_nodes, batch_size=1, vote=False, seed=3,
        node_chunk=node_chunk,
    )
    losses = [float(fed.run_round(epochs=1)["train_loss"]) for _ in range(3)]
    final = fed.evaluate()
    mosaic_calls = fed.lower_round(epochs=1).as_text().count("tpu_custom_call")
    check(bool(np.isfinite(losses + [final["test_loss"]]).all()), f"non-finite loss {losses}")
    check(losses[2] < losses[0], f"train loss did not fall over 3 rounds: {losses}")
    # the flag alone is not trusted: the program the round dispatches must
    # carry the Mosaic kernels (and must not, under the interpreter)
    check((mosaic_calls > 0) != interpret, f"{mosaic_calls} Mosaic calls, interpret={interpret}")
    leaf = jax.tree.leaves(fed.params)[0]
    return {
        "params_b": round(n_params / 1e9, 3),
        "vocab": cfg.vocab_size,
        "train_loss": [round(x, 4) for x in losses],
        "test_loss": round(final["test_loss"], 4),
        "mosaic_calls_in_round": mosaic_calls,
        "nodes_per_device": {s.device.id: s.data.shape[0] for s in leaf.addressable_shards},
    }


# ---- phase C: the Node stack ------------------------------------------------


def phase_nodes(data, *, rounds: int, batch_size: int, timeout: float) -> dict:
    """Two ``Node`` objects over the in-memory transport, sync round FSM."""
    from p2pfl_tpu.learning.learner import JaxLearner
    from p2pfl_tpu.management.logger import logger
    from p2pfl_tpu.management.profiling import (
        get_dispatch_counts,
        reset_dispatch_counts,
    )
    from p2pfl_tpu.models import mlp
    from p2pfl_tpu.node import Node
    from p2pfl_tpu.settings import set_low_latency_settings
    from p2pfl_tpu.utils import wait_convergence, wait_to_finish

    set_low_latency_settings()
    reset_dispatch_counts()
    logger.reset_comm_metrics()
    nodes = [
        Node(learner=JaxLearner(mlp(seed=i), data.partition(i, 2), batch_size=batch_size))
        for i in range(2)
    ]
    try:
        for node in nodes:
            node.start()
        nodes[0].connect(nodes[1].addr)
        wait_convergence(nodes, 1, only_direct=True)
        nodes[0].set_start_learning(rounds=rounds, epochs=1)
        wait_to_finish(nodes, timeout=timeout)
        counts = get_dispatch_counts()
        # a failed stage ends learning without ending the node, so "idle
        # again" is not "done": the experiment-end evaluation is logged at
        # round == rounds only by a node that finished every round
        logs = logger.get_global_logs()
        for node in nodes:
            finished = any(
                rnd == rounds
                for per_node in logs.values()
                for rnd, _ in per_node.get(node.addr, {}).get("test_acc", [])
            )
            check(finished, f"{node.addr} did not complete {rounds} rounds")
        pa, pb = (jax.tree.leaves(n.learner.get_parameters()) for n in nodes)
        diff = max(float(jnp.abs(a - b).max()) for a, b in zip(pa, pb))
    finally:
        for node in nodes:
            node.stop()
    # membership under the low-latency clocks (1.5 s heartbeat timeout): a
    # peer evicted mid-run trains on alone and the params then differ, so
    # name that cause first. ``local_pause`` counts the times a heartbeater
    # found its own process frozen and discounted the silence instead
    comm = [logger.get_comm_metrics(node.addr) for node in nodes]
    evicted = sum(int(m.get("neighbor_evicted", 0)) for m in comm)
    pauses = sum(int(m.get("local_pause", 0)) for m in comm)
    check(evicted == 0, f"{evicted} live peer(s) evicted mid-run (local pauses seen: {pauses})")
    check(diff <= 1e-5, f"final params differ across nodes by {diff:.3g}")
    # the fused round dispatched on the device every round; a compile
    # refusal would have been logged and the staged path (train_epoch)
    # taken instead (learner.fused_round keeps that recovery for fleets)
    check(counts.get("fused_round", 0) == 2 * rounds, f"dispatch counts {counts}")
    check(counts.get("train_epoch", 0) == 0, f"staged path ran: {counts}")
    return {"dispatch_counts": counts, "max_param_diff": diff, "local_pauses": pauses}


# ---- main -------------------------------------------------------------------


def main() -> int:
    from p2pfl_tpu.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r}", file=sys.stderr)
        return 1
    n_dev = len(jax.devices())

    import jaxlib
    from importlib.metadata import version

    from p2pfl_tpu import native, settings
    from p2pfl_tpu.learning.dataset import FederatedDataset
    from p2pfl_tpu.management.profiling import peak_flops
    from p2pfl_tpu.models.transformer import pick_attention
    from p2pfl_tpu.ops import autotune

    say(
        f"platform={dev.platform} device_kind={dev.device_kind!r} devices={n_dev} "
        f"jax={jax.__version__} jaxlib={jaxlib.__version__} libtpu={version('libtpu')}"
    )
    say(f"compile cache: {cache_dir}")
    say(f"native codec: NATIVE={native.NATIVE}")
    # both raise on a TPU kind they do not know — no guessed peak, no guessed blocks
    say(f"peak bf16 FLOP/s: {peak_flops(dev):.3g}")
    say(f"flash defaults for (T={SEQ_LEN}, D=64): {autotune.default_flash_config(SEQ_LEN, 64)}")
    say(
        "selectors on this backend: "
        f"pick_attention({SEQ_LEN})={pick_attention(SEQ_LEN)} "
        f"wire_compression_device={settings.wire_compression_device()} "
        f"telemetry_jax_annotations={settings.telemetry_jax_annotations()} "
        f"ici_backend={settings.ici_backend()} (planes not exercised: WEIGHTS_PLANE="
        f"{settings.Settings.WEIGHTS_PLANE!r})"
    )

    t0 = time.monotonic()
    report = {}
    report["kernels"] = run_phase(
        "kernels", phase_kernels, shapes=FLASH_SHAPES, interpret=False, scan_shapes=SCAN_SHAPES
    )
    sources = {c["config_source"] for c in report["kernels"]["checks"]}
    check(sources == {"defaults"}, f"flash config from outside the checkout: {sources}")
    report["A"] = run_phase(
        "A", phase_spmd, data=FederatedDataset.mnist(**HARD_TASK),
        n_nodes=64, batch_size=64, chunk=5, min_acc=0.9,
    )
    report["B"] = run_phase(
        "B", phase_lora, widths=TINYLLAMA, seq_len=SEQ_LEN,
        n_nodes=max(4, n_dev), node_chunk=4, steps_per_round=4, n_test=8,
        interpret=False,
    )
    report["C"] = run_phase(
        "C", phase_nodes,
        data=FederatedDataset.synthetic_mnist(n_train=4096, n_test=1024),
        rounds=2, batch_size=64, timeout=300.0,
    )
    say(f"total: {time.monotonic() - t0:.1f}s")
    say(f"chip_smoke report: {json.dumps(report)}")
    print(
        json.dumps(
            {
                "ok": True,
                "device": {"platform": dev.platform, "kind": dev.device_kind, "count": n_dev},
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
