"""Faults planted in the PROGRAM's expert layer, to show that the comparison
that decides ``correct`` sees them — the controls behind the tolerances of
``engines/spmd_lora_moe.py``. A fault replaces one of the routing functions
``models/transformer.ExpertFFN`` calls, for as long as the context lasts:

- ``bf16_router``            scores from bfloat16 operands (the router must be float32);
- ``dropped_assignment``     ONE of the ``T x k`` assignments weighs nothing (a dropless layer drops none);
- ``weigh_with_bias``        weights from ``s + bias`` (the bias chooses only);
- ``choose_without_bias``    the top-k of ``s`` alone.

``tests/test_glm_moe.py`` drives ``check_expert_layer`` under each on the CPU.
On the chip, the cell's whole reference check under one of them::

    python -m benchmark.planted_faults --workload glm_silo4_seq4096 --seed <n> --fault weigh_with_bias

builds the cell as ``benchmark.run`` does (the fault is planted AFTER the
weights are made), runs the engine's ``check``, prints
every comparison beside its limit, and exits 0 if at least one failed — the
fault was seen — and 1 if ``correct`` would still have been true. ``--fault
none`` prints the sound readings the same way (exit 0 if all hold).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

FAULTS = ("bf16_router", "dropped_assignment", "weigh_with_bias", "choose_without_bias")


@contextlib.contextmanager
def planted(fault: str):
    """``fault`` (one of :data:`FAULTS`, or ``"none"``) in every expert layer
    TRACED inside the context."""
    import jax
    import jax.numpy as jnp

    from p2pfl_tpu.models import transformer as tf

    if fault != "none" and fault not in FAULTS:
        raise SystemExit(f"planted_faults: no fault {fault!r} (has: none, {', '.join(FAULTS)})")
    sound = {name: getattr(tf, name) for name in ("router_scores", "choose_experts", "routing_weights")}
    seen_bias = []

    def bf16_scores(x, router):
        logits = jnp.dot(x.astype(jnp.bfloat16), router.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
        return jax.nn.sigmoid(logits)

    def weights_less_one(s, chosen, scale):
        weights = sound["routing_weights"](s, chosen, scale)
        return weights.at[weights.shape[0] // 2, -1].set(0.0)

    def choose_and_note_bias(s, bias, top_k):
        seen_bias.append(bias)
        return sound["choose_experts"](s, bias, top_k)

    def weights_with_bias(s, chosen, scale):
        return sound["routing_weights"](s + seen_bias[-1].astype(s.dtype), chosen, scale)

    patches = {
        "none": {},
        "bf16_router": {"router_scores": bf16_scores},
        "dropped_assignment": {"routing_weights": weights_less_one},
        "weigh_with_bias": {"choose_experts": choose_and_note_bias, "routing_weights": weights_with_bias},
        "choose_without_bias": {"choose_experts": lambda s, bias, top_k: sound["choose_experts"](s, 0.0 * bias, top_k)},
    }[fault]
    for name, fn in patches.items():
        setattr(tf, name, fn)
    try:
        yield
    finally:
        for name, fn in sound.items():
            setattr(tf, name, fn)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--fault", required=True, choices=("none", *FAULTS))
    parser.add_argument("--rehearsal", action="store_true", help="the CPU rehearsal's tiny sizes (control flow only)")
    args = parser.parse_args()

    from benchmark import run

    _, cell, cfg, traffic = run.resolve(run.load_json(run.ROOT / "BENCHMARK.json"), args.workload, args.rehearsal)
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    if args.rehearsal:  # as benchmark.rehearse: CPU programs have no business in the checkout's cache
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        from p2pfl_tpu.compile_cache import configure_compile_cache

        configure_compile_cache()

    from benchmark import checks as ck
    from benchmark import engines

    job = run.Job(args.workload, cell, cfg, traffic, args.seed, False, devices=jax.devices(), checks=ck.Checks())
    engine = engines.load(cell["engine"])
    state = engine.build(job)
    with planted(args.fault):
        engine.check(job, state)
    for row in job.checks.rows:
        run.say(f"check: {json.dumps(row)}")
    failed = [row["check"] for row in job.checks.rows if not row["ok"]]
    run.say(f"planted fault {args.fault!r}: {len(failed)} of {len(job.checks.rows)} comparisons failed: {failed}")
    return int(bool(failed)) if args.fault == "none" else int(not failed)


if __name__ == "__main__":
    sys.exit(main())
