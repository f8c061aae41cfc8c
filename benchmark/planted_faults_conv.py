"""Faults planted in the PROGRAM's gated short convolution, to show that the
comparison that decides ``correct`` sees them — the controls behind
``engines/spmd_lora_conv_moe.py``'s ``CONV_SCOPE_REL`` and the shared gradient
limits, as ``planted_faults.py``'s are behind the routing limits. A fault
replaces ``models/transformer.gated_short_conv`` — what ``ShortConvMixer`` runs
between its two projections — for as long as the context lasts:

- ``bf16_conv``     both gate products, every tap's product and every partial
                    sum rounded to bfloat16 (the configuration states float32
                    between the projections). ``lax.reduce_precision`` after
                    each operation: XLA may keep excess precision through a
                    chain of plain bfloat16 operations, which would make the
                    fault no fault;
- ``dropped_tap``   the oldest tap weighs nothing (a two-tap convolution).

On the chip, the cell's whole reference check under one of them::

    python -m benchmark.planted_faults_conv --workload lfm2_silo4_seq4096 --seed <n> --fault bf16_conv

exits 0 if at least one comparison failed — the fault was seen — and 1 if
``correct`` would still have been true (``benchmark.planted_faults``'s ``main``,
with these faults). ``--fault none`` prints the sound readings the same way.
"""

from __future__ import annotations

import contextlib
import sys

FAULTS = ("bf16_conv", "dropped_tap")


@contextlib.contextmanager
def planted(fault: str):
    """``fault`` (one of :data:`FAULTS`, or ``"none"``) in every short-convolution
    mixer TRACED inside the context."""
    import jax
    import jax.numpy as jnp

    from p2pfl_tpu.models import transformer as tf

    if fault != "none" and fault not in FAULTS:
        raise SystemExit(f"planted_faults_conv: no fault {fault!r} (has: none, {', '.join(FAULTS)})")
    sound = tf.gated_short_conv

    def bf16(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def in_bfloat16(bcu, kernel, dtype):
        b, c, u = (part.astype(jnp.float32) for part in jnp.split(bcu, 3, axis=-1))
        taps, t = kernel.shape[0], bcu.shape[1]
        padded = jnp.pad(bf16(b * u), ((0, 0), (taps - 1, 0), (0, 0)))
        out = None
        for k in range(taps):
            term = bf16(bf16(kernel[k].astype(jnp.float32)) * padded[:, k:k + t])
            out = term if out is None else bf16(out + term)
        return bf16(c * out).astype(dtype)

    def less_one_tap(bcu, kernel, dtype):
        return sound(bcu, kernel.at[0].set(0.0), dtype)

    patches = {"none": sound, "bf16_conv": in_bfloat16, "dropped_tap": less_one_tap}
    tf.gated_short_conv = patches[fault]
    try:
        yield
    finally:
        tf.gated_short_conv = sound


def main() -> int:
    from benchmark import planted_faults

    planted_faults.FAULTS, planted_faults.planted = FAULTS, planted
    return planted_faults.main()


if __name__ == "__main__":
    sys.exit(main())
