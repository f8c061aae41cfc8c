"""Faults planted in the PROGRAM's delta-rule layer, to show that the comparison
that decides ``correct`` sees them — the controls behind
``engines/spmd_lora_linear_moe.py``'s limits, as ``planted_faults_window.py``'s
are behind the window engine's. A fault replaces what ``models/transformer``
and ``ops/delta_rule`` look up by name when a model is TRACED, for as long as
the context lasts:

- ``beta_without_2``     β = σ(x W_b), in (0, 1): the negative eigenvalues gone;
- ``scalar_decay``       one decay a head (the mean of its channels' log-decays)
                         instead of one a key channel;
- ``no_boundary_decay``  the state carried across a chunk boundary is not decayed
                         by the chunk's cumulative decay;
- ``no_l2_norm``         q and k not L2-normalised;
- ``acausal_conv``       the q / k / v convolutions shifted one step: position t
                         reads t + 1;
- ``ungated_norm``       the output gate without its RMSNorm;
- ``bf16_state``         the state carried across chunks rounded to bfloat16: the
                         precision just below the configuration's float32 state,
                         and the reading ``KDA_OP_REL`` is set against.

On the chip, the cell's whole reference check under one of them::

    python -m benchmark.planted_faults_kda --workload solar_silo4_seq4096 --seed <n> --fault bf16_state

exits 0 if at least one comparison failed — the fault was seen — and 1 if
``correct`` would still have been true (``benchmark.planted_faults``'s ``main``,
with these faults). ``--fault none`` prints the sound readings the same way;
``--rehearsal`` runs at the CPU's tiny sizes.
"""

from __future__ import annotations

import contextlib
import sys
from functools import partial

FAULTS = ("beta_without_2", "scalar_decay", "no_boundary_decay", "no_l2_norm", "acausal_conv", "ungated_norm", "bf16_state")


@contextlib.contextmanager
def planted(fault: str):
    """``fault`` (one of :data:`FAULTS`, or ``"none"``) in every model TRACED
    inside the context."""
    import jax
    import jax.numpy as jnp

    from p2pfl_tpu.models import transformer as tf
    from p2pfl_tpu.ops import delta_rule as dr
    from p2pfl_tpu.ops import delta_rule_kernel as drk

    if fault != "none" and fault not in FAULTS:
        raise SystemExit(f"planted_faults_kda: no fault {fault!r} (has: none, {', '.join(FAULTS)})")
    sound = {
        (tf, "kda_beta"): tf.kda_beta, (tf, "_l2_heads"): tf._l2_heads, (tf, "causal_depthwise_conv"): tf.causal_depthwise_conv,
        (tf, "gated_head_norm"): tf.gated_head_norm, (dr, "gated_delta_rule"): dr.gated_delta_rule,
        (dr, "chunk_forward"): dr.chunk_forward, (drk, "chunk_forward"): drk.chunk_forward,
    }

    def half_beta(logits):
        return jax.nn.sigmoid(logits)

    def one_decay_a_head(q, k, v, log_alpha, beta, **kw):
        b, t, heads = beta.shape
        per_head = jnp.mean(log_alpha.reshape(b, t, heads, -1), axis=-1, keepdims=True)
        flat = jnp.broadcast_to(per_head, (b, t, heads, log_alpha.shape[-1] // heads)).reshape(log_alpha.shape)
        return sound[dr, "gated_delta_rule"](q, k, v, flat, beta, **kw)

    def undecayed_carry(st, q, k, v, g, beta, state_dtype=jnp.float32):
        _, p, _, lam, _, kb, _, u = dr._chunk_parts(q, k, v, g, beta, st)
        return dr._mm_t(q * lam, st) + dr._mm(p, u), st + dr._t_mm(u, kb)

    def unnormalised(x, heads, eps=1e-6):
        return x.astype(jnp.float32)

    def one_step_ahead(u, kernel, bias=None):
        ahead = jnp.pad(u[:, 1:], ((0, 0), (0, 1), (0, 0)))  # u[t + 1], zero past the end
        return sound[tf, "causal_depthwise_conv"](ahead, kernel, bias)

    def gate_only(o, gate, scale, eps):
        return o.astype(jnp.float32) * jax.nn.sigmoid(gate)

    bf16_carry = partial(sound[dr, "chunk_forward"], state_dtype=jnp.bfloat16)
    patches = {
        "none": {},
        "beta_without_2": {(tf, "kda_beta"): half_beta},
        "scalar_decay": {(dr, "gated_delta_rule"): one_decay_a_head},
        "no_boundary_decay": {(dr, "chunk_forward"): undecayed_carry, (drk, "chunk_forward"): undecayed_carry},
        "no_l2_norm": {(tf, "_l2_heads"): unnormalised},
        "acausal_conv": {(tf, "causal_depthwise_conv"): one_step_ahead},
        "ungated_norm": {(tf, "gated_head_norm"): gate_only},
        "bf16_state": {(dr, "chunk_forward"): bf16_carry, (drk, "chunk_forward"): bf16_carry},
    }[fault]
    for (module, name), fn in patches.items():
        setattr(module, name, fn)
    try:
        yield
    finally:
        for (module, name), fn in sound.items():
            setattr(module, name, fn)


def main() -> int:
    from benchmark import planted_faults

    planted_faults.FAULTS, planted_faults.planted = FAULTS, planted
    return planted_faults.main()


if __name__ == "__main__":
    sys.exit(main())
