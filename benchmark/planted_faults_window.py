"""Faults planted in the PROGRAM's sliding-window / gated / sandwich-normed
block, its held share and its head, to show that the comparison that decides
``correct`` sees them — the controls behind ``engines/spmd_lora_window_moe.py``'s
limits, as ``planted_faults.py``'s are behind the routing limits and
``planted_faults_conv.py``'s behind the convolution's. A fault replaces what
``models/transformer`` looks up by name when a model is TRACED, for as long as
the context lasts:

- ``window_off_by_one``   a sliding layer sees 2049 keys (``window + 1``: the
                          convention "itself included" missed by one);
- ``no_gate``             the attention output is not multiplied by its gate;
- ``rotated_full``        a full-attention layer is rotated like a sliding one;
- ``no_post_norm``        the sandwich's second norms are left out;
- ``held_normalised``     the routing weights normalised over the HELD chosen
                          experts only (they are normalised over all eight);
- ``tied_head``           the embedding used as the output head (it is untied);
- ``float8_attention``    q, k and v rounded to float8 (e4m3) before attention:
                          the nearest precision BELOW the configuration's
                          bfloat16 — no fault of the program's logic, and the
                          lower reading that ``WINDOW_EDGE_REL`` is set against.

On the chip, the cell's whole reference check under one of them::

    python -m benchmark.planted_faults_window --workload trinity_silo4_seq8192 --seed <n> --fault no_gate

exits 0 if at least one comparison failed — the fault was seen — and 1 if
``correct`` would still have been true (``benchmark.planted_faults``'s ``main``,
with these faults). ``--fault none`` prints the sound readings the same way;
``--rehearsal`` runs at the CPU's tiny sizes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import sys

FAULTS = ("window_off_by_one", "no_gate", "rotated_full", "no_post_norm", "held_normalised", "tied_head", "float8_attention")


@contextlib.contextmanager
def planted(fault: str):
    """``fault`` (one of :data:`FAULTS`, or ``"none"``) in every model TRACED
    inside the context."""
    import jax.numpy as jnp

    from p2pfl_tpu.models import transformer as tf

    if fault != "none" and fault not in FAULTS:
        raise SystemExit(f"planted_faults_window: no fault {fault!r} (has: none, {', '.join(FAULTS)})")
    names = ("_attend_fn", "Attention", "RMSNorm", "ExpertFFN", "routing_weights")
    sound = {name: getattr(tf, name) for name in names}
    sound_call = tf.CausalLM.__call__
    held = []  # (first expert, experts held) of the expert layer being traced

    def one_key_wider(cfg, attn_fn):
        attend = sound["_attend_fn"](cfg, attn_fn)
        return lambda q, k, v, window=None: attend(q, k, v) if window is None else attend(q, k, v, window=window + 1)

    def from_float8(cfg, attn_fn):
        import jax

        attend = sound["_attend_fn"](cfg, attn_fn)
        # reduce_precision, not a cast there and back: XLA removes such a pair on a TPU (excess
        # precision is allowed), and the first chip reading of this fault was the sound one
        low = lambda a: jax.lax.reduce_precision(a, exponent_bits=4, mantissa_bits=3)  # noqa: E731
        return lambda q, k, v, **kw: attend(low(q), low(k), low(v), **kw)

    def ungated(cfg, *args, **kw):
        return sound["Attention"](dataclasses.replace(cfg, attn_gate=False), *args, **kw)

    def no_second_norm(dtype=jnp.bfloat16, eps=1e-6, name=None, **kw):
        if name is not None and name.endswith("post_norm"):
            return lambda y: y  # the sublayer's output goes to the residual as it is
        return sound["RMSNorm"](dtype, eps, name=name, **kw)

    def rotating(cfg, attn_fn=None, mixer="attention", **kw):
        # the config-wide rule: rotated at rope_theta, every earlier key seen
        return sound["Attention"](cfg, attn_fn, "attention" if mixer == "full" else mixer, **kw)

    def noting_share(cfg, **kw):
        held.append((cfg.first_expert, cfg.held_experts))
        return sound["ExpertFFN"](cfg, **kw)

    def over_the_held(s, chosen, scale):
        first, count = held[-1]
        mine = (chosen >= first) & (chosen < first + count)
        picked = jnp.take_along_axis(s, chosen, axis=-1)
        return picked / (jnp.sum(jnp.where(mine, picked, 0.0), axis=-1, keepdims=True) + 1e-20) * scale

    def tied(self, tokens, head=True):
        out = sound_call(self, tokens, head)
        if head:
            raise SystemExit("planted_faults_window: tied_head is planted in the training path (head=False)")
        return out[0], self.get_variable("params", "embed")

    patches = {
        "none": {},
        "window_off_by_one": {"_attend_fn": one_key_wider},
        "no_gate": {"Attention": ungated},
        "rotated_full": {"Attention": rotating},
        "no_post_norm": {"RMSNorm": no_second_norm},
        "held_normalised": {"ExpertFFN": noting_share, "routing_weights": over_the_held},
        "tied_head": {},
        "float8_attention": {"_attend_fn": from_float8},
    }[fault]
    for name, fn in patches.items():
        setattr(tf, name, fn)
    if fault == "tied_head":
        tf.CausalLM.__call__ = tied
    try:
        yield
    finally:
        for name, fn in sound.items():
            setattr(tf, name, fn)
        tf.CausalLM.__call__ = sound_call


def main() -> int:
    from benchmark import planted_faults

    planted_faults.FAULTS, planted_faults.planted = FAULTS, planted
    return planted_faults.main()


if __name__ == "__main__":
    sys.exit(main())
