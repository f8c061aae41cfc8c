"""The comparison that decides ``correct``: tolerances, each with its reason,
and the tree arithmetic they are applied with. A failed check never raises; it
is collected with its numbers, printed on an earlier line, and makes the last
line say ``"correct": false``.

Why these tolerances (measured at PR 22 on a TPU v5 lite, then set about three
times wider; PERF.md section 6 has the readings):

- ``LOSS_REL``: the system computes matrix products in bfloat16 (8 mantissa
  bits, 2^-8 = 0.4% per rounded operand) with float32 accumulation, the
  reference in float32 at ``highest`` precision. A mean cross-entropy over
  >= 1000 targets averages the rounding; float8 compute (2-3 mantissa bits)
  would miss it by more than ten times.
- ``GRAD_COS`` / ``GRAD_REL``: cosine and relative L2 distance of the whole
  trainable-gradient tree against the reference's. Rounding noise of bf16
  through 8 transformer blocks (or 50 convolutions) leaves the direction
  intact; a wrong mask, a dropped layer, a missing 1/sqrt(d) or float8 compute
  does not.
- ``ROUND_COS``: cosine between the system's and the reference's one-round
  parameter change (aggregate minus start). Adam's first steps are
  g / (|g| + eps): every coordinate moves by about the learning rate in the
  direction of its gradient's SIGN, so the few coordinates whose gradient is
  smaller than the bf16 noise flip, and a relative distance would be large for
  a correct system. The cosine counts those flips.
- ``NODE_EQ``: after aggregation every node holds the same model; FedAvg
  broadcasts one array, so anything above float32 rounding of the fold is a
  protocol fault (the Node stack's own test suite asserts 1e-5 within a run).
"""

from __future__ import annotations

import math

import jax
import numpy as np

LOSS_REL = 5e-3
GRAD_COS = 0.995
GRAD_REL = 0.10
ROUND_COS = 0.90
NODE_EQ = 1e-5


def _flat(tree) -> np.ndarray:
    leaves = jax.tree.leaves(tree)
    return np.concatenate([np.asarray(leaf, np.float64).ravel() for leaf in leaves])


def cosine(a, b) -> float:
    fa, fb = _flat(a), _flat(b)
    return float(fa @ fb / (np.linalg.norm(fa) * np.linalg.norm(fb) + 1e-300))


def rel_l2(got, want) -> float:
    fg, fw = _flat(got), _flat(want)
    return float(np.linalg.norm(fg - fw) / (np.linalg.norm(fw) + 1e-300))


def tree_sub(a, b):
    return jax.tree.map(lambda x, y: np.asarray(x, np.float64) - np.asarray(y, np.float64), a, b)


class Checks:
    """Collects named comparisons; ``ok`` is the conjunction."""

    def __init__(self) -> None:
        self.rows: list[dict] = []

    def add(self, name: str, ok: bool, **numbers) -> None:
        self.rows.append({"check": name, "ok": bool(ok), **numbers})

    def close(self, name: str, got: float, want: float, rel: float) -> None:
        err = abs(got - want) / max(abs(want), 1e-30)
        self.add(name, math.isfinite(got) and err <= rel, got=got, want=want, rel_err=err, tol=rel)

    def at_least(self, name: str, got: float, floor: float) -> None:
        self.add(name, math.isfinite(got) and got >= floor, got=got, floor=floor)

    def at_most(self, name: str, got: float, ceiling: float) -> None:
        self.add(name, math.isfinite(got) and got <= ceiling, got=got, ceiling=ceiling)

    def gradients(self, name: str, got, want) -> None:
        self.at_least(f"{name}.grad_cosine", cosine(got, want), GRAD_COS)
        self.at_most(f"{name}.grad_rel_l2", rel_l2(got, want), GRAD_REL)

    @property
    def ok(self) -> bool:
        return all(r["ok"] for r in self.rows)
