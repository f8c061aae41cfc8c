"""Tracing / profiling helpers.

The reference has no tracing at all (SURVEY §5: wall-clock prints only);
``jax.profiler`` integration is the idiomatic TPU upgrade: traces capture
XLA op timelines, collective latencies and host↔device transfers, viewable
in TensorBoard/Perfetto.
"""

from __future__ import annotations

import contextlib
import functools
import threading as _threading
import time
from typing import Iterator, Optional

import jax

from p2pfl_tpu.management.logger import logger


@contextlib.contextmanager
def trace(log_dir: str = "/tmp/p2pfl_tpu_trace") -> Iterator[None]:
    """Capture a jax.profiler trace for the enclosed block."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        logger.info("profiler", f"trace written to {log_dir}")


# ---- the program's own names on the device timeline ----
#
# Every phase of a round's compiled program runs under one of these scopes.
# A scope is HLO metadata (the ``op_name`` path of every instruction traced
# inside it): it costs nothing at run time, so there is no switch. The
# benchmark reads device time by scope from a profiler trace
# (``benchmark/scope_reduce.py``); forward / remat's re-forward / backward
# need no scope of their own — under ``grad`` JAX itself writes ``jvp``,
# ``rematted_computation`` and ``transpose(jvp`` into the path.
DEVICE_SCOPES = (
    "grad",  # value_and_grad of the local loss: forward, re-forward, backward
    "optimizer",  # tx.update + apply_updates (and gradient corrections beside them)
    "fold",  # everything a round does after the last local step
    "base_cast",  # LoRADense: the frozen kernel's cast to the compute dtype
    "base_matmul",  # LoRADense: x @ W
    "adapter",  # LoRADense: (x @ A) @ B
    "flash_fwd",  # ops/flash_attention: the forward Mosaic call
    "flash_bwd",  # ops/flash_attention: the backward Mosaic call(s)
    "flash_win_fwd",  # ops/flash_attention: the forward call of a SLIDING layer (window=…: blocks outside it skipped)
    "flash_win_bwd",  # ops/flash_attention: the backward call(s) of a sliding layer
    "attn_gate",  # Attention: the output gate, sigmoid(x W_g) times the attention output, before wo
    "post_norm",  # Block: the second norm of the sandwich, on the mixer's and the feed-forward's output
    "ssm_conv",  # MambaMixer: the causal depthwise convolution and its silu
    "ssm_scan_fwd",  # ops/selective_scan: everything the op runs forward (kernel and glue)
    "ssm_scan_bwd",  # ops/selective_scan: everything its backward runs
    "short_conv",  # ShortConvMixer: both gate products and the taps, between its two projections
    "qk_norm",  # Attention: the per-head norms of q and k before RoPE
    "mla",  # MLAttention: the glue between its five projections (split, RoPE, concatenate, broadcast)
    "moe_route",  # ExpertFFN: router, top-k, the counted layout (each assignment's row, group sizes)
    "moe_experts",  # ExpertFFN: gather into expert rows (and its cotangent: k slabs summed), both grouped matmuls, the activation
    "moe_gmm",  # ops/grouped_matmul: the product alone (the Mosaic call p2pfl_gmm on a TPU), inside moe_experts
    "moe_combine",  # ExpertFFN: gather the k rows of each token back, weigh, add the k slabs, add the shared expert's output
    "head",  # CausalLM's logits, and both rules of ops/head_loss (the training loss: logits by block, statistics, dX)
)


def scope(name: str):
    """``jax.named_scope`` for one of :data:`DEVICE_SCOPES`."""
    if name not in DEVICE_SCOPES:
        raise ValueError(f"unknown device scope {name!r} (known: {DEVICE_SCOPES})")
    return jax.named_scope("p2pfl." + name)


def host_annotation(site: str):
    """``p2pfl:<site>`` on the profiler's host timeline around host work —
    a ``jax.profiler.TraceAnnotation`` where ``settings.
    telemetry_jax_annotations`` says so, else nothing. No counter and no
    telemetry span: for a jit call site use :func:`dispatch_span`, which
    runs its body under this too."""
    from p2pfl_tpu.settings import telemetry_jax_annotations

    if telemetry_jax_annotations():
        return jax.profiler.TraceAnnotation(f"p2pfl:{site}")
    return contextlib.nullcontext()


# bf16 peak matmul FLOP/s per chip by device kind (public spec sheets);
# used to turn achieved FLOP/s into model-FLOPs-utilization
_PEAK_FLOPS = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def peak_flops(device=None) -> Optional[float]:
    """Peak bf16 FLOP/s of ``device`` by its exact ``device_kind``.

    ``None`` off-TPU (CPU test runs have no peak to compare against); a TPU
    whose kind is not in :data:`_PEAK_FLOPS` raises — a utilization figure
    against a guessed peak is worse than none.
    """
    device = device or jax.devices()[0]
    if device.platform != "tpu":
        return None
    try:
        return _PEAK_FLOPS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s entry for TPU device_kind {device.device_kind!r} "
            f"(known: {sorted(_PEAK_FLOPS)})"
        ) from None


def force_execution(tree) -> float:
    """Block until ``tree``'s pending computation finished, by fetching ONE
    element of its first leaf to the host.

    A device-to-host fetch cannot complete before the producing program
    has, so it is a barrier on every backend; slicing a single element on
    device first keeps the barrier's own transfer at O(bytes) instead of
    billing a whole-leaf copy to whatever the caller is timing. All
    benchmark timers use this.
    """
    import numpy as np

    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return 0.0
    leaf = leaves[0]
    ndim = getattr(leaf, "ndim", None)
    if not ndim:  # Python scalar or 0-d array: nothing to slice
        return float(np.asarray(leaf))
    return float(np.asarray(leaf[(0,) * ndim]))


def compiled_flops(jitted, *args, **kwargs) -> Optional[float]:
    """Total FLOPs of one execution, from the compiled XLA cost analysis."""
    try:
        cost = jitted.lower(*args, **kwargs).compile().cost_analysis()
        return float(cost["flops"])
    except Exception:  # noqa: BLE001 — cost analysis is best-effort
        return None


def mfu(flops: Optional[float], seconds: float, n_devices: int = 1) -> Optional[float]:
    """Model-FLOPs-utilization: achieved FLOP/s over aggregate peak FLOP/s."""
    peak = peak_flops()
    if flops is None or peak is None or seconds <= 0:
        return None
    return flops / seconds / (peak * n_devices)


# ---- dispatch accounting (ISSUE 6: the host dispatch tax) ----
#
# Process-wide counters of MODEL-PLANE device dispatches, incremented at
# the jit call sites of the overlay round's compute: "eval_step",
# "train_epoch" (one per epoch on the staged path), "fused_round" (the
# whole-round program) and "aggregate" (one per Aggregator.aggregate
# invocation). Deliberately NOT a hook into jax internals — the counter
# measures how many times OUR hot path crosses the host↔device boundary,
# which is the tax the fused round exists to kill; incidental eager ops
# (optimizer re-init, tree utilities) are not the round's dispatch
# structure and are excluded. Per-node counts additionally land in
# ``logger.get_comm_metrics(addr)["device_dispatch"]`` so benches can
# attribute dispatches/round per node.
#
# Since the flight recorder the counters live in the unified telemetry
# registry (counter group "dispatch", node "" = process-wide site totals);
# this surface is a thin view, and :func:`dispatch_span` is the preferred
# call-site wrapper — it counts AND records a "dispatch"-plane span (with
# an optional jax.profiler annotation bridge on accelerators).


def record_dispatch(site: str, node: str = "") -> None:
    """Count one model-plane device dispatch issued at ``site``."""
    from p2pfl_tpu.management.telemetry import telemetry

    telemetry.inc("dispatch", "", site)
    if node:
        logger.log_comm_metric(node, "device_dispatch")


def get_dispatch_counts() -> dict:
    """Snapshot of per-site dispatch counters (``logger.get_comm_metrics``
    style: plain accumulators, reset via :func:`reset_dispatch_counts`)."""
    from p2pfl_tpu.management.telemetry import telemetry

    return {k: int(v) for k, v in telemetry.counters("dispatch", "").items()}


def total_dispatches() -> int:
    return int(sum(get_dispatch_counts().values()))


def reset_dispatch_counts() -> None:
    from p2pfl_tpu.management.telemetry import telemetry

    telemetry.reset_counters("dispatch")


def snapshot_and_reset_dispatch_counts() -> dict:
    """Atomic read-and-clear: a ``get`` + ``reset`` pair can lose
    dispatches recorded between the two calls (e.g. a gossip worker's
    decode-side aggregate landing mid-bench) — this cannot."""
    from p2pfl_tpu.management.telemetry import telemetry

    return {
        k: int(v)
        for k, v in telemetry.snapshot_and_reset("dispatch", "").items()
    }


@contextlib.contextmanager
def dispatch_span(site: str, node: str = "", **attrs) -> Iterator[None]:
    """Wrap one model-plane jit call site: counts the dispatch
    (:func:`record_dispatch`) and records a "dispatch"-plane span whose
    duration is the HOST-side dispatch cost (jax returns before the device
    finishes — the async tail bills to whoever blocks, which is exactly
    the host-dispatch-tax accounting ISSUE 6 established). On accelerators
    the span body additionally runs under ``jax.profiler.TraceAnnotation``
    so a captured profiler trace lines the host span up with the device
    timeline (``settings.telemetry_jax_annotations``).

    The count lands only when the body SUCCEEDS: a failed fused-round
    dispatch falls back to the staged path, and counting both would
    inflate dispatches_per_round with a program that never ran to
    completion (the span still records, with the error in its attrs).

    A dispatch under which a program reached the backend — the first call,
    a changed static argument, a new shape — leaves with ``compiled=<n>`` in
    its span's attrs (``compile_cache``'s bridge writes it on the span open
    on the compiling thread) and counts once in ``("compile", "",
    "<site>:compiled")``: a round that recompiled no longer looks like a
    slow round."""
    from p2pfl_tpu.management.telemetry import telemetry

    with telemetry.span(node, site, kind="dispatch", attrs=attrs or None) as span:
        with host_annotation(site):
            yield
    if span is not None and "compiled" in span.attrs:
        telemetry.inc("compile", "", f"{site}:compiled")
    record_dispatch(site, node)


def setup_span(name: str):
    """Decorator: a federation's method as one phase of its start
    (``fed_init``, ``data_put``, ``stage_state``, ``reset``) — a ``"setup"``
    span in the process ring, ``fed=id(self)`` in its attrs (the round
    dispatches carry the same, so a reader can tell one federation's start
    from another's in the same process). No profiler annotation: the
    benchmark counts device idle outside every ``p2pfl:*`` annotation, and
    set-up is not a round's host work."""

    def wrap(method):
        @functools.wraps(method)
        def run(self, *args, **kwargs):
            from p2pfl_tpu.management.telemetry import PROCESS_NODE, telemetry

            with telemetry.span(PROCESS_NODE, name, kind="setup", attrs={"fed": id(self)}):
                return method(self, *args, **kwargs)

        return run

    return wrap


def note_placed(nodes: int, *trees) -> None:
    """Write on the set-up span open on this thread how many nodes' worth
    of arrays it put on the device and their bytes — from ``nbytes``, so
    nothing waits for the device."""
    from p2pfl_tpu.management.telemetry import telemetry

    span = telemetry.current_span()
    if span is not None:
        placed = sum(leaf.nbytes for leaf in jax.tree.leaves(trees))
        span.attrs.update(nodes=nodes, bytes=span.attrs.get("bytes", 0) + placed)


class Stopwatch:
    """Cheap wall-clock section timing (the reference's --measure_time,
    generalized): ``with sw.section("fit"): ...`` then ``sw.summary()``.

    Thread-safe — sections run on gossip worker threads too — and backed
    by the telemetry registry's :class:`~p2pfl_tpu.management.telemetry.
    LatencyHistogram`, so ``summary()`` carries percentiles alongside the
    historical total/mean columns. ``totals``/``counts`` remain readable
    as plain dict snapshots for existing callers.
    """

    def __init__(self) -> None:
        from p2pfl_tpu.management.telemetry import LatencyHistogram

        self._lock = _threading.Lock()
        self._hists: dict[str, LatencyHistogram] = {}

    @contextlib.contextmanager
    def section(self, name: str) -> Iterator[None]:
        from p2pfl_tpu.management.telemetry import LatencyHistogram

        t0 = time.monotonic_ns()
        try:
            yield
        finally:
            hist = self._hists.get(name)
            if hist is None:
                with self._lock:
                    hist = self._hists.setdefault(name, LatencyHistogram())
            hist.record(time.monotonic_ns() - t0)

    @property
    def totals(self) -> dict[str, float]:
        with self._lock:
            items = list(self._hists.items())
        return {k: h.sum_ns / 1e9 for k, h in items}

    @property
    def counts(self) -> dict[str, int]:
        with self._lock:
            items = list(self._hists.items())
        return {k: h.count for k, h in items}

    def summary(self) -> dict[str, dict[str, float]]:
        with self._lock:
            items = list(self._hists.items())
        out: dict[str, dict[str, float]] = {}
        for k, h in items:
            s = h.summary()
            if not s.get("count"):
                continue
            out[k] = {
                "total_s": round(s["total_s"], 4),
                "calls": s["count"],
                "mean_s": round(s["total_s"] / s["count"], 4),
                "p50_ms": s["p50_ms"],
                "p95_ms": s["p95_ms"],
                "p99_ms": s["p99_ms"],
            }
        return out
