"""Where the persistent XLA compile cache lives.

Compiling the federation programs takes seconds (the MLP round) to minutes
(the 22-layer LoRA round); a machine that keeps nothing between runs except
one directory pays that every time unless the cache is in that directory.
Every entry point (``chip_smoke.py``, ``bench*.py``, ``python -m
p2pfl_tpu``) calls :func:`configure_compile_cache` before its first jit.

The same call installs the one listener this program has on JAX's
compile-path events (:func:`install_compile_bridge`): from then on every
trace, lowering and backend compilation (or cache retrieval) is a span of the
flight recorder, under the span that caused it.
"""

from __future__ import annotations

import os
import threading
import time
from pathlib import Path

from p2pfl_tpu.management.telemetry import PROCESS_NODE, telemetry

#: the default cache directory, ``<checkout>/.jax_cache`` — derived from the
#: package's own location so every process of one checkout agrees on it
DEFAULT_CACHE_DIR = Path(__file__).resolve().parent.parent / ".jax_cache"


def configure_compile_cache() -> str:
    """Place the persistent compile cache; returns the directory in use.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and no
    directory is set in code, so whoever runs the program decides where the
    cache lives. Unset: :data:`DEFAULT_CACHE_DIR`.

    Every program is cached, not only those that took JAX's default 1 s to
    compile. Measured with ``chip_smoke.py`` on a v5e (PR 21): of 136
    backend compiles per process the default kept 16 — the rest, setup
    programs of 0.05–0.3 s each, cost a warm process 11 s (35.7 s vs 24.6 s
    total) — and a program compiling in about 1 s was kept in some runs and
    not others, so one warm run's phase compiled for longer than the cold
    run's. At 0 a cold run costs the same and the cache holds 2 MB more.

    The key includes the program's metadata. JAX's default strips locations
    before hashing — and ``jax.named_scope`` names live there — so two
    programs that differ only in their scopes share one entry and the later
    one is handed the earlier one's executable, names and all (seen at PR
    24: a program scoped ``beta`` came back with every ``op_name`` reading
    ``alpha``). A profiler trace is read by those names
    (``management/profiling.DEVICE_SCOPES``), so a cache shared between
    two versions of this code must not mix them. The price: an edit that
    moves traced lines misses the cache once.
    """
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    install_compile_bridge()
    return jax.config.jax_compilation_cache_dir


#: JAX's compile-path span events → the name of the ``"compile"`` span each becomes
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",  # recorded when the entry is written
}
# A trace shorter than this is, nearly always, a jitted ``jax.numpy`` function
# met inside a larger trace (``multiply`` 824 times in a toy LoRA start, 66 us
# each): it lies inside that trace's span, adds nothing to any union, and
# thousands of them would push the start of the run out of the ring. JAX
# reports a trace at its end, so the listener cannot tell a nested one from a
# short one at the top (an eager op under ``data_put``): both are dropped, and
# ``startup_report()["compile"]`` says how many and how long (``short_traces_n``,
# ``short_trace_s``), which bounds what the unions can be short of.
_MIN_TRACE_NS = 1_000_000
_bridge_installed = False
# the persistent cache's answer for the compilation in flight on this thread:
# JAX reports it inside the backend span, before the span itself
_in_flight = threading.local()


def _on_cache_event(event: str, **_kw) -> None:
    answer = _CACHE_EVENTS.get(event)
    if answer is not None:
        _in_flight.cache = answer


def _on_compile_span(event: str, start: float, end: float, **kw) -> None:
    """JAX calls this on the thread that compiled, at the span's end, with
    ``time.time()`` stamps; the record goes on the flight recorder's clock:
    ``monotonic_ns`` now, less the duration."""
    name = _COMPILE_EVENTS.get(event)
    if name is None:
        return
    t1_ns = time.monotonic_ns()
    t0_ns = t1_ns - round((end - start) * 1e9)
    if name == "trace" and t1_ns - t0_ns < _MIN_TRACE_NS:
        telemetry.inc("compile", "", "short_traces")
        telemetry.inc("compile", "", "short_trace_s", (t1_ns - t0_ns) / 1e9)
        return
    fun_name = str(kw.get("fun_name", "?"))
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        fun_name = fun_name[4:-1]  # lowering and backend say jit(f) where tracing says f
    attrs = {"fun_name": fun_name}
    if name == "backend":
        attrs["cache"] = getattr(_in_flight, "cache", "off")
        _in_flight.cache = "off"
        telemetry.inc("compile", "", f"backend:{fun_name}")
        cause = telemetry.current_span()
        if cause is not None:  # the dispatch (or set-up phase) that compiled, marked
            cause.attrs["compiled"] = cause.attrs.get("compiled", 0) + 1
    telemetry.record_span(PROCESS_NODE, name, "compile", t0_ns, t1_ns, attrs)


def install_compile_bridge() -> None:
    """Register the listeners, once a process however often it is called."""
    global _bridge_installed
    if _bridge_installed:
        return
    _bridge_installed = True
    import jax

    jax.monitoring.register_event_time_span_listener(_on_compile_span)
    jax.monitoring.register_event_listener(_on_cache_event)
    telemetry.anchor_clock()
