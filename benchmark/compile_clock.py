"""JAX's own compile-path events, recorded so set-up can be split into
compile and the rest, and so a window can prove it compiled nothing.

A copy of ``chip_smoke.CompileClock`` (proven on the chip at PR 21), kept here
because the yardstick may not live in a file later PRs can edit; the original
is listed under Open questions in PERF.md for a later PR to delete.
"""

from __future__ import annotations

import jax


class CompileClock:
    """``backend`` is XLA/Mosaic compilation or, on a persistent-cache hit,
    retrieval. A jit traced inside another's trace reports both spans, so
    compile time is the length of the UNION of the spans; the per-kind sums
    keep the nesting. ``cache_misses`` counts entries WRITTEN to the
    persistent cache: programs this process had to compile."""

    KINDS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "backend_s",
    }

    def __init__(self) -> None:
        self.spans: list[tuple[float, float, str, str]] = []  # (start, end, kind, function)
        self.cache_events: list[str] = []
        jax.monitoring.register_event_time_span_listener(self._on_span)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_span(self, event: str, start: float, end: float, **kw) -> None:
        kind = self.KINDS.get(event)
        if kind is not None:
            self.spans.append((start, end, kind, str(kw.get("fun_name", "?"))))

    def _on_event(self, event: str, **_kw) -> None:
        if event.startswith("/jax/compilation_cache/cache_"):
            self.cache_events.append(event.rsplit("/", 1)[1])

    def mark(self) -> tuple[int, int]:
        return len(self.spans), len(self.cache_events)

    def since(self, mark: tuple[int, int] = (0, 0)) -> dict:
        spans, cache = self.spans[mark[0]:], self.cache_events[mark[1]:]
        union, edge = 0.0, float("-inf")
        for start, end, *_ in sorted(spans):
            union += max(0.0, end - max(start, edge))
            edge = max(edge, end)
        out = {"compile_s": union, "backend_n": sum(k == "backend_s" for _, _, k, _ in spans)}
        for kind in self.KINDS.values():
            out[kind] = sum(e - s for s, e, k, _ in spans if k == kind)
        out["cache_hits"] = cache.count("cache_hits")
        out["cache_misses"] = cache.count("cache_misses")
        return out

    def backend_names(self, mark: tuple[int, int] = (0, 0)) -> list[str]:
        """Names of the programs that reached the backend (compiled, or
        retrieved from the persistent cache) since ``mark``."""
        return [name for _, _, kind, name in self.spans[mark[0]:] if kind == "backend_s"]
