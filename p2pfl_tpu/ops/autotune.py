"""Flash-attention kernel autotuner: per-shape config search + caches.

Resolution order for ``flash_attention(config=None)`` — every step is a
pure lookup safe to run at trace time:

1. **Pinned** configs (:func:`pin_flash_config`) — the explicit override.
2. **In-process cache** — results of :func:`autotune_flash` this process,
   plus anything already loaded from disk.
3. **On-disk cache** — JSON at ``Settings.FLASH_TUNE_CACHE`` (default
   ``~/.cache/p2pfl_tpu/flash_tune.json``), loaded once per process.
   Entries are keyed on **device kind** (``TPU v4`` / ``TPU v5 lite`` /
   ``cpu`` …) plus (head_dim, seq_len, dtype, causal) and, for a windowed
   call, the sliding window (a key without a window is what it has always
   been), so a cache written on one platform never mis-tunes another.
4. **Shipped defaults tables** (:data:`DEFAULTS`) — the measured
   per-device-family block recipes, clamped to divide the actual sequence
   length.

:func:`autotune_flash` is the only step that runs kernels: it sweeps
candidate ``(block_q, block_k, q_span)`` forward schedules, then
``(bwd_mode, backward blocks)`` on the winner, timing real fwd / fwd+bwd
executions, and writes the result into both caches. It must be called
OUTSIDE any jit trace (it compiles and runs programs); everything else is
trace-safe.

The reference has no kernels to tune (SURVEY §2.9); this exists so the
flash forward's work partitioning is chosen per (D, seq, dtype) the way
FlashAttention-2-style partitioning is, instead of hard-coded blocks being
lucky on one shape and 1.5× off on another (round-5 verdict).
"""

from __future__ import annotations

import json
import os
import time
from pathlib import Path
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from p2pfl_tpu.ops.flash_attention import FlashConfig

# in-process config cache: key (see _key) -> FlashConfig
_MEM_CACHE: dict[str, FlashConfig] = {}
# explicit pins (pin_flash_config): session-only overrides that win over
# everything and are NEVER persisted — a pin is an experiment, not a
# measurement, and must not masquerade as tuned data in the disk cache
_PINNED: dict[str, FlashConfig] = {}
_DISK_LOADED: set[str] = set()  # cache paths already merged into _MEM_CACHE


def device_kind() -> str:
    """The tuning-cache platform key: TPU device kind, else backend name."""
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        return dev.device_kind
    return dev.platform  # "cpu" / "gpu" — interpret-mode territory


def _dtype_tag(dtype) -> str:
    return jnp.dtype(dtype).name


def _key(kind: str, d: int, t: int, dtype, causal: bool, window: Optional[int] = None) -> str:
    key = f"{kind}|d={d}|t={t}|{_dtype_tag(dtype)}|{'causal' if causal else 'full'}"
    return key if window is None else f"{key}|w={window}"


def cache_path() -> Path:
    from p2pfl_tpu.settings import Settings

    p = getattr(Settings, "FLASH_TUNE_CACHE", "") or os.environ.get(
        "P2PFL_FLASH_TUNE_CACHE", ""
    )
    if p:
        return Path(p).expanduser()
    return Path.home() / ".cache" / "p2pfl_tpu" / "flash_tune.json"


def _fit(t: int, n: int) -> int:
    """Largest divisor of t that is <= n and a multiple of 8 (Mosaic's
    tiling rule), falling back to t itself (block == T always tiles)."""
    got = next((b for b in range(min(n, t), 7, -1) if t % b == 0 and b % 8 == 0), None)
    return got or t


def _clamped(t: int, block_q: int, block_k: int, q_span: int = 1, **kw) -> FlashConfig:
    from p2pfl_tpu.ops.flash_attention import _fit_q_span

    bq, bk = _fit(t, block_q), _fit(t, block_k)
    return FlashConfig(block_q=bq, block_k=bk, q_span=_fit_q_span(t, bq, q_span), **kw)


# Shipped per-device-family recipes (functions of (t, d, window) → FlashConfig).
# v4/v5e numbers come from the bench config-7 sweeps (block 512 beat 256 at
# every measured length; fused bwd keeps the forward's blocks); narrow heads
# (D <= 64) take q_span=2 — each program owning two q sub-tiles amortizes
# the grid bookkeeping that dominates when the per-block matmuls are small,
# while per-sub-tile causal frontiers keep the masked-work fraction of the
# single-block schedule. CPU/interpret keeps small blocks so the unrolled
# interpret grid stays compilable. A sliding window keeps the same recipe (the
# band it streams, window + block_q - 1 keys wide, is several blocks at every
# shipped shape).
DEFAULTS = {
    "v4": lambda t, d, window=None: _clamped(t, 512, 512, q_span=2 if d <= 64 else 1),
    "v5e": lambda t, d, window=None: _clamped(t, 512, 512, q_span=2 if d <= 64 else 1),
    "cpu": lambda t, d, window=None: _clamped(t, 128, 128),
}


# exact ``device_kind`` strings (as ``jax.devices()[0].device_kind`` reports
# them) → defaults-table family; anything non-TPU runs interpret-mode recipes
_TPU_FAMILIES = {"TPU v4": "v4", "TPU v5 lite": "v5e"}


def _family(kind: str) -> str:
    """Defaults-table family of a device kind. A TPU kind with no measured
    recipe raises — block shapes tuned for another generation's VMEM are a
    guess, not a default."""
    family = _TPU_FAMILIES.get(kind)
    if family is not None:
        return family
    if "tpu" in kind.lower():
        raise ValueError(
            f"no flash defaults for TPU device_kind {kind!r} "
            f"(known: {sorted(_TPU_FAMILIES)}); add a measured recipe to "
            "autotune.DEFAULTS or pass an explicit FlashConfig"
        )
    return "cpu"


def default_flash_config(
    t: int, d: int, dtype=jnp.bfloat16, causal: bool = True, kind: Optional[str] = None,
    window: Optional[int] = None,
) -> FlashConfig:
    """The shipped defaults-table config for this shape (no caches)."""
    del dtype, causal  # tables are currently shape-driven only
    return DEFAULTS[_family(kind or device_kind())](t, d, window)


def _load_disk(path: Path) -> None:
    tag = str(path)
    if tag in _DISK_LOADED:
        return
    _DISK_LOADED.add(tag)
    try:
        raw = json.loads(path.read_text())
    except (OSError, ValueError):
        return
    for key, fields in raw.items():
        try:
            _MEM_CACHE.setdefault(key, FlashConfig(**fields))
        except (TypeError, ValueError):
            continue  # unknown/garbage entry: defaults still apply


def _save_disk(path: Path) -> None:
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        data = {k: dataclasses_asdict(v) for k, v in sorted(_MEM_CACHE.items())}
        path.write_text(json.dumps(data, indent=2, sort_keys=True))
    except OSError:  # read-only home etc. — tuning still works in-process
        pass


def dataclasses_asdict(cfg: FlashConfig) -> dict:
    import dataclasses

    return dataclasses.asdict(cfg)


def clear_memory_cache() -> None:
    """Drop in-process tuning state (tests; disk cache files are kept)."""
    _MEM_CACHE.clear()
    _PINNED.clear()
    _DISK_LOADED.clear()


def pin_flash_config(
    t: int, d: int, config: FlashConfig, dtype=jnp.bfloat16, causal: bool = True,
    kind: Optional[str] = None, window: Optional[int] = None,
) -> None:
    """Pin an explicit config for a shape — wins over tuned/default.
    Session-only: pins are never written to the on-disk tuning cache."""
    _PINNED[_key(kind or device_kind(), d, t, dtype, causal, window)] = config


def flash_config_source(
    t: int, d: int, dtype=jnp.bfloat16, causal: bool = True, kind: Optional[str] = None,
    window: Optional[int] = None,
) -> tuple[FlashConfig, str]:
    """Trace-safe config lookup plus where it came from:
    ``"pin"`` → ``"tune"`` (memory → disk) → ``"defaults"``."""
    kind = kind or device_kind()
    key = _key(kind, d, t, dtype, causal, window)
    got = _PINNED.get(key)
    if got is not None:
        return got, "pin"
    if key not in _MEM_CACHE:
        _load_disk(cache_path())
    got = _MEM_CACHE.get(key)
    if got is not None:
        return got, "tune"
    return default_flash_config(t, d, dtype, causal, kind, window), "defaults"


def get_flash_config(
    t: int, d: int, dtype=jnp.bfloat16, causal: bool = True, kind: Optional[str] = None,
    window: Optional[int] = None,
) -> FlashConfig:
    """Trace-safe config lookup: pinned → tuned (memory → disk) → defaults."""
    return flash_config_source(t, d, dtype, causal, kind, window)[0]


def candidate_configs(t: int, d: int, max_candidates: int = 12) -> list[FlashConfig]:
    """The forward sweep space: (block_q, block_k, q_span) combinations that
    divide t, tile on Mosaic, and keep the q-residency reasonable."""
    blocks = sorted({_fit(t, b) for b in (128, 256, 512)})
    spans = (1, 2, 4)
    out: list[FlashConfig] = []
    seen = set()
    for bq in blocks:
        for bk in blocks:
            for span in spans:
                if (t // bq) % span != 0:
                    continue
                if bq * span > t:
                    continue
                cfg = FlashConfig(block_q=bq, block_k=bk, q_span=span)
                sig = (cfg.block_q, cfg.block_k, cfg.q_span)
                if sig in seen:
                    continue
                seen.add(sig)
                out.append(cfg)
    # prefer larger tiles first (the measured winners) so a truncated sweep
    # still sees the likely-best region
    out.sort(key=lambda c: (-c.block_q * c.q_span, -c.block_k))
    return out[:max_candidates]


def _time_fn(fn, args, repeats: int) -> float:
    from p2pfl_tpu.management.profiling import force_execution

    out = fn(*args)
    force_execution(out)  # compile + warm (real device-to-host fetch)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        force_execution(out)
        best = min(best, time.perf_counter() - t0)
    return best


def amortize_iters(t: int) -> int:
    """Kernel executions chained per timed dispatch. Production runs the
    kernel inside a compiled train step, so candidates must be scored
    dispatch-amortized too — un-amortized, every small-shape measurement
    reads ~the same per-dispatch overhead and sweeps pick noise (the same
    correction bench_suite's _fused_timer applies; design.md "Measurement
    methodology")."""
    return max(2, 4096 // t)


def time_flash_fwd(
    q, k, v, config: FlashConfig, *, causal: bool = True,
    interpret: bool = False, iters: int = 1, repeats: int = 2, window: Optional[int] = None,
) -> float:
    """Seconds per forward execution: ``iters`` data-chained kernel calls
    inside ONE jitted scan, min over ``repeats``, ending on a device fetch.
    The ONE flash timing harness — the autotuner scores candidates with it
    and bench_kernels.py reports with it, so the two stay comparable."""
    from jax import lax

    from p2pfl_tpu.ops.flash_attention import flash_attention

    @jax.jit
    def many(q, k, v):
        def body(q, _):
            o = flash_attention(q, k, v, causal, config, interpret, window)
            # data-dependent chain (a *0.0 chain folds to identity and the
            # loop gets DCE'd — measured 0.0 ms in bench_suite)
            return q + (o * 1e-30).astype(q.dtype), None

        q, _ = lax.scan(body, q, None, length=iters)
        return q

    return _time_fn(many, (q, k, v), repeats) / iters


def time_flash_train(
    q, k, v, config: FlashConfig, *, causal: bool = True,
    interpret: bool = False, iters: int = 1, repeats: int = 2, window: Optional[int] = None,
) -> float:
    """Seconds per fwd+bwd execution (grad of a scalar loss), chained and
    timed like :func:`time_flash_fwd`. The loss is sum(out²), NOT sum(out):
    a constant all-ones cotangent lets XLA const-fold the dO·Vᵀ block
    matmuls into reductions at some block shapes — measured 2× "backwards"
    that weren't executing the backward's matmul count."""
    from jax import lax

    from p2pfl_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal, config, interpret, window)
        return jnp.sum(o * o)  # dO = 2·out: data-dependent cotangent

    grad = jax.grad(loss, argnums=(0, 1, 2))

    @jax.jit
    def many(q, k, v):
        def body(carry, _):
            q_, k_, v_ = carry
            dq, dk, dv = grad(q_, k_, v_)
            return (
                q_ + (dq * 1e-30).astype(q_.dtype),
                k_ + (dk * 1e-30).astype(k_.dtype),
                v_ + (dv * 1e-30).astype(v_.dtype),
            ), None

        carry, _ = lax.scan(body, (q, k, v), None, length=iters)
        return carry

    return _time_fn(many, (q, k, v), repeats) / iters


def autotune_flash(
    t: int,
    d: int,
    dtype=None,
    causal: bool = True,
    *,
    batch: int = 1,
    heads: int = 2,
    repeats: int = 2,
    iters: Optional[int] = None,
    candidates: Optional[Sequence[FlashConfig]] = None,
    tune_bwd: bool = True,
    interpret: Optional[bool] = None,
    cache: bool = True,
    force: bool = False,
    kind: Optional[str] = None,
    window: Optional[int] = None,
) -> FlashConfig:
    """Sweep kernel schedules for one (T, D, dtype, causal[, window]) shape and cache
    the winner. An existing tuned entry (in-process or on-disk) is returned
    WITHOUT re-sweeping unless ``force=True`` — so FLASH_AUTOTUNE model
    builds pay the sweep once per shape per cache lifetime, not per build.
    Two stages: forward over ``candidates`` (default
    :func:`candidate_configs`), then backward mode/blocks on the forward
    winner (fused-with-fwd-blocks vs split-with-upsized-blocks). Scores
    come from :func:`time_flash_fwd` / :func:`time_flash_train`
    (dispatch-amortized — see :func:`amortize_iters`). NOT trace-safe —
    call from setup code, never inside jit.
    """
    on_tpu = jax.default_backend() == "tpu"
    interpret = (not on_tpu) if interpret is None else interpret
    dtype = dtype if dtype is not None else (jnp.bfloat16 if on_tpu else jnp.float32)
    kind = kind or device_kind()
    iters = iters if iters is not None else amortize_iters(t)

    if cache and not force:
        key = _key(kind, d, t, dtype, causal, window)
        _load_disk(cache_path())
        got = _PINNED.get(key) or _MEM_CACHE.get(key)
        if got is not None:
            return got

    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (
        jax.random.normal(s, (batch, t, heads, d)).astype(dtype) for s in keys
    )

    def fwd_time(cfg: FlashConfig) -> float:
        return time_flash_fwd(
            q, k, v, cfg, causal=causal, interpret=interpret,
            iters=iters, repeats=repeats, window=window,
        )

    def train_time(cfg: FlashConfig) -> float:
        return time_flash_train(
            q, k, v, cfg, causal=causal, interpret=interpret,
            iters=iters, repeats=repeats, window=window,
        )

    cands = list(candidates) if candidates is not None else candidate_configs(t, d)
    timed = [(fwd_time(c), c) for c in cands]
    _, best_fwd = min(timed, key=lambda x: x[0])

    best = best_fwd
    if tune_bwd:
        import dataclasses

        bwd_cands = [
            dataclasses.replace(best_fwd, bwd_mode="fused"),
            dataclasses.replace(best_fwd, bwd_mode="split"),
        ]
        big = _fit(t, 1024)
        if big > best_fwd.block_q:
            bwd_cands.append(
                dataclasses.replace(
                    best_fwd, bwd_mode="split", block_q_bwd=big, block_k_bwd=big
                )
            )
        _, best = min(((train_time(c), c) for c in bwd_cands), key=lambda x: x[0])

    if cache:
        _MEM_CACHE[_key(kind, d, t, dtype, causal, window)] = best
        # merge existing on-disk entries before writing: a force=True tune
        # skips the read path above, and saving bare _MEM_CACHE would clobber
        # every other shape/device entry the file holds (_load_disk's
        # setdefault keeps the fresh winner over the stale disk copy)
        _load_disk(cache_path())
        _save_disk(cache_path())
    return best
