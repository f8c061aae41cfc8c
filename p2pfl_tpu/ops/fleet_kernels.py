"""Megafleet kernels: the async fleet as ONE jitted array program.

:mod:`~p2pfl_tpu.federation.simfleet` drives the async plane as a Python
event heap — exact, but ~10⁴ heap pops/sec caps it three orders of
magnitude short of "heavy traffic from millions of users". This module
re-expresses the same run as a single ``lax.scan`` over the
chronologically sorted contribution arrivals, with the whole edge
population held as dense per-client arrays. The scan body reuses the
REAL aggregation math — :func:`~p2pfl_tpu.ops.aggregation.fedavg` over
effective weights ``num_samples · w(τ)`` and
:func:`~p2pfl_tpu.ops.aggregation.server_merge`, the exact kernels
:class:`~p2pfl_tpu.federation.buffer.BufferedAggregator` folds with
(inlined when traced inside the scan), and
:func:`staleness_weight_arr`, the elementwise twin of
:func:`~p2pfl_tpu.federation.staleness.staleness_weight` — so a
vectorized run is the same algorithm, not a lookalike.

Why a scan over sorted arrivals is EXACT for the flat topology: every
quantity the heap driver derives from event interleaving is a function
of *time* —

- a client's adoption base at a train completion ``t`` is the number of
  global versions whose push had ARRIVED by then, i.e.
  ``searchsorted(mint_times, t − adopt_delay)`` (one binary search
  against the carry's mint-time array replaces the heap's
  ``model_arrive`` events entirely);
- the buffer window an arrival joins is determined by processing
  arrivals in ``t_arr`` order — exactly the heap's pop order;
- and every mint time is the ``K``-th accepted arrival's time, which the
  scan knows at the step that fires the flush.

Because the scan is sorted by arrival time and an update's training time
precedes its arrival, every ``searchsorted`` read only ever sees mint
times that are already final — causality is the sort order. The
hierarchical program extends the same carry with vectorized per-regional
windows (one scatter row per arrival); its one deliberate approximation
is that a regional flush's aggregate is *processed* at the flush step
while its ``link_delay`` shows up only in the recorded mint time and the
adoption bookkeeping — aggregates from different regionals that would
interleave inside one in-flight window can order differently than the
heap's, which is the documented tolerance of the hierarchical parity
anchor (``docs/design.md`` "megafleet").

**Branch-free by design.** The body contains no ``lax.cond``: XLA
double-buffers carry arrays that cross a conditional boundary, and a
per-step copy of the ``[R, K, dim]`` regional windows turns a 4M-event
scan into terabytes of memcpy (measured: 5× the per-event cost at 1M
clients vs 100k before this layout). Instead every step executes the
same straight-line program — predicated scatters into the big carries
(in-place under ``scan``) and an unconditionally computed window fold
whose result is ``where``-masked by the flush predicate. A not-yet-full
window's fold is garbage (even ``0/0`` when empty) that the mask
discards; the extra fold per event is ~100 flops on a ``[K, dim]``
window — noise next to the copies it replaces.

**The cross-buffer copy law** (measured on XLA:CPU, jax 0.4.37; every
rule below is worth ~3 orders of magnitude at 1M clients):

- writing carry ``A`` with a value that reads carry ``B``'s *pre-update*
  state while ``B`` is also written in the same step makes XLA preserve
  ``B`` with a full copy per step — a read→write pair it cannot
  linearize. Copies of an ``[N, …]`` buffer per event are catastrophic.
- Fix 1 — *re-gather*: when the dependent write wants the POST-update
  value, read it back from the already-updated carry (``w_cur``,
  ``agg_params`` below) instead of reusing the temporary that also fed
  the first write. The dataflow becomes linear and everything updates in
  place.
- Fix 2 — *pack coupled state into one buffer*: the adoption bookkeeping
  (``base_seen``) is read to pick the train branch and written every
  step; as a separate ``[N]`` carry it pairs with the ``w`` write and
  re-copies itself per event. It rides as column ``dim`` of the ``w``
  rows instead (f32 — exact for versions < 2²⁴), making adopt+train a
  single-buffer read-modify-write.
- Residual pairs are left where ``B`` is small and R-bounded (``rcount``
  / ``radopt`` / ``mint`` / ``G``): their per-step copies are KB-scale
  in the hierarchical shape. This is also why the FLAT program is the
  1k-parity anchor rather than the fleet-scale engine — its ``G``/
  ``mint`` histories grow with total merges, and the copy law would
  re-copy them per event at 1M clients; the hierarchical shape (the
  production topology) keeps them at the global-version count.

The jit-staleness contract: nothing in a scan body reads ``Settings`` or
mutable module state — every knob (α, η, K, staleness bound, rate gaps)
arrives through the static :class:`FleetConfig`, so a config change
provably re-traces.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp
import optax

from p2pfl_tpu.ops.aggregation import fedavg, server_merge

Pytree = Any

#: sort key for empty window slots — pads order last and carry weight 0,
#: so they add exact +0.0 terms to the fold (see fold_window)
PAD_KEY = jnp.iinfo(jnp.int32).max


class FleetConfig(NamedTuple):
    """Static shape/knob tuple baked into one compiled fleet program.

    Everything here participates in the trace (a changed value compiles a
    new program — the jit-staleness rule's explicit-argument contract).
    ``rate_gap_*`` are the Bonawitz per-tier rate limits in virtual
    seconds between *accepted* offers (0 disables the gate and compiles
    it out); ``hist_bins`` sizes the staleness histograms (the last bin
    absorbs the tail).
    """

    hier: bool  #: two-tier (regional windows + global) vs flat
    n_clients: int
    dim: int  #: consensus-task parameter dimension
    n_regionals: int  #: R (1 in flat mode; regional 0 is the global root)
    k_global: int  #: global window size (flat: the only window)
    k_reg_max: int  #: widest regional window (per-regional K in reg["k"])
    v_cap: int  #: global version capacity (host-computed upper bound)
    alpha: float  #: FedBuff staleness exponent
    server_lr: float  #: η of the server merge
    local_lr: float  #: consensus-task pull rate toward the private target
    max_staleness: int
    rate_gap_reg: float
    rate_gap_glob: float
    hist_bins: int
    agg_key_stride: int  #: grid column count for (regional, up_seq) lookups
    unroll: int  #: lax.scan unroll factor
    # ---- chunked-engine extensions (defaults keep the per-event
    # construction sites working; see run_fleet_program_chunked) ----
    chunk: int = 1  #: events per scan step (1 = per-event reference engine)
    gf_cap: int = 0  #: max global mints per chunk (host bound: chunk//k+2)
    fold_kind: str = "fedavg"  #: window fold family (Settings.ASYNC_ROBUST_AGG)
    trim: int = 1  #: trimmed-mean clamp (Settings.ASYNC_TRIM)
    task: str = "consensus"  #: "consensus" | "linear" | "mlp" train kernel
    t_din: int = 0  #: gradient-task input dim
    t_nout: int = 0  #: gradient-task class count
    t_hidden: int = 0  #: MLP hidden width (0 for linear)
    t_bs: int = 0  #: per-step batch size
    t_steps: int = 0  #: SGD steps per local round
    data_seed: int = 0  #: PRNG root of the per-(client, round) data streams
    byz: bool = False  #: byzantine payload columns present in events
    dup: bool = False  #: duplicate verdict grids present


def staleness_weight_arr(tau: jax.Array, alpha: float) -> jax.Array:
    """Elementwise FedBuff weight ``w(τ) = 1/(1+τ)^α`` — the array twin
    of :func:`p2pfl_tpu.federation.staleness.staleness_weight` (same
    clamp, same formula, f32; pointwise parity pinned by test). ``alpha``
    is static: 0 compiles to ones like the scalar's early-out."""
    t = jnp.maximum(tau.astype(jnp.float32), 0.0)
    if float(alpha) == 0.0:
        return jnp.ones_like(t)
    return 1.0 / (1.0 + t) ** jnp.float32(alpha)


def grad_param_dim(kind: str, din: int, nout: int, hidden: int = 0) -> int:
    """Flat parameter count of the vmapped tiny learner (``linear``:
    one dense layer; ``mlp``: dense→relu→dense)."""
    if kind == "linear":
        return din * nout + nout
    if kind == "mlp":
        return din * hidden + hidden + hidden * nout + nout
    raise ValueError(f"unknown gradient task kind {kind!r}")


def grad_logits(
    kind: str, din: int, nout: int, hidden: int, flat: jax.Array, x: jax.Array
) -> jax.Array:
    """Forward pass from a FLAT parameter vector — the same dense math a
    flax ``Dense`` stack computes, unflattened by index arithmetic so the
    whole model rides as one ``[dim]`` row of the fleet carry."""
    if kind == "linear":
        w = flat[: din * nout].reshape(din, nout)
        b = flat[din * nout :]
        return x @ w + b
    o = din * hidden
    w1 = flat[:o].reshape(din, hidden)
    b1 = flat[o : o + hidden]
    o += hidden
    w2 = flat[o : o + hidden * nout].reshape(hidden, nout)
    b2 = flat[o + hidden * nout :]
    h = jax.nn.relu(x @ w1 + b1)
    return h @ w2 + b2


def make_grad_fns(
    kind: str,
    din: int,
    nout: int,
    hidden: int,
    bs: int,
    steps: int,
    lr: float,
    data_seed: int,
):
    """Build the gradient-task kernels shared by every consumer that must
    agree on the SAME local round: the chunked fleet engine, the heap
    driver's vectorized-twin ``train_fn`` (1k parity pin) and the
    :class:`~p2pfl_tpu.learning.learner.JaxLearner` parity test.

    Returns ``(gen_batch, train_one, train_vec)``:

    - ``gen_batch(i, m, mu_row, tw, tb)`` → ``(xs [steps, bs, din],
      ys [steps, bs] int32)`` — the i-th client's m-th local round drawn
      from the counter-keyed stream ``fold_in(fold_in(key(data_seed), i),
      m)``: a Gaussian cloud around the client's ``mu`` (the non-IID
      knob) labeled by a fixed teacher — order-independent, so heap and
      scan derive identical batches from (client, seq) alone;
    - ``train_one(flat, xs, ys)`` — ``steps`` plain-SGD steps on
      softmax cross-entropy, arranged as ``p + g·(−lr)`` which is
      bit-identical to ``optax.sgd`` + ``apply_updates`` (the exact
      update :meth:`JaxLearner.train_epoch` applies);
    - ``train_vec`` — ``train_one∘gen_batch`` vmapped over
      ``(flat, i, m, mu)`` with the teacher broadcast.
    """
    root = jax.random.PRNGKey(data_seed)

    def gen_batch(i, m, mu_row, tw, tb):
        key = jax.random.fold_in(jax.random.fold_in(root, i), m)
        x = mu_row[None, None, :] + jax.random.normal(key, (steps, bs, din), jnp.float32)
        y = jnp.argmax(x @ tw + tb, axis=-1).astype(jnp.int32)
        return x, y

    neg_lr = jnp.float32(-lr)

    def train_one(flat, xs, ys):
        def step(p, xy):
            x, y = xy

            def loss_fn(q):
                logits = grad_logits(kind, din, nout, hidden, q, x)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, y
                ).mean()

            g = jax.grad(loss_fn)(p)
            return p + g * neg_lr, None

        out, _ = jax.lax.scan(step, flat, (xs, ys))
        return out

    def train_vec(flats, his, los, mus, tw, tb):
        def one(flat, i, m, mu):
            xs, ys = gen_batch(i, m, mu, tw, tb)
            return train_one(flat, xs, ys)

        return jax.vmap(one)(flats, his, los, mus)

    return gen_batch, train_one, train_vec


def fold_window(
    rows: jax.Array,
    weights: jax.Array,
    keys: jax.Array,
    prev: jax.Array,
    server_lr: float,
    kind: str = "fedavg",
    trim: int = 1,
    keys_hi: jax.Array | None = None,
) -> jax.Array:
    """One buffer flush on a dense window — exactly the live
    :meth:`BufferedAggregator._merge_locked` math: sort the window by its
    ``(origin, seq)`` fold keys, fold (:func:`fedavg`, or the robust
    family of :func:`~p2pfl_tpu.ops.aggregation.buffered_robust_merge`)
    over the effective weights, :func:`server_merge` into ``prev``.
    Empty pad slots (``weights == 0``, ``keys == PAD_KEY``) sort last and
    contribute exact ``+0.0`` terms to the fedavg path, so a clamped-K
    regional window folds bit-identically to a dense K-length fold. (An
    ALL-empty window divides 0/0 — callers inside the scan mask the
    result with the flush predicate, which is False exactly then.)

    ``keys_hi`` is the high word of the two-word ``(origin, seq)`` fold
    key (``lexsort((keys, keys_hi))`` == the heap's tuple sort over
    zero-padded origin addresses); when ``None`` the single int32 ``keys``
    carries the whole order — the pre-two-word calling convention.

    ``kind``/``trim`` are static and select the flush family exactly as
    ``Settings.ASYNC_ROBUST_AGG``/``ASYNC_TRIM`` select the heap
    buffer's. The robust kinds are pad-AWARE twins of ``trimmed_mean`` /
    ``fedmedian``: rank statistics over the ``weights > 0`` slots only
    (same clamp ``trim ≤ (n-1)//2``, degrade-to-mean at ``n`` too small,
    weights ignored by construction), computed branch-free over a
    possibly-padded window so a clamped-K regional flush matches the
    heap's dense n-row fold to fp tolerance. ``"krum-screen"`` needs the
    pairwise-distance screen and stays heap-only (host raises upstream).

    ``rows [K, dim]``, ``weights [K]``, ``prev [dim]``; ``server_lr`` is
    static. Reuses the SAME jitted kernels the live buffer calls — under
    an outer trace they inline, standalone they dispatch once each.
    """
    if keys_hi is None:
        order = jnp.argsort(keys)
    else:
        order = jnp.lexsort((keys, keys_hi))
    sorted_rows = jnp.take(rows, order, axis=0)
    sorted_w = jnp.take(weights, order)
    if kind == "fedavg":
        avg = fedavg({"p": sorted_rows}, sorted_w, agg_dtype="float32")["p"]
    elif kind in ("trimmed-mean", "median"):
        # pads (weight 0) sort to +inf per coordinate; n live rows occupy
        # ranks [0, n) after the sort, so rank selection is index math
        live = sorted_w > 0.0
        n = jnp.sum(live.astype(jnp.int32))
        vals = jnp.where(live[:, None], sorted_rows.astype(jnp.float32), jnp.inf)
        svals = jnp.sort(vals, axis=0)
        k = rows.shape[0]
        ranks = jnp.arange(k, dtype=jnp.int32)
        if kind == "median":
            lo = svals[jnp.clip((n - 1) // 2, 0, k - 1)]
            hi = svals[jnp.clip(n // 2, 0, k - 1)]
            avg = 0.5 * (lo + hi)
        else:
            t = jnp.minimum(jnp.int32(trim), (n - 1) // 2)
            keep = (ranks[:, None] >= t) & (ranks[:, None] < n - t)
            kept = jnp.where(keep, svals, 0.0)
            avg = jnp.sum(kept, axis=0) / jnp.maximum(n - 2 * t, 1).astype(jnp.float32)
        # single-row window: rank stats degrade to that row (heap: n==1
        # short-circuits to fedavg of one)
        avg = jnp.where(n >= 1, avg, jnp.zeros_like(avg))
    else:  # pragma: no cover - host guards reject krum-screen upstream
        raise ValueError(f"fold kind {kind!r} has no vectorized window fold")
    return server_merge({"p": prev}, {"p": avg}, lr=server_lr, agg_dtype="float32")["p"]


def _init_carry(cfg: FleetConfig, init_params) -> Dict[str, jax.Array]:
    n, dim, r = cfg.n_clients, cfg.dim, cfg.n_regionals
    row0 = jnp.concatenate(
        [jnp.asarray(init_params, jnp.float32), jnp.zeros((1,), jnp.float32)]
    )
    carry = {
        # per-client lazy state: current params, with the highest adopted
        # version packed as column `dim` (the cross-buffer copy law — a
        # separate [N] base_seen carry would be re-copied per event)
        "w": jnp.broadcast_to(row0, (n, dim + 1)).astype(jnp.float32),
        # global model history: G[v] = params of version v (G[0] = init);
        # mint[v-1] = virtual time version v was minted (+inf = unminted)
        "G": jnp.zeros((cfg.v_cap + 1, dim), jnp.float32).at[0].set(init_params),
        "mint": jnp.full((cfg.v_cap,), jnp.inf, jnp.float32),
        "last_mint": jnp.float32(-jnp.inf),
        "version": jnp.int32(0),
        # global window; fold keys are two int32 words (hi = origin
        # index, lo = sequence) — the heap's (origin, seq) tuple order
        # without int64, so 1M clients × long runs never overflow a key
        "gbuf": jnp.zeros((cfg.k_global, dim), jnp.float32),
        "gwt": jnp.zeros((cfg.k_global,), jnp.float32),
        "gkey_hi": jnp.full((cfg.k_global,), PAD_KEY, jnp.int32),
        "gkey_lo": jnp.full((cfg.k_global,), PAD_KEY, jnp.int32),
        "gcount": jnp.int32(0),
        "last_acc_g": jnp.float32(-jnp.inf),
        # counters + staleness histograms, split by seam: "edge" = where
        # client updates enter a window (the regional tier, or the global
        # window in flat mode), "agg" = where regional aggregates enter
        # the global window (hier only)
        "merges": jnp.int32(0),
        "stale_edge": jnp.int32(0),
        "rate_edge": jnp.int32(0),
        "stale_agg": jnp.int32(0),
        "rate_agg": jnp.int32(0),
        "hist_edge": jnp.zeros((cfg.hist_bins,), jnp.int32),
        "hist_glob": jnp.zeros((cfg.hist_bins,), jnp.int32),
    }
    if cfg.hier:
        carry.update(
            {
                # vectorized regional tier: one window + lazily-adopted
                # params per regional, all scatter-addressed by r
                "rbuf": jnp.zeros((r, cfg.k_reg_max, dim), jnp.float32),
                "rwt": jnp.zeros((r, cfg.k_reg_max), jnp.float32),
                "rsamp": jnp.zeros((r, cfg.k_reg_max), jnp.float32),
                "rkey_hi": jnp.full((r, cfg.k_reg_max), PAD_KEY, jnp.int32),
                "rkey_lo": jnp.full((r, cfg.k_reg_max), PAD_KEY, jnp.int32),
                "rcount": jnp.zeros((r,), jnp.int32),
                "rparams": jnp.broadcast_to(init_params, (r, dim)).astype(jnp.float32),
                "radopt": jnp.zeros((r,), jnp.int32),
                "up_seq": jnp.zeros((r,), jnp.int32),
                "last_acc_r": jnp.full((r,), -jnp.inf, jnp.float32),
                "rmerges": jnp.int32(0),
                "agg_drop": jnp.int32(0),
            }
        )
    return carry


def run_fleet_program(
    cfg: FleetConfig,
    events: Dict[str, jax.Array],
    clients: Dict[str, jax.Array],
    reg: Dict[str, jax.Array],
    init_params: jax.Array,
) -> Dict[str, Any]:
    """Compile and run the fleet scan. ``events`` are the pre-sorted
    arrival rows (``client/key/t_train/t_arr/send_ok``, each ``[E]``);
    ``clients`` holds ``targets [N, dim]``, ``samples [N]``,
    ``adopt_delay [N]`` and (hier) ``regional_of [N]``; ``reg`` holds the
    per-regional ``k``, ``adopt_delay`` and ``agg_delay`` arrays. Returns
    the final carry (host-side consumers slice ``G``/``mint`` by
    ``version``). One compile per :class:`FleetConfig`.
    """

    def offer_global(c, accept, params, wgt, key_hi, key_lo, tau, t_evt, seam):
        """Predicated offer into the global window + masked flush.
        ``seam`` ("edge" | "agg") is a trace-time label selecting which
        counter/histogram family the admission feeds."""
        fresh = tau <= cfg.max_staleness
        if cfg.rate_gap_glob > 0.0:
            rate_ok = (t_evt - c["last_acc_g"]) >= cfg.rate_gap_glob
        else:
            rate_ok = jnp.bool_(True)
        ins = accept & fresh & rate_ok
        hist = "hist_edge" if seam == "edge" else "hist_glob"
        c[f"stale_{seam}"] = c[f"stale_{seam}"] + (accept & ~fresh).astype(jnp.int32)
        c[f"rate_{seam}"] = c[f"rate_{seam}"] + (
            accept & fresh & ~rate_ok
        ).astype(jnp.int32)

        slot = c["gcount"]
        c["gbuf"] = c["gbuf"].at[slot].set(jnp.where(ins, params, c["gbuf"][slot]))
        c["gwt"] = c["gwt"].at[slot].set(jnp.where(ins, wgt, c["gwt"][slot]))
        c["gkey_hi"] = c["gkey_hi"].at[slot].set(
            jnp.where(ins, key_hi, c["gkey_hi"][slot])
        )
        c["gkey_lo"] = c["gkey_lo"].at[slot].set(
            jnp.where(ins, key_lo, c["gkey_lo"][slot])
        )
        c["last_acc_g"] = jnp.where(ins, t_evt, c["last_acc_g"])
        c[hist] = c[hist].at[jnp.clip(tau, 0, cfg.hist_bins - 1)].add(
            ins.astype(jnp.int32)
        )
        count = c["gcount"] + ins.astype(jnp.int32)
        flush = ins & (count == cfg.k_global)
        c["gcount"] = jnp.where(flush, 0, count)

        # the fold runs every step (garbage when not flushing, masked
        # below) — cheaper than letting the window cross a cond boundary
        new_g = fold_window(
            c["gbuf"],
            c["gwt"],
            c["gkey_lo"],
            c["G"][c["version"]],
            cfg.server_lr,
            kind=cfg.fold_kind,
            trim=cfg.trim,
            keys_hi=c["gkey_hi"],
        )
        v = c["version"] + flush.astype(jnp.int32)
        c["G"] = c["G"].at[v].set(jnp.where(flush, new_g, c["G"][v]))
        # the recorded mint time is clamped monotone: out-of-order
        # aggregate arrival times (the hier ordering tolerance) must not
        # make the searchsorted axis non-ascending
        t_mint = jnp.maximum(t_evt, c["last_mint"])
        mi = jnp.where(flush, v - 1, 0)
        c["mint"] = c["mint"].at[mi].set(jnp.where(flush, t_mint, c["mint"][mi]))
        c["last_mint"] = jnp.where(flush, t_mint, c["last_mint"])
        c["version"] = v
        c["merges"] = c["merges"] + flush.astype(jnp.int32)
        empty_w = jnp.zeros((cfg.k_global,), jnp.float32)
        empty_k = jnp.full((cfg.k_global,), PAD_KEY, jnp.int32)
        c["gwt"] = jnp.where(flush, empty_w, c["gwt"])
        c["gkey_hi"] = jnp.where(flush, empty_k, c["gkey_hi"])
        c["gkey_lo"] = jnp.where(flush, empty_k, c["gkey_lo"])
        return c

    def offer_regional(
        c, accept, r, params, raw_samples, wgt, key_hi, key_lo, tau, rv, t_arr
    ):
        """Predicated offer into regional ``r``; a full window flushes
        into the regional params and sends the aggregate up."""
        fresh = tau <= cfg.max_staleness
        if cfg.rate_gap_reg > 0.0:
            rate_ok = (t_arr - c["last_acc_r"][r]) >= cfg.rate_gap_reg
        else:
            rate_ok = jnp.bool_(True)
        ins = accept & fresh & rate_ok
        c["stale_edge"] = c["stale_edge"] + (accept & ~fresh).astype(jnp.int32)
        c["rate_edge"] = c["rate_edge"] + (accept & fresh & ~rate_ok).astype(jnp.int32)

        slot = c["rcount"][r]
        c["rbuf"] = c["rbuf"].at[r, slot].set(jnp.where(ins, params, c["rbuf"][r, slot]))
        c["rwt"] = c["rwt"].at[r, slot].set(jnp.where(ins, wgt, c["rwt"][r, slot]))
        c["rsamp"] = c["rsamp"].at[r, slot].set(
            jnp.where(ins, raw_samples, c["rsamp"][r, slot])
        )
        c["rkey_hi"] = c["rkey_hi"].at[r, slot].set(
            jnp.where(ins, key_hi, c["rkey_hi"][r, slot])
        )
        c["rkey_lo"] = c["rkey_lo"].at[r, slot].set(
            jnp.where(ins, key_lo, c["rkey_lo"][r, slot])
        )
        c["last_acc_r"] = c["last_acc_r"].at[r].set(
            jnp.where(ins, t_arr, c["last_acc_r"][r])
        )
        c["hist_edge"] = c["hist_edge"].at[jnp.clip(tau, 0, cfg.hist_bins - 1)].add(
            ins.astype(jnp.int32)
        )
        count = c["rcount"][r] + ins.astype(jnp.int32)
        flush = ins & (count == reg["k"][r])
        c["rcount"] = c["rcount"].at[r].set(jnp.where(flush, 0, count))

        # regional flush (masked): current params = lazily-adopted
        # freshest arrived global (set_global semantics — only the last
        # adoption before the flush matters), fold, push the aggregate up
        cur = jnp.where(rv > c["radopt"][r], c["G"][rv], c["rparams"][r])
        merged = fold_window(
            c["rbuf"][r],
            c["rwt"][r],
            c["rkey_lo"][r],
            cur,
            cfg.server_lr,
            kind=cfg.fold_kind,
            trim=cfg.trim,
            keys_hi=c["rkey_hi"][r],
        )
        raw = jnp.sum(c["rsamp"][r])
        c["rparams"] = c["rparams"].at[r].set(jnp.where(flush, merged, c["rparams"][r]))
        # same re-gather trick as w_cur: the aggregate pushed upward reads
        # the updated rparams row (== merged whenever flush, the only
        # predicate under which offer_global consumes it) so `merged`
        # never feeds two carry buffers
        agg_params = c["rparams"][r]
        c["radopt"] = c["radopt"].at[r].set(
            jnp.where(flush, jnp.maximum(c["radopt"][r], rv), c["radopt"][r])
        )
        c["rmerges"] = c["rmerges"] + flush.astype(jnp.int32)
        up = c["up_seq"][r] + flush.astype(jnp.int32)
        c["up_seq"] = c["up_seq"].at[r].set(up)
        empty_w = jnp.zeros((cfg.k_reg_max,), jnp.float32)
        empty_k = jnp.full((cfg.k_reg_max,), PAD_KEY, jnp.int32)
        c["rwt"] = c["rwt"].at[r].set(jnp.where(flush, empty_w, c["rwt"][r]))
        c["rsamp"] = c["rsamp"].at[r].set(jnp.where(flush, empty_w, c["rsamp"][r]))
        c["rkey_hi"] = c["rkey_hi"].at[r].set(jnp.where(flush, empty_k, c["rkey_hi"][r]))
        c["rkey_lo"] = c["rkey_lo"].at[r].set(jnp.where(flush, empty_k, c["rkey_lo"][r]))

        # the upward aggregate: version triple (r, up, rv) with effective
        # weight raw_samples · w(τ_g) — processed now, arrival-time
        # bookkeeping via the regional's agg_delay (0 for the root's own
        # cluster: a direct offer). The regional→root hop is a real wire
        # in the heap driver, so it sees the fault plan too: per-send
        # drop verdicts and jitter from the host-precomputed
        # (regional, up_seq) grids (all-pass / zero when no plan).
        sidx = jnp.clip(up - 1, 0, reg["send_ok"].shape[1] - 1)
        agg_ok = reg["send_ok"][r, sidx]
        t_agg = t_arr + reg["agg_delay"][r] + reg["jit"][r, sidx]
        c["agg_drop"] = c["agg_drop"] + (flush & ~agg_ok).astype(jnp.int32)
        tau_g = jnp.maximum(c["version"] - rv, 0)
        gwgt = raw * staleness_weight_arr(tau_g, cfg.alpha)
        return offer_global(
            c, flush & agg_ok, agg_params, gwgt, r, up, tau_g, t_agg, "agg"
        )

    def body(c, e):
        i = e["client"]
        # ---- adopt + train (always: a wire drop loses the SEND, not the
        # local step — heap semantics). The train step is distributed
        # into the two adoption branches with the heap's exact arithmetic
        # order (x + lr·(t − x)) so each branch is bit-identical to the
        # event driver's numpy step.
        base = jnp.searchsorted(
            c["mint"], e["t_train"] - clients["adopt_delay"][i]
        ).astype(jnp.int32)
        row = c["w"][i]
        wvec, prev = row[: cfg.dim], row[cfg.dim]
        base_f = base.astype(jnp.float32)
        adopt = base_f > prev
        g = c["G"][base]
        ti = clients["targets"][i]
        lr = jnp.float32(cfg.local_lr)
        new_vec = jnp.where(adopt, g + lr * (ti - g), wvec + lr * (ti - wvec))
        new_base = jnp.maximum(base_f, prev)
        c["w"] = c["w"].at[i].set(jnp.concatenate([new_vec, new_base[None]]))
        # re-gather from the UPDATED carry instead of reusing the new_vec
        # temporary: one value feeding two carry buffers (the w scatter
        # above + a window scatter below) defeats XLA's in-place buffer
        # reuse and re-copies the whole [N, dim] state per step —
        # measured 1000× the per-event cost at 100k clients
        row_cur = c["w"][i]
        w_cur = row_cur[: cfg.dim]
        base_eff = row_cur[cfg.dim].astype(jnp.int32)

        ok = e["send_ok"]
        samples = clients["samples"][i]
        if cfg.hier:
            r = clients["regional_of"][i]
            rv = jnp.searchsorted(
                c["mint"], e["t_arr"] - reg["adopt_delay"][r]
            ).astype(jnp.int32)
            tau = jnp.maximum(rv - base_eff, 0)
            wgt = samples * staleness_weight_arr(tau, cfg.alpha)
            c = offer_regional(
                c, ok, r, w_cur, samples, wgt, e["key_hi"], e["key_lo"], tau, rv,
                e["t_arr"],
            )
        else:
            tau = jnp.maximum(c["version"] - base_eff, 0)
            wgt = samples * staleness_weight_arr(tau, cfg.alpha)
            c = offer_global(
                c, ok, w_cur, wgt, e["key_hi"], e["key_lo"], tau, e["t_arr"], "edge"
            )
        return c, None

    @jax.jit
    def program(events, carry):
        carry, _ = jax.lax.scan(body, carry, events, unroll=cfg.unroll)
        return carry

    carry = _init_carry(cfg, init_params)
    return program(events, carry)


# ---------------------------------------------------------------------------
# chunked-event engine
# ---------------------------------------------------------------------------


def _init_carry_chunked(cfg: FleetConfig, init_params) -> Dict[str, jax.Array]:
    """The per-event carry plus one TRASH row per scatter target (client
    ``N``, regional ``R``, version ``v_cap+1``, mint ``v_cap``): masked
    scatters route their dead lanes there instead of predicating every
    write, which keeps the chunk body one straight-line program."""
    n, dim, r = cfg.n_clients, cfg.dim, cfg.n_regionals
    row0 = jnp.concatenate(
        [jnp.asarray(init_params, jnp.float32), jnp.zeros((1,), jnp.float32)]
    )
    carry = {
        "w": jnp.broadcast_to(row0, (n + 1, dim + 1)).astype(jnp.float32),
        "G": jnp.zeros((cfg.v_cap + 2, dim), jnp.float32).at[0].set(init_params),
        "mint": jnp.full((cfg.v_cap + 1,), jnp.inf, jnp.float32),
        "last_mint": jnp.float32(-jnp.inf),
        "version": jnp.int32(0),
        "gbuf": jnp.zeros((cfg.k_global + 1, dim), jnp.float32),
        "gwt": jnp.zeros((cfg.k_global + 1,), jnp.float32),
        "gkey_hi": jnp.full((cfg.k_global + 1,), PAD_KEY, jnp.int32),
        "gkey_lo": jnp.full((cfg.k_global + 1,), PAD_KEY, jnp.int32),
        "gcount": jnp.int32(0),
        "last_acc_g": jnp.float32(-jnp.inf),
        "merges": jnp.int32(0),
        "stale_edge": jnp.int32(0),
        "rate_edge": jnp.int32(0),
        "stale_agg": jnp.int32(0),
        "rate_agg": jnp.int32(0),
        "dup_agg": jnp.int32(0),
        "byz_agg": jnp.int32(0),
        "hist_edge": jnp.zeros((cfg.hist_bins,), jnp.int32),
        "hist_glob": jnp.zeros((cfg.hist_bins,), jnp.int32),
    }
    if cfg.hier:
        carry.update(
            {
                "rbuf": jnp.zeros((r + 1, cfg.k_reg_max, dim), jnp.float32),
                "rwt": jnp.zeros((r + 1, cfg.k_reg_max), jnp.float32),
                "rsamp": jnp.zeros((r + 1, cfg.k_reg_max), jnp.float32),
                "rkey_hi": jnp.full((r + 1, cfg.k_reg_max), PAD_KEY, jnp.int32),
                "rkey_lo": jnp.full((r + 1, cfg.k_reg_max), PAD_KEY, jnp.int32),
                "rcount": jnp.zeros((r + 1,), jnp.int32),
                "rparams": jnp.broadcast_to(init_params, (r + 1, dim)).astype(
                    jnp.float32
                ),
                "radopt": jnp.zeros((r + 1,), jnp.int32),
                "up_seq": jnp.zeros((r + 1,), jnp.int32),
                "last_acc_r": jnp.full((r + 1,), -jnp.inf, jnp.float32),
                "rmerges": jnp.int32(0),
                "agg_drop": jnp.int32(0),
            }
        )
    return carry


def _make_train_vec(cfg: FleetConfig, clients: Dict[str, jax.Array]):
    """The chunked engine's batched local round: ``train_vec(starts, idx,
    e)`` trains every lane of a ``[C, dim]`` start matrix as client
    ``idx``'s next local round (consensus pull toward the private
    target, or the :func:`make_grad_fns` SGD round keyed by the lane's
    ``(key_hi, key_lo)`` fold key — which IS (client, seq), so the
    drawn batch is order-independent)."""
    if cfg.task == "consensus":

        def train_vec(starts, idx, e):
            ti = clients["targets"][idx]
            lr = jnp.float32(cfg.local_lr)
            return starts + lr * (ti - starts)

    else:
        _, _, tv = make_grad_fns(
            cfg.task,
            cfg.t_din,
            cfg.t_nout,
            cfg.t_hidden,
            cfg.t_bs,
            cfg.t_steps,
            cfg.local_lr,
            cfg.data_seed,
        )

        def train_vec(starts, idx, e):
            mu = clients["mu"][idx]
            return tv(starts, e["key_hi"], e["key_lo"], mu, clients["tw"], clients["tb"])

    return train_vec


def _make_apply_byz(cfg: FleetConfig, clients: Dict[str, jax.Array]):
    """Vectorized ByzantineSpec payload transforms at the send seam
    (sign_flip / scale / noise by per-event kind code); the noise
    rows are host-drawn per attacker send (counter stream 47) and
    pre-scaled by ``noise_std``."""

    def apply_byz(p, e):
        if not cfg.byz:
            return p
        k = e["bkind"][:, None]
        p = jnp.where(k == 1, -p, p)
        p = jnp.where(k == 2, e["blam"][:, None] * p, p)
        if "bnoise" in e:
            p = jnp.where(k == 3, p + clients["noise"][e["bnoise"]], p)
        return p

    return apply_byz


def _make_chunk_body(
    cfg: FleetConfig,
    clients: Dict[str, jax.Array],
    reg: Dict[str, jax.Array],
):
    """One scan step of :func:`run_fleet_program_chunked`: the four
    passes its docstring describes (A: adopt + train, B: admission scan,
    C: flush loop, D: writebacks) over the ``[C]``-chronological event
    grids of one chunk."""
    train_vec = _make_train_vec(cfg, clients)
    apply_byz = _make_apply_byz(cfg, clients)
    C = cfg.chunk
    GF = cfg.gf_cap
    dim = cfg.dim
    v_cap = cfg.v_cap
    k_max = cfg.k_reg_max
    k_glob = cfg.k_global
    stride = cfg.agg_key_stride
    n_trash = cfg.n_clients
    r_trash = cfg.n_regionals
    v_trash = cfg.v_cap + 1
    m_trash = cfg.v_cap

    def chunk_body(c, e):
        idx = e["client"]
        live = e["live"]

        # ---- pass A: adopt + train against the PRE-chunk mint history
        mint_hist = c["mint"][:v_cap]
        base0 = jnp.searchsorted(mint_hist, e["t_adopt"]).astype(jnp.int32)
        rows0 = c["w"][idx]
        wvec0 = rows0[:, :dim]
        prev0 = rows0[:, dim]
        base0_f = base0.astype(jnp.float32)
        adopt0 = base0_f > prev0
        g0 = c["G"][base0]
        starts0 = jnp.where(adopt0[:, None], g0, wvec0)
        outs0 = train_vec(starts0, idx, e)
        newver0 = jnp.maximum(base0_f, prev0)
        c["w"] = c["w"].at[idx].set(jnp.concatenate([outs0, newver0[:, None]], axis=1))
        # re-gather from the UPDATED carry (copy law, fix 1): the staged
        # payloads must not be the same temporary that fed the w scatter
        rows_cur = c["w"][idx]
        wcur = rows_cur[:, :dim]
        prev0i = prev0.astype(jnp.int32)

        payload0 = apply_byz(wcur, e)
        samples = clients["samples"][idx]
        v0 = c["version"]
        ok0 = e["send_ok"] & live
        nm0 = jnp.full((GF,), jnp.inf, jnp.float32)

        # ---- pass B: scalar admission scan (window bookkeeping only)
        if cfg.hier:
            rr = e["r"]
            rv0 = jnp.searchsorted(mint_hist, e["t_radopt"]).astype(jnp.int32)
            rcnt0 = c["rcount"][rr]
            up0 = c["up_seq"][rr]
            lacc0 = c["last_acc_r"][rr]

            def adm(s, x):
                (ver, gcnt, gwin, nmn, lastm, laccg, nm, cnt_sc, win_sc, up_sc,
                 lacc_sc, j) = s
                adj = jnp.sum((nm < x["t_adopt"]).astype(jnp.int32))
                radj = jnp.sum((nm < x["t_radopt"]).astype(jnp.int32))
                v_a = jnp.maximum(x["base0"] + adj, x["prev0"])
                rv = x["rv0"] + radj
                tau = jnp.maximum(rv - v_a, 0)
                fresh = tau <= cfg.max_staleness
                p = x["prev_r"]
                has_p = p >= 0
                pc = jnp.clip(p, 0, C - 1)
                cnt_in = jnp.where(has_p, cnt_sc[pc], x["rcnt0"])
                win_in = jnp.where(has_p, win_sc[pc], 0)
                up_in = jnp.where(has_p, up_sc[pc], x["up0"])
                lacc_in = jnp.where(has_p, lacc_sc[pc], x["lacc0"])
                if cfg.rate_gap_reg > 0.0:
                    rate_ok = (x["t_arr"] - lacc_in) >= cfg.rate_gap_reg
                else:
                    rate_ok = jnp.bool_(True)
                acc = x["ok"]
                ins = acc & fresh & rate_ok
                cnt_new = cnt_in + ins.astype(jnp.int32)
                # >= not ==: a churn epoch can shrink k below an already
                # part-filled window; the next insertion still flushes
                flush_r = ins & (cnt_new >= x["k_r"])
                cnt_out = jnp.where(flush_r, 0, cnt_new)
                win_out = win_in + flush_r.astype(jnp.int32)
                up_new = up_in + flush_r.astype(jnp.int32)
                lacc_out = jnp.where(ins, x["t_arr"], lacc_in)

                # inline aggregate admission — the heap's order: the
                # flush's upward send crosses the wire grids, then the
                # global window, at this same position in the chunk
                sidx = jnp.clip(up_new - 1, 0, stride - 1)
                rrj = x["rr"]
                agg_ok = reg["send_ok"][rrj, sidx]
                t_agg = x["t_arr"] + reg["agg_delay"][rrj] + reg["jit"][rrj, sidx]
                if cfg.dup:
                    dup = flush_r & agg_ok & reg["dup"][rrj, sidx]
                else:
                    dup = jnp.bool_(False)
                tau_g = jnp.maximum(ver - rv, 0)
                fresh_g = tau_g <= cfg.max_staleness
                if cfg.rate_gap_glob > 0.0:
                    rate_g_ok = (t_agg - laccg) >= cfg.rate_gap_glob
                else:
                    rate_g_ok = jnp.bool_(True)
                acc_g = flush_r & agg_ok
                gins = acc_g & fresh_g & rate_g_ok
                gslot = gcnt
                gcnt_new = gcnt + gins.astype(jnp.int32)
                gflush = gins & (gcnt_new >= k_glob)
                gcnt_out = jnp.where(gflush, 0, gcnt_new)
                gwin_ins = gwin
                gwin_out = gwin + gflush.astype(jnp.int32)
                laccg_out = jnp.where(gins, t_agg, laccg)
                tm = jnp.maximum(t_agg, lastm)
                nmi = jnp.clip(nmn, 0, GF - 1)
                nm_out = nm.at[nmi].set(jnp.where(gflush, tm, nm[nmi]))
                nmn_out = nmn + gflush.astype(jnp.int32)
                lastm_out = jnp.where(gflush, tm, lastm)
                ver_out = ver + gflush.astype(jnp.int32)

                cnt_sc = cnt_sc.at[j].set(cnt_out)
                win_sc = win_sc.at[j].set(win_out)
                up_sc = up_sc.at[j].set(up_new)
                lacc_sc = lacc_sc.at[j].set(lacc_out)
                ys = {
                    "ins": ins,
                    "slot": cnt_in,
                    "win": win_in,
                    "cnt_out": cnt_out,
                    "win_out": win_out,
                    "up": up_new,
                    "tau": tau,
                    "adj": adj,
                    "flush_r": flush_r,
                    "lacc": lacc_out,
                    "stale_e": acc & ~fresh,
                    "rate_e": acc & fresh & ~rate_ok,
                    "rv": rv,
                    "gins": gins,
                    "gslot": gslot,
                    "gwin": gwin_ins,
                    "taug": tau_g,
                    "gflush": gflush,
                    "aggdrop": flush_r & ~agg_ok,
                    "dup": dup,
                    "stale_g": acc_g & ~fresh_g,
                    "rate_g": acc_g & fresh_g & ~rate_g_ok,
                }
                return (
                    ver_out, gcnt_out, gwin_out, nmn_out, lastm_out, laccg_out,
                    nm_out, cnt_sc, win_sc, up_sc, lacc_sc, j + 1,
                ), ys

            xs = {
                "t_adopt": e["t_adopt"],
                "t_radopt": e["t_radopt"],
                "t_arr": e["t_arr"],
                "base0": base0,
                "prev0": prev0i,
                "rv0": rv0,
                "rr": rr,
                "k_r": e["k_r"],
                "prev_r": e["prev_r"],
                "ok": ok0,
                "rcnt0": rcnt0,
                "up0": up0,
                "lacc0": lacc0,
            }
            s0 = (
                v0, c["gcount"], jnp.int32(0), jnp.int32(0), c["last_mint"],
                c["last_acc_g"], nm0,
                jnp.zeros((C,), jnp.int32), jnp.zeros((C,), jnp.int32),
                jnp.zeros((C,), jnp.int32), jnp.zeros((C,), jnp.float32),
                jnp.int32(0),
            )
            sf, ys = jax.lax.scan(adm, s0, xs)
            (ver_f, gcnt_f, gwin_f, nmn_f, lastm_f, laccg_f, nm_f) = sf[:7]
            valid = ys["flush_r"]
        else:

            def adm(s, x):
                (ver, gcnt, gwin, nmn, lastm, laccg, nm, j) = s
                adj = jnp.sum((nm < x["t_adopt"]).astype(jnp.int32))
                v_a = jnp.maximum(x["base0"] + adj, x["prev0"])
                tau = jnp.maximum(ver - v_a, 0)
                fresh = tau <= cfg.max_staleness
                if cfg.rate_gap_glob > 0.0:
                    rate_ok = (x["t_arr"] - laccg) >= cfg.rate_gap_glob
                else:
                    rate_ok = jnp.bool_(True)
                acc = x["ok"]
                ins = acc & fresh & rate_ok
                gslot = gcnt
                gcnt_new = gcnt + ins.astype(jnp.int32)
                gflush = ins & (gcnt_new >= k_glob)
                gcnt_out = jnp.where(gflush, 0, gcnt_new)
                gwin_ins = gwin
                gwin_out = gwin + gflush.astype(jnp.int32)
                laccg_out = jnp.where(ins, x["t_arr"], laccg)
                tm = jnp.maximum(x["t_arr"], lastm)
                nmi = jnp.clip(nmn, 0, GF - 1)
                nm_out = nm.at[nmi].set(jnp.where(gflush, tm, nm[nmi]))
                nmn_out = nmn + gflush.astype(jnp.int32)
                lastm_out = jnp.where(gflush, tm, lastm)
                ver_out = ver + gflush.astype(jnp.int32)
                ys = {
                    "ins": ins,
                    "tau": tau,
                    "adj": adj,
                    "gslot": gslot,
                    "gwin": gwin_ins,
                    "gflush": gflush,
                    "stale_e": acc & ~fresh,
                    "rate_e": acc & fresh & ~rate_ok,
                }
                return (
                    ver_out, gcnt_out, gwin_out, nmn_out, lastm_out, laccg_out,
                    nm_out, j + 1,
                ), ys

            xs = {
                "t_adopt": e["t_adopt"],
                "t_arr": e["t_arr"],
                "base0": base0,
                "prev0": prev0i,
                "ok": ok0,
            }
            s0 = (
                v0, c["gcount"], jnp.int32(0), jnp.int32(0), c["last_mint"],
                c["last_acc_g"], nm0, jnp.int32(0),
            )
            sf, ys = jax.lax.scan(adm, s0, xs)
            (ver_f, gcnt_f, gwin_f, nmn_f, lastm_f, laccg_f, nm_f) = sf[:7]
            valid = ys["gflush"]

        wgt_all = samples * staleness_weight_arr(ys["tau"], cfg.alpha)
        n_ent = jnp.sum(valid.astype(jnp.int32))
        pos = jnp.arange(C, dtype=jnp.int32)
        perm = jnp.argsort(jnp.where(valid, pos, C + pos))

        # ---- pass C: the actual flushes over compacted entry records
        if cfg.hier:
            ent = {
                "valid": valid[perm],
                "r": rr[perm],
                "win": ys["win"][perm],
                "rv": ys["rv"][perm],
                "up": ys["up"][perm],
                "gslot": ys["gslot"][perm],
                "gwin": ys["gwin"][perm],
                "gins": ys["gins"][perm],
                "taug": ys["taug"][perm],
                "gflush": ys["gflush"][perm],
            }
            wg_ent = staleness_weight_arr(ent["taug"], cfg.alpha)
            if cfg.byz:
                akind = reg["akind"][ent["r"]]
                alam = reg["alam"][ent["r"]]
                anrow = reg["agg_noise_idx"][
                    ent["r"], jnp.clip(ent["up"] - 1, 0, stride - 1)
                ]

            def ent_body(q, st):
                (prev_g, fresh_g, mcount, payload, aggout, aggw, rparams_c,
                 radopt_c) = st
                r_q = ent["r"][q]
                win_q = ent["win"][q]
                rv_q = ent["rv"][q]
                # one-hot window reconstruction (exact: ≤1 event per slot)
                mt = ys["ins"] & (rr == r_q) & (ys["win"] == win_q)
                sl = jnp.where(mt, ys["slot"], k_max)
                onehot = sl[None, :] == jnp.arange(k_max, dtype=jnp.int32)[:, None]
                any_s = jnp.any(onehot, axis=1)
                of = onehot.astype(jnp.float32)
                oi = onehot.astype(jnp.int32)
                first = win_q == 0
                base_wt = jnp.where(first, c["rwt"][r_q], 0.0)
                base_samp = jnp.where(first, c["rsamp"][r_q], 0.0)
                base_hi = jnp.where(first, c["rkey_hi"][r_q], PAD_KEY)
                base_lo = jnp.where(first, c["rkey_lo"][r_q], PAD_KEY)
                rows = jnp.where(any_s[:, None], of @ payload, c["rbuf"][r_q])
                wts = jnp.where(any_s, of @ wgt_all, base_wt)
                samp = jnp.where(any_s, of @ samples, base_samp)
                khi = jnp.where(any_s, (oi * e["key_hi"][None, :]).sum(1), base_hi)
                klo = jnp.where(any_s, (oi * e["key_lo"][None, :]).sum(1), base_lo)
                g_rv = jnp.where(
                    rv_q > v0,
                    fresh_g[jnp.clip(rv_q - v0 - 1, 0, GF - 1)],
                    c["G"][jnp.clip(rv_q, 0, v_cap)],
                )
                cur = jnp.where(rv_q > radopt_c[r_q], g_rv, rparams_c[r_q])
                merged = fold_window(
                    rows, wts, klo, cur, cfg.server_lr,
                    kind=cfg.fold_kind, trim=cfg.trim, keys_hi=khi,
                )
                rparams_c = rparams_c.at[r_q].set(merged)
                radopt_c = radopt_c.at[r_q].set(jnp.maximum(radopt_c[r_q], rv_q))
                aggp = merged
                if cfg.byz:
                    ak = akind[q]
                    aggp = jnp.where(ak == 1, -aggp, aggp)
                    aggp = jnp.where(ak == 2, alam[q] * aggp, aggp)
                    aggp = jnp.where(
                        ak == 3, aggp + reg["agg_noise"][anrow[q]], aggp
                    )
                aggout = aggout.at[q].set(aggp)
                aggw = aggw.at[q].set(jnp.sum(samp) * wg_ent[q])

                # masked global flush (the fold runs every entry — the
                # branch-free contract at entry granularity)
                gw_q = ent["gwin"][q]
                gmt = ent["gins"] & (ent["gwin"] == gw_q)
                gsl = jnp.where(gmt, ent["gslot"], k_glob)
                goh = gsl[None, :] == jnp.arange(k_glob, dtype=jnp.int32)[:, None]
                gany = jnp.any(goh, axis=1)
                gof = goh.astype(jnp.float32)
                goi = goh.astype(jnp.int32)
                gfirst = gw_q == 0
                gb_wt = jnp.where(gfirst, c["gwt"][:k_glob], 0.0)
                gb_hi = jnp.where(gfirst, c["gkey_hi"][:k_glob], PAD_KEY)
                gb_lo = jnp.where(gfirst, c["gkey_lo"][:k_glob], PAD_KEY)
                rows_g = jnp.where(gany[:, None], gof @ aggout, c["gbuf"][:k_glob])
                wts_g = jnp.where(gany, gof @ aggw, gb_wt)
                ghi = jnp.where(gany, (goi * ent["r"][None, :]).sum(1), gb_hi)
                glo = jnp.where(gany, (goi * ent["up"][None, :]).sum(1), gb_lo)
                newg = fold_window(
                    rows_g, wts_g, glo, prev_g, cfg.server_lr,
                    kind=cfg.fold_kind, trim=cfg.trim, keys_hi=ghi,
                )
                gfl = ent["gflush"][q]
                mcount_new = mcount + gfl.astype(jnp.int32)
                mi = jnp.clip(mcount, 0, GF - 1)
                fresh_g = fresh_g.at[mi].set(jnp.where(gfl, newg, fresh_g[mi]))
                prev_g = jnp.where(gfl, newg, prev_g)
                # correction sweep: adopters of this mint retrain from it
                # and their staged payloads are re-corrupted
                cm = (ys["adj"] == mcount_new) & gfl & live
                couts = train_vec(jnp.broadcast_to(newg, (C, dim)), idx, e)
                payload = jnp.where(cm[:, None], apply_byz(couts, e), payload)
                return (
                    prev_g, fresh_g, mcount_new, payload, aggout, aggw,
                    rparams_c, radopt_c,
                )

            st0 = (
                c["G"][v0],
                jnp.zeros((GF, dim), jnp.float32),
                jnp.int32(0),
                payload0,
                jnp.zeros((C, dim), jnp.float32),
                jnp.zeros((C,), jnp.float32),
                c["rparams"],
                c["radopt"],
            )
            (_, fresh_g, _, payload, aggout, aggw, rparams_c, radopt_c) = (
                jax.lax.fori_loop(0, n_ent, ent_body, st0)
            )
        else:
            ent = {"gwin": ys["gwin"][perm]}

            def ent_body(q, st):
                prev_g, fresh_g, mcount, payload = st
                gw_q = ent["gwin"][q]
                gmt = ys["ins"] & (ys["gwin"] == gw_q)
                gsl = jnp.where(gmt, ys["gslot"], k_glob)
                goh = gsl[None, :] == jnp.arange(k_glob, dtype=jnp.int32)[:, None]
                gany = jnp.any(goh, axis=1)
                gof = goh.astype(jnp.float32)
                goi = goh.astype(jnp.int32)
                gfirst = gw_q == 0
                gb_wt = jnp.where(gfirst, c["gwt"][:k_glob], 0.0)
                gb_hi = jnp.where(gfirst, c["gkey_hi"][:k_glob], PAD_KEY)
                gb_lo = jnp.where(gfirst, c["gkey_lo"][:k_glob], PAD_KEY)
                rows_g = jnp.where(gany[:, None], gof @ payload, c["gbuf"][:k_glob])
                wts_g = jnp.where(gany, gof @ wgt_all, gb_wt)
                ghi = jnp.where(gany, (goi * e["key_hi"][None, :]).sum(1), gb_hi)
                glo = jnp.where(gany, (goi * e["key_lo"][None, :]).sum(1), gb_lo)
                newg = fold_window(
                    rows_g, wts_g, glo, prev_g, cfg.server_lr,
                    kind=cfg.fold_kind, trim=cfg.trim, keys_hi=ghi,
                )
                # every flat entry IS a flush (valid == gflush)
                mcount_new = mcount + 1
                fresh_g = fresh_g.at[jnp.clip(mcount, 0, GF - 1)].set(newg)
                cm = (ys["adj"] == mcount_new) & live
                couts = train_vec(jnp.broadcast_to(newg, (C, dim)), idx, e)
                payload = jnp.where(cm[:, None], apply_byz(couts, e), payload)
                return newg, fresh_g, mcount_new, payload

            st0 = (
                c["G"][v0],
                jnp.zeros((GF, dim), jnp.float32),
                jnp.int32(0),
                payload0,
            )
            _, fresh_g, _, payload = jax.lax.fori_loop(0, n_ent, ent_body, st0)

        # ---- pass D: vectorized writebacks (one predicated scatter per
        # carry; dead lanes route to the trash rows)
        ar_gf = jnp.arange(GF, dtype=jnp.int32)
        mmask = ar_gf < nmn_f
        c["G"] = c["G"].at[jnp.where(mmask, v0 + 1 + ar_gf, v_trash)].set(fresh_g)
        c["mint"] = c["mint"].at[jnp.where(mmask, v0 + ar_gf, m_trash)].set(nm_f)
        c["version"] = ver_f
        c["last_mint"] = lastm_f
        c["gcount"] = gcnt_f
        c["last_acc_g"] = laccg_f
        c["merges"] = c["merges"] + nmn_f
        c["stale_edge"] = c["stale_edge"] + jnp.sum(ys["stale_e"].astype(jnp.int32))
        c["rate_edge"] = c["rate_edge"] + jnp.sum(ys["rate_e"].astype(jnp.int32))
        c["hist_edge"] = c["hist_edge"].at[jnp.clip(ys["tau"], 0, cfg.hist_bins - 1)].add(
            ys["ins"].astype(jnp.int32)
        )

        # global window: reset if it turned over, then fill staged slots
        greset = gwin_f > 0
        c["gwt"] = jnp.where(greset, jnp.zeros_like(c["gwt"]), c["gwt"])
        pad_g = jnp.full_like(c["gkey_hi"], PAD_KEY)
        c["gkey_hi"] = jnp.where(greset, pad_g, c["gkey_hi"])
        c["gkey_lo"] = jnp.where(greset, pad_g, c["gkey_lo"])
        if cfg.hier:
            gfill = ent["gins"] & (ent["gwin"] == gwin_f)
            gs_f = jnp.where(gfill, ent["gslot"], k_glob)
            c["gbuf"] = c["gbuf"].at[gs_f].set(aggout)
            c["gwt"] = c["gwt"].at[gs_f].set(aggw)
            c["gkey_hi"] = c["gkey_hi"].at[gs_f].set(ent["r"])
            c["gkey_lo"] = c["gkey_lo"].at[gs_f].set(ent["up"])
        else:
            gfill = ys["ins"] & (ys["gwin"] == gwin_f)
            gs_f = jnp.where(gfill, ys["gslot"], k_glob)
            c["gbuf"] = c["gbuf"].at[gs_f].set(payload)
            c["gwt"] = c["gwt"].at[gs_f].set(wgt_all)
            c["gkey_hi"] = c["gkey_hi"].at[gs_f].set(e["key_hi"])
            c["gkey_lo"] = c["gkey_lo"].at[gs_f].set(e["key_lo"])

        if cfg.hier:
            c["stale_agg"] = c["stale_agg"] + jnp.sum(ys["stale_g"].astype(jnp.int32))
            c["rate_agg"] = c["rate_agg"] + jnp.sum(ys["rate_g"].astype(jnp.int32))
            c["agg_drop"] = c["agg_drop"] + jnp.sum(ys["aggdrop"].astype(jnp.int32))
            c["dup_agg"] = c["dup_agg"] + jnp.sum(ys["dup"].astype(jnp.int32))
            c["rmerges"] = c["rmerges"] + n_ent
            c["hist_glob"] = c["hist_glob"].at[
                jnp.clip(ys["taug"], 0, cfg.hist_bins - 1)
            ].add(ys["gins"].astype(jnp.int32))
            if cfg.byz:
                c["byz_agg"] = c["byz_agg"] + jnp.sum(
                    (ent["valid"] & (akind > 0)).astype(jnp.int32)
                )
            rr_t = jnp.where(e["last_r"], rr, r_trash)
            c["rcount"] = c["rcount"].at[rr_t].set(ys["cnt_out"])
            c["up_seq"] = c["up_seq"].at[rr_t].set(ys["up"])
            c["last_acc_r"] = c["last_acc_r"].at[rr_t].set(ys["lacc"])
            c["rparams"] = rparams_c
            c["radopt"] = radopt_c
            # regional windows: reset every regional whose window turned
            # over, then fill the final window's staged slots
            rr_rst = jnp.where(e["last_r"] & (ys["win_out"] > 0), rr, r_trash)
            c["rwt"] = c["rwt"].at[rr_rst].set(jnp.zeros((C, k_max), jnp.float32))
            c["rsamp"] = c["rsamp"].at[rr_rst].set(jnp.zeros((C, k_max), jnp.float32))
            pad_r = jnp.full((C, k_max), PAD_KEY, jnp.int32)
            c["rkey_hi"] = c["rkey_hi"].at[rr_rst].set(pad_r)
            c["rkey_lo"] = c["rkey_lo"].at[rr_rst].set(pad_r)
            winfin = jnp.zeros((r_trash + 1,), jnp.int32).at[rr_t].set(ys["win_out"])
            fill = ys["ins"] & (ys["win"] == winfin[rr])
            rr_f = jnp.where(fill, rr, r_trash)
            sl_f = jnp.where(fill, ys["slot"], 0)
            c["rbuf"] = c["rbuf"].at[rr_f, sl_f].set(payload)
            c["rwt"] = c["rwt"].at[rr_f, sl_f].set(wgt_all)
            c["rsamp"] = c["rsamp"].at[rr_f, sl_f].set(samples)
            c["rkey_hi"] = c["rkey_hi"].at[rr_f, sl_f].set(e["key_hi"])
            c["rkey_lo"] = c["rkey_lo"].at[rr_f, sl_f].set(e["key_lo"])

        # corrected adopters: retrain from the fresh global they actually
        # saw (honest weights — corruption only touches the SENT copy)
        cmask = (ys["adj"] > 0) & live
        starts2 = fresh_g[jnp.clip(ys["adj"] - 1, 0, GF - 1)]
        couts2 = train_vec(starts2, idx, e)
        newver2 = (v0 + ys["adj"]).astype(jnp.float32)
        wt2 = jnp.where(cmask, idx, n_trash)
        c["w"] = c["w"].at[wt2].set(
            jnp.concatenate([couts2, newver2[:, None]], axis=1)
        )
        return c, None

    return chunk_body


def _strip_chunk_out(cfg: FleetConfig, out: Dict[str, Any]) -> Dict[str, Any]:
    """Strip the trash rows so consumers see the per-event carry shapes."""
    out["w"] = out["w"][: cfg.n_clients]
    out["G"] = out["G"][: cfg.v_cap + 1]
    out["mint"] = out["mint"][: cfg.v_cap]
    for k in ("gbuf", "gwt", "gkey_hi", "gkey_lo"):
        out[k] = out[k][: cfg.k_global]
    if cfg.hier:
        for k in (
            "rbuf", "rwt", "rsamp", "rkey_hi", "rkey_lo", "rcount", "rparams",
            "radopt", "up_seq", "last_acc_r",
        ):
            out[k] = out[k][: cfg.n_regionals]
    return out


def run_fleet_program_chunked(
    cfg: FleetConfig,
    events: Dict[str, jax.Array],
    clients: Dict[str, jax.Array],
    reg: Dict[str, jax.Array],
    init_params: jax.Array,
) -> Dict[str, Any]:
    """The fleet scan with ``cfg.chunk`` events per step — same algorithm
    as :func:`run_fleet_program`, amortizing XLA:CPU's per-op dispatch
    (the per-event engine's actual bottleneck: ~200 tiny HLO ops per
    29µs event) over a whole chunk. Flat-topology results are
    bit-identical to the per-event scan (the parity test's contract);
    the hierarchical engine inherits the per-event engine's documented
    aggregate-ordering tolerance unchanged.

    The decomposition (see docs/design.md "chunked-event scan"):

    1. **Pass A** — batched gather + one vmapped train for all ``C``
       events against the PRE-chunk mint history, one scatter into
       ``w``. Sound because the host pads chunks so no client appears
       twice per chunk, and any event whose adoption base is moved by an
       IN-chunk mint is provably an adopter (a new mint time sits below
       its threshold ⟹ every earlier mint does too ⟹ ``base0`` was
       already the pre-chunk version), so its row is recomputed from the
       fresh global in pass C and re-scattered.
    2. **Admission scan** — the sequential window bookkeeping reduced to
       SCALAR ops: one inner ``lax.scan`` over the chunk carrying only
       counters, the in-chunk mint times (for the ``adj``/``radj``
       base corrections) and tiny per-chunk chain scratches (per-regional
       counts threaded through ``prev_r`` links precomputed by the
       host). Big-array state is never touched here — per-event outputs
       ride out as stacked ``ys``.
    3. **Pass C** — the few actual flushes (``n_ent ≤ C``, typically
       ``C/k``) run in a ``fori_loop`` over COMPACTED entry records;
       each reconstructs its window by an exact one-hot gather over the
       chunk's staged payloads (masked-tail rule: slots not staged
       in-chunk fall back to the pre-chunk window for window 0 and to
       empty pads — weight 0, PAD key, an exact ``+0.0`` in the fold —
       for later windows), folds it with :func:`fold_window`, and
       applies byzantine transforms at the aggregate seam.
    4. **Writebacks** — one predicated scatter per carry buffer: fresh
       globals/mints via trash-masked index vectors, window resets then
       final-window fills, and the corrected-adopter ``w`` rows. The
       cross-buffer copy law survives because every value that feeds two
       buffers is re-gathered from an already-updated carry (pass A's
       ``w`` re-gather) or materialized per-chunk (``[C]``-sized
       temporaries), exactly the per-event engine's two fixes at chunk
       granularity.

    The step itself is :func:`_make_chunk_body`.
    """
    chunk_body = _make_chunk_body(cfg, clients, reg)

    @jax.jit
    def program(events, carry):
        carry, _ = jax.lax.scan(chunk_body, carry, events, unroll=cfg.unroll)
        return carry

    carry = _init_carry_chunked(cfg, init_params)
    return _strip_chunk_out(cfg, dict(program(events, carry)))
