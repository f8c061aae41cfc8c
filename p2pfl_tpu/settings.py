"""Global configuration knobs.

Mirrors the reference's single mutable ``Settings`` class
(``p2pfl/settings.py:26-115``): class attributes mutated in place, read by
every layer. Same knob names where the concept is the same, so users of the
reference find what they expect; TPU-specific knobs are added at the bottom.
"""

from __future__ import annotations

from typing import Optional


class Settings:
    """Mutable global settings (class attributes, no instances needed)."""

    # --- general ---
    GRPC_TIMEOUT: float = 10.0  # seconds; also used by the memory transport
    LOG_LEVEL: str = "INFO"
    LOG_DIR: str = "logs"
    EXCLUDE_BEAT_LOGS: bool = True

    # --- heartbeat (membership / failure detection) ---
    HEARTBEAT_PERIOD: float = 2.0
    HEARTBEAT_TIMEOUT: float = 5.0

    # --- gossip (message plane) ---
    GOSSIP_PERIOD: float = 0.1
    TTL: int = 10
    GOSSIP_MESSAGES_PER_PERIOD: int = 100
    AMOUNT_LAST_MESSAGES_SAVED: int = 100

    # --- gossip (model plane) ---
    GOSSIP_MODELS_PERIOD: float = 1.0
    GOSSIP_MODELS_PER_ROUND: int = 2
    GOSSIP_EXIT_ON_X_EQUAL_ROUNDS: int = 10

    # --- gossip (data plane: encode-once + concurrent fan-out) ---
    # Worker threads per gossiper for dispatching sends (both planes). A
    # stalled peer occupies one worker slot instead of serializing the
    # whole tick behind it; 1 restores the pre-overhaul strictly sequential
    # behavior — sends run inline on the calling thread with NO send
    # budget, so a stalled peer once again blocks its whole tick.
    GOSSIP_SEND_WORKERS: int = 4
    # Per-send wall-clock budget: a tick stops waiting for a send after
    # this many seconds (the send keeps running on its worker and the
    # neighbor is skipped while it is still in flight).
    GOSSIP_SEND_TIMEOUT: float = 5.0
    # Reuse encoded weight payload bytes across candidates/ticks while the
    # model version is unchanged (learning/weights.py PayloadCache). False
    # re-encodes per send — only useful for benchmarking the cache itself.
    GOSSIP_PAYLOAD_CACHE: bool = True
    # In-memory transport: round-trip weight payloads through the wire
    # codec (encode on send, materialize on receive) instead of passing
    # the pytree by reference. Simulations stay zero-copy by default; True
    # exercises/benches the real byte path without sockets (bench_gossip).
    MEMORY_WIRE_CODEC: bool = False

    # --- control-plane reliability (communication/reliability.py) ---
    # Failed message-plane sends are retried with exponential backoff +
    # jitter up to this many attempts (0 restores the old fire-and-forget
    # behavior where a False return silently lost the broadcast).
    MESSAGE_RETRY_MAX: int = 4
    # First-retry backoff; attempt a waits BASE * 2**(a-1), capped below.
    MESSAGE_RETRY_BASE: float = 0.25
    MESSAGE_RETRY_CAP: float = 2.0
    # Consecutive send failures (any plane) before a neighbor is SUSPECT.
    BREAKER_THRESHOLD: int = 3
    # A suspect neighbor is evicted after this many seconds of beat
    # silence instead of the full HEARTBEAT_TIMEOUT — send failures feed
    # failure detection continuously (accrual-style) rather than relying
    # on one binary timeout. Must exceed HEARTBEAT_PERIOD with slack
    # (keep ~2x): a live suspect's last_beat age reaches a full period
    # between beats, and a window equal to the period would evict on
    # ordinary delivery jitter rather than actual silence.
    BREAKER_SUSPECT_TIMEOUT: float = 4.0
    # Mid-round train-set repair (learning/aggregators/aggregator.py):
    # when a train-set member is evicted mid-round, shrink the round's
    # coverage target to the live members and re-announce coverage, so
    # aggregation resolves to the survivors' partial instead of burning
    # the full AGGREGATION_TIMEOUT. Automatically inert under
    # SECURE_AGGREGATION (secagg's seed-recovery machinery owns dropouts
    # there — masks must be recovered, not skipped).
    TRAIN_SET_REPAIR: bool = True
    # An init_model that arrives BEFORE this node processed start_learning
    # (the weights plane can beat the TTL-flooded control broadcast,
    # especially when start_learning rides a retry backoff) is stashed and
    # consumed by StartLearningStage if the experiment starts within this
    # many seconds — instead of being dropped and relying on a redelivery
    # the initiator's push loop may never make (it exits once its status
    # view stops changing). The window is the ONLY discriminator between
    # that race and a LATE init from a previous aborted experiment for
    # frames from OLD senders that lack the optional "xp" experiment-
    # identity header (frames that carry it are filtered exactly, with no
    # heuristics — Node.take_early_init), so keep it just wide enough
    # for the race: total message-plane retry backoff (~ MESSAGE_RETRY_MAX
    # backoffs capped at MESSAGE_RETRY_CAP) plus flood relay lag — and
    # well under any realistic gap between experiments, or a stale stash
    # could seed the next experiment and shadow its real init.
    EARLY_INIT_TTL: float = 15.0

    # --- learning round ---
    TRAIN_SET_SIZE: int = 4
    VOTE_TIMEOUT: float = 60.0
    AGGREGATION_TIMEOUT: float = 300.0
    WAIT_HEARTBEATS_CONVERGENCE: float = 1.0
    # The reference votes only in round 0 and reuses that train set forever
    # (``round_finished_stage.py:69-70``). False replicates that; True
    # re-elects every round (recommended when nodes churn).
    VOTE_EVERY_ROUND: bool = False

    # --- monitoring ---
    # Round flight recorder (management/telemetry.py): wire-propagated
    # trace spans, the unified counter/histogram registry and the
    # Perfetto-loadable exporter. Disabling skips span/histogram recording
    # entirely (counters — comm metrics, dispatch counts — always stay on:
    # they are load-bearing for tests and benches, and one locked dict
    # increment is not measurable overhead).
    TELEMETRY_ENABLED: bool = True
    # Per-node span ring-buffer bound: a flight recorder keeps the recent
    # past, not an archive — old spans fall off instead of growing memory
    # for the life of a long federation.
    TELEMETRY_RING_SPANS: int = 4096
    # Record spans for heartbeat 'beat' sends/receives. Off by default:
    # beats flood at 1/HEARTBEAT_PERIOD per neighbor and would both crowd
    # the ring and dominate the overhead budget (the same rationale as
    # EXCLUDE_BEAT_LOGS; beat *evictions* and breaker transitions are
    # always recorded as events).
    TELEMETRY_BEAT_SPANS: bool = False
    # Bridge dispatch spans to jax.profiler.TraceAnnotation so the host-side
    # dispatch timeline lines up with XLA's device timeline in a captured
    # profiler trace. None = auto (annotate on accelerators, skip on CPU
    # where there is no separate device timeline to correlate).
    TELEMETRY_JAX_ANNOTATIONS: Optional[bool] = None
    RESOURCE_MONITOR_PERIOD: float = 1.0
    # Stall watchdog (management/watchdog.py): when > 0, a daemon thread
    # dumps every thread's stack if a learning node makes no stage
    # transition for this many seconds. Detection only; 0 disables.
    STALL_WATCHDOG_S: float = 0.0

    # --- TPU-native additions ---
    # Default dtype for on-wire / aggregation math. bfloat16 keeps matmuls on
    # the MXU; aggregation accumulates in float32 for exactness.
    COMPUTE_DTYPE: str = "bfloat16"
    AGG_DTYPE: str = "float32"
    # Donate weight buffers into jitted aggregation / train steps.
    DONATE_BUFFERS: bool = True
    # Mesh axis names used by the parallel runtime. ``nodes`` indexes
    # federated nodes (or node slices), ``model`` is intra-node tensor
    # parallelism, ``data`` is intra-node batch parallelism (submesh
    # federations — parallel/submesh.py — give every node a
    # ``(data, model)`` slice of the global ``(nodes, data, model)`` mesh).
    MESH_NODES_AXIS: str = "nodes"
    MESH_MODEL_AXIS: str = "model"
    MESH_DATA_AXIS: str = "data"
    # Outgoing gRPC frame format: "envelope" (compact JSON-header frames,
    # the default) | "protobuf" (the reference's node.proto schema —
    # communication/proto_wire.py; control plane fully interoperable with
    # a reference node, weight payloads stay the safe P2TW codec).
    # Receivers sniff per frame, so mixed-format federations interoperate
    # regardless of this knob.
    WIRE_FORMAT: str = "envelope"
    # Wire compression for network transports: "none" | "int8" | "topk8"
    # (int8 = symmetric per-tensor quantization, 4x smaller gossip payloads,
    # native C++ hot loop when p2pfl_tpu/native is built; topk8 = top-k
    # sparsified int8 DELTAS against the round-start global model — 0.25
    # bytes/param at the default fraction, 16x under dense float32 — with
    # error feedback).
    WIRE_COMPRESSION: str = "none"
    # Fraction of delta coordinates kept per tensor by topk8.
    TOPK_FRACTION: float = 0.05
    # Run the int8/topk8 encode as one fused device program
    # (ops/compression.py): (params − anchor) delta, error-feedback add,
    # batched top-k and int8 quantization in a single jit dispatch, with
    # only the compressed (idx, q, scale) buffers crossing device→host and
    # the EF residual staying device-resident between rounds. Engages only
    # when the params are already jax Arrays; False forces the host numpy
    # path (bit-format-compatible baseline — one decoder decodes both).
    # The decode side mirrors it: a device-resident anchor is updated by a
    # fused scatter-add instead of a host ravel-copy.
    #
    # None (the default) auto-selects by backend: the device producer on
    # accelerators (where eliminating the D2H pull is the point), the host
    # producer on XLA:CPU (where "device" is the same host and XLA's exact
    # TopK loses wall-clock to numpy's introselect — the PR-4 measurement).
    # An explicit True/False overrides the auto-select either way; read the
    # resolved value through :func:`wire_compression_device`.
    WIRE_COMPRESSION_DEVICE: Optional[bool] = None
    # Fuse the overlay round's node compute (eval forward + local epochs +
    # the node's own weighted fp32 partial-aggregation fold) into ONE
    # donated jit dispatch per node per round (parallel/spmd.py
    # fused_node_round, driven by JaxLearner.fused_round). The staged path
    # (eval dispatch + one train dispatch per epoch + host-side metric
    # syncs between them) is kept as the bit-parity baseline behind
    # False — the same pattern as CHUNK_FUSED_REDUCE. Learners that
    # cannot fuse (DummyLearner, LoRA, personalization, DP-SGD) fall back
    # to the staged path automatically.
    ROUND_FUSED: bool = True
    # Error feedback for topk8: dropped coordinates accumulate locally and
    # re-enter the next round's delta (Seide et al. 2014).
    TOPK_ERROR_FEEDBACK: bool = True

    # --- streaming byte plane (learning/weights.py + grpc_transport.py) ---
    # Client-streaming weights sends: a payload estimated at/above
    # WIRE_STREAM_THRESHOLD megabytes ships as a sequence of
    # self-delimiting P2TC chunk frames over ``send_weights_stream``
    # instead of one unary blob — encode of chunk i+1, wire transfer of
    # chunk i and receiver-side decode of chunk i−1 overlap, and the
    # receiver's peak payload memory is O(chunk × window) instead of
    # O(model). Chunk bodies concatenate to EXACTLY the unary P2TW frame
    # (one decoder core, byte-compatible at the leaf level). False
    # disables both sending streams and accepting them (a peer with
    # streaming off answers "stream-unsupported" and senders fall back
    # loudly to unary for that peer — ``stream_fallback_unary`` metric).
    # Protobuf-interop peers (WIRE_FORMAT="protobuf") never stream.
    WIRE_STREAM_ENABLED: bool = True
    # Stream-vs-unary cut, in MB of ESTIMATED payload (cheap metadata walk,
    # no encode): small payloads keep the one-round-trip unary path — the
    # pipeline only pays for itself when a payload spans many chunks.
    WIRE_STREAM_THRESHOLD: float = 8.0
    # Chunk slab size (MB). Cuts are leaf-aligned when leaves are smaller
    # than a slab (so the receiver decodes whole leaves per chunk); leaves
    # larger than a slab are split. 1–4 MB amortizes per-chunk overhead
    # (17-byte frame + CRC32C pass) while keeping the bounded-memory
    # window small.
    WIRE_CHUNK_MB: float = 2.0
    # In-flight chunk budget of the memory transport's streaming pump (a
    # bounded queue between the producer thread and the receiving
    # dispatch) — the backpressure window a real socket's flow control
    # gives the gRPC path. Receiver scratch is bounded by roughly
    # WIRE_CHUNK_MB × this window plus one leaf.
    WIRE_STREAM_WINDOW: int = 4
    # gRPC max send/receive message size (MB), applied to every channel
    # AND the server. gRPC's 4 MB default silently caps unary weights
    # payloads (RESOURCE_EXHAUSTED); raise this for big unary models —
    # streamed chunks stay ~WIRE_CHUNK_MB regardless.
    GRPC_MAX_MESSAGE_MB: int = 512
    # gRPC server executor threads (was hardcoded 4): a high-fan-in
    # aggregator otherwise serializes every inbound handler behind 4
    # threads.
    GRPC_SERVER_WORKERS: int = 4

    # --- shard-native ICI weights plane (communication/ici.py) ---
    # Which transport carries MODEL payloads between co-located nodes:
    # "bytes" is the existing behavior (the weights plane rides the same
    # transport as the control plane — encoded frames over gRPC, or the
    # in-memory reference/byte path); "ici" exchanges SHARDS shard-to-
    # shard between nodes registered on the shard-plane registry — each
    # device copies its parameter block directly to the matching device
    # of the peer's slice (a collective-permute / Pallas remote DMA over
    # the interconnect), composing with the device-side top-k/int8 codec
    # so the encode→transfer→decode→merge chain never touches the host.
    # The control plane (votes, coverage, beats) ALWAYS keeps riding the
    # byte transport; per-peer ineligibility (unregistered peer,
    # different process, mismatched slice topology) falls back loudly to
    # the byte path for that peer only (``ici_fallback_bytes`` metric),
    # never aborts the round. "dcn" is the superset plane: co-resident
    # peers still ride ICI, and peers in a DIFFERENT process of the same
    # ``jax.distributed`` world move model payloads as device arrays over
    # XLA's cross-host collectives (communication/dcn.py +
    # parallel/dcn_plane.py) — never pickled numpy over gRPC — with the
    # same per-edge loud byte fallback (``dcn_fallback_bytes``) for
    # everything else. Per-edge ladder under "dcn": ICI → DCN → bytes.
    WEIGHTS_PLANE: str = "bytes"
    # Shard-transfer backend for the ICI plane: "pallas" is the TPU
    # remote-DMA kernel (parallel/ici_plane.py — each device RDMAs its
    # block straight to the partner device's HBM), "ppermute" the pure-
    # XLA collective-permute program that runs anywhere (the CPU-runnable
    # bit-parity fallback the chaos suite and tier-1 exercise). "auto"
    # resolves by backend via :func:`ici_backend`: pallas on TPU,
    # ppermute elsewhere. Both move the same shards — backend choice can
    # never change what the receiver decodes.
    ICI_BACKEND: str = "auto"
    # --- DCN weights-plane rendezvous (communication/dcn.py) ---
    # World-directory snapshot TTL: peer-address → process-placement
    # lookups read the distributed runtime's KV store at most once per
    # this many seconds and serve from the snapshot in between.
    DCN_DIR_TTL_S: float = 2.0
    # How long a sender waits for the receiver's accept/nack before
    # aborting the rendezvous and falling back to the byte path.
    DCN_ACCEPT_TIMEOUT_S: float = 5.0
    # How long either side waits for the peer's ready (and for this
    # process's dispatch-order lock) before aborting — the bound that
    # turns any rendezvous disorder into a loud fallback, never a hang.
    DCN_READY_TIMEOUT_S: float = 10.0
    # How long a sender waits for the receiver's decode+delivery verdict
    # AFTER the collective fired. Expiry FAILS the send (gossip retry
    # machinery takes over) instead of falling back — the payload may
    # already have landed, and a byte resend could double-deliver.
    DCN_DONE_TIMEOUT_S: float = 60.0

    # --- async bounded-staleness federation (p2pfl_tpu/federation/) ---
    # Which control plane drives the learning thread: "sync" is the round
    # FSM (stages/learning_stages.py — barrier-synchronized rounds, the
    # reference semantics); "async" is the FedBuff-style buffered control
    # plane (federation/workflow.py — contributions apply as they arrive
    # with a staleness weight, no round barrier; Nguyen et al. 2022).
    FEDERATION_MODE: str = "sync"
    # Buffer size K: an aggregator merges once K accepted contributions
    # are buffered (FedBuff's one tunable). Aggregator tiers clamp it to
    # their fan-in (min(K, #children)) so a small cluster still flushes.
    FEDBUFF_K: int = 4
    # Staleness-weight exponent α in w(τ) = 1/(1+τ)^α: τ is how many
    # global model versions elapsed between the version a contribution
    # was trained FROM and the version it merges INTO. 0 disables
    # down-weighting; 0.5 is FedBuff's default polynomial weighting.
    FEDBUFF_ALPHA: float = 0.5
    # Server mixing rate η: new_global = (1-η)·global + η·weighted_avg.
    # 1.0 replaces the global with the buffer's staleness-weighted average
    # (the FedAvg-like limit); lower values damp each merge.
    FEDBUFF_SERVER_LR: float = 1.0
    # BOUNDED staleness: contributions older than this many global
    # versions are dropped (counted async_stale_drop) instead of merged
    # with a vanishing weight — the bound that keeps a wedged straggler's
    # months-old update from ever touching the model.
    ASYNC_MAX_STALENESS: int = 16
    # Hierarchical topology (federation/topology.py): members are chunked
    # into edge clusters of this size, each with an elected regional
    # aggregator buffering locally and pushing one aggregate per flush to
    # the global tier. 0 = flat (single global aggregator, FedBuff
    # classic). Clamped to the fleet size.
    HIER_CLUSTER_SIZE: int = 0
    # How long an aggregator keeps serving after finishing its own local
    # update budget, waiting for slower members' async_done announcements
    # (eviction of a dead member also releases it) before it exits.
    ASYNC_DRAIN_TIMEOUT: float = 30.0
    # How long a node JOINING a running async experiment
    # (Node.join_async_experiment) waits for its bootstrap pull — the
    # nearest aggregator's current global, requested via async_pull —
    # before contributing from its own local init instead. The pull is a
    # single direct round-trip, so this only needs to cover connection
    # setup plus one full-model push.
    ASYNC_JOIN_TIMEOUT: float = 15.0
    # --- crash-resurrection journal (federation/durability.py) ---
    # Snapshot cadence: a node with a journal attached commits one
    # snapshot every N of its own training updates (plus one final
    # snapshot at drain/leave). 1 = after every update — the tightest
    # recovery point; raise to amortize the disk write against very
    # short local epochs.
    JOURNAL_EVERY_N_UPDATES: int = 1
    # Journal retention: keep the newest N committed snapshots (the
    # manifest-committed one is always kept). 0 = keep all — only for
    # forensic runs; a long-lived fleet member writes one snapshot per
    # update forever.
    JOURNAL_KEEP_N: int = 3
    # Resurrection sequence margin: a resumed node restarts its own
    # train/up sequence counters at journaled_next + margin, covering
    # updates minted AFTER the last snapshot but BEFORE the crash (at
    # most JOURNAL_EVERY_N_UPDATES of them in flight, but duplicate
    # timers can re-deliver). Upstream VersionVectors accept seq gaps by
    # design (a gap is a lost update, not a protocol error), so the only
    # cost of a generous margin is a cosmetic hole in the sequence.
    JOURNAL_SEQ_MARGIN: int = 16
    # Orbax retention for learning/checkpoint.py save_state: keep the
    # newest N checkpoint steps (CheckpointManagerOptions.max_to_keep).
    # 0 = unbounded (the pre-durability behavior, kept as the default
    # for standalone checkpointing); the journal passes its own
    # JOURNAL_KEEP_N explicitly.
    CHECKPOINT_KEEP_N: int = 0
    # --- Megafleet (federation/megafleet.py, ops/fleet_kernels.py) ---
    # Default Bonawitz production knobs for the vectorized fleet engine,
    # read ONCE at MegaFleet construction (never inside a traced body —
    # the jit-staleness contract). Pace steering: each simulated client's
    # whole schedule is offset by a seeded uniform draw in
    # [0, PACE_WINDOW) virtual seconds, spreading the first-wave
    # thundering herd (0 disables).
    MEGAFLEET_PACE_WINDOW: float = 0.0
    # Selection: each (client, update) slot participates with this
    # probability (an unselected device idles the period — Bonawitz §4's
    # device selection; over-provisioning = selecting more than the
    # buffers need and measuring the wasted work). 1.0 = everyone.
    MEGAFLEET_SELECT_FRAC: float = 1.0
    # Per-tier rate limits (virtual seconds between ACCEPTED offers at a
    # regional window / the global window): a tier refuses offers landing
    # inside the gap (counted rate_limited, never raising). 0 disables
    # and compiles the gate out of the scan.
    MEGAFLEET_REGIONAL_RATE_S: float = 0.0
    MEGAFLEET_GLOBAL_RATE_S: float = 0.0
    # lax.scan unroll factor for the fleet program — a throughput/compile
    # -time trade on multi-million-event scans.
    MEGAFLEET_SCAN_UNROLL: int = 1
    # Events per scan step of the chunked engine (ops/fleet_kernels.py
    # run_fleet_program_chunked): each step batch-gathers CHUNK sorted
    # arrivals, runs the sequential admission logic as cheap scalar ops,
    # and scatters every dense-carry write back in one predicated pass —
    # amortizing XLA:CPU's per-op dispatch over the chunk. 1 selects the
    # per-event reference engine (the bit-parity baseline); anything
    # below 1 is refused at construction.
    MEGAFLEET_CHUNK: int = 256
    # --- Byzantine robustness (federation/defense.py, ops/aggregation.py) ---
    # Which merge kernel the async plane's BufferedAggregator folds a
    # flushed buffer with: "fedavg" is the FedBuff staleness-weighted mean
    # (the pre-robustness behavior); "trimmed-mean" and "median" are the
    # per-coordinate rank-based robust rules (they ignore the staleness
    # weights by construction — rank statistics have no weighted analogue
    # that keeps their breakdown point); "krum-screen" runs Krum selection
    # to DROP the BYZ_F most outlying contributions and then applies the
    # normal staleness-weighted mean over the survivors (weights kept).
    # Every kernel folds the same (origin, seq)-sorted buffer, so the
    # arrival-order-independence determinism contract is unchanged.
    ASYNC_ROBUST_AGG: str = "fedavg"
    # Coordinates trimmed from EACH side per coordinate by the
    # "trimmed-mean" kernel (clamped to (K-1)//2 — at least one value must
    # survive). Robust to ASYNC_TRIM Byzantine contributions per buffer.
    ASYNC_TRIM: int = 1
    # Assumed Byzantine contribution count f for "krum-screen" (and the
    # sharded robust folds' krum variant): f contributions are screened
    # out of each flush. Clamped so at least one contribution survives.
    BYZ_F: int = 1
    # Defense-in-depth admission screen (federation/defense.py): every
    # single-origin contribution at BOTH aggregator seams (the sync
    # Aggregator.add_model and the async BufferedAggregator.offer) is
    # checked against the current global — an L2-norm gate plus a
    # cosine-distance outlier score, one tiny jitted reduction per
    # contribution — before it may enter a fold. Rejections feed a
    # per-origin suspicion EWMA; past BYZ_SUSPICION_THRESHOLD the origin
    # is QUARANTINED through the existing eviction path (breaker /
    # mark_dead / TierRouter re-derivation), so a semantic attacker is
    # removed by the same machinery that removes a corpse. Off by
    # default: screening is a behavioral change (it can reject honest
    # outliers under extreme non-IID data) and is opt-in like the robust
    # kernels.
    BYZ_SCREEN: bool = False
    # Norm gate: reject a contribution whose L2 norm is more than this
    # factor away from the current global's (ratio outside
    # [1/gate, gate]). Sized for weights-space updates (a local step's
    # norm stays near the global's); scale attacks at |λ| >= gate are
    # caught here.
    BYZ_NORM_GATE: float = 4.0
    # Cosine gate: reject when cos(update, global) falls below this.
    # Honest weights-space updates stay close to the global they trained
    # from (cos ≈ 1); sign flips sit at −1, heavy noise near 0.
    BYZ_COS_GATE: float = 0.5
    # Suspicion EWMA step: s ← (1−β)·s + β·[rejected]. At 0.5 two
    # consecutive rejections cross the default threshold.
    BYZ_SUSPICION_BETA: float = 0.5
    # Suspicion level at which an origin is quarantined (monotone: once
    # quarantined, an origin's contributions are dropped for the rest of
    # the experiment even if it starts behaving).
    BYZ_SUSPICION_THRESHOLD: float = 0.7

    # Secure aggregation (pairwise masking, learning/secagg.py): when True,
    # train-set nodes Diffie-Hellman a seed per peer at experiment start and
    # mask their model contribution; masks cancel in the FedAvg sum, so no
    # individual model ever crosses the wire in the clear. FedAvg only.
    SECURE_AGGREGATION: bool = False
    # Per-pair Gaussian mask scale: pair (i,j) is masked at
    # STD*sqrt(w_j/w_i) on node i (sample counts announced with the DH
    # keys), so the mask drowns the parameters regardless of how large the
    # local datasets are. Requires WIRE_COMPRESSION="none".
    SECAGG_MASK_STD: float = 100.0
    # --- federation round hot path (parallel/chunked.py, parallel/spmd.py) ---
    # How many chunks ahead ChunkedFederation stages inputs (per-round perm
    # indices, and x/y chunks when the dataset is not device-resident)
    # while earlier chunks compute. 1 = stage each chunk immediately before
    # its dispatch (the pre-overhaul serial behavior); 2 = classic double
    # buffering (chunk k+1's host→device copies overlap chunk k's compute).
    # Host-side knob — changing it never retraces or recompiles.
    CHUNK_STAGING_DEPTH: int = 2
    # Fold the per-chunk weighted reduce into the chunk program: partial
    # sums ride donated accumulator arguments and update ON DEVICE (one
    # dispatch per chunk). False restores the host-side
    # ``jax.tree.map(jnp.add, ...)`` over full pytrees after every chunk —
    # 2×leaf-count eager dispatches per chunk — kept as the reference
    # semantics for the bit-parity test and for debugging.
    CHUNK_FUSED_REDUCE: bool = True
    # Donate the running accumulators (param/opt partial sums) into the
    # chunk program so XLA writes each chunk's update into the same HBM
    # buffers instead of allocating a fresh full-model set per chunk.
    # False keeps every chunk's inputs alive (copy-safe debugging path).
    CHUNK_DONATE_BUFFERS: bool = True
    # SCAFFOLD fast path: derive each node's new control variate from the
    # mean of its local raw gradients accumulated in the epoch scan carry
    # (algebraically identical to Karimireddy et al. 2020 option II under
    # plain SGD: (x − y_i)/(K·η) = mean_t(g_t) + (c − c_i)), instead of
    # re-deriving it from the retained round-start params. Kills the fp32
    # anchor round-trip after the scan; False restores the anchor-based
    # formula (parity-tested — tests/test_round_pipeline.py). Participates
    # in the jit cache key (traced-program knob).
    SCAFFOLD_FUSED_CI: bool = True
    # Sequence length at/above which attn="auto" picks the Pallas flash
    # kernel over fused dense XLA attention (TPU backends only — anywhere
    # else the kernel runs in interpret mode and "auto" stays dense).
    # The builders' rounds-3–5 account put the crossover at 1024 (flash
    # ahead from T=1024 up, behind at 512; record deleted at PR 21, see git
    # history). Not measured on this installation — `python bench_suite.py
    # 7` on the chip re-measures it.
    FLASH_MIN_SEQ_LEN: int = 1024
    # Autotune the flash-attention kernel schedule at model-build time:
    # tiny_transformer(attn="flash"|"ring_flash") sweeps (block_q, block_k,
    # q_span) + backward mode for the model's (seq_len, head_dim, dtype)
    # and caches the winner (ops/autotune.py — in-process + on-disk, keyed
    # on device kind). False = pure lookup: pinned config → existing tune
    # cache → shipped defaults table (no kernels run at build time).
    FLASH_AUTOTUNE: bool = False
    # Path of the on-disk autotune cache; "" = the default
    # ~/.cache/p2pfl_tpu/flash_tune.json (P2PFL_FLASH_TUNE_CACHE env var
    # also honored).
    FLASH_TUNE_CACHE: str = ""
    # How long a train-set node waits for peers' secagg_recover seed
    # disclosures after an aggregation timeout with dropouts, before giving
    # the round up (keeping the previous global instead of applying noise).
    SECAGG_RECOVERY_TIMEOUT: float = 30.0
    # Full Bonawitz double masking: each contribution also carries a
    # per-round SELF mask whose seed is t-of-n Shamir-shared with the train
    # set (learning/secagg.py). Guarantees that for every (node, round) at
    # most one of {pair seeds, self seed} ever becomes public, so a masked
    # update captured on the wire stays masked even through dropout
    # recovery. Costs one extra mask stream + two small control broadcasts
    # per node per round. False = round-3 behavior (pairwise masks only,
    # with the documented single-update disclosure risk on dropout).
    SECAGG_DOUBLE_MASK: bool = True


def wire_compression_device() -> bool:
    """Resolve ``Settings.WIRE_COMPRESSION_DEVICE`` (None = by backend).

    The auto-select encodes the PR-4 measurement: the fused device
    producer exists to keep the full fp32 model + anchor pull off the
    D2H link, which only pays on a real accelerator; on XLA:CPU the
    "device" is the same host and its exact TopK (partial sort) loses
    wall-clock to numpy's introselect, so the host producer wins there.
    Both producers emit bit-layout-identical frames, so the auto-select
    can never change what a receiver decodes — only who does the work.
    """
    explicit = Settings.WIRE_COMPRESSION_DEVICE
    if explicit is not None:
        return bool(explicit)
    import jax

    return jax.default_backend() != "cpu"


def ici_backend() -> str:
    """Resolve ``Settings.ICI_BACKEND`` ("auto" = by backend).

    The Pallas remote-DMA kernel only lowers on real TPU hardware; the
    pure-XLA ``ppermute`` program is the bit-parity fallback everywhere
    else (including the 8-virtual-device CPU mesh tier-1 runs on). An
    explicit "pallas"/"ppermute" overrides the auto-select either way.
    """
    explicit = Settings.ICI_BACKEND
    if explicit != "auto":
        return explicit
    import jax

    return "pallas" if jax.default_backend() == "tpu" else "ppermute"


def telemetry_jax_annotations() -> bool:
    """Resolve ``Settings.TELEMETRY_JAX_ANNOTATIONS`` (None = by backend).

    The annotation bridge exists to line host dispatch spans up with XLA's
    device timeline inside a captured ``jax.profiler`` trace — which only
    exists on a real accelerator; on CPU the extra TraceAnnotation call is
    pure overhead with nothing to correlate against.
    """
    explicit = Settings.TELEMETRY_JAX_ANNOTATIONS
    if explicit is not None:
        return bool(explicit)
    import jax

    return jax.default_backend() != "cpu"


def set_low_latency_settings() -> None:
    """Documented low-latency profile for reliable local networks.

    The defaults above mirror the reference's knobs, which are tuned for
    lossy wide-area overlays (1 s model-gossip ticks, 2 s heartbeats,
    60 s vote windows). On a reliable local network — one host, a rack,
    or a TPU-pod's DCN — those quantize every round to multiples of
    whole seconds for no benefit. This profile keeps EVERY semantic
    (same verbs, same stall/timeout exits, same vote formula; only the
    clocks shrink) while cutting protocol overhead per round to
    sub-second (fan-out and capacity knobs like GOSSIP_MODELS_PER_ROUND
    are deliberately untouched):

    - model-gossip tick 1 s → 0.05 s: the tick loop re-checks peer
      status 20×/s instead of 1×/s, so the diffusion/partial loops exit
      ~0.5 s after the decisive message instead of up to 1 s + stall
      window (stall exit stays at GOSSIP_EXIT_ON_X_EQUAL_ROUNDS ticks —
      the same number of unchanged observations).
    - heartbeats 2/5 s → 0.3/1.5 s: membership converges in ~0.3 s; the
      WAIT_HEARTBEATS_CONVERGENCE pause shrinks to match.
    - vote/aggregation ceilings 60/300 s → 15/60 s: failure detection
      latency, not steady-state cost — rounds that complete never see
      them.

    Measured effect (BASELINE config 1, 2-node MNIST MLP, CPU): protocol
    overhead drops under the per-round compute (fit + eval dominate).
    """
    Settings.GRPC_TIMEOUT = 2.0
    Settings.HEARTBEAT_PERIOD = 0.3
    Settings.HEARTBEAT_TIMEOUT = 1.5
    Settings.GOSSIP_PERIOD = 0.02
    Settings.GOSSIP_MODELS_PERIOD = 0.05
    Settings.VOTE_TIMEOUT = 15.0
    Settings.AGGREGATION_TIMEOUT = 60.0
    Settings.SECAGG_RECOVERY_TIMEOUT = 10.0
    Settings.WAIT_HEARTBEATS_CONVERGENCE = 0.4
    Settings.MESSAGE_RETRY_BASE = 0.1
    Settings.MESSAGE_RETRY_CAP = 0.8
    Settings.BREAKER_SUSPECT_TIMEOUT = 0.8


def set_test_settings() -> None:
    """Shrink every timeout for fast tests.

    Reference equivalent: ``p2pfl/utils.py:37-53``.
    """
    Settings.GRPC_TIMEOUT = 0.5
    Settings.HEARTBEAT_PERIOD = 0.3
    Settings.HEARTBEAT_TIMEOUT = 1.5
    Settings.GOSSIP_PERIOD = 0.05
    Settings.TTL = 10
    Settings.GOSSIP_MESSAGES_PER_PERIOD = 100
    Settings.AMOUNT_LAST_MESSAGES_SAVED = 100
    Settings.GOSSIP_MODELS_PERIOD = 0.1
    Settings.GOSSIP_MODELS_PER_ROUND = 4
    Settings.GOSSIP_EXIT_ON_X_EQUAL_ROUNDS = 4
    Settings.GOSSIP_SEND_WORKERS = 4
    Settings.GOSSIP_SEND_TIMEOUT = 2.0
    Settings.GOSSIP_PAYLOAD_CACHE = True
    Settings.MESSAGE_RETRY_MAX = 4
    Settings.MESSAGE_RETRY_BASE = 0.05
    Settings.MESSAGE_RETRY_CAP = 0.4
    Settings.BREAKER_THRESHOLD = 3
    Settings.BREAKER_SUSPECT_TIMEOUT = 0.6
    Settings.TRAIN_SET_REPAIR = True
    Settings.EARLY_INIT_TTL = 15.0
    Settings.MEMORY_WIRE_CODEC = False
    # explicit (not auto): tests exercise the device-producer code paths
    # on whatever backend CI runs them on
    Settings.WIRE_COMPRESSION_DEVICE = True
    # streaming on but the threshold far above any test model: streams
    # engage only where a test forces the threshold down
    Settings.WIRE_STREAM_ENABLED = True
    Settings.WIRE_STREAM_THRESHOLD = 8.0
    Settings.WIRE_CHUNK_MB = 2.0
    Settings.WIRE_STREAM_WINDOW = 4
    Settings.GRPC_MAX_MESSAGE_MB = 512
    Settings.GRPC_SERVER_WORKERS = 4
    Settings.ROUND_FUSED = True
    Settings.CHUNK_STAGING_DEPTH = 2
    Settings.CHUNK_FUSED_REDUCE = True
    Settings.CHUNK_DONATE_BUFFERS = True
    Settings.SCAFFOLD_FUSED_CI = True
    Settings.TELEMETRY_ENABLED = True
    Settings.TELEMETRY_RING_SPANS = 4096
    Settings.TELEMETRY_BEAT_SPANS = False
    Settings.WEIGHTS_PLANE = "bytes"
    Settings.ICI_BACKEND = "auto"
    # tight DCN rendezvous bounds: a multi-process test that degrades to
    # the byte path should do so in seconds, not minutes
    Settings.DCN_DIR_TTL_S = 0.5
    Settings.DCN_ACCEPT_TIMEOUT_S = 2.0
    Settings.DCN_READY_TIMEOUT_S = 4.0
    Settings.DCN_DONE_TIMEOUT_S = 20.0
    Settings.FEDERATION_MODE = "sync"
    Settings.ASYNC_ROBUST_AGG = "fedavg"
    Settings.ASYNC_TRIM = 1
    Settings.BYZ_F = 1
    Settings.BYZ_SCREEN = False
    Settings.BYZ_NORM_GATE = 4.0
    Settings.BYZ_COS_GATE = 0.5
    Settings.BYZ_SUSPICION_BETA = 0.5
    Settings.BYZ_SUSPICION_THRESHOLD = 0.7
    Settings.FEDBUFF_K = 4
    Settings.FEDBUFF_ALPHA = 0.5
    Settings.FEDBUFF_SERVER_LR = 1.0
    Settings.ASYNC_MAX_STALENESS = 16
    Settings.HIER_CLUSTER_SIZE = 0
    Settings.ASYNC_DRAIN_TIMEOUT = 15.0
    Settings.ASYNC_JOIN_TIMEOUT = 5.0
    Settings.JOURNAL_EVERY_N_UPDATES = 1
    Settings.JOURNAL_KEEP_N = 3
    Settings.JOURNAL_SEQ_MARGIN = 16
    Settings.CHECKPOINT_KEEP_N = 0
    Settings.MEGAFLEET_PACE_WINDOW = 0.0
    Settings.MEGAFLEET_SELECT_FRAC = 1.0
    Settings.MEGAFLEET_REGIONAL_RATE_S = 0.0
    Settings.MEGAFLEET_GLOBAL_RATE_S = 0.0
    Settings.MEGAFLEET_SCAN_UNROLL = 1
    # small odd chunk in tests: every parity suite then crosses chunk
    # boundaries (masked tails, mid-chunk flushes, fresh-mint adoption)
    Settings.MEGAFLEET_CHUNK = 48
    Settings.TRAIN_SET_SIZE = 4
    Settings.VOTE_TIMEOUT = 10.0
    Settings.AGGREGATION_TIMEOUT = 10.0
    Settings.SECAGG_RECOVERY_TIMEOUT = 6.0
    Settings.WAIT_HEARTBEATS_CONVERGENCE = 0.4
    Settings.LOG_LEVEL = "DEBUG"
