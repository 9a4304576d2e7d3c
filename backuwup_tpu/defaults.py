"""Compile-time tunables for the whole framework.

Mirrors the constant surface of the reference (``client/src/defaults.rs:1-68``,
``shared/src/constants.rs:4-7``, ``client/src/backup/filesystem/packfile/mod.rs:25-31``,
``shared/src/p2p_message.rs:8``, ``client/src/backup/filesystem/dir_packer.rs:35``,
``client/src/backup/filesystem/packfile/blob_index.rs:16``), plus the
TPU-kernel tunables that have no reference equivalent.
"""

KiB = 1024
MiB = 1024 * KiB
GiB = 1024 * MiB

# --- content-defined chunking (reference client/src/defaults.rs:62-68) ------
CDC_MIN_CHUNK = 256 * KiB
CDC_DESIRED_CHUNK = 1 * MiB
CDC_MAX_CHUNK = 3 * MiB

# Normalized-chunking mask widths (FastCDC 2020, normalization level 2):
# below the desired size a stricter mask applies, above it a looser one.
CDC_MASK_S_BITS = 22  # desired 2**20 => 20 + 2
CDC_MASK_L_BITS = 18  # 20 - 2

# --- packfiles (reference packfile/mod.rs:25-31) -----------------------------
PACKFILE_TARGET_SIZE = 3 * MiB
PACKFILE_MAX_SIZE = 16 * MiB
PACKFILE_MAX_BLOBS = 100_000
ZSTD_COMPRESSION_LEVEL = 3

# --- blob index (reference blob_index.rs:16) --------------------------------
INDEX_FILE_MAX_ENTRIES = 50_000

# --- tree blobs (reference dir_packer.rs:35) --------------------------------
TREE_MAX_CHILDREN = 10_000

# --- send pipeline / backpressure (reference defaults.rs:38-59) -------------
PACKFILE_LOCAL_BUFFER_LIMIT = 100 * MiB
PACKFILE_RESUME_THRESHOLD = 50 * MiB  # resume packing when this much is free
PACKFILE_SEND_TIMEOUT_S = 20.0
ACK_TIMEOUT_S = 5.0
STORAGE_REQUEST_RETRY_S = 10.0
RESTORE_REQUEST_THROTTLE_S = 60.0
STORAGE_REQUEST_STEP = 50 * 1000 * 1000  # 50 MB (decimal, like the reference)
STORAGE_REQUEST_CAP = 150 * 1000 * 1000  # 150 MB
PEER_OVERUSE_GRACE = 16 * MiB  # tolerated overshoot per peer (defaults.rs:34)

# --- unified retry policies (utils/retry.py; no reference equivalent — the
# reference hardcodes each of these inline) ----------------------------------
RETRY_JITTER = 0.1  # default +/- fraction applied to every delay
DIAL_RETRY_BASE_S = 0.5  # p2p dial (handle_connections.rs:145-165 used 0.5)
DIAL_RETRY_CAP_S = 2.0
DIAL_RETRY_ATTEMPTS = 2  # retries after the first dial (3 dials total)
WS_RECONNECT_BASE_S = 0.2  # server push channel (net_server/mod.rs:26-55)
WS_RECONNECT_CAP_S = 30.0
# Grace given to in-flight handlers when a coordination node stops.
# aiohttp's 60s default lets one live WebSocket push channel stall a
# node's shutdown for a minute; clients reconnect elsewhere anyway, so
# a stopping (or dying) node cuts stragglers fast.
SERVER_SHUTDOWN_GRACE_S = 2.0
STORAGE_REQUEST_RETRY_CAP_S = 60.0  # re-request backoff ceiling
SEND_IDLE_BASE_S = 0.05  # send loop waiting on the packer
SEND_IDLE_CAP_S = 0.25
PEER_WAIT_BASE_S = 0.2  # send loop waiting for a usable peer
PEER_WAIT_CAP_S = 1.0

# --- peer-loss repair (utils/faults.py, engine.repair_round) -----------------
# A peer unseen for this long is treated as lost even without an audit
# demotion: its placements are orphaned and repair re-replicates them.
PEER_DARK_DEADLINE_S = 3 * 24 * 3600.0

# --- erasure-coded shard placement (erasure/, docs/erasure.md; no reference
# equivalent — the reference is replication-only) -----------------------------
# Each sealed packfile is split into RS_K data shards plus RS_M parity
# shards (systematic GF(2^8) Reed-Solomon); any RS_K of the RS_K+RS_M
# shards reconstruct the packfile.  Sharding activates per packfile only
# when a full stripe of distinct peers is available at send time;
# otherwise the legacy whole-packfile single-peer path runs.
RS_K = 4
RS_M = 2

# --- concurrent transfer plane (net/transfer.py, docs/transfer.md; no
# reference equivalent — send.rs transmits strictly one file at a time) -------
# Uploads admitted concurrently across all peers; per-peer ordering is
# still serialized (the signed transport sequence demands it).
TRANSFER_MAX_INFLIGHT = 8
# Distinct peers the whole-packfile path fans out to per send tick (the
# stripe path always uses one peer per missing shard).
TRANSFER_MAX_PEERS = 4
# In-flight payload RAM cap; a single transfer larger than the cap is
# still admitted when the plane is empty (no deadlock on oversize files).
TRANSFER_INFLIGHT_BYTE_CAP = 64 * MiB
# Packfile seal pipeline (snapshot/packfile.py): worker threads running
# zstd + AES-GCM (both release the GIL) and the bound on
# assembled-but-unwritten packfile batches (double buffering).  0 workers
# = the original synchronous seal-in-add_blob behavior.
PACK_SEAL_WORKERS = 2
PACK_SEAL_QUEUE_PACKFILES = 2
# Streaming dataflow (docs/dataflow.md): blobs buffered below the
# packfile target size are force-emitted into the seal pipeline once
# they have waited this long, so the wire never starves behind the
# end-of-tree flush while the packer walks small directories.
PACK_EMIT_MAX_LAG_S = 2.0
# Missed-wakeup backstop for the event-driven send loop: the seal
# callback wakes the loop the moment a packfile commits; this timeout
# only bounds how long a (theoretical) lost wakeup could park it.
SEND_WAKEUP_BACKSTOP_S = 0.5

# --- resumable WAN transfer plane (net/p2p.py send_file, docs/transfer.md) ---
# Payloads larger than this go out as FILE_PART frames with per-part acks
# and receiver-side partial persistence, preceded by a RESUME_QUERY so a
# reconnect continues from the verified offset.  0 disables chunking
# entirely (every file rides the legacy whole-FILE frame).
TRANSFER_CHUNK_BYTES = 1 * MiB
# Reconnect-and-resume attempts after a mid-transfer failure of a chunked
# send, before the failure surfaces to the scheduler as a failed transfer.
TRANSFER_RESUME_ATTEMPTS = 2
# False = reconnect attempts restart from byte zero (no RESUME_QUERY);
# a baseline shape, never what production wants (ROADMAP D3b).
TRANSFER_RESUME_ENABLED = True
# Adaptive per-transfer deadline (replaces the fixed send/ack timeout pair
# for sized payloads): budget = ACK_TIMEOUT_S + size / floor, where floor
# is the larger of the assumed minimum link rate and the peer's measured
# EWMA throughput derated by the safety fraction.  The minimum keeps a
# never-measured peer from being declared stalled on its first large
# send; the safety fraction tolerates throughput regressions before the
# stall detector calls abort-and-resume.
TRANSFER_MIN_THROUGHPUT_BPS = 256 * KiB
TRANSFER_DEADLINE_SAFETY = 0.25
TRANSFER_DEADLINE_CAP_S = 600.0

# --- restore data plane (engine.run_restore planner, net/transfer.py
# download lanes; docs/transfer.md restore data plane) ------------------------
# Per-stripe source fan-out: each stripe's shards are pulled from its k
# currently-fastest live holders (k = the stripe's data-shard count); the
# remaining m holders are held back as hedge spares.  When a pull has been
# running for this fraction of its adaptive deadline without finishing, a
# redundant pull of a spare shard is launched and the first completion
# wins — the stall is raced, not waited out.
RESTORE_HEDGE_DEADLINE_FRACTION = 0.5
# Serve-side throttle for RESTORE_FETCH sessions.  Deliberately decoupled
# from RESTORE_REQUEST_THROTTLE_S and off by default: one multi-source
# restore legitimately opens several fetch connections to the same holder
# in quick succession (per-stripe pulls, hedges, the index sweep), and a
# fetch serves only the named items, so the abuse surface is bounded.
# Operators worried about hostile pullers can raise it.
RESTORE_FETCH_MIN_INTERVAL_S = 0.0
# Upper bound on items one FETCH_REQUEST may name (mirrors the audit
# batch bound: reject absurd batches before doing any disk work).
RESTORE_FETCH_MAX_WANTS = 4096

# --- capacity-aware placement (store.find_peers_with_storage,
# net/peer_stats.py; docs/transfer.md) ----------------------------------------
# Peers are ranked by log2-bucketed (EWMA throughput x success ratio) with
# free space as the tiebreak; a peer needs this many samples before its
# measurement outranks the neutral prior, so fresh peers stay schedulable.
PLACEMENT_MIN_SAMPLES = 3
# Score assumed for unmeasured peers: they sort above measured-slow peers
# and below measured-fast ones.
PLACEMENT_NEUTRAL_SCORE_BPS = TRANSFER_MIN_THROUGHPUT_BPS
# Placement demotion (recoverable; distinct from audit demotion): a peer
# whose success EWMA sinks below the demote threshold over at least
# min-samples transfers stops receiving placements until either its
# success EWMA climbs back over the recovery threshold or the probation
# window expires.
PLACEMENT_DEMOTE_SUCCESS = 0.25
PLACEMENT_RECOVER_SUCCESS = 0.6
PLACEMENT_DEMOTE_MIN_SAMPLES = 4
PLACEMENT_PROBATION_S = 600.0

# --- protocol limits (reference shared/src/constants.rs:4-7) ----------------
MAX_BACKUP_STORAGE_REQUEST_SIZE = 16 * GiB
BACKUP_REQUEST_EXPIRY_S = 300.0

# --- p2p transport (reference shared/src/p2p_message.rs:8) ------------------
MAX_P2P_MESSAGE_SIZE = 8 * MiB
# Signed-envelope framing budget (P2PBody FILE encoding + Ed25519
# signature is ~150 bytes; 4 KiB leaves generous slack).  Every file the
# send pipeline ships must fit one transport message, so the packfile
# writer's effective cap is the wire max minus this — the analog of the
# reference's validate_size_constraints proof (pack.rs:257-288), which
# only had to prove 16 MiB because its transport cap was not smaller.
P2P_ENVELOPE_OVERHEAD = 4 * KiB
PACKFILE_WIRE_MAX = MAX_P2P_MESSAGE_SIZE - P2P_ENVELOPE_OVERHEAD

# --- storage attestation (no reference equivalent; docs/audit.md) -----------
AUDIT_CHALLENGES_PER_PACKFILE = 16  # precomputed table entries per packfile
AUDIT_WINDOW_BYTES = 64 * KiB  # sampled window length (clamped to file size)
AUDIT_SAMPLES_PER_ROUND = 8  # challenges issued per peer per audit round
AUDIT_MAX_CHALLENGES_PER_MSG = 256  # prover-side cap on one CHALLENGE body
AUDIT_INTERVAL_S = 6 * 3600.0  # healthy-peer re-audit cadence
AUDIT_RETRY_BASE_S = 60.0  # first retry delay after a miss/failure
AUDIT_BACKOFF_CAP_S = 24 * 3600.0  # exponential-backoff ceiling
AUDIT_DEMOTE_MISSES = 3  # consecutive offline windows before demotion
AUDIT_DEMOTE_FAILURES = 1  # confirmed bad/missing proofs before demotion
AUDIT_PROOF_TIMEOUT_S = 15.0  # verifier wait for the PROOF body
AUDIT_SERVE_MIN_INTERVAL_S = 5.0  # prover-side per-peer rate limit
AUDIT_SERVER_BLOCK_FAILURES = 2  # distinct failing verifiers to block matches
AUDIT_REPORT_WINDOW_S = 24 * 3600.0  # server aggregation window

# --- observability plane (obs/, docs/observability.md; no reference
# equivalent — the reference prints ad-hoc lines) ------------------------------
OBS_JOURNAL_MAX_BYTES = 4 * MiB  # rotate the JSONL journal past this size
OBS_JOURNAL_KEEP = 3  # rotated generations retained (<path>.1 .. .keep)
OBS_PANIC_TAIL_LINES = 200  # journal lines embedded in a panic dump
# EWMA smoothing for the per-peer throughput/latency/success estimators
# (net/peer_stats.py): each new TransferResult carries 20% weight, so
# ~10 transfers dominate the estimate — reactive on WAN shifts without
# one stalled send cratering a peer's score.
PEER_STATS_ALPHA = 0.2

# --- live SLO plane (obs/series.py, obs/slo.py, obs/diagnose.py,
# docs/observability.md §SLOs; no reference equivalent) ------------------------
# Registry sampling cadence of the in-process time-series recorder and
# the ring-buffer depth per series.  At the default 10 s cadence 2048
# points retain ~5.7 h — enough to feed the 1 h fast burn window with
# real headroom; the 6 h/3 d slow windows clamp to available history
# while the buffer fills (burn math uses the actual covered span).
SERIES_SAMPLE_INTERVAL_S = 10.0
SERIES_RETENTION_POINTS = 2048
# Robust-zscore anomaly flagging: |z| at/above this flags a series, and
# a series needs this many points in the window before it is judged at
# all (a two-point baseline flags everything).
SERIES_ANOMALY_Z = 3.5
SERIES_ANOMALY_MIN_POINTS = 6
# Google-SRE multi-window burn alerts: the fast pair catches an active
# incident (page-grade), the slow pair a smoldering budget leak
# (ticket-grade).  Both windows of a pair must burn past the threshold
# before the objective's status moves — one spike in a short window is
# not an incident.  The sim plane reuses these spans verbatim on
# virtual time; the scenario harness shrinks them via the monitor's
# ``windows=`` override.
SLO_WINDOWS = ((300.0, 3600.0), (21600.0, 259200.0))
SLO_FAST_BURN = 14.4
SLO_SLOW_BURN = 6.0
# The declarative objective catalog (bkwlint BKW007 keeps it honest
# against the registered metric families and the docs table).  Entries
# are plain literals — the linter AST-parses this tuple, so no computed
# values.  ``budget`` is the tolerated bad-event fraction (error
# budget); ``burn = bad_fraction / budget``.  Kinds:
#   counter_rate — bad seconds per clock second (delta / covered span)
#   ratio        — bad events / total events (needs total_family)
#   quantile     — histogram observations above target / all in window
#   gauge_below  — window samples below target / all samples
SLO_CATALOG = (
    {"id": "durability", "kind": "counter_rate",
     "family": "bkw_durability_violation_seconds_total", "labels": {},
     "budget": 0.001,
     "description": "fraction of time any durability invariant is"
                    " violated stays ~0"},
    {"id": "transfer_stalls", "kind": "ratio",
     "family": "bkw_transfer_stalls_total", "labels": {},
     "total_family": "bkw_transfers_total", "budget": 0.02,
     "description": "adaptive-deadline stall aborts per completed"
                    " transfer"},
    {"id": "backup_p99", "kind": "quantile",
     "family": "bkw_span_seconds", "labels": {"name": "engine.backup"},
     "target": 120.0, "budget": 0.01,
     "description": "p99 end-to-end backup wall seconds"},
    {"id": "restore_p99", "kind": "quantile",
     "family": "bkw_span_seconds", "labels": {"name": "engine.restore"},
     "target": 120.0, "budget": 0.01,
     "description": "p99 end-to-end restore wall seconds"},
    {"id": "matchmaking_p99", "kind": "quantile",
     "family": "bkw_server_request_seconds",
     "labels": {"route": "/backups/request"},
     "target": 5.0, "budget": 0.01,
     "description": "p99 matchmaking request latency at the"
                    " coordination server"},
    {"id": "backup_overlap", "kind": "gauge_below",
     "family": "bkw_backup_overlap_efficiency", "labels": {},
     "target": 0.5, "budget": 0.25,
     "description": "streaming-dataflow overlap efficiency holds above"
                    " the floor for most of the window"},
    {"id": "repl_promote_p99", "kind": "quantile",
     "family": "bkw_repl_promote_seconds", "labels": {},
     "target": 30.0, "budget": 0.05,
     "description": "p99 successor promotion seconds (epoch commit +"
                    " log-tail replay)"},
)
# Evidence ranking for the breach explainer (obs/diagnose.py): how far
# back from the breach instant evidence is gathered when the caller
# does not pin a window, and how many ranked causes a report keeps.
DIAGNOSE_WINDOW_S = 600.0
DIAGNOSE_TOP_CAUSES = 5

# --- durability invariant monitor (obs/invariants.py, docs/scenarios.md) -----
# Background sweep cadence of the client's InvariantMonitor; health is
# current within one interval of any placement/ledger change.
DURABILITY_SWEEP_INTERVAL_S = 5.0
# Stalest tolerated attestation over a placement-holding peer before the
# monitor reports degraded audit coverage (4x the audit cadence: one
# missed round is routine backoff, four is a stuck verifier).
DURABILITY_AUDIT_MAX_AGE_S = 4 * AUDIT_INTERVAL_S

# --- crash consistency (engine.recover, net/p2p.py PartialStore janitor,
# docs/crash_consistency.md; no reference equivalent) -------------------------
# A receiver-side partial transfer untouched for this long is abandoned:
# the TTL janitor deletes the bin/json pair and frees the quota.  Kept
# shorter than PEER_DARK_DEADLINE_S — a sender that has been gone for a
# day will restart the transfer from its own resume handshake anyway.
PARTIAL_STORE_TTL_S = 24 * 3600.0

# --- snapshot lifecycle / GC (engine.run_gc, docs/lifecycle.md; no
# reference equivalent — the reference is append-only) ------------------------
# Retention: a store with no policy keeps every snapshot
# (store.apply_retention); operators narrow it to comma-separated
# keep-last:N / keep-daily:N rules.
# A packfile whose live-byte fraction (bytes still referenced by some
# retained snapshot / total payload bytes) drops below this is sparse:
# GC pulls it back, extracts the live blobs, and re-packs them into
# fresh packfiles.  At/above the threshold the dead bytes ride along —
# compaction I/O costs more than the space it would free.
GC_COMPACT_OCCUPANCY = 0.5
# Holder-side RECLAIM rate limit, same posture as the restore throttle:
# one reclaim service per peer per interval, so a buggy (or hostile)
# peer cannot grind a holder's disk with delete storms.
RECLAIM_MIN_INTERVAL_S = 5.0
# Max file ids accepted in one RECLAIM body (mirrors the restore-fetch
# wants cap): bounds the per-request unlink loop and the ack's freed-
# bytes accounting.
RECLAIM_MAX_ITEMS = 4096

# --- scale-out coordination plane (net/serverstore.py, net/matchmaking.py,
# docs/server.md; no reference equivalent — the reference is one process
# over one Postgres) ----------------------------------------------------------
# In-memory matchmaking shards, keyed by client pubkey.  Each shard has
# its own lock, FIFO, and deadline heap; fulfill walks shards starting at
# the requester's home shard (cross-shard work stealing), so the count
# bounds lock contention, not matchable peers.
MATCHMAKING_SHARDS = 8
# Write-behind store: max operations drained into one group commit.  The
# batch is whatever queued since the last commit, capped here so a
# firehose cannot defer the commit (and the durability acks) unboundedly.
SERVER_STORE_MAX_BATCH = 256

# --- federated coordination plane (net/ring.py, net/server.py /fed/*,
# docs/server.md §Federation; no reference equivalent) ------------------------
# Virtual nodes per physical coordination node on the consistent-hash
# ring.  More vnodes smooth the key distribution (max node share decays
# ~1/sqrt(vnodes)) at the cost of a larger sorted point list; 64 keeps
# add/remove key movement within ~2/N in practice.
FEDERATION_RING_VNODES = 64
# Store partitions behind PartitionedServerStore when the caller does
# not pin a count.  Partition count is a *file layout* choice, fixed for
# the lifetime of the data directory — nodes route to partitions by
# pubkey, so every node must agree on it.
SERVER_STORE_PARTITIONS = 4
# Inter-node RPC (/fed/steal, /fed/notify) total timeout.  Steal RPCs
# sit on the client's matchmaking request path, so this bounds the tail
# a dead peer can add to a fulfill.
FEDERATION_RPC_TIMEOUT_S = 2.0
# After a failed inter-node RPC the peer is skipped (steal order walks
# past it, wrong-node redirects are not issued toward it) for this long.
FEDERATION_PEER_BACKOFF_S = 3.0
# Client-side: after a refused dial or a failed redirect hop the client
# pins itself to whatever node answers (sends ``fed_pinned`` so servers
# skip redirects) for this long, preventing redirect ping-pong while the
# ring view is stale.
FEDERATION_CLIENT_PIN_S = 10.0
# After a remote-steal walk finds every peer empty, the remote leg sits
# out this long before walking again.  A starved federation otherwise
# pays a full ring of RPCs on EVERY unfulfilled matchmaking request —
# an RPC storm that throttles local throughput (~4x on loopback) while
# producing nothing.
FEDERATION_STEAL_COOLDOWN_S = 0.05

# --- replicated coordination metadata (net/serverstore.py ReplicatedServerStore,
# net/server.py /repl/*, docs/server.md §Replication; no reference equivalent) -
# Ring successors each partition's operation log ships to (the primary/
# backup chain).  A write's future resolves only after the log record is
# durable on the primary AND acked by at least one live successor, so 2
# keeps a replica margin: after one permanent node loss the promoted
# successor still ships to one live peer in a 3-node ring.
REPL_SUCCESSORS = 2
# Synchronous ship RPC (/repl/ship) timeout.  Shipping happens on the
# store's writer thread inside the group commit, so this bounds the
# latency a dead successor can add to a write batch before it is marked
# down and the batch proceeds degraded.
REPL_SHIP_TIMEOUT_S = 2.0
# Extra full-chain retry rounds when a shipped batch collects ZERO
# acks (serverstore.py _ship_tail).  Degraded mode (resolving write
# futures no successor holds) is the last resort, not the first
# response to one slow peer — each retry round ignores the ship-down
# backoff and waits REPL_SHIP_RETRY_BASE_S * 2^round before trying.
REPL_SHIP_RETRIES = 2
REPL_SHIP_RETRY_BASE_S = 0.2
# Forward/tail RPC deadline (net/server.py _repl_post).  Deliberately
# LOOSER than the federation RPC timeout: a forward's owner is the only
# correct target (there is no fallback peer to try), so a slow owner
# should mean a slow request, not a failed one — timeouts here surface
# as client-visible errors.  This bounds livelock, not latency.
REPL_FORWARD_TIMEOUT_S = 10.0
# Successor-side health probing of the primaries it backs: probe
# interval and the consecutive-failure count that (together with every
# more-senior chain member also being dead) triggers promotion.  The
# promote deadline seen by clients is roughly INTERVAL x FAILURES plus
# one replay.
REPL_PROBE_INTERVAL_S = 2.0
REPL_PROBE_FAILURES = 2

# --- server-side TTLs (reference server/src/client_auth_manager.rs:17-20) ---
AUTH_CHALLENGE_TTL_S = 30.0
SESSION_TTL_S = 24 * 3600.0
P2P_REQUEST_TTL_S = 60.0

# --- UI cadence (reference ws_status_message.rs:134-141, backup/mod.rs:112) -
PROGRESS_DEBOUNCE_S = 0.1
PEERS_DEBOUNCE_S = 0.25
PROGRESS_TICKER_S = 0.4

# --- TPU execution tunables (no reference equivalent) -----------------------
# Leaf bucket sizes (in 1 KiB blake3 chunks) used when batching variable-size
# CDC chunks for fingerprinting; chunks are padded up to the nearest bucket.
BLAKE3_LEAF_BUCKETS = (16, 64, 256, 1024, 2048, 3072)
# Sharded dedup index: default capacity per device shard (slots) and probe cap.
DEDUP_SHARD_CAPACITY = 1 << 20
DEDUP_MAX_PROBES = 32

# --- tiered dedup index (dedupstore/, docs/dedup_tiering.md) -----------------
# Ceiling on HBM bytes the hot fingerprint table may occupy across the whole
# mesh: slots x 20 bytes (16-byte truncated key + u32 value) x n_devices.
# When an insert would force a 4x growth past this cap, the tiered index
# demotes cold fingerprints to the host LSM store instead of growing.
DEDUP_HBM_BUDGET_BYTES = 256 * MiB
# Cold-tier LSM store: memtable entries before a sorted run is committed,
# prefix-bucket count per run (first key word, top bits), and the size-tiered
# compaction fan-in (merge when a tier accumulates this many runs).
DEDUP_COLD_MEMTABLE_LIMIT = 1 << 16
DEDUP_COLD_BUCKETS = 256
DEDUP_COLD_COMPACT_FANIN = 4
# Promotion/demotion clock: one period every this many classify dispatch
# windows; cold fingerprints hit at least PROMOTE_MIN_HITS times within a
# period are promoted into the hot table.
DEDUP_TIER_CLOCK_WINDOWS = 8
DEDUP_TIER_PROMOTE_MIN_HITS = 2
