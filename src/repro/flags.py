"""Process-wide lowering flags.

``UNROLL_SCANS``: XLA's ``cost_analysis()`` counts a while-loop body ONCE,
not times its trip count, so scanned layers / chunked attention would
under-report FLOPs and bytes by 30-100x in the roofline.  The dry-run driver
sets this True to lower with unrolled scans (identical math, accurate
accounting, slower compile).  Training/serving keep scans (fast compile).

Time-recurrences (mamba selective scan) stay scanned even when set — their
FLOPs are corrected analytically in the roofline report (see
EXPERIMENTS.md §Roofline notes).
"""
import os

UNROLL_SCANS = False


def scan_unroll():
    """Value to pass as lax.scan(..., unroll=...)."""
    return True if UNROLL_SCANS else 1


def paged_attention_impl() -> str:
    """Default decode impl for the paged-attention ops ('pallas' | 'ref').

    'pallas' means *read KV blocks in place* — the Pallas kernel on TPU, an
    O(live-tokens) XLA twin elsewhere (see repro.kernels.paged_attention.ops
    for the full dispatch, incl. JAX_PALLAS_INTERPRET=1).  'ref' restores
    the full-view gather path.  The ops resolve this EAGERLY per call (the
    jit cache is keyed on the resolved path); a ContinuousEngine snapshots
    it at construction for its stats and bakes it into its per-instance
    jits on first trace — flip the env before constructing the engine.
    """
    return os.environ.get("REPRO_PAGED_ATTN_IMPL", "pallas")


def default_spec_steps() -> int:
    """Default MTP speculative draft depth for ``ContinuousEngine``.

    ``REPRO_SPEC_STEPS`` (int, default 0 = speculation off) is used when
    an engine is constructed with ``spec_steps=None`` — one env flips a
    whole serving deployment to speculative decode (greedy-only; the
    engine validates the config has an MTP head).  An explicit
    ``spec_steps=`` always wins.
    """
    return int(os.environ.get("REPRO_SPEC_STEPS", "0"))


def frontend_wait_s() -> float:
    """Idle-wait granularity of the ``AsyncFrontend`` serve thread.

    When the engine has no work the serve thread parks on its condition
    variable and re-checks at this cadence (``REPRO_FRONTEND_WAIT_S``,
    seconds, default 0.05).  Submissions/pushes notify the condition
    immediately, so this only bounds wakeup latency against lost
    notifications — it is NOT a polling tax on the hot path (a busy
    engine steps back-to-back without waiting)."""
    return float(os.environ.get("REPRO_FRONTEND_WAIT_S", "0.05"))


def trace_enabled() -> bool:
    """Process-wide default for serving trace capture (``REPRO_TRACE``).

    ``1``/``true``/``yes`` turns every newly-constructed engine's
    ``Tracer`` on (per-request lifecycle + engine-step spans, exportable
    as Chrome trace-event JSON — see ``repro.obs.trace``).  Off (the
    default) the tracer hooks are single attribute checks: no buffer
    growth, no timestamps, byte-identical serving behavior.  An explicit
    ``ContinuousEngine(tracer=...)`` always wins.
    """
    return os.environ.get("REPRO_TRACE", "0").lower() in ("1", "true", "yes")


def trace_buffer_limit() -> int:
    """Max buffered trace events per ``Tracer`` (``REPRO_TRACE_BUFFER``,
    default 200000).  Beyond it new events are counted as dropped instead
    of appended — a trace left on for a long-running serve loop degrades
    to a bounded prefix, never an OOM."""
    return int(os.environ.get("REPRO_TRACE_BUFFER", "200000"))


def fault_spec() -> str:
    """Deterministic fault-injection spec (``REPRO_FAULTS``, default "").

    Comma-separated ``point@i`` / ``point@i..j`` / ``point~p`` clauses
    (optionally ``=x`` parameterized) naming the serving stack's
    injection points — see ``repro.faults`` for the grammar and the
    wired points (alloc storms, step exceptions, slow steps, serve-loop
    crashes, rollout-worker crashes).  Empty disables injection: every
    site then costs one attribute check."""
    return os.environ.get("REPRO_FAULTS", "")


def fault_seed() -> int:
    """Seed for probabilistic (``~p``) fault clauses
    (``REPRO_FAULTS_SEED``, default 0).  A (spec, seed) pair replays the
    identical fault sequence — the reproducibility contract the
    fault-injection CI matrix relies on."""
    return int(os.environ.get("REPRO_FAULTS_SEED", "0"))


def max_waiting_default() -> int:
    """Default bound on ``ContinuousEngine``'s waiting queue
    (``REPRO_MAX_WAITING``, default 1024).  Beyond it ``submit`` raises
    the typed ``EngineOverloaded`` instead of growing an unbounded
    backlog — admission backpressure the caller can see and act on.
    An explicit ``max_waiting=`` always wins; ``<= 0`` means unbounded."""
    return int(os.environ.get("REPRO_MAX_WAITING", "1024"))


def admit_window() -> int:
    """Head-of-line scan window for admission (``REPRO_ADMIT_WINDOW``,
    default 4).  When the queue head cannot admit (not enough free
    blocks), the scheduler scans up to this many queued requests behind
    it for a smaller one that fits instead of stalling ALL admission on
    the head (``stats["admit_skips"]`` counts out-of-order admissions).
    0 restores strict FCFS."""
    return int(os.environ.get("REPRO_ADMIT_WINDOW", "4"))


def max_restarts_default() -> int:
    """Bound on ``AsyncFrontend`` supervisor engine restarts
    (``REPRO_MAX_RESTARTS``, default 3).  Each serve-loop crash rebuilds
    the engine and re-queues un-started work; past the bound the
    front-end marks itself crashed and refuses new submissions (a crash
    loop must not masquerade as a healthy server)."""
    return int(os.environ.get("REPRO_MAX_RESTARTS", "3"))


def pd_threshold_default() -> int:
    """Prompt-length cutoff for the disagg router (``REPRO_PD_THRESHOLD``,
    tokens, default 64).  Prompts at least this long take the
    prefill-tier path (prefill remotely, migrate KV blocks, decode on
    the decode tier); shorter prompts prefill colocated on the decode
    engine — the migration overhead only pays for itself on prefills
    long enough to stall decode streams.  An explicit
    ``DisaggServer(pd_threshold=...)`` always wins."""
    return int(os.environ.get("REPRO_PD_THRESHOLD", "64"))


def migrate_timeout_s() -> float:
    """Per-attempt wall-clock budget for one KV-block migration
    (``REPRO_MIGRATE_TIMEOUT_S``, seconds, default 5.0).  An attempt
    that exceeds it counts as failed and consumes one retry; after the
    retry budget the router degrades the request to colocated prefill
    instead of stalling it behind a wedged transfer."""
    return float(os.environ.get("REPRO_MIGRATE_TIMEOUT_S", "5.0"))


def migrate_retries() -> int:
    """Bounded retry budget per migration beyond the first attempt
    (``REPRO_MIGRATE_RETRIES``, default 2).  Exhaustion raises the typed
    ``MigrationFailed``; the disagg router answers with colocated
    fallback, so retries trade latency for migration reuse — they never
    trade away the request."""
    return int(os.environ.get("REPRO_MIGRATE_RETRIES", "2"))


def migrate_backoff_s() -> float:
    """Base backoff between migration retries
    (``REPRO_MIGRATE_BACKOFF_S``, seconds, default 0.01), doubled per
    attempt — a transient fault (one injected ``xfer`` hit, a momentary
    pool squeeze) clears in one cheap beat without hammering the
    engines."""
    return float(os.environ.get("REPRO_MIGRATE_BACKOFF_S", "0.01"))


def tier_restarts_default() -> int:
    """Bound on prefill-TIER respawns by ``DisaggServer``
    (``REPRO_TIER_RESTARTS``, default 2).  Distinct from
    ``REPRO_MAX_RESTARTS`` (the per-frontend supervisor): the prefill
    frontend runs with ``max_restarts=0`` so a crash surfaces as a tier
    outage the router can observe (degraded colocated mode), and the
    DisaggServer owns the respawn/fail-back cycle up to this bound.
    Past it the tier stays down and the server keeps serving colocated
    — degraded forever beats a respawn loop."""
    return int(os.environ.get("REPRO_TIER_RESTARTS", "2"))


def spill_enabled() -> bool:
    """Process-wide default for the host-RAM KV spill tier
    (``REPRO_SPILL_ENABLE``).  ``1``/``true``/``yes`` makes every
    newly-constructed prefix-cached ``ContinuousEngine`` attach a
    ``HostSpillTier`` (``repro.serving.spill``): the radix tree's LRU
    evictor DEMOTES cold leaves to pinned host memory instead of
    forgetting them, and ``PrefixCache.match`` restores spilled prefixes
    on a hit — effective cache capacity beyond HBM.  Spill is byte-exact
    (greedy outputs are byte-identical with the tier on or off).  An
    explicit ``ContinuousEngine(spill=...)`` always wins."""
    return os.environ.get("REPRO_SPILL_ENABLE",
                          "0").lower() in ("1", "true", "yes")


def spill_blocks() -> int:
    """Capacity of the host spill tier in BLOCKS (``REPRO_SPILL_BLOCKS``,
    default 512).  Beyond it the OLDEST spilled entry is dropped
    (``spill.dropped_capacity``) — host memory is a bigger tier, not an
    unbounded one.  ``<= 0`` means unbounded (tests only).  An explicit
    ``ContinuousEngine(spill_blocks=...)`` always wins."""
    return int(os.environ.get("REPRO_SPILL_BLOCKS", "512"))


def paged_prefill_impl() -> str:
    """Default PREFILL impl for the paged-attention ops ('pallas' | 'ref').

    Mirrors ``paged_attention_impl`` for multi-token spans: 'pallas' runs
    the paged flash-prefill kernels (block-table index maps, no padded-view
    gather; Pallas on TPU / interpret under JAX_PALLAS_INTERPRET=1 / the
    O(live) XLA twin elsewhere), 'ref' restores the ``paged_view`` gather.
    ``REPRO_PAGED_PREFILL_IMPL`` overrides; it falls back to
    ``REPRO_PAGED_ATTN_IMPL`` so one env flips the whole engine step.
    """
    return os.environ.get("REPRO_PAGED_PREFILL_IMPL",
                          os.environ.get("REPRO_PAGED_ATTN_IMPL", "pallas"))
