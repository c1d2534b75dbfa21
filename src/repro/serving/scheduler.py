"""Continuous-batching scheduler over the paged KV cache (GLM-5 §3.6).

Iteration-level scheduling: instead of padding a static batch and decoding
lock-step until the longest request drains (``ServingEngine``), the engine
keeps ``max_batch`` decode *slots* and, every step,

  1. retires any sequence that has produced its ``max_new`` tokens,
     handing its KV blocks to the prefix cache (or straight back to the
     free list when the cache is off);
  2. admits waiting requests into free slots — a request is admitted as
     soon as a slot AND enough blocks for its whole lifetime
     (``ceil((prompt + max_new) / block_size)``) are available, so it can
     never run out of cache mid-flight;
  3. advances chunked prefills (one chunk per slot per step), so one huge
     prompt cannot stall the decode batch
     (the §3.6.2 prefill/decode interference, engine-side);
  4. runs ONE batched decode step for every decoding sequence, each at
     its own position (``models/*.decode_step(..., block_tables=...)``).
     BOTH phases read KV blocks IN PLACE through the paged-attention
     kernels (``repro.kernels.paged_attention``) — the decode step via the
     flash-decode kernels, prefill spans via the flash-PREFILL kernels
     whose index maps walk the block table at per-sequence start offsets.
     That is O(live tokens) HBM traffic instead of the old full-view
     ``paged_view`` gather, which copied B × max_blocks × block_size
     tokens per call regardless of occupancy.  ``attn_impl="ref"``
     restores the gather for both phases (the parity oracle).  The same
     program samples every row's next token (``sample_rows``: per-row
     temperature, greedy at T <= 0) and its logprob, so a step hands the
     host B tokens, not B × V logits.  A prefill program hands back only
     its last position's logits, and the final span's first token is
     drawn from them by the same sampler (``_draw``).

Prefix reuse (``prefix_cache=True``, attention-cache families): on admit
the engine asks the radix cache (``repro.serving.prefix_cache``) for the
longest cached prefix of the prompt, aliases those blocks into the
sequence's block table (read-only, refcounted), copy-on-write forks the
final block when the match ends mid-block, and prefills ONLY the suffix —
``prefill`` takes a per-sequence start offset, so suffix queries attend
over the aliased prefix KV through the same gathered view.  On retire the
sequence's blocks are inserted into the radix tree instead of freed;
identical content deduplicates, and LRU eviction reclaims cold cached
blocks under allocation pressure.  Greedy outputs are byte-identical with
the cache on or off (tests/test_prefix_cache.py).

The hybrid family (mamba2 + shared attention) pages its shared-attention
KV like everyone else but carries per-slot recurrent state: admission
zeroes the slot's mamba2 state, chunked prefill threads it through the
slot, and decode steps restore it for slots still prefilling.  Recurrent
state cannot be recovered from KV blocks, so the prefix cache is
force-disabled for hybrid.

MTP speculative decode (``spec_steps=n``, GLM-5 §2.1/Table 2): every
scheduler step emits up to ``n+1`` tokens per slot instead of one.  Each
slot carries the trunk hidden state at its last cached position; the
shared-parameter MTP head drafts ``n`` tokens from it
(``repro.serving.speculative.mtp_draft``), and ONE batched S=n+1 span
forward (``models/transformer.verify_step`` — the paged flash-PREFILL
kernels at per-sequence start offsets) verifies [pending, draft_1..n],
scatters their KV, and returns per-position logits + hidden states.  The
accept length is 1 + the greedy-matching draft prefix (capped per slot so
verification never writes past the request's lifetime blocks); rejected
drafts are ROLLED BACK by truncating the slot's length — their pool
writes are dead weight the next span overwrites before any causal mask
admits them, and no block changes hands (admission preallocated the
lifetime, so COW/refcount invariants are untouched by a rollback).
Greedy outputs are byte-identical for spec on/off; drafting quality only
moves throughput.  Speculation is greedy-only (temperature>0 requests
are rejected) and excluded for hybrid (a partial accept cannot roll back
recurrent state).  ``stats["draft_tokens"]`` / ``stats["accepted_
tokens"]`` / ``rolling_accept_length`` track the Table-2 quantity.

Weight pushes (``push_weights``, GLM-5 §4.1.1): the trainer can hand the
engine a new parameter snapshot AT ANY TIME without resetting the world.
A push is applied at a DRAIN BARRIER: admission pauses, in-flight
sequences finish under the weights they were admitted with (so no
trajectory ever mixes versions — every ``Request`` comes back stamped
with ``out_version``), and once the last slot retires the engine swaps
params, bumps ``weight_version``, and resumes admitting.  The prefix
cache is NOT reset: blocks carry the version that wrote them
(``PagedKVCache`` stamps at alloc), admission simply refuses to alias
older-version blocks (``PrefixCache.match``), retiring sequences refresh
stale tree paths in place, and the LRU evictor reclaims stale blocks
lazily — incremental invalidation, so same-version reuse is never
sacrificed to a push.  ``AsyncFrontend`` (``repro.serving.frontend``)
drives this from a background thread for genuinely non-blocking pushes.

Speculative rollouts compose with logprob capture two ways:
``capture_logprobs=True`` alone keeps the sampling convention (greedy
fragments carry lp ~= 0 — the scaled-argmax logprob);
``true_logprobs=True`` additionally records the model's TRUE
(temperature-1) per-token logprob for every emitted token — for spec
rounds the verified span logits are already on the host, so accepted
drafts get exact logprobs for free.  ``step_token_budget`` adds
accept-length-aware slot budgeting: a speculating slot burns up to
``spec_steps+1`` tokens of step capacity, so admission holds back new
slots once the projected per-step token emission (live slots x rolling
accept estimate) would exceed the budget — instead of over-admitting
slots whose ``max_new`` headroom it will burn at >1 token/step.

Device layout: one block pool (``init_paged_cache``, LAYER-MAJOR flat —
scanned layers carry it through the layer scan as a scan-invariant and
update it in place, instead of round-tripping stacked xs/ys pools through
HBM every step) shared by all slots; a (max_batch, max_blocks) block
table; a (max_batch,) length vector.  Idle slots point at a reserved trash
block with length 0, so the decode step has a fixed shape (one
compilation) regardless of occupancy.  Prompt suffixes are EXACT spans —
the kernels mask by start offset / sequence length, so the old
right-pad-to-whole-blocks trick (padded garbage hidden behind the causal
mask) is gone.
"""
from __future__ import annotations

import collections
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.faults import FaultInjector
from repro.models import get_model
from repro.obs.metrics import MetricsRegistry, StatsView
from repro.obs.trace import NO_SPAN, Tracer
from repro.serving.engine import Request, sample_rows, sample_token
from repro.serving.errors import (DeadlineExceeded, EngineOverloaded,
                                  RequestCancelled, RequestShed)
from repro.serving.paged import CacheFull, PagedKVCache, blocks_for
from repro.serving.prefix_cache import PrefixCache


class _Active:
    """One in-flight sequence: its request, blocks, sampling state, and —
    while its prompt is still being chunk-prefilled — the prefill cursor."""
    __slots__ = ("req", "blocks", "out", "lps", "ts", "pending",
                 "pending_lp", "pending_t", "row", "pos", "h_last",
                 "version")

    def __init__(self, req: Request, blocks: List[int], row: np.ndarray,
                 pos: int, version: int = 0):
        self.req = req
        self.blocks = blocks
        self.version = version               # weight version at admission
        self.out: List[int] = []
        self.lps: List[float] = []
        self.ts: List[float] = []            # perf_counter stamp per token
        self.pending: Optional[int] = None   # None: prompt not fully prefilled
        self.pending_lp = 0.0
        self.pending_t = 0.0                 # when ``pending`` was sampled
        self.row = row                       # full block-table row
        self.pos = pos                       # next prefill position
        self.h_last: Optional[np.ndarray] = None   # (D,) trunk hidden at the
        # last CACHED position (spec_steps only: the MTP draft input)


class _Draw:
    """One row's token and logprob as the device sampler drew them, the
    ``row`` that ``ContinuousEngine._sample`` hands out.  ``len()`` is the
    vocabulary it was drawn over, as a logits row's is, so code wrapping
    ``_sample`` (the benchmark's fault checks alter a token by id) reads
    either kind of row alike."""
    __slots__ = ("token", "logprob", "vocab")

    def __init__(self, token: int, logprob: float, vocab: int):
        self.token, self.logprob, self.vocab = token, logprob, vocab

    def __len__(self) -> int:
        return self.vocab


def _emit_pending(s: _Active) -> None:
    """Append the sampled ``pending`` token, its logprob and its stamp to
    the sequence's output."""
    s.out.append(s.pending)
    s.lps.append(s.pending_lp)
    s.ts.append(s.pending_t)


class ContinuousEngine:
    """Paged-KV continuous-batching engine with radix prefix reuse."""

    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 4,
                 block_size: int = 16, num_blocks: int = 64,
                 max_len: int = 512, seed: int = 0,
                 prefix_cache: bool = True,
                 prefill_chunk: Optional[int] = None,
                 capture_logprobs: bool = False,
                 attn_impl: Optional[str] = None,
                 spec_steps: Optional[int] = None,
                 weight_version: int = 0,
                 true_logprobs: bool = False,
                 step_token_budget: Optional[int] = None,
                 registry: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None,
                 max_waiting: Optional[int] = None,
                 admit_hol_window: Optional[int] = None,
                 spill: Optional[bool] = None,
                 spill_blocks: Optional[int] = None,
                 faults: Optional[FaultInjector] = None):
        if cfg.family not in ("dense", "moe", "vlm", "hybrid"):
            raise NotImplementedError(
                f"ContinuousEngine supports transformer + hybrid families, "
                f"got {cfg.family!r}")
        if prefill_chunk is not None and (
                prefill_chunk <= 0 or prefill_chunk % block_size):
            raise ValueError("prefill_chunk must be a positive multiple of "
                             f"block_size, got {prefill_chunk}")
        if spec_steps is None:
            from repro.flags import default_spec_steps
            spec_steps = default_spec_steps()
        if spec_steps < 0:
            raise ValueError(f"spec_steps must be >= 0, got {spec_steps}")
        if spec_steps > 0:
            if cfg.family == "hybrid":
                raise ValueError(
                    "spec_steps > 0 is unsupported for the hybrid family: "
                    "a partial accept cannot roll back recurrent state "
                    "(KV rollback is a length truncation; mamba2 state "
                    "advanced over rejected drafts is unrecoverable)")
            if cfg.mtp is None:
                raise ValueError("spec_steps > 0 needs an MTP head "
                                 "(cfg.mtp is None)")
            if not cfg.mtp.share_params and \
                    spec_steps > cfg.mtp.num_predict:
                raise ValueError(
                    f"spec_steps={spec_steps} exceeds the "
                    f"{cfg.mtp.num_predict} separately-trained MTP layers "
                    f"(share_params=False has no layer to draft beyond)")
        if true_logprobs and not capture_logprobs:
            raise ValueError("true_logprobs=True records per-token logprobs"
                             " and therefore needs capture_logprobs=True")
        if step_token_budget is not None and step_token_budget < 1:
            raise ValueError("step_token_budget must be >= 1, got "
                             f"{step_token_budget}")
        # one registry per engine unless the caller shares one (e.g. a
        # RolloutEngine pooling serving + rollout metrics); the tracer
        # defaults to the process-wide REPRO_TRACE switch and is a no-op
        # (single attribute check, no buffer growth) when disabled
        from repro.flags import (admit_window, max_waiting_default,
                                 spill_enabled, trace_enabled)
        self.registry = registry if registry is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None \
            else Tracer(enabled=trace_enabled())
        # admission backpressure: bound on the waiting queue (submit
        # fast-fails with EngineOverloaded beyond it; <= 0 = unbounded)
        # and the head-of-line scan window (how many queued requests
        # behind a stalled head are probed for a smaller fit)
        if max_waiting is None:
            max_waiting = max_waiting_default()
        self.max_waiting = max_waiting if max_waiting > 0 else None
        self.admit_hol_window = admit_window() \
            if admit_hol_window is None else admit_hol_window
        # deterministic fault injection (repro.faults): shared with the
        # allocator so an injected alloc storm surfaces through the REAL
        # CacheFull pressure paths.  Disabled specs cost one attr check.
        self.faults = FaultInjector.from_env() if faults is None else faults
        self.spec_steps = spec_steps
        self.cfg = cfg
        self.params = params
        self.weight_version = weight_version
        self.true_logprobs = true_logprobs
        self.step_token_budget = step_token_budget
        self._pending_push: Optional[tuple] = None
        self.model = get_model(cfg)
        self.max_batch = max_batch
        self.block_size = block_size
        self.max_blocks = max(1, max_len // block_size)   # capacity per seq
        # table WIDTH: speculative verification writes up to spec_steps
        # positions beyond a slot's lifetime allocation (the batched span
        # has one shape); the extra columns point at the trash block, so
        # those writes land in trash instead of clamping into a live block
        self.table_width = self.max_blocks + \
            (-(-spec_steps // block_size) if spec_steps else 0)
        self.kv = PagedKVCache(num_blocks, block_size,
                               registry=self.registry, faults=self.faults)
        self.kv.set_version(weight_version)
        # everything a supervisor needs to rebuild this engine after a
        # crash (``respawn``) — geometry and policy, all RESOLVED values
        # so a respawn is deterministic even if env flags change later.
        # Params/weight_version are taken from live state at respawn time.
        # host-RAM spill tier for the radix cache (the KV memory
        # hierarchy's lever 2): resolve the env default NOW so respawn
        # is deterministic; the tier itself attaches after the pool
        # exists below
        self._spill_on = spill_enabled() if spill is None else bool(spill)
        self._spill_blocks = spill_blocks
        self._init_kw = dict(
            max_batch=max_batch, block_size=block_size,
            num_blocks=num_blocks, max_len=max_len, seed=seed,
            prefix_cache=prefix_cache, prefill_chunk=prefill_chunk,
            capture_logprobs=capture_logprobs, attn_impl=attn_impl,
            spec_steps=spec_steps, true_logprobs=true_logprobs,
            step_token_budget=step_token_budget,
            max_waiting=0 if self.max_waiting is None else self.max_waiting,
            admit_hol_window=self.admit_hol_window,
            spill=self._spill_on, spill_blocks=spill_blocks)
        self.prefill_chunk = prefill_chunk
        self.capture_logprobs = capture_logprobs
        self.hybrid = cfg.family == "hybrid"
        # recurrent state is not reconstructible from KV blocks: no reuse
        self.prefix = PrefixCache(self.kv) \
            if (prefix_cache and not self.hybrid) else None
        self.trash = num_blocks          # reserved scratch block: idle slots
        # pool dtype follows the params (e.g. the bf16 rollout regime) so
        # cached KV never silently promotes the residual stream
        dtype = jax.tree.leaves(params)[0].dtype
        if self.hybrid:
            self.pool, _ = self.model.init_paged_cache(
                cfg, num_blocks + 1, block_size, dtype, batch=max_batch)
        else:
            self.pool, _ = self.model.init_paged_cache(cfg, num_blocks + 1,
                                                       block_size, dtype)
        # lever 2 of the KV memory hierarchy: demote-instead-of-forget.
        # Needs both a radix tree (to key entries by token path) and the
        # pool built above (restores scatter into it); hybrid/cache-off
        # engines have neither, so they get no tier.
        self.spill_tier = None
        if self._spill_on and self.prefix is not None:
            from repro.serving.spill import HostSpillTier
            self.spill_tier = HostSpillTier(
                self, capacity_blocks=self._spill_blocks)
            self.spill_tier.attach(self.prefix)
        self.tables = np.full((max_batch, self.table_width), self.trash,
                              np.int32)
        self.lengths = np.zeros((max_batch,), np.int32)
        self.slots: List[Optional[_Active]] = [None] * max_batch
        self.waiting: collections.deque = collections.deque()
        # host sampler: the speculative path only (its verified span
        # logits are on the host); every other token is drawn on the
        # device (the decode program, ``_draw``) from fold_in(key(seed),
        # draw counter).
        # The key is the seed's two 32-bit words, so seeds past 2**32
        # stay distinct; it is an argument of the programs, not a
        # constant, so their compiled form (and the persistent compile
        # cache's key) does not depend on the seed.
        self._rng = np.random.default_rng(seed)
        self._key_words = np.array([(seed >> 32) & 0xFFFFFFFF,
                                    seed & 0xFFFFFFFF], np.uint32)
        self._draws = 0
        self._logprobs = ("true" if true_logprobs else "scaled") \
            if capture_logprobs else None
        # the historical stats dict, now a VIEW over registry counters
        # (same keys, same reads/writes); "compiles" counts actual jit
        # traces of the engine's compiled steps — the re-jitting hazard as
        # a first-class metric; "sample_ns" sums the host time spent in
        # ``_sample`` (handing a device-drawn token and logprob to its
        # slot; on the speculative path, softmax, draw and logprob);
        # "device_sampled_rows" counts the rows drawn on the device
        self._stats = StatsView(
            self.registry, "engine",
            ["steps", "prefills", "decode_steps", "decode_tokens",
             "prefill_tokens", "cached_tokens", "cow_forks", "chunk_steps",
             "sample_ns", "device_sampled_rows",
             # speculative decode (spec_steps > 0): drafted vs accepted
             # counts; spec_rounds counts (slot, step) verifications that
             # drafted at least one token
             "draft_tokens", "accepted_tokens", "spec_rounds",
             # weight pushes applied at the drain barrier, and admissions
             # deferred by the step-token budget
             "weight_pushes", "budget_deferrals", "compiles",
             # fault tolerance: client cancellations, elapsed deadlines,
             # load-shed requests, bounded-queue submit rejections,
             # out-of-order (head-of-line window) admissions, and
             # per-request isolated faults (the request died, not the
             # engine)
             "cancels", "deadline_expired", "sheds", "overloads",
             "admit_skips", "request_faults"])
        self._next_rid = 0
        self._push_t0: Optional[float] = None
        # 'pallas' reads KV blocks in place (decode kernels at S==1, the
        # flash-prefill kernels on spans); 'ref' restores the full-view
        # gather for both phases (byte-identical greedy — the parity
        # oracle).  attn_impl covers BOTH; with attn_impl=None each phase
        # falls back to its own env default (repro.flags).  Resolved here
        # only to fail fast on an unknown impl.
        from repro.kernels.paged_attention.ops import resolve_impl
        self.attn_impl = attn_impl
        resolve_impl(attn_impl)
        # donate the pool through the hot jits: paged_update then scatters
        # into the live buffer instead of copying the whole pool every step
        # (hybrid decode donates only the KV pool — _ssm_restore must read
        # the pre-step recurrent state, which donation would invalidate).
        # Decode samples inside its program.  A (non-speculative) prefill
        # program hands back its last position's logits, and ``_draw``
        # samples them on the final span: one program for every span
        # length, where a sampler in each prefill program would be lowered
        # and compiled once per span length.
        hy = "_hybrid" if self.hybrid else ""
        self._decode = jax.jit(self._last_position(hy + "_decode_fn", True),
                               donate_argnums=(2,))
        self._prefill = jax.jit(
            self._prefill_fn if self.spec_steps
            else self._last_position(hy + "_prefill_fn", False),
            donate_argnums=(2,))
        self._draw = jax.jit(self._draw_fn)
        # donating the pool makes the COW fork a single-block in-place
        # write instead of a whole-pool HBM round trip
        self._cow = jax.jit(self._cow_fn, donate_argnums=(0,))
        if self.hybrid:
            self._ssm_reset = jax.jit(self._ssm_reset_fn)
            self._ssm_restore = jax.jit(self._ssm_restore_fn)
        if self.spec_steps:
            # ONE fused jit per speculative round: n chained MTP draft
            # steps feeding a batched S=spec_steps+1 span verification
            # through the paged flash-prefill path (replaces the S==1
            # decode entirely while speculating — a round is one dispatch,
            # like the decode step it substitutes)
            self._spec_round = jax.jit(self._spec_round_fn,
                                       donate_argnums=(4,))

    # ------------------------------------------------------------ telemetry
    @property
    def stats(self):
        """The historical stats dict, as a registry-backed ``StatsView``."""
        return self._stats

    @stats.setter
    def stats(self, values) -> None:
        # benchmark idiom: ``eng.stats = {k: 0, ...}`` resets the counters
        # (registry-backed, so the snapshot resets with the view)
        self._stats.reset(values)

    def latency_summary(self) -> dict:
        """Live per-request latency distributions (ms): TTFT (submit ->
        first token), TPOT (mean inter-token after the first), total
        latency, and queue wait — each a fixed-bucket histogram summary
        with count/mean/min/max/p50/p95/p99.  Measured on the REAL engine
        (wall-clock stamps on every request), not the pd_sim model."""
        return {name: self.registry.summary(f"engine.{name}")
                for name in ("ttft_ms", "tpot_ms", "latency_ms",
                             "queue_ms")}

    def _compiled(self, fn: str) -> None:
        """Runs INSIDE an engine jit's Python body — i.e. only when jax is
        actually tracing (a compile).  Steady-state steps hit the jit
        cache and never re-enter Python, so this counts recompiles."""
        self._stats["compiles"] += 1
        self.tracer.instant("jit.compile", fn=fn)

    # ------------------------------------------------------------------ jit
    def _last_position(self, name: str, sample: bool):
        """The model step ``self.<name>`` (args -> (logits (B,S,V), pool))
        cut to each row's last position, as one program: (*args) ->
        (logits (B,V), pool), or with ``sample`` (*args,
        *``_sampler_args``) -> (tokens (B,), logprobs (B,), pool), drawn
        by ``_draw_fn`` inside it.  The program keeps the step's name,
        which names its XLA module (``jit__decode_fn``,
        ``jit__prefill_fn``): device traces are read by module name."""
        step_fn = getattr(self, name)

        def program(*args):
            if not sample:
                logits, pool = step_fn(*args)
                return logits[:, -1], pool
            *args, temps, key_words, draw = args
            logits, pool = step_fn(*args)
            return (*self._draw_fn(logits[:, -1], temps, key_words, draw),
                    pool)
        program.__name__ = program.__qualname__ = name
        return program

    def _draw_fn(self, logits, temps, key_words, draw):
        """``sample_rows`` keyed fold_in(key(seed), draw): (tokens (B,),
        logprobs (B,)) for logits (B, V)."""
        key = jax.random.fold_in(jax.random.wrap_key_data(key_words), draw)
        return sample_rows(logits, temps, key, self._logprobs)

    def _sampler_args(self, temps: np.ndarray) -> tuple:
        """The trailing arguments of a draw's next call: per-row
        temperatures (float32), the seed's key words and the draw counter
        (the PRNG key is fold_in(key(seed), counter)).  All are traced, so
        none of them recompiles."""
        self._draws += 1
        return temps, self._key_words, np.int32(self._draws & 0x7FFFFFFF)

    def _decode_fn(self, params, tok, pool, tables, lengths):
        self._compiled("decode")
        return self.model.decode_step(params, tok, self.cfg, pool, lengths,
                                      block_tables=tables,
                                      paged_impl=self.attn_impl)

    def _hybrid_decode_fn(self, params, tok, kv, ssm, tables, lengths):
        # kv rides in the DONATED slot (argnums 2); ssm stays undonated so
        # the pre-step recurrent state survives for _ssm_restore
        self._compiled("decode")
        return self.model.decode_step(params, tok, self.cfg,
                                      {"ssm": ssm, "kv": kv}, lengths,
                                      block_tables=tables,
                                      paged_impl=self.attn_impl)

    def _prefill_fn(self, params, toks, pool, table, starts):
        self._compiled("prefill")
        if self.spec_steps:
            # speculating engines prefill through verify_step — the same
            # span forward, but it also returns the trunk hidden states
            # the first MTP draft chains from: (logits, hidden, pool)
            return self.model.verify_step(params, toks, self.cfg, pool,
                                          starts, block_tables=table,
                                          paged_impl=self.attn_impl)
        return self.model.prefill(params, toks, self.cfg, pool,
                                  block_tables=table, cache_index=starts,
                                  paged_impl=self.attn_impl)

    def _spec_round_fn(self, params, h_last, tok, positions, pool, tables,
                       lengths):
        """Draft-then-verify, fused: MTP chains ``spec_steps`` greedy
        drafts from each slot's trunk hidden ``h_last`` (at ``positions``)
        and pending token ``tok``; [tok, drafts] then rides ONE batched
        span forward (``verify_step`` — KV scattered at ``lengths`` + i,
        flash-prefill reads in place).  Returns (drafts (B,n), verify
        (B,n+1) greedy argmax per position, logits (B,n+1,V), hidden
        (B,n+1,D), pool); acceptance is host-side.  The host only pulls
        drafts/verify/hidden — the full-vocab logits cross the wire
        solely under ``capture_logprobs`` (the decode step this round
        replaces transferred (B,1,V); (B,n+1,V) would scale the hot
        path's device->host traffic with the vocab for an argmax)."""
        from repro.serving.speculative import mtp_draft
        self._compiled("spec_round")
        drafts = mtp_draft(params, self.cfg, h_last, tok, positions,
                           self.spec_steps).astype(jnp.int32)
        toks = jnp.concatenate([tok, drafts], axis=1)
        logits, hid, pool = self.model.verify_step(
            params, toks, self.cfg, pool, lengths, block_tables=tables,
            paged_impl=self.attn_impl)
        verify = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return drafts, verify, logits, hid, pool

    def _hybrid_prefill_fn(self, params, toks, pool, table, starts, slot):
        # thread ONE slot's recurrent state through the batch-1 prefill;
        # the shared-attention KV pool is global, the ssm state per-slot
        self._compiled("prefill")
        ssm_i = jax.tree.map(
            lambda x: jax.lax.dynamic_slice_in_dim(x, slot, 1, axis=1),
            pool["ssm"])
        logits, new = self.model.prefill(
            params, toks, self.cfg, {"ssm": ssm_i, "kv": pool["kv"]},
            block_tables=table, cache_index=starts,
            paged_impl=self.attn_impl)
        ssm = jax.tree.map(
            lambda full, one: jax.lax.dynamic_update_slice_in_dim(
                full, one, slot, axis=1),
            pool["ssm"], new["ssm"])
        return logits, {"ssm": ssm, "kv": new["kv"]}

    def _cow_fn(self, pool, src, dst):
        """Copy block ``src`` -> ``dst`` across every KV leaf (COW fork).

        Every leaf is a (layer-major) flat pool with ``num_blocks + 1``
        rows per layer, so the copy is one ``copy_block_strided`` per leaf.
        Jitted with the pool DONATED, so each update is in place — a fork
        moves L·block_size rows, not the pool."""
        from repro.core.paging import copy_block_strided
        stride = self.kv.num_blocks + 1
        out = {}
        for k, v in pool.items():
            if k == "ssm":
                out[k] = v                       # recurrent state: per-slot
            else:
                out[k] = jax.tree.map(
                    lambda x: copy_block_strided(x, src, dst, stride), v)
        return out

    def _ssm_reset_fn(self, pool, slot):
        return dict(pool, ssm=jax.tree.map(
            lambda x: x.at[:, slot].set(jnp.zeros_like(x[:, slot])),
            pool["ssm"]))

    def _ssm_restore_fn(self, ssm, old_ssm, mask):
        # ONLY the ssm subtree passes through this (non-donating) jit —
        # threading the whole pool would copy the untouched KV leaves
        # input-to-output and undo the decode donation
        def mix(new, old):
            m = mask.reshape((1, -1) + (1,) * (new.ndim - 2))
            return jnp.where(m, old, new)
        return jax.tree.map(mix, ssm, old_ssm)

    # ------------------------------------------------------------ scheduler
    def validate(self, req: Request) -> None:
        """Reject requests the engine could NEVER serve (size/sampling).

        Pure read of fixed engine geometry — safe from any thread, which
        is what lets ``AsyncFrontend.submit`` fail fast on the caller's
        thread while the serve thread owns all mutable state."""
        if self.spec_steps and req.temperature > 0:
            raise ValueError(
                "speculative decoding is greedy-only: acceptance compares "
                "drafts against the model's argmax (submit temperature=0 "
                "requests or build the engine with spec_steps=0)")
        need = len(req.prompt) + req.max_new
        if need > self.max_blocks * self.block_size:
            raise ValueError(
                f"request needs {need} token slots > max_len "
                f"{self.max_blocks * self.block_size}")
        if blocks_for(need, self.block_size) > self.kv.num_blocks:
            raise CacheFull(
                f"request needs {blocks_for(need, self.block_size)} blocks "
                f"> pool capacity {self.kv.num_blocks}")

    def submit(self, req: Request) -> None:
        if self.max_waiting is not None \
                and len(self.waiting) >= self.max_waiting:
            # admission backpressure: fast-fail instead of growing an
            # unbounded backlog (the caller sees saturation NOW, not as
            # a deadline blowout minutes later)
            self.stats["overloads"] += 1
            raise EngineOverloaded(
                f"waiting queue full ({len(self.waiting)} >= max_waiting "
                f"{self.max_waiting}); retry later or raise "
                f"REPRO_MAX_WAITING")
        self.validate(req)
        req.rid = self._next_rid
        self._next_rid += 1
        if req.t_submit is None:      # AsyncFrontend stamps on the caller's
            req.t_submit = time.perf_counter()   # thread, before the queue
        self.tracer.instant("req.submit", req=req.rid,
                            prompt_tokens=len(req.prompt),
                            max_new=req.max_new)
        self.waiting.append(req)

    # -------------------------------------------------------- weight pushes
    def push_weights(self, params, version: int) -> bool:
        """Hand the engine a new weight snapshot (same pytree structure
        and dtypes — the trainer/rollout layer casts).

        Applied at the DRAIN BARRIER: if any sequence is in flight the
        push is deferred — admission pauses, live sequences finish under
        their admitted weights, and the swap happens in ``step()`` the
        moment the engine drains.  A newer deferred push replaces an
        older one (latest snapshot wins; intermediate versions were never
        observable anyway).  Returns True when applied immediately."""
        if version < self.weight_version:
            raise ValueError(f"weight versions are monotone: push {version}"
                             f" < current {self.weight_version}")
        pend = self._pending_push
        if pend is not None and version < pend[1]:
            raise ValueError(f"weight versions are monotone: push {version}"
                             f" < pending {pend[1]}")
        self._pending_push = (params, version)
        if self._push_t0 is None:      # drain clock starts at the FIRST
            self._push_t0 = time.perf_counter()   # push of a deferred run
        self.tracer.instant("push.requested", version=version)
        return self._apply_push_if_drained()

    def _apply_push_if_drained(self) -> bool:
        if self._pending_push is None or \
                any(s is not None for s in self.slots):
            return False
        params, version = self._pending_push
        self._pending_push = None
        self.params = params
        self.weight_version = version
        # drain barrier latency: push requested -> applied (how long the
        # oldest pending snapshot waited on in-flight sequences)
        drain_ms = (time.perf_counter() - self._push_t0) * 1e3 \
            if self._push_t0 is not None else 0.0
        self._push_t0 = None
        self.registry.observe("engine.push_drain_ms", drain_ms)
        self.tracer.instant("push.applied", version=version,
                            drain_ms=drain_ms)
        # existing cached blocks keep their old stamps: match() now walks
        # past none of them, insert() refreshes hot paths, evict() takes
        # stale blocks first — the incremental invalidation
        self.kv.set_version(version)
        self.stats["weight_pushes"] += 1
        return True

    def serve(self, requests: List[Request]) -> List[Request]:
        for r in requests:
            self.submit(r)
        while self.waiting or any(s is not None for s in self.slots):
            self.step()
        self._apply_push_if_drained()     # push arrived on the last step
        return requests

    def step(self) -> None:
        """One iteration: retire -> apply drained weight push -> admit ->
        chunk prefill -> batched decode."""
        tr = self.tracer
        # step span args: the timeline quantities an SLO post-mortem
        # needs — batch occupancy, queue depth, live tokens, pool use
        span = tr.span(
            "engine.step",
            occupancy=sum(1 for s in self.slots if s is not None),
            waiting=len(self.waiting),
            live_tokens=int(self.lengths.sum()),
            pool_used=self.kv.used_blocks, pool_free=self.kv.free_blocks,
            phase="spec" if self.spec_steps else "decode") \
            if tr.enabled else NO_SPAN
        with span:
            if self.faults.enabled:
                # "slow": a straggler step (param = seconds); "step": an
                # unattributable engine-level exception — nothing ties it
                # to one request, so it propagates to the frontend
                # supervisor
                if self.faults.fires("slow"):
                    time.sleep(self.faults.param("slow", 0.02))
                self.faults.check("step")
            self._expire_deadlines()
            self._retire()
            self._apply_push_if_drained()
            self._admit()
            self._prefill_chunks()
            if self.spec_steps:
                self._spec_decode_active()
            else:
                self._decode_active()
            self.stats["steps"] += 1
        self.registry.set_gauge(
            "engine.pool_utilization",
            self.kv.used_blocks / self.kv.num_blocks)

    def reset_cache(self) -> None:
        """Drop all cached prefix blocks (benchmark hygiene; weight pushes
        do NOT need this — see ``push_weights``)."""
        if self.prefix is not None:
            self.prefix.clear()
        if self.spill_tier is not None:
            self.spill_tier.clear()

    @property
    def busy(self) -> bool:
        """Does the engine have work for another ``step()``?  True while
        requests wait or run, or a weight push awaits its drain barrier."""
        return bool(self.waiting) or self._pending_push is not None \
            or any(s is not None for s in self.slots)

    @property
    def cached_blocks(self) -> int:
        return self.prefix.cached_blocks if self.prefix is not None else 0

    @property
    def stale_cached_blocks(self) -> int:
        """Cached blocks orphaned by a weight push, awaiting lazy LRU
        reclamation (0 when the prefix cache is off)."""
        return self.prefix.stale_cached_blocks \
            if self.prefix is not None else 0

    @property
    def spilled_blocks(self) -> int:
        """Blocks resident in the host spill tier (0 when spill is off).
        ``cached_blocks + spilled_blocks`` is the engine's EFFECTIVE
        prefix-cache capacity — the tier's whole point is letting it
        exceed the HBM pool."""
        return self.spill_tier.spilled_blocks \
            if self.spill_tier is not None else 0

    # --------------------------------------------------------------- retire
    def _retire(self) -> None:
        with self.tracer.span("engine.retire"):
            for i, s in enumerate(self.slots):
                if s is not None and s.pending is not None \
                        and len(s.out) + 1 >= s.req.max_new:
                    _emit_pending(s)        # final token needs no decode
                    self._finish(i)

    def _finish(self, i: int) -> None:
        s = self.slots[i]
        # the drain barrier guarantees a sequence retires under the same
        # weights it was admitted with — the whole trajectory is one
        # version, and that is what the TITO stamp records
        assert s.version == self.weight_version, (s.version,
                                                  self.weight_version)
        s.req.out_version = s.version
        s.req.out = np.asarray(s.out[:s.req.max_new], np.int32)
        if self.capture_logprobs:
            s.req.out_logprobs = np.asarray(s.lps[:s.req.max_new],
                                            np.float32)
        s.req.t_tokens = np.asarray(s.ts[:s.req.max_new], np.float64)
        # live latency SLO metrics (GLM-5 §3.6 / SNIPPETS Snippet 3):
        # per-request TTFT (submit -> first token, queueing included) and
        # TPOT (mean inter-token time after the first) feed fixed-bucket
        # histograms — p50/p95/p99 with no samples stored
        s.req.t_finish = time.perf_counter()
        reg = self.registry
        if s.req.t_submit is not None:
            reg.observe("engine.latency_ms",
                        (s.req.t_finish - s.req.t_submit) * 1e3)
            ttft = s.req.ttft_s
            if ttft is not None:
                reg.observe("engine.ttft_ms", ttft * 1e3)
        tpot = s.req.tpot_s
        if tpot is not None and len(s.req.out) > 1:
            reg.observe("engine.tpot_ms", tpot * 1e3)
        self.tracer.instant("req.finished", req=s.req.rid,
                            version=s.version,
                            new_tokens=int(len(s.req.out)))
        if self.prefix is not None:
            # KV exists for every position actually written: the prompt
            # plus all DECODED output tokens (the final sampled token was
            # never forwarded, so its KV is absent by construction)
            kv_len = int(self.lengths[i])
            toks = list(map(int, s.req.prompt)) + s.out[:kv_len
                                                        - len(s.req.prompt)]
            ncover = blocks_for(kv_len, self.block_size)
            self.prefix.insert(toks[:kv_len], s.blocks[:ncover])
            if s.blocks[ncover:]:
                self.kv.release(s.blocks[ncover:])
        else:
            self.kv.free(s.blocks)          # blocks recycle immediately
        self.slots[i] = None
        self.tables[i] = self.trash
        self.lengths[i] = 0

    # ------------------------------------------------------ fault tolerance
    # terminal failure bookkeeping: status -> (stats counter, trace event)
    _FAIL_KINDS = {"cancelled": ("cancels", "req.cancelled"),
                   "deadline": ("deadline_expired", "req.deadline_expired"),
                   "shed": ("sheds", "req.shed"),
                   "failed": ("request_faults", "req.failed")}

    def _fail_waiting(self, req: Request, error: Exception,
                      status: str) -> None:
        """Terminally fail a request that never reached a slot (no device
        state, no blocks — just stamp the typed outcome)."""
        req.error = error
        req.status = status
        req.t_finish = time.perf_counter()
        counter, event = self._FAIL_KINDS[status]
        self.stats[counter] += 1
        self.tracer.instant(event, req=req.rid, error=repr(error))

    def _retire_slot_error(self, i: int, error: Exception, status: str,
                           donate: bool) -> None:
        """Retire slot ``i`` mid-flight with a typed error.

        Mirrors ``_finish``'s block disposal: with ``donate=True`` the KV
        actually WRITTEN so far (prompt prefix + decoded tokens) is
        inserted into the radix tree — a cancelled/expired agentic prompt
        still seeds the prefix cache for its successors.  ``donate=False``
        (an isolated fault: the KV may be suspect) releases the blocks
        without caching them.  Either way every block this slot held goes
        back through the refcount machinery — retirement can never leak."""
        s = self.slots[i]
        req = s.req
        # KV exists up to the slot's cached length; a slot still mid-
        # chunked-prefill has only prefilled s.pos positions (lengths[i]
        # stays 0 until the final span installs the decode view)
        kv_len = int(self.lengths[i]) if s.pending is not None else s.pos
        donate = donate and self.prefix is not None and kv_len > 0 \
            and s.version == self.weight_version
        if donate:
            toks = (list(map(int, req.prompt)) + s.out)[:kv_len]
            ncover = blocks_for(kv_len, self.block_size)
            self.prefix.insert(toks, s.blocks[:ncover])
            if s.blocks[ncover:]:
                self.kv.release(s.blocks[ncover:])
        elif self.prefix is not None:
            self.kv.release(s.blocks)
        else:
            self.kv.free(s.blocks)
        self.slots[i] = None
        self.tables[i] = self.trash
        self.lengths[i] = 0
        req.error = error
        req.status = status
        req.t_finish = time.perf_counter()
        counter, event = self._FAIL_KINDS[status]
        self.stats[counter] += 1
        self.tracer.instant(event, req=req.rid, slot=i, kv_len=kv_len,
                            donated=bool(donate), error=repr(error))

    def _isolate_fault(self, req: Request, error: Exception) -> None:
        """Per-request fault isolation: an exception attributable to ONE
        request (its admission or its prefill span) kills that request
        with a typed terminal error and leaves the engine serving."""
        slot = next((i for i, s in enumerate(self.slots)
                     if s is not None and s.req is req), None)
        if slot is not None:
            # the fault hit after slot install (e.g. mid-prefill): the
            # slot's KV is suspect, so release without donating
            self._retire_slot_error(slot, error, "failed", donate=False)
        else:
            self._fail_waiting(req, error, "failed")

    def _expire_deadlines(self) -> None:
        """Retire every request whose ``deadline_s`` budget (relative to
        t_submit) has elapsed — queued or mid-flight.  Mid-flight expiry
        donates written KV through the radix path, exactly like a client
        cancellation."""
        now = time.perf_counter()

        def expired(r: Request) -> bool:
            return r.deadline_s is not None and r.t_submit is not None \
                and now - r.t_submit > r.deadline_s

        for i, s in enumerate(self.slots):
            if s is not None and expired(s.req):
                self._retire_slot_error(
                    i, DeadlineExceeded(
                        f"request {s.req.rid} exceeded deadline_s="
                        f"{s.req.deadline_s} mid-flight"),
                    "deadline", donate=True)
        for r in [r for r in self.waiting if expired(r)]:
            self.waiting.remove(r)
            self._fail_waiting(
                r, DeadlineExceeded(f"request {r.rid} exceeded deadline_s="
                                    f"{r.deadline_s} while queued"),
                "deadline")

    def cancel(self, rid: int) -> bool:
        """Cancel one request by id, queued or mid-flight.

        A mid-flight cancellation retires the slot immediately — its
        blocks are DONATED to the prefix cache (the cancelled prefix
        still seeds future requests), not just freed.  Returns False if
        the rid is unknown or already terminal (cancellation races
        completion; the caller keeps whichever outcome landed first)."""
        for r in self.waiting:
            if r.rid == rid:
                self.waiting.remove(r)
                self._fail_waiting(
                    r, RequestCancelled(f"request {rid} cancelled while "
                                        f"queued"), "cancelled")
                return True
        for i, s in enumerate(self.slots):
            if s is not None and s.req.rid == rid:
                self._retire_slot_error(
                    i, RequestCancelled(f"request {rid} cancelled "
                                        f"mid-flight"),
                    "cancelled", donate=True)
                return True
        return False

    def respawn(self) -> "ContinuousEngine":
        """Build a FRESH engine with this engine's geometry and policy
        (the supervisor's restart path: device pool, block tables, and
        prefix cache are rebuilt from scratch — in-flight KV died with
        the crash).  Weights are the newest this engine was handed: a
        push still waiting at the drain barrier wins over the running
        params.  The registry/tracer/fault-injector are SHARED so
        counters, traces, and the injection schedule continue across the
        restart (a ``crash@3`` clause must not re-fire at the respawned
        engine's step 3)."""
        params, version = (self._pending_push
                           if self._pending_push is not None
                           else (self.params, self.weight_version))
        return ContinuousEngine(self.cfg, params, weight_version=version,
                                registry=self.registry, tracer=self.tracer,
                                faults=self.faults, **self._init_kw)

    # ---------------------------------------------------------------- admit
    def _admit(self) -> None:
        with self.tracer.span("engine.admit") as span:
            free = self.slots.count(None)
            self._fill_slots()
            span.set(admitted=free - self.slots.count(None))

    def _fill_slots(self) -> None:
        if self._pending_push is not None:
            return          # draining toward the weight-push barrier
        while self.waiting and None in self.slots:
            if not self._step_budget_allows():
                self.stats["budget_deferrals"] += 1
                return
            head = self.waiting[0]
            try:
                admitted = self._try_admit(head)
            except Exception as e:
                # attributable to THIS request: isolate it, keep serving
                self.waiting.popleft()
                self._isolate_fault(head, e)
                continue
            if admitted:
                self.waiting.popleft()
                continue
            # head can't admit (not enough free blocks): probe a bounded
            # window behind it for a smaller request that fits, instead
            # of stalling ALL admission on the head
            if self._admit_from_window():
                continue
            self._shed_if_wedged()
            return

    def _admit_from_window(self) -> bool:
        """Out-of-order admission behind a stalled head: try up to
        ``admit_hol_window`` queued requests for one that fits the free
        blocks the head cannot use.  Bounded so a huge head is delayed at
        most a window's worth of queue positions, not starved.  Returns
        True when the queue changed (admit or isolated fault) — the
        caller re-enters the admission loop."""
        limit = min(self.admit_hol_window, len(self.waiting) - 1)
        for k in range(1, limit + 1):
            req = self.waiting[k]
            try:
                ok = self._try_admit(req)
            except Exception as e:
                del self.waiting[k]
                self._isolate_fault(req, e)
                return True
            if ok:
                del self.waiting[k]
                self.stats["admit_skips"] += 1
                return True
        return False

    def _shed_if_wedged(self) -> None:
        """Admission failed with an EMPTY engine: no live sequence will
        ever release blocks, so the queue would wedge forever (this was
        the engine-killing ``CacheFull`` crash).  Every free-list block
        is pinned outside the engine — session-pinned, or an injected
        alloc storm — so shed the DEEPEST-queued request with a typed
        per-request error.  One shed per step: pressure drains the queue
        tail-first while the head keeps its chance at admission, and
        each shed is individually observable."""
        if any(s is not None for s in self.slots) or not self.waiting:
            return          # live sequences will release blocks: just wait
        req = self.waiting.pop()
        self._fail_waiting(
            req, RequestShed(
                f"pool exhausted with an empty engine "
                f"({self.kv.free_blocks}/{self.kv.num_blocks} blocks free "
                f"after eviction; pinned by sessions?): shed request "
                f"{req.rid} at queue depth {len(self.waiting) + 1}"),
            "shed")

    def _step_budget_allows(self) -> bool:
        """Accept-length-aware slot budgeting (``step_token_budget``).

        Every live slot emits up to ``spec_steps + 1`` tokens per step;
        admission projects the per-step emission of ``live + 1`` slots at
        the rolling accept-length estimate (the conservative
        ``spec_steps + 1`` until a measurement exists) and defers when it
        would exceed the budget.  The first slot is always admitted —
        a budget can shape concurrency, never deadlock the engine."""
        if self.step_token_budget is None:
            return True
        live = sum(1 for s in self.slots if s is not None)
        if live == 0:
            return True
        per_slot = 1.0
        if self.spec_steps:
            est = self.rolling_accept_length
            per_slot = est if est > 0 else float(self.spec_steps + 1)
        return (live + 1) * per_slot <= self.step_token_budget

    def _try_admit(self, req: Request) -> bool:
        if self.faults.enabled:
            self.faults.check("admit", rid=req.rid)
        bs = self.block_size
        plen = len(req.prompt)
        m, mblocks = (self.prefix.match(req.prompt, limit=plen - 1)
                      if self.prefix is not None else (0, []))
        n_full, partial = m // bs, m % bs
        # blocks for the request's whole lifetime (suffix spans are exact,
        # so nothing beyond prompt+max_new is ever written)
        total = blocks_for(plen + req.max_new, bs)
        # aliased full blocks cover table slots [0, n_full); fresh blocks
        # cover the rest — on a partial match fresh[0] is the COW fork
        # destination replacing the partially-matched source block
        n_fresh = total - n_full
        try:
            fresh = self.kv.alloc(n_fresh) if n_fresh > 0 else []
        except CacheFull:
            # the match's own refs may be pinning evictable blocks: drop
            # the reuse and retry cold before giving up
            if mblocks:
                self.kv.release(mblocks)
                m, mblocks, n_full, partial = 0, [], 0, 0
                try:
                    fresh = self.kv.alloc(total)
                except CacheFull:
                    return False    # stalled: _admit decides what's next
            else:
                return False
        # from here the admission HOLDS references (owned = one ref per
        # block); any exception before the slot install must return them
        # or per-request isolation would leak blocks
        owned = mblocks + fresh
        installed = False
        try:
            if partial:
                # the match ends inside a shared block: fork it so the
                # suffix write never touches the cached copy
                src, dst = mblocks[-1], fresh[0]
                self.pool = self._cow(self.pool,
                                      jnp.asarray(src, jnp.int32),
                                      jnp.asarray(dst, jnp.int32))
                self.kv.release([src])
                blocks = mblocks[:n_full] + fresh
                owned = blocks
                self.stats["cow_forks"] += 1
            else:
                blocks = mblocks + fresh

            # version-tag invariant: every aliased block was written under
            # the CURRENT weights (match() refuses older stamps; fresh
            # allocations are stamped now, and the drain barrier keeps
            # this version live until the sequence retires)
            assert all(self.kv.block_version(b) == self.weight_version
                       for b in blocks), "stale block aliased into admission"
            slot = self.slots.index(None)
            row = np.full((self.table_width,), self.trash, np.int32)
            row[:len(blocks)] = blocks
            if self.hybrid:
                self.pool = self._ssm_reset(self.pool,
                                            jnp.asarray(slot, jnp.int32))
            s = _Active(req, blocks, row, pos=m,
                        version=self.weight_version)
            self.slots[slot] = s
            installed = True
            self.stats["prefills"] += 1
            self.stats["cached_tokens"] += m
            self.stats["prefill_tokens"] += plen - m
            if req.t_submit is not None:
                self.registry.observe(
                    "engine.queue_ms",
                    (time.perf_counter() - req.t_submit) * 1e3)
            self.tracer.instant("req.admitted", req=req.rid, slot=slot,
                                cached_tokens=m, blocks=len(blocks),
                                version=self.weight_version,
                                step=self.stats["steps"])
            if self.prefill_chunk is None:
                self._prefill_span(slot, s, span=plen - m)  # whole suffix
        except Exception:
            # past the slot install the slot owns the blocks and
            # _isolate_fault retires it (releasing them); before it, we
            # still hold them and must give them back here
            if not installed:
                self.kv.release(owned)
            raise
        return True

    # ---------------------------------------------------------- prefill
    def _prefill_span(self, slot: int, s: _Active, span: int) -> None:
        """Prefill ``span`` token positions starting at ``s.pos``; samples
        the first token and installs the decode view on the final span.

        Spans are EXACT (no right-padding to whole blocks): the in-place
        kernels mask by the span's start offset and the gather oracle by
        the causal mask, so padded garbage would be dead weight — and the
        recurrent hybrid family could never pad anyway (pad garbage would
        advance the mamba2 state)."""
        if self.faults.enabled:
            self.faults.check("prefill", rid=s.req.rid)
        prompt, plen = s.req.prompt, len(s.req.prompt)
        start = s.pos
        real = min(plen - start, span)
        assert real > 0 and start + real <= self.max_blocks * self.block_size
        tr = self.tracer
        with tr.span("engine.prefill", req=s.req.rid, start=start,
                     span=real):
            toks = np.asarray(prompt[start:start + real], np.int32)[None]
            row = s.row[None]
            args = [self.params, jnp.asarray(toks), self.pool,
                    jnp.asarray(row), jnp.asarray([start], jnp.int32)]
            if self.hybrid:
                args.append(jnp.asarray(slot, jnp.int32))
            if self.spec_steps:
                logits, hid, self.pool = self._prefill(*args)
            else:           # logits (1, V): the span's last position only
                logits, self.pool = self._prefill(*args)
            tr.instant("req.prefill", req=s.req.rid, start=start, span=real)
            s.pos = start + real
            if s.pos < plen:
                return
            # final span: token 1
            if self.spec_steps:
                row = np.asarray(logits[0, real - 1], np.float32)
            else:
                temps = np.asarray([s.req.temperature], np.float32)
                tok, lp = jax.device_get(
                    self._draw(logits, *self._sampler_args(temps)))
                row = _Draw(int(tok[0]), float(lp[0]), self.cfg.vocab_size)
                self.stats["device_sampled_rows"] += 1
            with tr.span("engine.sample", rows=[s.req.rid]):
                s.pending, s.pending_lp = self._sample(row, s.req.temperature)
                # the first generated token is KNOWN here (``pending``; it
                # is emitted unchanged) — this is the TTFT stamp
                s.pending_t = time.perf_counter()
            if s.req.t_first is None:
                s.req.t_first = s.pending_t
            if self.spec_steps:                 # the first draft's input
                s.h_last = np.asarray(hid[0, real - 1], np.float32)
            self.tables[slot] = s.row
            self.lengths[slot] = plen
            tr.instant("req.first_token", req=s.req.rid)

    def _prefill_chunks(self) -> None:
        if self.prefill_chunk is None:
            return
        for i, s in enumerate(self.slots):
            if s is not None and s.pending is None:
                try:
                    self._prefill_span(i, s, span=self.prefill_chunk)
                except Exception as e:
                    # attributable to this slot's request alone: retire
                    # it (suspect KV: no donation) and keep serving
                    self._retire_slot_error(i, e, "failed", donate=False)
                    continue
                self.stats["chunk_steps"] += 1

    # ----------------------------------------------------------- decode
    def _decode_active(self) -> None:
        # a slot whose pending token already completes the request skips
        # decode and waits for _retire — its last token needs no forward;
        # slots still prefilling (pending None) present trash rows/len 0
        active = [i for i, s in enumerate(self.slots)
                  if s is not None and s.pending is not None
                  and len(s.out) + 1 < s.req.max_new]
        if not active:
            return
        tr = self.tracer
        rows = [self.slots[i].req.rid for i in active] if tr.enabled else None
        with tr.span("engine.decode", rows=rows):
            prefilling = [i for i, s in enumerate(self.slots)
                          if s is not None and s.pending is None]
            old_ssm = self.pool["ssm"] if (self.hybrid and prefilling) \
                else None
            tok = np.zeros((self.max_batch, 1), np.int32)
            temps = np.zeros((self.max_batch,), np.float32)
            for i in active:
                tok[i, 0] = self.slots[i].pending
                temps[i] = self.slots[i].req.temperature
            pool = (self.pool["kv"], self.pool["ssm"]) if self.hybrid \
                else (self.pool,)
            toks, lps, self.pool = self._decode(
                self.params, jnp.asarray(tok), *pool,
                jnp.asarray(self.tables), jnp.asarray(self.lengths),
                *self._sampler_args(temps))
            if old_ssm is not None:
                # a decode step must not advance the recurrent state of
                # slots whose prompt is still mid-chunked-prefill
                mask = np.zeros((self.max_batch,), bool)
                mask[prefilling] = True
                self.pool = dict(self.pool, ssm=self._ssm_restore(
                    self.pool["ssm"], old_ssm, jnp.asarray(mask)))
            toks, lps = jax.device_get((toks, lps))
        V = self.cfg.vocab_size
        with tr.span("engine.sample", rows=rows):
            for i in active:
                s = self.slots[i]
                _emit_pending(s)
                self.lengths[i] += 1        # pending now lives in the cache
                s.pending, s.pending_lp = self._sample(
                    _Draw(int(toks[i]), float(lps[i]), V), s.req.temperature)
                s.pending_t = time.perf_counter()
        self.stats["decode_steps"] += 1
        self.stats["decode_tokens"] += len(active)
        self.stats["device_sampled_rows"] += len(active)

    # ------------------------------------------------------- speculative
    @property
    def rolling_accept_length(self) -> float:
        """Mean accepted tokens per speculative round so far (Table 2's
        accept length; 1.0 = every draft rejected, spec_steps+1 = all
        accepted).  Rounds near a request's budget cap draft fewer than
        ``spec_steps`` tokens (never zero), slightly deflating the mean
        relative to an unbounded decode."""
        r = self.stats["spec_rounds"]
        return self.stats["accepted_tokens"] / r if r else 0.0

    def _spec_decode_active(self) -> None:
        """One speculative round for every decoding slot: draft ``n``
        tokens per slot with the MTP head, verify [pending, drafts] as ONE
        batched S=n+1 paged span forward, accept the greedy-matching
        prefix, roll back the rest.

        Per-slot draft depth is capped at ``max_new - len(out) - 1`` (the
        only useful depth: deeper accepts could not be emitted) — which is
        exactly the bound keeping every TRUSTED verify position inside the
        slot's lifetime block allocation.  The batched span still runs at
        full width for one compiled shape; a capped slot's deeper writes
        land in its own dead tail or the trash columns, and its deeper
        logits are never read (queries at trusted positions cannot attend
        to them: causal masking by absolute position)."""
        n = self.spec_steps
        active = [i for i, s in enumerate(self.slots)
                  if s is not None and s.pending is not None
                  and len(s.out) + 1 < s.req.max_new]
        if not active:
            return
        tr = self.tracer
        rows = [self.slots[i].req.rid for i in active] if tr.enabled else None
        with tr.span("engine.decode", rows=rows):
            h = np.zeros((self.max_batch, 1, self.cfg.d_model), np.float32)
            tok = np.zeros((self.max_batch, 1), np.int32)
            pos = np.zeros((self.max_batch, 1), np.int32)
            for i in active:
                s = self.slots[i]
                h[i, 0] = s.h_last
                tok[i, 0] = s.pending
                pos[i, 0] = self.lengths[i] - 1      # h_last's position
            drafts, verify, logits, hid, self.pool = self._spec_round(
                self.params, jnp.asarray(h), jnp.asarray(tok),
                jnp.asarray(pos), self.pool, jnp.asarray(self.tables),
                jnp.asarray(self.lengths))
            drafts = np.asarray(drafts)                     # (B, n)
            verify = np.asarray(verify)                     # (B, n+1)
            hid = np.asarray(hid, np.float32)               # (B, n+1, D)
            # full-vocab logits cross to host ONLY for logprob capture
            lg = np.asarray(logits, np.float32) \
                if self.capture_logprobs else None
        # every token this round emits becomes known now: accepted drafts
        # and the bonus token share the round's stamp
        t_round = time.perf_counter()
        with tr.span("engine.sample", rows=rows):
            for i in active:
                s = self.slots[i]
                L = int(self.lengths[i])
                n_i = min(n, s.req.max_new - len(s.out) - 1)
                matches = 0
                while matches < n_i and drafts[i, matches] == \
                        verify[i, matches]:
                    matches += 1
                acc = 1 + matches
                _emit_pending(s)                # the guaranteed token
                for j in range(1, acc):         # accepted draft tokens
                    s.out.append(int(drafts[i, j - 1]))
                    s.lps.append(self._sample(lg[i, j - 1],
                                              s.req.temperature)[1]
                                 if self.capture_logprobs else 0.0)
                    s.ts.append(t_round)
                self._rollback(i, s, L + acc)
                # bonus token: the model's own choice after the accept
                # point
                if self.capture_logprobs:
                    s.pending, s.pending_lp = self._sample(
                        lg[i, acc - 1], s.req.temperature)
                else:
                    s.pending, s.pending_lp = int(verify[i, acc - 1]), 0.0
                s.pending_t = t_round
                s.h_last = hid[i, acc - 1]
                # the active filter guarantees len(out)+1 < max_new, so
                # every processed slot drafted at least one token
                assert n_i >= 1, (i, n_i)
                self.stats["spec_rounds"] += 1
                self.stats["draft_tokens"] += n_i
                self.stats["accepted_tokens"] += acc
                self.stats["decode_tokens"] += acc
                self.tracer.instant("req.spec_round", req=s.req.rid,
                                    drafted=n_i, accepted=acc)
        self.stats["decode_steps"] += 1

    def _rollback(self, i: int, s: _Active, new_len: int) -> None:
        """Roll rejected drafts out of the paged cache: truncate the
        slot's length to the accept point.

        No block changes hands: admission preallocated blocks for the
        request's whole lifetime (prompt+max_new), the draft-depth cap
        keeps every trusted position inside them, and the spec table
        columns route any deeper (untrusted) write to trash — so there is
        never a block allocated past the accept point to free, and the
        COW/refcount state is untouched (verification writes only at
        positions >= the prompt's COW point, exactly like decode; shared
        refcount>1 prefix blocks are never writable).  The rejected
        positions' KV stays as dead garbage in exclusively-owned blocks:
        the next round's span rewrites positions [new_len, new_len+n]
        before any causal mask can admit them, and `_finish` only hands
        the prefix cache blocks covering the final truncated length."""
        assert new_len <= len(s.blocks) * self.block_size, \
            (new_len, len(s.blocks))
        self.lengths[i] = new_len

    # ----------------------------------------------------------- sampling
    def _sample(self, row, temperature: float):
        """Hand one row's token and logprob to its slot.  ``row`` is the
        device sampler's draw (``_Draw``: the decode program and the final
        prefill span), or on the speculative path the row's float32 logits,
        sampled here on the host with the same conventions."""
        t0 = time.perf_counter_ns()
        if isinstance(row, _Draw):
            tok, lp = row.token, row.logprob
        else:
            tok, lp = self._host_sample(row, temperature)
        self._stats["sample_ns"] += time.perf_counter_ns() - t0
        return tok, lp

    def _host_sample(self, row: np.ndarray, temperature: float):
        tok = sample_token(row, temperature, self._rng)
        lp = 0.0
        if self.capture_logprobs:
            if self.true_logprobs:
                # the model's TRUE (temperature-1) logprob of the emitted
                # token — beyond the greedy-lp convention: a greedy rollout
                # still yields exact behavior logprobs for distillation /
                # IS.  Spec rounds get these for free from the verified
                # span logits (every accepted position's row is on host).
                z = row - row.max()
            else:
                # same convention as RolloutEngine.generate (logits /
                # max(t, 1e-6)): greedy fragments carry lp ~= 0 for the
                # argmax token, so engine-backed and loop-backed behavior
                # logprobs are comparable in the IS ratios downstream
                z = (row - row.max()) / max(temperature, 1e-6)
            lp = float(z[tok] - np.log(np.exp(z).sum()))
        return tok, lp
