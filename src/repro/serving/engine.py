"""STATIC-batching serving engine: the reference oracle.

Left-pads every prompt in a batch to the batch max and decodes lock-step
until the LONGEST ``max_new`` finishes — the design the continuous-batching
engine (``repro.serving.scheduler.ContinuousEngine``) replaces.  It is kept
as (a) the numerically-simple oracle the scheduler's byte-identical greedy
parity tests compare against, and (b) the baseline that
``benchmarks/serving_throughput.py`` measures the paged engine's speedup
over.  The production layout (DP-attention + EP, PD disaggregation) is
exercised by the dry-run and pd_sim respectively.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import get_model


# eq=False: requests are identities, not values.  The generated __eq__
# would compare numpy prompts elementwise (ambiguous-truth ValueError the
# moment a deque.remove or ``in`` scans the waiting queue); identity
# equality is also the semantics every queue/slot lookup actually wants.
@dataclasses.dataclass(eq=False)
class Request:
    prompt: np.ndarray
    max_new: int = 32
    temperature: float = 0.0
    # optional completion deadline, seconds RELATIVE to t_submit: the
    # scheduler retires the request (queued or mid-flight) with a typed
    # ``DeadlineExceeded`` once the budget elapses, donating any written
    # KV blocks through the radix path.  None = no deadline.
    deadline_s: Optional[float] = None
    out: Optional[np.ndarray] = None
    # per-token behavior logprobs of ``out`` (filled by ContinuousEngine
    # when capture_logprobs=True — the TITO contract for RL rollouts)
    out_logprobs: Optional[np.ndarray] = None
    # weight version the WHOLE generation ran under (stamped on admit by
    # ContinuousEngine; the drain-barrier push protocol guarantees one
    # request never spans two versions — the TITO version stamp)
    out_version: Optional[int] = None
    # telemetry (repro.obs): request id unique per engine, and monotonic
    # wall-clock stamps (time.perf_counter seconds) at submission, first
    # generated token, and completion.  AsyncFrontend stamps t_submit on
    # the CALLER's thread so queueing ahead of the serve thread counts
    # toward TTFT; the engine stamps the rest and derives the TTFT/TPOT/
    # latency histograms from them on finish.
    rid: Optional[int] = None
    t_submit: Optional[float] = None
    t_first: Optional[float] = None
    t_finish: Optional[float] = None
    # ContinuousEngine: one perf_counter stamp per token of ``out``, taken
    # when the token was sampled (t_tokens[0] == t_first; the tokens of
    # one speculative round share its stamp)
    t_tokens: Optional[np.ndarray] = None
    # fault-tolerance terminal state: exactly one of ``out`` / ``error``
    # is set when the request leaves the engine.  ``error`` is one of the
    # typed ``repro.serving.errors`` classes (or the isolated fault that
    # killed just this request); ``status`` names the outcome —
    # ok | failed | cancelled | deadline | shed | restarted.
    error: Optional[Exception] = None
    status: str = "ok"

    @property
    def finished(self) -> bool:
        """Has the request reached a terminal state (success OR typed
        failure)?  The fault-tolerance contract: every submitted request
        eventually flips this, never hangs."""
        return self.out is not None or self.error is not None

    @property
    def ttft_s(self) -> Optional[float]:
        """Time-to-first-token (seconds); None until the first token."""
        if self.t_submit is None or self.t_first is None:
            return None
        return self.t_first - self.t_submit

    @property
    def tpot_s(self) -> Optional[float]:
        """Mean time-per-output-token AFTER the first (seconds); None
        until finished, 0.0 for single-token requests."""
        if self.t_first is None or self.t_finish is None or self.out is None:
            return None
        n = len(self.out)
        return (self.t_finish - self.t_first) / (n - 1) if n > 1 else 0.0


def sample_token(logits_row: np.ndarray, temperature: float, rng) -> int:
    """Greedy argmax (temperature<=0) or softmax sampling for one request.

    Shared by the static and continuous engines so greedy outputs are
    byte-comparable between them.
    """
    if temperature <= 0:
        return int(logits_row.argmax())
    p = np.exp((logits_row - logits_row.max()) / temperature)
    p /= p.sum()
    return int(rng.choice(len(p), p=p))


def sample_rows(logits: jax.Array, temps: jax.Array, key: jax.Array,
                logprobs: Optional[str] = None):
    """``sample_token`` for a batch of rows, on the device, inside jit.

    logits (B, V) in any float dtype, temps float32 (B,).  Rows with
    ``T <= 0`` take the first argmax of the float32 logits, as
    ``sample_token`` does; the others draw exactly from
    ``softmax(float32(logits) / T)`` over the whole vocabulary (Gumbel-max,
    ``jax.random.categorical``).  ``logprobs`` picks the logprob returned
    for each drawn token, in float32 and in the host's arithmetic order:
    ``"true"`` is ``log_softmax(logits)[tok]``, ``"scaled"`` is
    ``log_softmax((logits - max) / max(T, 1e-6))[tok]`` (the rollout
    convention: greedy rows read ~0), ``None`` computes none and returns
    zeros.  Returns (tokens int32 (B,), logprobs float32 (B,))."""
    x = logits.astype(jnp.float32)
    t = temps[:, None]
    drawn = jax.random.categorical(key, x / jnp.where(t > 0, t, 1.0), axis=-1)
    tok = jnp.where(temps > 0, drawn, jnp.argmax(x, axis=-1)).astype(jnp.int32)
    if logprobs is None:
        return tok, jnp.zeros(temps.shape, jnp.float32)
    z = x - jnp.max(x, axis=-1, keepdims=True)
    if logprobs == "scaled":
        z = z / jnp.maximum(t, 1e-6)
    picked = jnp.take_along_axis(z, tok[:, None], axis=-1)[:, 0]
    return tok, picked - jnp.log(jnp.sum(jnp.exp(z), axis=-1))


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 8,
                 max_len: int = 512, seed: int = 0):
        self.cfg = cfg
        self.model = get_model(cfg)
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self._rng = np.random.default_rng(seed)
        self._decode = jax.jit(self._decode_fn)

    def _decode_fn(self, params, token, cache, idx):
        return self.model.decode_step(params, token, self.cfg, cache, idx)

    def serve(self, requests: List[Request]) -> List[Request]:
        """Static batching: pad prompts, joint prefill, step decode."""
        for i in range(0, len(requests), self.max_batch):
            self._serve_batch(requests[i:i + self.max_batch])
        return requests

    def _serve_batch(self, batch: List[Request]):
        B = len(batch)
        plen = max(len(r.prompt) for r in batch)
        toks = np.zeros((B, plen), np.int32)
        for i, r in enumerate(batch):
            toks[i, plen - len(r.prompt):] = r.prompt   # left-pad
        cache, _ = self.model.init_cache(
            self.cfg, B, self.max_len,
            jax.tree.leaves(self.params)[0].dtype)
        logits, cache = self.model.prefill(self.params,
                                           jnp.asarray(toks), self.cfg,
                                           cache)
        max_new = max(r.max_new for r in batch)
        outs = [[] for _ in range(B)]
        tok = self._sample(logits, batch)
        for step in range(max_new):
            for i in range(B):
                outs[i].append(int(tok[i, 0]))
            logits, cache = self._decode(self.params, tok, cache,
                                         jnp.asarray(plen + step, jnp.int32))
            tok = self._sample(logits, batch)
        for i, r in enumerate(batch):
            r.out = np.asarray(outs[i][:r.max_new], np.int32)

    def _sample(self, logits, batch) -> jax.Array:
        lg = np.asarray(logits[:, -1], np.float32)
        out = np.zeros((len(batch), 1), np.int32)
        for i, r in enumerate(batch):
            out[i, 0] = sample_token(lg[i], r.temperature, self._rng)
        return jnp.asarray(out)
