"""The one traffic generator: reads a mix file (``bench/traffic/<t>.json``).

A mix holds its distributions and a pool of request shapes drawn from
them once, with the mix's own ``pool_seed``, and stored in the file.
The schedule is the pool's, in its stored order: the same sizes at the
same times for every seed.  ``--seed`` draws every token id (and the
sampler's choices), so every seed offers the same work, a run's spread
is the system's own, and a dozen seeds reuse one set of compiled
programs.

Two loops:

* ``sessions``: agent sessions, open across sessions (rate x horizon
  starts, at the first stored exponential gaps scaled to the horizon, the
  pool's scripts in turn), closed within
  one (turn k+1 is due a stored tool time after turn k's reply).  Turn 0
  is a shared system prompt plus a task context; turn k+1 is the previous
  prompt, the reply, and a tool output.
* ``groups``: GRPO rollout groups, closed: groups of ``group_size``
  samples of one prompt, submitted together while fewer than the cell's
  outstanding target are in flight.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Iterator, List

import numpy as np

FIRST_ID = 3          # token ids are drawn from [FIRST_ID, vocab)


def _lognormal(rng, d: dict, n: int) -> np.ndarray:
    x = np.exp(np.log(d["median"]) + d["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), d["min"], d["max"]).astype(int)


def draw_pool(mix: dict) -> dict:
    """The stored pool of a mix, drawn from its distributions with its
    ``pool_seed``.  Tests check that the file holds exactly this."""
    rng = np.random.default_rng(mix["pool_seed"])
    d = mix["distributions"]
    if mix["loop"] == "sessions":
        scripts = []
        for _ in range(mix["pool_size"]):
            n = int(rng.integers(d["turns"]["min"], d["turns"]["max"] + 1))
            tool = _lognormal(rng, d["tool_tokens"], n)
            ans = _lognormal(rng, d["answer_tokens"], n)
            wait = rng.uniform(*d["tool_time_s"], n)
            turns = [{"tool": 0 if k == 0 else int(tool[k]),
                      "max_new": int(ans[k]),
                      "tool_time_s": 0.0 if k == 0 else
                      round(float(wait[k]), 3)} for k in range(n)]
            scripts.append({"task": int(_lognormal(rng, d["task_tokens"],
                                                   1)[0]),
                            "turns": turns})
        gaps = rng.exponential(1.0, mix["gap_pool_size"])
        return {"scripts": scripts,
                "gaps": [round(float(g), 4) for g in gaps / gaps.mean()]}
    if mix["loop"] == "groups":
        return {"prompts": [int(x) for x in _lognormal(
                    rng, d["prompt_tokens"], mix["pool_size"])],
                "responses": [int(x) for x in _lognormal(
                    rng, d["response_tokens"], mix["response_pool_size"])]}
    raise ValueError(f"unknown loop {mix['loop']!r}")


# ------------------------------------------------------------- sessions

@dataclass
class Session:
    idx: int
    start: float                 # seconds after the traffic's origin
    script: dict
    first: np.ndarray            # system prompt + task context
    tools: List[np.ndarray]      # tool output before turns 1..n-1


def sessions(mix: dict, seed: int, vocab: int, rate: float,
             horizon_s: float, *, warmup: bool = False,
             system_tokens: int = 0) -> List[Session]:
    """Sessions starting within ``horizon_s`` at ``rate`` per second.
    ``warmup`` gives every pool script once, all starting at 0, with
    their own token stream and a system prompt of ``system_tokens``."""
    rng = np.random.default_rng([seed, 1 if warmup else 0])
    pool = mix["pool"]
    system = rng.integers(FIRST_ID, vocab, system_tokens if warmup
                          else mix["system_prompt_tokens"])
    scripts, gaps = pool["scripts"], pool["gaps"]
    if warmup:
        starts = [0.0] * len(scripts)
        order: List[int] = list(range(len(scripts)))
    else:
        # every seed offers the same work: N = rate * horizon sessions,
        # the pool's scripts in turn, started at the first N stored gaps
        # rescaled to span the horizon
        n = max(1, int(round(rate * horizon_s)))
        if n > len(gaps):
            raise ValueError(f"{n} sessions in {horizon_s} s need more "
                             f"than the {len(gaps)} stored gaps")
        g = np.asarray(gaps[:n], float)
        starts = list((np.cumsum(g) - g) * horizon_s / g.sum())
        order = list(np.arange(n) % len(scripts))
    # a distinct first task token per session: no two sessions share a
    # prefix beyond the system prompt, so the radix cache's partial-block
    # match never shifts a span
    firsts = rng.choice(np.arange(FIRST_ID, vocab), len(starts),
                        replace=False)
    out = []
    for i, t0 in enumerate(starts):
        sc = scripts[order[i]]
        task = rng.integers(FIRST_ID, vocab, sc["task"])
        task[0] = firsts[i]
        tools = [rng.integers(FIRST_ID, vocab, tn["tool"])
                 for tn in sc["turns"][1:]]
        out.append(Session(idx=i, start=t0, script=sc,
                           first=np.concatenate([system, task]),
                           tools=tools))
    return out


# --------------------------------------------------------------- groups

@dataclass
class Group:
    idx: int
    prompt: np.ndarray
    max_new: List[int]


def groups(mix: dict, seed: int, vocab: int, *, warmup: bool = False
           ) -> Iterator[Group]:
    """Rollout groups, endlessly: the pool's prompt lengths and response
    lengths each in their stored order, round and round; ``warmup`` gives
    each pool prompt length once, with its own token stream."""
    rng = np.random.default_rng([seed, 1 if warmup else 0])
    pool = mix["pool"]
    n = mix["group_size"]
    p_order = iter(range(len(pool["prompts"]))) if warmup \
        else itertools.cycle(range(len(pool["prompts"])))
    r_order = itertools.cycle(range(len(pool["responses"])))
    # distinct first tokens: no two groups share a prefix, so the radix
    # cache's partial-block match never shifts a span
    firsts = iter(rng.permutation(np.arange(FIRST_ID, vocab)))
    i = 0
    for p in p_order:
        prompt = rng.integers(FIRST_ID, vocab, pool["prompts"][p])
        prompt[0] = next(firsts)
        yield Group(idx=i, prompt=prompt,
                    max_new=[pool["responses"][next(r_order)]
                             for _ in range(n)])
        i += 1


def max_tokens(mix: dict) -> int:
    """The longest sequence (prompt + reply) the pool can produce."""
    pool = mix["pool"]
    if mix["loop"] == "sessions":
        return max(mix["system_prompt_tokens"] + sc["task"]
                   + sum(t["tool"] + t["max_new"] for t in sc["turns"])
                   for sc in pool["scripts"])
    return max(pool["prompts"]) + max(pool["responses"])
