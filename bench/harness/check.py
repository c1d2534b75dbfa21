"""The comparison that decides ``correct``.

Each checked sequence is a prompt with what the timed path served after
it.  Every served token was sampled at the traffic's temperature, and
the engine recorded its logprob: the log-softmax, at temperature 1, of
the program's own logits at that position.  The plain reference runs
once over the whole sequence and gives, at every served position, the
log-softmax the model should have produced:

* ``logprob_gap``: the widest absolute difference, over the served
  tokens, between the program's logprob and the reference's
  (``logprob_gap_mean``: the mean of the same differences).

The control puts the reference, computed in fp8, in the program's place:
``control.logprob_gap`` is the same reading of the fp8 log-softmax of
each served token against the float32 reference's.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 128


@dataclass
class Seq:
    """A token sequence and what was served in it: ``pos[i]`` is the
    index of a served token ``tokens[pos[i]]`` and ``lp[i]`` the logprob
    the program recorded for it."""
    tokens: np.ndarray
    pos: np.ndarray
    lp: np.ndarray


@jax.jit
def _log_softmax_at(lg, tok):
    mx = lg.max(-1)
    lse = mx + jnp.log(jnp.exp(lg - mx[:, None]).sum(-1))
    return jnp.take_along_axis(lg, tok[:, None], -1)[:, 0] - lse


def readings(ref, w: dict, cfg: dict, seqs: List[Seq],
             control: bool = False) -> Dict[str, float]:
    """Readings of the served tokens against the f32 reference; with
    ``control`` also the fp8 control's reading at the same positions."""
    gap, c_gap = [], []
    for s in seqs:
        rows = np.asarray(s.pos, np.int64) - 1          # predicting row
        h = ref.hidden(w, cfg, s.tokens, "f32")
        hc = ref.hidden(w, cfg, s.tokens, "fp8") if control else None
        for b in range(0, len(rows), ROWS):
            n = min(ROWS, len(rows) - b)
            # every block has ROWS rows (the last repeats its final row), so
            # the head compiles once per run, not once per sequence
            r = np.pad(rows[b:b + n], (0, ROWS - n), mode="edge")
            tok = jnp.asarray(s.tokens[r + 1], jnp.int32)
            lsm = np.asarray(_log_softmax_at(
                ref.logits(w, cfg, h[jnp.asarray(r)], "f32"), tok))[:n]
            lp = np.asarray(s.lp[b:b + n], np.float64)
            gap += list(np.abs(lp - lsm))
            if control:
                c_lsm = np.asarray(_log_softmax_at(
                    ref.logits(w, cfg, hc[jnp.asarray(r)], "fp8"), tok))[:n]
                c_gap += list(np.abs(c_lsm - lsm))
        del h, hc
    out: Dict[str, float] = {"lp_tokens": len(gap)}
    for name, g in (("logprob_gap", gap), ("control.logprob_gap", c_gap)):
        if g:
            out[name] = float(max(g))
            out[name + "_mean"] = float(np.mean(g))
    return out


def verdict(reads: Dict[str, float], limits: Dict[str, float],
            failed: int) -> tuple:
    """(correct, compared) where ``compared`` lists [name, value, limit]
    for every number held to a limit.  A missing or non-finite reading
    is not correct."""
    compared: List[list] = []
    ok = True
    for name, limit in limits.items():
        v: Optional[float] = reads.get(name)
        compared.append([name, v, limit])
        if v is None or not np.isfinite(v) or v > limit:
            ok = False
    compared.append(["failed_requests", failed, 0])
    return ok and failed == 0, compared


def control_verdict(reads: Dict[str, float], limits: Dict[str, float]
                    ) -> tuple:
    """The verdict the control would get in the program's place: each
    ``control.<name>`` reading held to ``<name>``'s limit."""
    return verdict({k: reads.get(f"control.{k}") for k in limits},
                   limits, 0)
