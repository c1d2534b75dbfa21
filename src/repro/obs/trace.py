"""Per-request / per-step tracing, exportable as Chrome trace-event JSON
and mirrored into the JAX profiler.

The continuous engine's behavior under mixed agentic traffic — chunked
prefills interleaving with decode, speculative rounds, weight-push drain
barriers — is fundamentally a *timeline* artifact; counters alone cannot
show why one request's TTFT blew past the SLO.  ``Tracer`` records:

* **engine spans** (``Tracer.span``: a ``B``/``E`` pair): ``engine.step``
  around each ``step()`` (batch occupancy, waiting-queue depth, live
  tokens and pool use as args), and inside it ``engine.admit``,
  ``engine.prefill`` (``req``, ``start``, ``span``), ``engine.decode``
  (dispatch to logits on the host; ``rows`` = request ids),
  ``engine.sample`` (``rows``) and ``engine.retire``; the front end adds
  ``frontend.harvest`` after each step;
* **request lifecycle instants**: ``req.submit`` -> ``req.admitted``
  (slot, cached tokens, blocks, engine step) -> ``req.prefill`` per
  chunk -> ``req.first_token`` -> ``req.spec_round`` per speculative
  verification -> ``req.finished`` (``out_version`` attached);
* **engine events**: ``jit.compile`` whenever an engine jit actually
  traces (the recompile hazard, now first-class), ``push.requested`` /
  ``push.applied`` with the drain duration.

Every span and instant is also entered as a ``jax.profiler.TraceAnnotation``
(instants as zero-length ones) with the same name and args, so a running
profile shows the engine's own spans on the host plane, on the device
trace's clock.

The export (``Tracer.export``) is the Chrome trace-event format —
``{"traceEvents": [...]}`` with ``ts`` in microseconds of
``time.perf_counter()`` since ``Tracer._t0`` — directly loadable in
Perfetto (https://ui.perfetto.dev) or ``chrome://tracing``.  Its
``otherData.clock_offset_ns`` maps that clock onto the profiler's:
``ts * 1e3 + clock_offset_ns`` is the event's time in the profiler's
nanoseconds (``time.time_ns()``, the clock of an xplane's
``profile_start_time``), so an export and an xplane of the same run line
up.

**Gating**: tracing defaults off and is enabled per engine
(``ContinuousEngine(tracer=Tracer(enabled=True))``) or process-wide via
``REPRO_TRACE=1`` (``repro.flags.trace_enabled``).  Disabled, every hook
is one attribute check — ``span`` hands back the shared ``NO_SPAN``; no
buffer growth, no timestamps taken, no behavior change (the oracle
parity suites run byte-identical either way).

``validate_chrome_trace`` is the schema checker CI runs against an
exported trace: required keys, non-decreasing ``ts``, strictly matched
``B``/``E`` stacks per thread, and a complete lifecycle for every
finished request.
"""
from __future__ import annotations

import json
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation


class _NoSpan:
    """What ``Tracer.span`` returns while tracing is off: does nothing."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **args) -> None:
        pass


NO_SPAN = _NoSpan()


class _Span:
    """An enabled span: a Chrome ``B``/``E`` pair around a profiler
    annotation of the same name and args."""
    __slots__ = ("_tr", "_name", "_args", "_end_args", "_ann")

    def __init__(self, tr: "Tracer", name: str, args: dict):
        self._tr, self._name, self._args = tr, name, args
        self._end_args: dict = {}

    def __enter__(self):
        self._tr._phase("B", self._name, self._args)
        self._ann = TraceAnnotation(self._name, **self._args)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        self._ann.__exit__(*exc)
        self._tr._phase("E", self._name, self._end_args)
        return False

    def set(self, **args) -> None:
        """Args known only when the span closes (``E`` event args, and
        metadata of the profiler's event)."""
        self._end_args.update(args)
        self._ann.set_metadata(**args)


def _clock_offset_ns() -> tuple:
    """``time.time_ns() - time.perf_counter_ns()``, from the tightest of
    five bracketed reads: (offset, bracket width in ns)."""
    best = None
    for _ in range(5):
        a = time.perf_counter_ns()
        w = time.time_ns()
        b = time.perf_counter_ns()
        if best is None or b - a < best[1]:
            best = (w - (a + b) // 2, b - a)
    return best


class Tracer:
    """Bounded in-memory trace-event buffer (thread-safe appends)."""

    def __init__(self, enabled: bool = False, limit: Optional[int] = None):
        if limit is None:
            from repro.flags import trace_buffer_limit
            limit = trace_buffer_limit()
        self.enabled = enabled
        self.limit = limit
        self.dropped = 0
        self._events: List[dict] = []
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()
        self._tids: Dict[int, int] = {}          # thread ident -> small tid

    # -------------------------------------------------------------- plumbing
    def now_us(self) -> float:
        """Microseconds since tracer epoch (also the TTFT clock base)."""
        return (time.perf_counter() - self._t0) * 1e6

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            tid = self._tids[ident] = len(self._tids)
        return tid

    def _emit(self, ev: dict) -> None:
        with self._lock:
            if len(self._events) >= self.limit:
                self.dropped += 1
                return
            self._events.append(ev)

    # ------------------------------------------------------------ recording
    def _phase(self, ph: str, name: str, args: dict) -> None:
        self._emit({"name": name, "ph": ph, "ts": self.now_us(),
                    "pid": 0, "tid": self._tid(), "args": args})

    def instant(self, name: str, **args) -> None:
        """A point event; in a running profile, a zero-length annotation
        with the same args."""
        if not self.enabled:
            return
        self._emit({"name": name, "ph": "i", "s": "t",
                    "ts": self.now_us(), "pid": 0, "tid": self._tid(),
                    "args": args})
        with TraceAnnotation(name, **args):
            pass

    def begin(self, name: str, **args) -> None:
        if self.enabled:
            self._phase("B", name, args)

    def end(self, name: str, **args) -> None:
        if self.enabled:
            self._phase("E", name, args)

    def span(self, name: str, **args):
        """Context manager around one unit of work: a ``B``/``E`` pair in
        the export and a ``TraceAnnotation`` in any running profile.
        Disabled, the shared ``NO_SPAN``.  ``.set(**args)`` on the span
        adds args known only at its end."""
        if not self.enabled:
            return NO_SPAN
        return _Span(self, name, args)

    # -------------------------------------------------------------- reading
    @property
    def n_events(self) -> int:
        with self._lock:
            return len(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events = []
            self.dropped = 0

    def export(self, path: Optional[str] = None) -> dict:
        """Freeze the buffer into a Chrome trace object; optionally write
        it to ``path``.  Events are sorted by ``ts`` (appends from client
        threads can interleave slightly out of order)."""
        with self._lock:
            events = sorted(self._events, key=lambda e: e["ts"])
        offset, width = _clock_offset_ns()
        obj = {"traceEvents": events, "displayTimeUnit": "ms",
               "otherData": {
                   "dropped_events": self.dropped,
                   # ts * 1e3 + clock_offset_ns = the profiler's ns
                   "clock_offset_ns": round(self._t0 * 1e9) + offset,
                   "clock_offset": "time.time_ns() - time.perf_counter_ns()"
                                   f" at export, bracketed within {width} ns,"
                                   " plus the tracer epoch in ns"}}
        if path is not None:
            with open(path, "w") as f:
                json.dump(obj, f)
        return obj


# --------------------------------------------------------------- validation
_REQUIRED_KEYS = ("name", "ph", "ts", "pid", "tid")
_LIFECYCLE_PRELUDE = ("req.submit", "req.admitted", "req.first_token")


def validate_chrome_trace(obj: dict) -> List[str]:
    """Schema-check an exported trace; returns a list of problems
    (empty == valid).  Checks:

    * top level is ``{"traceEvents": [...]}``;
    * every event carries name/ph/ts/pid/tid, numeric non-negative ts;
    * ``ts`` is non-decreasing across the file (export sorts);
    * ``B``/``E`` spans match as a stack per (pid, tid), names agreeing;
    * every ``req.finished`` request id also has ``req.submit``,
      ``req.admitted`` and ``req.first_token`` events (the full
      lifecycle of a served request).
    """
    problems: List[str] = []
    if not isinstance(obj, dict) or not isinstance(
            obj.get("traceEvents"), list):
        return ["top level must be a dict with a 'traceEvents' list"]
    events = obj["traceEvents"]
    last_ts = float("-inf")
    stacks: Dict[tuple, List[str]] = {}
    seen: Dict[str, set] = {}                    # event name -> {req ids}
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            problems.append(f"event {i}: not an object")
            continue
        missing = [k for k in _REQUIRED_KEYS if k not in ev]
        if missing:
            problems.append(f"event {i}: missing keys {missing}")
            continue
        ts = ev["ts"]
        if not isinstance(ts, (int, float)) or ts < 0:
            problems.append(f"event {i}: bad ts {ts!r}")
            continue
        if ts < last_ts:
            problems.append(f"event {i}: ts {ts} < previous {last_ts}")
        last_ts = ts
        ph = ev["ph"]
        key = (ev["pid"], ev["tid"])
        if ph == "B":
            stacks.setdefault(key, []).append(ev["name"])
        elif ph == "E":
            stack = stacks.setdefault(key, [])
            if not stack:
                problems.append(f"event {i}: E '{ev['name']}' with no "
                                f"open span on {key}")
            elif stack[-1] != ev["name"]:
                problems.append(f"event {i}: E '{ev['name']}' closes "
                                f"'{stack[-1]}' on {key}")
                stack.pop()
            else:
                stack.pop()
        elif ph not in ("i", "I", "X", "C", "M"):
            problems.append(f"event {i}: unknown phase {ph!r}")
        rid = (ev.get("args") or {}).get("req")
        if rid is not None:
            seen.setdefault(ev["name"], set()).add(rid)
    for key, stack in stacks.items():
        if stack:
            problems.append(f"unclosed spans on {key}: {stack}")
    for rid in sorted(seen.get("req.finished", set())):
        for name in _LIFECYCLE_PRELUDE:
            if rid not in seen.get(name, set()):
                problems.append(f"request {rid}: finished without a "
                                f"'{name}' event")
    return problems


def validate_trace_file(path: str) -> List[str]:
    try:
        with open(path) as f:
            obj = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return [f"cannot load {path}: {e}"]
    return validate_chrome_trace(obj)
