"""Instrumentation from the benchmark's side of the program's calls.

``Instrument`` wraps, on one engine instance, the calls into each layer:
the jitted prefill and decode programs, admission, retirement, sampling
and the front end's harvest.  In every run it notes which prefill span
lengths compiled.  With tracing on it also

* writes a host span (``jax.profiler.TraceAnnotation``) around each call,
  on the profiler's clock, so idle device time can be attributed to what
  the host was doing;
* records, while the profiler runs, the work of every program call: the
  span and start of each prefill, the position of every decoding row.

``Profiler`` starts and stops the profiler on the serve thread between
engine iterations, after the device has finished the work dispatched so
far, so the calls recorded and the device work traced are the same set.
``load_trace`` turns the profiler's ``.xplane.pb`` into the normalized
trace of ``bench.harness.reduce``.
"""
from __future__ import annotations

import glob
import os
import threading
import time
from contextlib import nullcontext
from typing import List, Optional, Tuple

import jax
import numpy as np

clock = time.perf_counter
OPEN, CLOSE = "bench.trace_open", "bench.trace_close"


class Instrument:
    def __init__(self, eng, fe, annotate: bool):
        self.eng, self.fe = eng, fe
        self.annotate = annotate
        self.recording = False
        self.prefill_calls: List[Tuple[int, int]] = []   # (span, start)
        self.decode_calls: List[np.ndarray] = []         # row positions
        self.compiled_spans: List[Tuple[float, int]] = []  # (time, span)
        self._wrap_prefill()
        if annotate:
            self._wrap_decode()
            for obj, attr, name in [
                    (eng, "step", "bench.step"),
                    (eng, "_admit", "bench.admit"),
                    (eng, "_retire", "bench.retire"),
                    (eng, "_prefill_chunks", "bench.prefill_chunks"),
                    (eng, "_decode_active", "bench.decode_active"),
                    (eng, "_sample", "bench.sample"),
                    (fe, "_harvest", "bench.harvest")]:
                setattr(obj, attr, self._span(name, getattr(obj, attr)))

    def _ann(self, name):
        return jax.profiler.TraceAnnotation(name) if self.annotate \
            else nullcontext()

    def _span(self, name, fn):
        def wrapped(*a, **k):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **k)
        return wrapped

    def _wrap_prefill(self):
        eng, orig = self.eng, self.eng._prefill

        def prefill(*args):
            c0 = eng.stats["compiles"]
            with self._ann("bench.prefill_call"):
                out = orig(*args)
            span = int(args[1].shape[1])
            if eng.stats["compiles"] != c0:
                self.compiled_spans.append((clock(), span))
            if self.recording:
                self.prefill_calls.append((span, int(np.asarray(args[4])[0])))
            return out
        eng._prefill = prefill

    def _wrap_decode(self):
        eng, orig = self.eng, self.eng._decode

        def decode(*args):
            if self.recording:
                rows = [i for i, s in enumerate(eng.slots)
                        if s is not None and s.pending is not None
                        and len(s.out) + 1 < s.req.max_new]
                self.decode_calls.append(eng.lengths[rows].copy())
            with jax.profiler.TraceAnnotation("bench.decode_call"):
                return orig(*args)
        eng._decode = decode


class Profiler:
    """``stop`` marks the traced window's end and then writes the trace
    out on a thread of its own: writing takes seconds, in which the load
    generator keeps offering the cell's load; ``join`` waits for it."""

    def __init__(self, inst: Instrument, logdir: str):
        self.inst, self.logdir = inst, logdir
        self._writer: Optional[threading.Thread] = None

    def start(self) -> None:
        inst = self.inst

        def f():
            jax.block_until_ready(inst.eng.pool)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.logdir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(OPEN):
                pass
            inst.recording = True
        inst.fe.call(f)

    def stop(self) -> None:
        inst = self.inst

        def f():
            jax.block_until_ready(inst.eng.pool)
            inst.recording = False
            with jax.profiler.TraceAnnotation(CLOSE):
                pass
        inst.fe.call(f)
        self._writer = threading.Thread(target=jax.profiler.stop_trace,
                                        name="bench-trace-writer")
        self._writer.start()

    def join(self) -> None:
        if self._writer is not None:
            self._writer.join()


def _detail(event) -> str:
    """The event's string-valued stats (the HLO op's long name, its
    framework op, a kernel's name), where a kernel may be named."""
    return " ".join(str(v)[:200] for _, v in event.stats
                    if isinstance(v, str))


def _line(plane, *names):
    for ln in plane.lines:
        if ln.name in names:
            return ln
    return None


def load_trace(logdir: str, devices: int) -> dict:
    """The normalized trace of the newest profile under ``logdir``."""
    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    devs, host, window = [], [], [None, None]
    # the accelerator's planes: a TPU trace also holds device planes with
    # no operations of the program (``/device:CUSTOM:...``)
    dev_planes = sorted((p for p in pd.planes
                         if p.name.startswith("/device:")
                         and _line(p, "XLA Ops", "Ops") is not None),
                        key=lambda p: p.name)
    for p in dev_planes[:devices]:
        ops = _line(p, "XLA Ops", "Ops")
        mods = _line(p, "XLA Modules", "Modules")
        devs.append({
            "ops": [[e.name, e.start_ns, e.duration_ns, _detail(e)]
                    for e in (ops.events if ops else [])],
            "modules": [[e.name, e.start_ns, e.duration_ns]
                        for e in (mods.events if mods else [])]})
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.name == OPEN:
                    window[0] = e.start_ns + e.duration_ns
                elif e.name == CLOSE:
                    window[1] = e.start_ns
                elif e.name.startswith("bench."):
                    host.append([e.name[6:], e.start_ns, e.duration_ns])
    if None in window or not devs or not devs[0]["ops"]:
        planes = {p.name: [ln.name for ln in p.lines] for p in pd.planes}
        raise RuntimeError(f"trace lacks its window marks or device ops: "
                           f"window {window}; planes and lines {planes}")
    return {"window": window, "devices": devs, "host": host}
