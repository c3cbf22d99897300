"""Host spans, counters and the profiler, opened from the benchmark's side.

Only a ``--trace 1`` run installs any of this.  The spans wrap the calls the
benchmark makes into the program's layers (``jax.profiler.TraceAnnotation``
puts them on the trace's clock, so an idle gap on the device can be put to
what the host was doing); the probes count, per call and inside the
measured window, what the per-layer metrics need (lanes and depths).
"""

from __future__ import annotations

import shutil
import tempfile
import time

import numpy as np

from bench import tracefile

class SetupClock:
    """Splits set-up into named phases, and counts JAX's compile time."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, t0: float):
        import jax

        self.t0 = self.last = t0
        self.phases: list[tuple[str, float]] = []
        self.compile_s = 0.0
        self.compiles = 0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_count)

    def _on_count(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache["misses"] += 1

    def _on_event(self, event: str, duration: float, **_):
        if event in self.EVENTS:
            self.compile_s += duration
            if event.endswith("backend_compile_duration"):
                self.compiles += 1

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.phases.append((name, now - self.last))
        self.last = now


class SpannedSampling:
    """The engine's sampling policy under a ``sampling`` span."""

    def __init__(self, inner):
        self.inner = inner
        self.vocab = inner.vocab
        self.name = getattr(inner, "name", "sampling")

    def __call__(self, row, rid, index):
        import jax

        with jax.profiler.TraceAnnotation("sampling"):
            return self.inner(row, rid, index)


def _in(win, now) -> bool:
    return win is not None and win[0] <= now < win[1]


class ServeProbe:
    """Spans around ``engine.step`` and the session's decode and chunk
    steps; per call inside the window, the lanes and their depths."""

    def __init__(self, engine):
        import jax

        self.win = None
        self.decodes: list[tuple[int, int]] = []      # (active lanes, sum of key rows)
        self.chunks: list[list[tuple[int, int]]] = []  # per call: (start, rows held before)
        sess = engine.session
        step, decode, chunks = engine.step, sess.decode, sess.prefill_chunks
        ann = jax.profiler.TraceAnnotation

        def step_w():
            with ann("engine.step"):
                return step()

        def decode_w(tokens, pos=None, *, active=None):
            if _in(self.win, time.perf_counter()):
                p = np.asarray(sess.pos if pos is None else pos)
                act = np.ones(p.shape, bool) if active is None else np.asarray(active, bool)
                self.decodes.append((int(act.sum()), int((p[act] + 1).sum())))
            with ann("session.decode"):
                return decode(tokens, pos, active=active)

        def chunks_w(pending):
            if _in(self.win, time.perf_counter()):
                self.chunks.append([(int(st), 0 if st == 0 else int(sess.pos[b]))
                                    for b, (_t, st) in pending.items()])
            with ann("session.prefill_chunks"):
                return chunks(pending)

        engine.step, sess.decode, sess.prefill_chunks = step_w, decode_w, chunks_w

    def open(self, w_open: float, w_close: float) -> None:
        self.win = (w_open, w_close)

    def summary(self) -> dict:
        return {"decodes": list(self.decodes), "chunks": list(self.chunks)}


class Tracer:
    """The profiler over the whole window."""

    def __init__(self, until: float):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.until = until
        self.t_start = time.perf_counter()
        self.t_stop = None

    @classmethod
    def start(cls, w_open: float, seconds: float) -> "Tracer":
        import jax

        t = cls(w_open + float(seconds))
        jax.profiler.start_trace(t.dir)
        return t

    def maybe_stop(self, force: bool = False) -> None:
        import jax

        if self.t_stop is None and (force or time.perf_counter() >= self.until):
            jax.profiler.stop_trace()
            self.t_stop = time.perf_counter()

    def finish(self) -> dict:
        """Stop if still running, reduce the trace, delete its files."""
        self.maybe_stop(force=True)
        try:
            rec = tracefile.read_xplane(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        out = tracefile.summarize(rec)
        out["record"] = rec
        out["wall_s"] = self.t_stop - self.t_start
        return out
