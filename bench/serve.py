"""Open-loop serving of a decoder configuration through ``AsyncEngine``.

The driver of configurations whose ``driver`` is ``serve``.

Set-up builds the deployment the configuration states (``compile`` ->
``Engine`` with the seeded weights), warms every shape the traffic uses
(the chunk step, the decode step, the copy-on-write block copy and the
greedy sampler) on a few requests of its own, then starts the schedule: a
ramp at the mix's rate, so the slots are full when the window opens, and
the window itself.  Each request is timed from the moment it was due; the
benchmark's ``on_token`` callback only appends a timestamp.

When the window closes the engine stops (requests still in flight are not
failures), the device's peak memory is read, the program's state is freed,
and the plain reference reads a seeded sample of the requests that had
served tokens by the close, the longest among them, over every token each
had served: the widest gap by which a served token's logit lies below the
reference's best.  A request that finished short of its length fails.
"""

from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from bench import spans, spec
from bench.reference import models as R

@dataclasses.dataclass
class Stream:
    due: float                 # perf_counter time the request was due
    prompt: tuple
    max_new: int
    times: list = dataclasses.field(default_factory=list)
    handle: object = None
    lateness: float = 0.0


def percentile(xs, pct: float) -> float | None:
    """Nearest-rank percentile (None of an empty sample)."""
    s = sorted(xs)
    if not s:
        return None
    return float(s[max(1, int(np.ceil(pct / 100.0 * len(s)))) - 1])


def _warm(engine, seq_len: int, vocab: int) -> None:
    """Compile every shape the window uses on requests of set-up's own: two
    chunks plus a pinned tail, decode, and an exact repeat whose first
    decode writes into a shared partial block (copy-on-write)."""
    rng = np.random.default_rng(12345)
    prompt = rng.integers(0, vocab, size=2 * seq_len + 5).tolist()
    handles = [engine.submit(prompt, 3), engine.submit(prompt, 3)]
    engine.run_until_idle()
    bad = [h.finish_reason for h in handles if h.finish_reason not in ("eos", "length")]
    if bad:
        raise RuntimeError(f"warm-up requests ended {bad}")


def setup(cell, seed: int, trace: bool, clock, hooks=None):
    """The deployment the configuration states, with the seeded weights,
    warmed on set-up's own requests: ``(engine, probe)``."""
    import jax

    from repro.configs.base import ArchConfig
    from repro.deploy import api
    from repro.deploy.engine import Engine, Greedy

    cfg = cell.config
    sv = cfg["serve"]
    arch = ArchConfig(**cfg["arch"])
    ref_arch = R.ref_arch(cfg)
    clock.mark("imports")
    model = api.compile(arch, backend=sv["backend"], seq_len=sv["seq_len"],
                        max_len=sv["max_len"], kv_block_size=sv["kv_block_size"],
                        kv_blocks=sv["kv_blocks"], prefix_cache=sv["prefix_cache"],
                        cache_dir=spec.PLAN_CACHE)
    clock.mark("plan")
    params = R.float_params(ref_arch, R.make_codes(ref_arch, cfg["weights"], seed))
    jax.block_until_ready(params)
    clock.mark("weights")
    sampling = Greedy(arch.vocab)
    if trace:
        sampling = spans.SpannedSampling(sampling)
    engine = Engine(model, sv["slots"], params=params, sampling=sampling)
    del params
    clock.mark("ptq+bind")
    if hooks and "engine" in hooks:
        hooks["engine"](engine)
    probe = spans.ServeProbe(engine) if trace else None
    _warm(engine, sv["seq_len"], arch.vocab)
    engine.reset_stats()
    clock.mark("warm-up")
    return engine, probe


def drive(engine, requests, ramp_s: float, seconds: float, on_open=None, tick=None,
          on_close=None):
    """Submit ``requests`` on their schedule through a fresh ``AsyncEngine``
    over ``engine``; the window is ``seconds`` long after ``ramp_s``.
    ``on_open`` and ``on_close`` are called at the window's edges, ``on_close``
    once the counters and streams have been read; ``tick`` while waiting.

    Returns the streams, how each stood at the close, the engine's counters
    at the open and the close, and the window.  The engine is idle after."""
    from repro.deploy.serving import AsyncEngine

    aeng = AsyncEngine(engine)
    start = time.perf_counter()
    w_open = start + float(ramp_s)
    w_close = w_open + float(seconds)
    streams, before = [], None
    try:
        for req in requests:
            due = start + req.due_s
            if before is None and due >= w_open:
                _sleep_until(w_open)
                before = engine.stats_snapshot()
                if on_open:
                    on_open(w_open, w_close)
            _sleep_until(due, tick)
            st = Stream(due, req.prompt, req.max_new_tokens)
            st.lateness = time.perf_counter() - due
            st.handle = aeng.submit(req.prompt, req.max_new_tokens,
                                    on_token=lambda _t, ts=st.times: ts.append(time.perf_counter()))
            streams.append(st)
        if before is None:
            raise RuntimeError("no request was due in the window")
        _sleep_until(w_close, tick)
        after = engine.stats_snapshot()
        ended = _ended(streams)
        if on_close:
            on_close()
    finally:
        aeng.close(drain=False, timeout=300)
        for st in streams:
            st.handle = None  # a handle holds the engine, its pool and weights
    return streams, ended, before, after, (w_open, w_close)


def _ended(streams) -> list[tuple]:
    """(finish reason, tokens timed so far, tokens served) of each stream."""
    return [(s.handle.finish_reason, len(s.times), list(s.handle.tokens)) for s in streams]


def run(cell, seed: int, seconds: float, trace: bool, t0: float, clock, hooks=None):
    import jax

    cfg, mix = cell.config, cell.traffic
    engine, probe = setup(cell, seed, trace, clock, hooks)
    requests = cell.requests(seconds, seed)
    tracer = None

    def on_open(w_open, w_close):
        nonlocal tracer
        clock.mark("ramp")
        if probe:
            probe.open(w_open, w_close)
            tracer = spans.Tracer.start(w_open, seconds)

    def on_close():
        if tracer:
            tracer.maybe_stop(force=True)

    try:
        streams, ended, before, after, (w_open, w_close) = drive(
            engine, requests, mix["ramp_s"], seconds, on_open, on_close=on_close)
    finally:
        trace_summary = tracer.finish() if tracer else None
    mem = jax.devices()[0].memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    window = window_metrics(streams, ended, w_open, w_close)
    counters = _deltas(before, after)
    layer_ctx = probe.summary() if probe else None
    del engine, probe
    gc.collect()

    t_ref = time.perf_counter()
    gap, checked, short = _check(cfg, mix, seed, streams, ended)
    limit = float(cfg["limits"]["max_logit_gap"])
    checks = [("max_logit_gap", gap, "<=", limit), ("requests_checked", len(checked), ">=", 1),
              ("streams_cut_short", short, "<=", 0)]
    lateness = max((s.lateness for s in streams), default=0.0)
    return dict(window, memory_peak_bytes=peak, counters=counters, checks=checks,
                layer_ctx=layer_ctx, trace=trace_summary, checked=checked,
                notes={"max_submit_lateness_s": lateness,
                       "tokens_checked": sum(len(t) for _, t in checked),
                       "reference_s": time.perf_counter() - t_ref})


def _sleep_until(t: float, tick=None) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        if tick:
            tick()
        time.sleep(min(left, 0.05))


def _deltas(before, after) -> dict:
    keys = ("decode_dispatches", "slot_steps_busy", "decode_time_s", "prefill_dispatches",
            "prefill_time_s", "prompt_tokens_prefilled", "tokens_generated", "max_batch",
            "prefix_hits", "prefix_lookups", "peak_queue_depth", "queue_depth")
    out = {}
    for k in keys:
        a, b = getattr(after, k), getattr(before, k)
        out[k] = a if k in ("max_batch", "peak_queue_depth", "queue_depth") else a - b
    out["queue_depth_at_open"] = before.queue_depth
    return out


def _ms(seconds):
    return None if seconds is None else seconds * 1e3


def window_metrics(streams, ended, w_open: float, w_close: float) -> dict:
    ttft, gaps, tokens, attempted, failed = [], [], 0, 0, 0
    for st, (reason, n, _toks) in zip(streams, ended):
        times = st.times[:n]
        tokens += sum(1 for t in times if w_open <= t < w_close)
        gaps += [b - a for a, b in zip(times, times[1:]) if w_open <= b < w_close]
        if not w_open <= st.due < w_close:
            continue
        attempted += 1
        first = times[0] if times and times[0] < w_close else w_close
        ttft.append(first - st.due)  # a request with no token yet: its wait so far
        if reason is not None and reason not in ("eos", "length"):
            failed += 1
    span = w_close - w_open
    return {
        "attempted": attempted, "failed": failed, "window_s": span,
        "e2e": {"ttft_p95_ms": _ms(percentile(ttft, 95)),
                "itl_p95_ms": _ms(percentile(gaps, 95)),
                "output_tokens_per_s": tokens / span},
        "samples": {"ttft": len(ttft), "itl": len(gaps)},
    }


def _check(cfg, mix, seed, streams, ended) -> tuple[float, list, int]:
    """Widest logit gap over a seeded sample of the requests that had served
    tokens by the close, the longest among them, each over the tokens it had
    served; the (prompt, served tokens) it read; and how many of them
    finished short of their length.  The program's state is already freed."""
    served = [i for i, (_reason, _n, toks) in enumerate(ended) if toks]
    if not served:
        return 0.0, [], 0
    rng = np.random.default_rng([seed, 7])
    longest = max(served, key=lambda i: (len(ended[i][2]), len(streams[i].prompt)))
    rest = [i for i in served if i != longest]
    k = min(len(rest), int(mix["check"]["requests"]) - 1)
    pick = [longest] + ([int(i) for i in rng.choice(rest, size=k, replace=False)] if k > 0 else [])
    ref_arch = R.ref_arch(cfg)
    codes = R.make_codes(ref_arch, cfg["weights"], seed)
    worst, checked, short = 0.0, [], 0
    for i in pick:
        reason, _n, toks = ended[i]
        checked.append((streams[i].prompt, toks))
        if reason == "length" and len(toks) != streams[i].max_new:
            short += 1
            continue
        worst = max(worst, R.decoder_gap(ref_arch, cfg["serve"], codes, streams[i].prompt, toks))
    return worst, checked, short
