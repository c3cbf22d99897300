"""Closed-loop batches through an encoder's ``InferenceSession.forward``.

The driver of configurations whose ``driver`` is ``encode``.

Set-up compiles the plan, binds the seeded weights, draws the mix's distinct
batches on the host and warms the one forward shape.  In the window each
call uploads a batch from the host, runs the plan and fetches the features
back.  The mix's ``in_flight`` calls are kept dispatched ahead of the one
being fetched, each with its copy to the host started, so the chip stays fed
while the host stands still.  When the time is up nothing more is sent, every
call sent is fetched, and the clock is read after that: the rate is all
sequences fetched over all of that time.  A seeded reservoir keeps a few of
the window's outputs, which the plain reference recomputes once the
program's state is freed.
"""

from __future__ import annotations

import collections
import gc
import time

import numpy as np

from bench import spans, spec
from bench.reference import models as R

def run(cell, seed: int, seconds: float, trace: bool, t0: float, clock, hooks=None):
    import jax

    from repro.configs.base import ArchConfig
    from repro.deploy import api

    cfg, mix = cell.config, cell.traffic
    sv = cfg["serve"]
    arch = ArchConfig(**cfg["arch"])
    ref_arch = R.ref_arch(cfg)
    s_act = cfg["quant"]["s_act"]

    clock.mark("imports")
    model = api.compile(arch, backend=sv["backend"], include_head=sv["include_head"],
                        cache_dir=spec.PLAN_CACHE)
    clock.mark("plan")
    params = R.float_params(ref_arch, R.make_codes(ref_arch, cfg["weights"], seed))
    jax.block_until_ready(params)
    clock.mark("weights")
    session = model.session(int(mix["batch"]), params=params)
    del params
    clock.mark("ptq+bind")
    if hooks and "session" in hooks:
        hooks["session"](session)
    inputs = cell.requests(seconds, seed)
    for x in inputs[:2]:
        np.asarray(session.forward(x))
    clock.mark("warm-up")

    forward = session.forward
    ann = jax.profiler.TraceAnnotation if trace else None
    rng = np.random.default_rng([seed, 11])
    keep = int(mix["check"]["calls"])
    depth = int(mix["in_flight"])
    kept: list[tuple[int, np.ndarray]] = []
    pending: collections.deque = collections.deque()

    def send(i):
        out = forward(inputs[i])
        out.copy_to_host_async()
        pending.append((i, out))

    def fetch():
        nonlocal done
        i, out = pending.popleft()
        if ann:
            with ann("host.fetch"):
                y = np.asarray(out)
        else:
            y = np.asarray(out)
        done += 1
        # reservoir sample of the window's outputs, drawn from the seed
        if len(kept) < keep:
            kept.append((i, y))
        elif (j := int(rng.integers(0, done))) < keep:
            kept[j] = (i, y)

    w_open = time.perf_counter()
    w_close = w_open + float(seconds)
    tracer = spans.Tracer.start(w_open, seconds) if trace else None
    calls = done = 0
    while time.perf_counter() < w_close:
        while len(pending) < depth:
            if ann:
                with ann("session.forward"):
                    send(calls % len(inputs))
            else:
                send(calls % len(inputs))
            calls += 1
        fetch()
    while pending:
        fetch()
    w_end = time.perf_counter()
    trace_summary = tracer.finish() if tracer else None
    mem = jax.devices()[0].memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    del session, model, forward
    gc.collect()

    codes = R.make_codes(ref_arch, cfg["weights"], seed)
    worst = 0
    for idx in sorted({i for i, _ in kept}):
        want = R.encoder_features(ref_arch, codes, inputs[idx])
        for i, y in kept:
            if i == idx:
                got = np.rint(y / s_act).astype(np.int32)
                worst = max(worst, int(np.max(np.abs(got - want.astype(np.int32)))))
    limit = float(cfg["limits"]["max_code_diff"])
    checks = [("max_code_diff", worst, "<=", limit), ("outputs_checked", len(kept), ">=", 1)]
    span = w_end - w_open
    seqs = done * int(mix["batch"])
    return {
        "attempted": calls, "failed": 0, "window_s": span,
        "e2e": {"encoder_seq_per_s": seqs / span},
        "memory_peak_bytes": peak, "checks": checks,
        "counters": {"calls": calls, "calls_done": done, "batch": int(mix["batch"]),
                     "seq_len": int(mix["seq_len"]), "in_flight": depth,
                     "drain_s": w_end - w_close},
        "trace": trace_summary, "samples": {"calls": calls}, "notes": {},
        "checked": [(inputs[i], y) for i, y in kept],
    }
