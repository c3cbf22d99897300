"""Open-loop serving of a decoder with expert layers through ``AsyncEngine``.

The driver of configurations whose ``driver`` is ``serve_moe``.  It runs
the window as ``bench/serve.py`` does (``drive``, ``window_metrics``,
``_warm``, ``_deltas``) and differs in what it builds and checks against:

* set-up draws the weights as int8 codes on the device
  (``bench/reference/moe.make_codes``) and hands them to the program as
  its quantized pytree, so no float copy of the weights is ever made;
* the run's counters add the engine's ``expert_rows`` and
  ``expert_groups`` over the window, and ``requests_done``: the requests
  whose last token came inside it;
* the check reads the plain integer reference of ``bench/reference/moe.py``
  on a seeded sample of the requests that had served tokens by the close,
  the longest among them, over every token each had served.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench import spans, spec
from bench.reference import moe as R
from bench.serve import _deltas, _warm, drive, window_metrics


def program_params(codes: dict) -> dict:
    """The codes as the program's quantized pytree (``quantize_params``'s
    layout, one tree a layer), sharing their buffers."""
    def layer(c):
        return {
            "norm1": {"g_q": c["norm1"]}, "norm2": {"g_q": c["norm2"]},
            "attn": {"wqkv": {"w_q": c["wqkv"], "b_q": c["bqkv"]}, "wo": {"w_q": c["wo"]}},
            "mlp": {"router": {"w_q": c["router"]},
                    "experts": {k + "_q": c["experts_" + k] for k in ("gate", "up", "down")},
                    "shared": {k: {"w_q": c[f"shared_{k}_proj"]} for k in ("gate", "up", "down")},
                    "shared_gate": {"w_q": c["shared_expert_gate"]}},
        }

    return {"embed": {"table_q": codes["embed"]}, "lm_head": {"w_q": codes["lm_head"]},
            "final_norm": {"g_q": codes["final_norm"]},
            "layers": [layer(c) for c in codes["layers"]]}


def setup(cell, seed: int, trace: bool, clock, hooks=None):
    """The deployment the configuration states, bound to the seeded codes,
    warmed on set-up's own requests: ``(engine, probe)``."""
    import jax

    from repro.configs.base import ArchConfig
    from repro.deploy import api
    from repro.deploy.engine import Engine, Greedy

    cfg = cell.config
    sv = cfg["serve"]
    arch = ArchConfig(**cfg["arch"])
    clock.mark("imports")
    model = api.compile(arch, backend=sv["backend"], seq_len=sv["seq_len"],
                        max_len=sv["max_len"], kv_block_size=sv["kv_block_size"],
                        kv_blocks=sv["kv_blocks"], prefix_cache=sv["prefix_cache"],
                        cache_dir=spec.PLAN_CACHE)
    clock.mark("plan")
    params = program_params(R.make_codes(R.ref_arch(cfg), cfg["weights"], seed))
    jax.block_until_ready(params)
    clock.mark("weights")
    sampling = Greedy(arch.vocab)
    if trace:
        sampling = spans.SpannedSampling(sampling)
    engine = Engine(model, sv["slots"], params=params, sampling=sampling)
    del params
    clock.mark("bind")
    if hooks and "engine" in hooks:
        hooks["engine"](engine)
    probe = spans.ServeProbe(engine) if trace else None
    _warm(engine, sv["seq_len"], arch.vocab)
    engine.reset_stats()
    clock.mark("warm-up")
    return engine, probe


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
    counters.update(expert_rows=after.expert_rows - before.expert_rows,
                    expert_groups=after.expert_groups - before.expert_groups,
                    requests_done=sum(1 for st, (reason, n, _t) in zip(streams, ended)
                                      if reason == "length" and n
                                      and w_open <= st.times[n - 1] < w_close))
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
    arch = R.ref_arch(cfg)
    codes = R.make_codes(arch, cfg["weights"], seed)
    worst, checked, short = 0.0, [], 0
    for i in pick:
        reason, _n, toks = ended[i]
        checked.append((streams[i].prompt, toks))
        if reason == "length" and len(toks) != streams[i].max_new:
            short += 1
            continue
        worst = max(worst, R.decoder_gap(arch, cfg["serve"], codes, streams[i].prompt, toks))
    return worst, checked, short
