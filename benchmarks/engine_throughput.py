"""Engine throughput under request traffic: continuous batching vs serial.

The many-tiny-core result (arXiv 2405.19284) in miniature: serving
throughput on the deployed artifact comes from keeping the batch
dimension full.  This benchmark submits the same request trace to a
``repro.deploy.engine.Engine`` at ``max_batch = 1`` (serial: every
request waits for the previous one) and at ``max_batch = B``
(continuous batching: admissions fill evicted slots mid-flight) and
reports the scheduler's own :class:`EngineStats` — tokens/s, slot
occupancy, recycling, TTFT/TPOT percentiles — plus the resulting
speedup.

``--open-loop`` switches from the closed-loop trace to *open-loop
arrivals*: a seeded Poisson process (``numpy`` rng — the seed is an
argument, no ambient entropy) submits mixed-SLO traffic into an
:class:`AsyncEngine` at each ``--rates`` requests/s and reports
goodput-under-SLO (fraction of ALL arrivals whose TTFT met their
``ttft_slo_ms``; shed submissions count as missed) per arrival rate for
both scheduler policies.  By default the urgent SLO is *calibrated* to
1.5x the measured single-request latency — anchored to service time,
not wall-clock luck — so the comparison is reproducible across machine
speeds.  The policies: ``fifo`` (unbounded queue: p99 TTFT grows with
the backlog) vs ``priority-deadline`` (deadline-ordered admission,
preemption, bounded-queue displacement shedding — overload drops the
worst-ranked queued request, never an urgent arrival: p99 stays bounded
and urgent traffic keeps its SLO).

Run:  PYTHONPATH=src python benchmarks/engine_throughput.py --batch 4
      PYTHONPATH=src python benchmarks/engine_throughput.py --batch 2 \\
          --requests 24 --gen 24 --open-loop --rates 60,120
Prints CSV like the other benchmark sections (``verify_ms`` is the
one-time static plan-verification cost).
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro.configs import get_config, reduced


def _run_trace(model, prompts, *, max_batch: int, gen: int, sampling):
    from repro.deploy.engine import Engine

    engine = Engine(model, max_batch=max_batch, sampling=sampling)
    # warm-up one request end to end so each mode's jitted prefill/decode
    # is compiled before the timed trace — the CSV should compare
    # scheduling + steady-state dispatch, not XLA trace time (>= 2
    # generated tokens so the decode dispatch itself traces)
    engine.submit(prompts[0], max_new_tokens=3)
    engine.run_until_idle()
    engine.reset_stats()
    handles = [engine.submit(p, max_new_tokens=gen) for p in prompts]
    stats = engine.run_until_idle()
    assert all(h.status.value == "done" for h in handles)
    assert stats.tokens_generated == sum(len(h.tokens) for h in handles)
    return stats, handles


def _traffic(rng: np.random.Generator, n: int, rate: float, slo_ms: float):
    """Seeded open-loop trace: Poisson arrivals (exponential
    inter-arrival times at ``rate`` req/s) carrying a mixed SLO
    contract — every 4th request is *urgent* (priority 0, tight TTFT
    SLO), the rest background (priority 5, loose SLO, a completion
    deadline that makes them preemptible once over budget)."""
    gaps = rng.exponential(1.0 / rate, size=n)
    at = np.cumsum(gaps)
    specs = []
    for i in range(n):
        if i % 4 == 0:
            specs.append(dict(priority=0, ttft_slo_ms=slo_ms))
        else:
            specs.append(dict(priority=5, ttft_slo_ms=4 * slo_ms,
                              deadline_ms=8 * slo_ms))
        specs[-1]["at"] = float(at[i])
    return specs


def _run_open_loop(model, prompts, specs, *, max_batch: int, gen: int,
                   sampling, scheduler):
    """Submit the timed trace into an AsyncEngine; returns
    (met, shed, completed, stats) where ``met`` counts arrivals whose
    TTFT satisfied their own ``ttft_slo_ms`` and ``shed`` counts both
    429-refused and displacement-shed submissions."""
    from repro.deploy.serving.async_engine import AsyncEngine
    from repro.deploy.serving.scheduler import QueueFullError

    with AsyncEngine(model, max_batch, sampling=sampling,
                     scheduler=scheduler) as eng:
        # warm-up: jit the prefill AND decode paths before the timed
        # arrivals (>= 2 generated tokens forces a decode dispatch even
        # when the prompt is exactly seq_len, where token 1 comes from
        # the prefill logits)
        eng.submit(prompts[0], 3).result(timeout=120)
        eng.engine.reset_stats()
        t0, shed, handles = time.monotonic(), 0, []
        for prompt, spec in zip(prompts, specs):
            delay = t0 + spec["at"] - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            try:
                handles.append(eng.submit(
                    prompt, gen, priority=spec["priority"],
                    ttft_slo_ms=spec.get("ttft_slo_ms"),
                    deadline_ms=spec.get("deadline_ms")))
            except QueueFullError:
                shed += 1
        eng.drain(timeout=600)
        # displacement sheds finish a queued handle with reason "shed";
        # they never produce a TTFT sample so they count as missed too
        shed += sum(1 for h in handles if h.finish_reason == "shed")
        completed = sum(1 for h in handles if h.finish_reason != "shed")
        met = sum(
            1 for h in handles
            if h.handle.ttft_s is not None
            and h.handle.ttft_slo_ms is not None
            and h.handle.ttft_s <= h.handle.ttft_slo_ms / 1e3)
        return met, shed, completed, eng.stats


def _closed_loop(args, model, prompts, n, make_sampling):
    print("mode,max_batch,requests,tokens,decode_dispatches,"
          "step_p50_ms,step_p99_ms,occupancy,tok_per_s,"
          "ttft_p50_ms,ttft_p99_ms,tpot_p50_ms,tpot_p99_ms,"
          "preemptions,requeues,shed,verify_ms,"
          "prefix_hit_blocks,prefix_hit_rate,blocks_shared,cow_copies")
    rows = {}
    for mode, mb in (("serial", 1), ("continuous", args.batch)):
        stats, _ = _run_trace(model, prompts, max_batch=mb, gen=args.gen,
                              sampling=make_sampling(args))
        rows[mode] = stats
        print(f"{mode},{mb},{n},{stats.tokens_generated},"
              f"{stats.decode_dispatches},"
              f"{stats.step_latency_p50() * 1e3:.2f},"
              f"{stats.step_latency_p99() * 1e3:.2f},"
              f"{stats.occupancy():.2f},{stats.tokens_per_s():.1f},"
              f"{stats.ttft(50) * 1e3:.2f},{stats.ttft(99) * 1e3:.2f},"
              f"{stats.tpot(50) * 1e3:.2f},{stats.tpot(99) * 1e3:.2f},"
              f"{stats.preemptions},{stats.requeues},{stats.shed_requests},"
              f"{stats.verify_ms:.2f},"
              # prefix-cache columns: all zero unless the artifact was
              # compiled with prefix_cache=True (paged decoders only)
              f"{stats.prefix_hit_blocks},{stats.prefix_hit_rate():.3f},"
              f"{stats.blocks_shared},{stats.cow_copies}")
    serial, cont = rows["serial"], rows["continuous"]
    speedup = cont.tokens_per_s() / max(serial.tokens_per_s(), 1e-9)
    dispatch_ratio = serial.decode_dispatches / max(cont.decode_dispatches, 1)
    print(f"# continuous batching: {speedup:.2f}x tok/s over serial "
          f"({dispatch_ratio:.1f}x fewer decode dispatches, "
          f"{cont.slots_recycled} slots recycled)")


def _calibrate_slo_ms(model, prompts, *, max_batch: int, gen: int, sampling):
    """Measure one post-jit single-request latency and derive the urgent
    TTFT SLO from it (1.5x).  An absolute-millisecond SLO makes the
    policy comparison a lottery on machine speed; anchored to the
    measured service time, an urgent request meets its SLO iff it is
    admitted within ~a service interval (queue-jump) and misses it when
    it waits behind a FIFO backlog — the behavior under test."""
    from repro.deploy.serving.async_engine import AsyncEngine

    with AsyncEngine(model, max_batch, sampling=sampling) as eng:
        eng.submit(prompts[0], 3).result(timeout=120)  # jit both paths
        t0 = time.monotonic()
        eng.submit(prompts[0], gen).result(timeout=120)
        return 1.5 * (time.monotonic() - t0) * 1e3


def _open_loop(args, model, prompts, n, make_sampling):
    from repro.deploy.serving.scheduler import make_scheduler

    rates = [float(r) for r in args.rates.split(",")]
    slo_ms = args.slo_ms
    if slo_ms <= 0:
        slo_ms = _calibrate_slo_ms(model, prompts, max_batch=args.batch,
                                   gen=args.gen,
                                   sampling=make_sampling(args))
        print(f"# calibrated urgent ttft_slo_ms={slo_ms:.1f} "
              f"(1.5x measured single-request latency)")
    print("policy,rate_rps,requests,shed,completed,goodput_slo,"
          "ttft_p50_ms,ttft_p99_ms,preemptions,requeues")
    goodput: dict[tuple[str, float], float] = {}
    for rate in rates:
        rng = np.random.default_rng(args.seed)  # same trace for both policies
        specs = _traffic(rng, n, rate, slo_ms)
        for policy in ("fifo", "priority-deadline"):
            # FIFO models the historical unbounded queue (its p99 TTFT
            # grows with the backlog); priority-deadline gets the bound
            # so overload sheds instead of queueing without limit
            sched = make_scheduler(
                policy,
                max_queue=None if policy == "fifo" else args.max_queue)
            met, shed, completed, stats = _run_open_loop(
                model, prompts, specs, max_batch=args.batch, gen=args.gen,
                sampling=make_sampling(args), scheduler=sched)
            goodput[(policy, rate)] = met / n
            print(f"{policy},{rate:g},{n},{shed},{completed},"
                  f"{met / n:.3f},{stats.ttft(50) * 1e3:.1f},"
                  f"{stats.ttft(99) * 1e3:.1f},{stats.preemptions},"
                  f"{stats.requeues}")
    for rate in rates:
        f, pd = goodput[("fifo", rate)], goodput[("priority-deadline", rate)]
        print(f"# rate {rate:g} req/s: priority-deadline goodput {pd:.3f} "
              f"vs fifo {f:.3f} ({'+' if pd >= f else ''}{pd - f:.3f})")


def main(argv=None):
    from repro.deploy import api
    from repro.launch.cli import (
        add_engine_args,
        make_sampling,
        parse_backend,
        resolve_requests,
        synthesize_prompts,
    )

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--full", action="store_true",
                    help="benchmark the full config (default: reduced())")
    ap.add_argument("--backend", type=parse_backend, default="w8a8")
    ap.add_argument("--open-loop", action="store_true",
                    help="Poisson arrivals + goodput-under-SLO per rate "
                         "(fifo vs priority-deadline) instead of the "
                         "closed-loop serial-vs-continuous trace")
    ap.add_argument("--rates", default="60,120",
                    help="comma-separated arrival rates (req/s) for "
                         "--open-loop")
    ap.add_argument("--slo-ms", type=float, default=0.0,
                    help="tight TTFT SLO for the urgent quarter of the "
                         "open-loop traffic (background gets 4x, with an "
                         "8x completion deadline); <= 0 calibrates to "
                         "1.5x the measured single-request latency")
    ap.add_argument("--max-queue", type=int, default=10,
                    help="priority-deadline admission bound in --open-loop "
                         "(fifo stays unbounded for contrast)")
    ap.add_argument("--seed", type=int, default=0,
                    help="numpy rng seed for the Poisson arrival trace")
    add_engine_args(ap)  # the serve CLI's block: one serving surface
    args = ap.parse_args(argv)
    n = resolve_requests(args, factor=3)

    cfg = get_config(args.arch)
    if not args.full:
        cfg = reduced(cfg)
    model = api.compile(cfg, backend=args.backend, seq_len=args.prompt_len,
                        max_len=args.prompt_len + args.gen + 1)
    prompts = synthesize_prompts(cfg.vocab, n=n, prompt_len=args.prompt_len)

    if args.open_loop:
        return _open_loop(args, model, prompts, n, make_sampling)
    return _closed_loop(args, model, prompts, n, make_sampling)


if __name__ == "__main__":
    main()
