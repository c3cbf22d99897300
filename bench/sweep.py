#!/usr/bin/env python3
"""Find the knee of a serving cell: the highest offered rate at which the
admission queue does not grow over a window and no request fails.

    python3 bench/sweep.py --workload olmo-1b.reason --rates 0.5 1 1.5 --seconds 30

One process sets the deployment up once and steps through the rates, each
with the cell's mix at that rate: a ramp, then a window in which the queue
depth is sampled.  Between rates every request still in flight is
cancelled, so each rate starts empty.  Prints one JSON line per rate.  The
cell's rate is then written, as a number, into its mix's file.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import run, serve, spans, spec  # noqa: E402


def queue_slope(samples) -> float:
    """Least-squares growth of the queue depth, requests per second."""
    if len(samples) < 2:
        return 0.0
    t, q = np.asarray(samples, float).T
    return float(np.polyfit(t - t[0], q, 1)[0])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    run.require_tpu(cell.chips)
    run.enable_cache(run.JAX_CACHE)
    clock = spans.SetupClock(T0)
    engine, _ = serve.setup(cell, args.seed, False, clock)
    print(json.dumps({"setup": clock.phases}), flush=True)
    for i, rate in enumerate(args.rates):
        mix = dict(cell.traffic, rate_per_s=rate)
        at_rate = dataclasses.replace(cell, traffic=mix)
        # a seed of its own per rate: the same prompts again would be full
        # prefix-cache hits and skip their prefill
        reqs = at_rate.requests(args.seconds, args.seed + i)
        depth, win = [], []

        def tick():
            now = time.perf_counter()
            if win and win[0] <= now < win[1]:
                depth.append((now, engine.queue_depth))

        streams, ended, before, after, (w0, w1) = serve.drive(
            engine, reqs, mix["ramp_s"], args.seconds, lambda a, b: win.extend((a, b)), tick)
        m = serve.window_metrics(streams, ended, w0, w1)
        c = serve._deltas(before, after)
        print(json.dumps({
            "rate_per_s": rate, "attempted": m["attempted"], "failed": m["failed"],
            "queue_at_open": c["queue_depth_at_open"], "queue_at_close": c["queue_depth"],
            "queue_slope_per_s": queue_slope(depth), **m["e2e"],
            "decode_ms": 1e3 * c["decode_time_s"] / max(1, c["decode_dispatches"]),
            "chunk_ms": 1e3 * c["prefill_time_s"] / max(1, c["prefill_dispatches"]),
            "occupancy": c["slot_steps_busy"] / max(1, c["decode_dispatches"] * c["max_batch"]),
            "decode_dispatches": c["decode_dispatches"], "chunk_dispatches": c["prefill_dispatches"],
            "prefix_hits": c["prefix_hits"],
            "finished": sum(r in ("eos", "length") for r, _n, _t in ended),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
