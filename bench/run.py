#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``.  The run needs
the accelerators the cell asks for and never falls back to the CPU: with no
TPU it exits 2 and prints no result.  It sets up (compiling, or reading the
compile cache in ``.jax_cache/`` of the checkout), measures for ``--seconds``,
checks what the timed path produced against the plain reference, and prints
as the last line of standard output one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, ``breakdown`` (traced
runs) and ``checks``, each compared number beside its limit.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, for set-up time

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import spec  # noqa: E402

JAX_CACHE = os.path.join(ROOT, ".jax_cache")


class NoAccelerator(RuntimeError):
    pass


def require_tpu(chips: int) -> dict:
    """The device as JAX reports it; raises unless it is a TPU with at
    least ``chips`` devices."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU (first device: {devs[0]}); the benchmark "
                            "measures the chip and has no CPU fallback")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": chips}


def enable_cache(path: str) -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, device: dict,
        t0: float = T0, hooks: dict | None = None, peaks: dict | None = None) -> dict:
    """One run of ``cell``; returns the result line as a dict."""
    from bench import layer, spans

    clock = spans.SetupClock(t0)
    rec = cell.driver().run(cell, seed, seconds, trace, t0, clock, hooks)
    if hooks and "record" in hooks:
        hooks["record"](rec)
    setup_s = sum(d for _, d in clock.phases)
    e2e = dict(rec["e2e"], setup_s=setup_s)
    dev = dict(device, memory_peak_bytes=rec["memory_peak_bytes"])
    line = {"correct": spec.judge(rec["checks"]), "attempted": rec["attempted"],
            "failed": rec["failed"]}
    if trace:
        tr = rec["trace"]
        dev.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        ctx = layer.Context(cell, peaks or {}, rec, e2e)
        metrics = {}
        for m in cell.per_layer:
            value = spec.load_reader(cell.metrics_dir, m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        line["metrics"] = metrics
        line["breakdown"] = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
    else:
        line["metrics"] = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                           for m in cell.end_to_end}
    line["device"] = dev
    line["checks"] = {name: {"value": v, "rule": rule, "limit": lim}
                      for name, v, rule, lim in rec["checks"]}
    # for the log: where set-up went, the counters, and the samples behind tails
    print("setup: " + ", ".join(f"{n} {d:.3f}s" for n, d in clock.phases)
          + f"; jax compile/trace {clock.compile_s:.3f}s over {clock.compiles} compiles, "
          f"persistent cache {clock.cache['hits']} hits / {clock.cache['misses']} misses",
          file=sys.stderr)
    print("window: " + json.dumps({"e2e": e2e, "samples": rec.get("samples"),
                                   "counters": rec.get("counters"),
                                   "notes": rec.get("notes")}), file=sys.stderr)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    try:
        device = require_tpu(cell.chips)
    except NoAccelerator as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    enable_cache(JAX_CACHE)
    peaks = spec.load_peaks(device["kind"])
    line = run(cell, args.seed, args.seconds, bool(args.trace), device, peaks=peaks)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} {c['rule']} {c['limit']}", file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
