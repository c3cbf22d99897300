#!/usr/bin/env python3
"""The control of a cell whose configuration's driver is ``serve_moe``,
read on the chip.

    python3 bench/control_moe.py --workload <cell> --seeds 1 2 3 --seconds <s>

As ``bench/control.py`` for the other drivers: for each seed, in one
process, one run of the cell, then on the requests that run checked the
plain reference of ``bench/reference/moe.py`` with int4 weights in the
program's place, read by the number the run compares (``control_gap``) and
judged by the rule that decides the run's ``correct``.  The control has to
come out not correct.  Prints one JSON line per seed.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run, spec  # noqa: E402
from bench.control import control_checks  # noqa: E402
from bench.reference import moe as R  # noqa: E402


def control_reading(cell, seed: int, checked) -> float:
    """The int4 control's widest gap on the run's checked requests."""
    cfg = cell.config
    arch = R.ref_arch(cfg)
    codes = R.make_codes(arch, cfg["weights"], seed)
    four = R.int4_codes(codes)
    return max(R.control_gap(arch, cfg["serve"], codes, four, p, t) for p, t in checked)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    if cell.config["driver"] != "serve_moe":
        ap.error(f"{cell.name} is driven by {cell.config['driver']!r}: use bench/control.py")
    device = run.require_tpu(cell.chips)
    run.enable_cache(run.JAX_CACHE)
    for seed in args.seeds:
        captured = {}
        line = run.run(cell, seed, args.seconds, False, device, t0=time.perf_counter(),
                       hooks={"record": captured.update})
        checks = control_checks(captured["checks"], control_reading(cell, seed, captured["checked"]))
        print(json.dumps({"workload": cell.name, "seed": seed, "program": line["checks"],
                          "correct": line["correct"], "control": checks,
                          "control_correct": spec.judge(checks)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
