#!/usr/bin/env python3
"""The control of a cell's comparison, read on the chip.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 --seconds <s>

For each seed, in one process: one run of the cell (a short window at the
cell's own load), then on the same requests or batches the plain reference
put in the program's place at the next precision below the configuration's
(int4 weights for its int8), read by the same number the run compares and
judged by the same rule that decides the run's ``correct``.  The control
has to come out not correct; the program's readings of the same seeds set
the lower end.  Prints one JSON line per seed.
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
from bench.reference import models as R  # noqa: E402


def control_reading(cell, seed: int, checked) -> float:
    """The int4 control's number on the run's checked requests or batches."""
    import numpy as np

    cfg = cell.config
    ref_arch = R.ref_arch(cfg)
    codes = R.make_codes(ref_arch, cfg["weights"], seed)
    four = R.int4_codes(codes)
    if cfg["driver"] == "serve":
        return max(R.control_gap(ref_arch, cfg["serve"], codes, four, p, t) for p, t in checked)
    worst = 0
    for x, _y in checked:
        want = R.encoder_features(ref_arch, codes, x).astype(np.int32)
        got = R.encoder_features(ref_arch, four, x).astype(np.int32)
        worst = max(worst, int(np.max(np.abs(got - want))))
    return float(worst)


def control_checks(program_checks, reading: float) -> list:
    """The run's checks with the compared number (the first) read from the
    control instead of the program."""
    (name, _value, rule, limit), *rest = program_checks
    return [(name, reading, rule, limit), *rest]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
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
