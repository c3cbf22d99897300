"""What a run reads: ``BENCHMARK.json``, and the files it names by name.

A cell (an entry of ``workloads``) names a configuration and a traffic mix.
Each is a data file found by its name: ``bench/configs/<config>.json`` and
``bench/traffic/<traffic>.json``.  The configuration names its driver, the
module ``bench/<driver>.py`` that sets it up and runs its window; the mix
names its generator, ``bench/generators/<generator>.py`` with one function
``make(mix, seconds, seed, arch)``.  A per-layer metric is a reader found by
its name, ``bench/metrics/<metric>.py``, with one function ``read(ctx)``.
Adding a cell, a mix, a generator or a metric adds files and entries; no
file here changes.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
#: where ``compile()`` keeps lowered plans: in the checkout, so the next run finds them
PLAN_CACHE = os.path.join(BENCH_DIR, ".plan_cache")


class SpecError(ValueError):
    """A name that no file or entry answers to."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # bench/configs/<config>.json
    traffic: dict         # bench/traffic/<traffic>.json
    end_to_end: list      # BENCHMARK.json end_to_end entries this cell reports
    per_layer: list       # BENCHMARK.json per_layer entries this cell reports
    bench_dir: str

    @property
    def metrics_dir(self) -> str:
        return os.path.join(self.bench_dir, "metrics")

    def driver(self):
        """The module ``bench/<driver>.py`` the configuration names."""
        name = self.config["driver"]
        try:
            return importlib.import_module(f"bench.{name}")
        except ModuleNotFoundError:
            raise SpecError(f"no driver bench/{name}.py for configuration "
                            f"{self.config.get('name')!r}") from None

    def requests(self, seconds: float, seed: int):
        """The mix's traffic, from the generator it names."""
        gen = _load_file(os.path.join(self.bench_dir, "generators",
                                      self.traffic["generator"] + ".py"), "bench_generator_")
        return gen.make(self.traffic, seconds, seed, self.config["arch"])


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"no file {os.path.relpath(path, ROOT)}") from None


def _load_file(path: str, prefix: str):
    """The module of the Python file ``path``."""
    if not os.path.exists(path):
        raise SpecError(f"no file {os.path.relpath(path, ROOT)}")
    name = os.path.splitext(os.path.basename(path))[0]
    modname = prefix + name.replace(".", "_")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod  # dataclasses look their module up by name
    spec.loader.exec_module(mod)
    return mod


def _reports(entry: dict, cell: str, e2e_names: set | None = None) -> bool:
    """Does ``cell`` report this metric?  A ``workloads`` list says so;
    without one an end-to-end metric is everywhere, and a per-layer metric
    is wherever the end-to-end metric it ``moves`` is."""
    if "workloads" in entry:
        return cell in entry["workloads"]
    return e2e_names is None or entry["moves"] in e2e_names


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json (have {sorted(cells)})")
    w = cells[name]
    bench_dir = os.path.join(root, "bench")
    config = _load_json(os.path.join(bench_dir, "configs", w["config"] + ".json"))
    config.setdefault("name", w["config"])
    traffic = _load_json(os.path.join(bench_dir, "traffic", w["traffic"] + ".json"))
    traffic.setdefault("name", w["traffic"])
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer, bench_dir)


def load_reader(metrics_dir: str, metric: str):
    """``read(ctx)`` of ``<metrics_dir>/<metric>.py``."""
    path = os.path.join(metrics_dir, metric + ".py")
    if not os.path.exists(path):
        raise SpecError(f"no reader {os.path.relpath(path, ROOT)} for metric {metric!r}")
    return _load_file(path, "bench_metric_").read


def judge(checks) -> bool:
    """The rule that decides ``correct``: every ``(name, value, rule,
    limit)`` holds."""
    ops = {"<=": lambda v, lim: v <= lim, ">=": lambda v, lim: v >= lim}
    return all(ops[rule](value, limit) for _name, value, rule, limit in checks)


def load_peaks(kind: str, root: str = ROOT) -> dict:
    """The published peaks of ``device_kind``; a device not in the table is
    an error, never a default."""
    table = _load_json(os.path.join(root, "bench", "peaks.json"))["devices"]
    if kind not in table:
        raise SpecError(f"device kind {kind!r} is not in bench/peaks.json ({sorted(table)})")
    return table[kind]
