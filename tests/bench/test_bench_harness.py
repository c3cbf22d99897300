"""The harness: names to files, the result line, and no run without a TPU."""

import json
import os
import subprocess
import sys

import pytest

from bench import run, spec

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_every_cell_of_the_benchmark_resolves():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        for m in cell.per_layer:
            assert m["moves"] in {e["name"] for e in cell.end_to_end}
            assert callable(spec.load_reader(cell.metrics_dir, m["name"]))


def test_a_cell_mix_and_metric_added_as_files_only(tiny_root):
    """A new configuration, mix, traffic generator and per-layer metric need
    new files and BENCHMARK.json entries, and no edit to the harness."""
    with open(os.path.join(tiny_root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "new.cell", "config": "new-config",
                               "traffic": "new-mix", "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "new_metric", "unit": "%", "better": "higher",
                               "source": "program_counter", "layer": "l",
                               "moves": "setup_s", "workloads": ["new.cell"]})
    root = os.path.join(tiny_root, "..", "added")
    os.makedirs(os.path.join(root, "bench", "configs"), exist_ok=True)
    os.makedirs(os.path.join(root, "bench", "traffic"), exist_ok=True)
    os.makedirs(os.path.join(root, "bench", "metrics"), exist_ok=True)
    os.makedirs(os.path.join(root, "bench", "generators"), exist_ok=True)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    with open(os.path.join(root, "bench", "configs", "new-config.json"), "w") as f:
        json.dump({"driver": "serve", "arch": {"vocab": 7}}, f)
    with open(os.path.join(root, "bench", "traffic", "new-mix.json"), "w") as f:
        json.dump({"generator": "new_kind", "rate_per_s": 2.0}, f)
    with open(os.path.join(root, "bench", "generators", "new_kind.py"), "w") as f:
        f.write("def make(mix, seconds, seed, arch):\n"
                "    return [(seed, mix['rate_per_s'] * seconds, arch['vocab'])]\n")
    with open(os.path.join(root, "bench", "metrics", "new_metric.py"), "w") as f:
        f.write("def read(ctx):\n    return 42.0\n")
    cell = spec.load_cell("new.cell", root=root)
    assert cell.config["arch"]["vocab"] == 7 and cell.traffic["rate_per_s"] == 2.0
    assert [m["name"] for m in cell.per_layer] == ["new_metric"]
    assert spec.load_reader(cell.metrics_dir, "new_metric")(None) == 42.0
    assert cell.requests(3.0, 5) == [(5, 6.0, 7)]
    from bench import serve
    assert cell.driver() is serve
    with pytest.raises(spec.SpecError):
        spec.load_cell("no.such.cell", root=root)


def test_unknown_device_kind_is_an_error():
    assert spec.load_peaks("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(spec.SpecError):
        spec.load_peaks("cpu")


def test_the_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(REPO, "bench", "run.py"),
                        "--workload", "olmo-1b.reason", "--seed", str(2**33), "--seconds", "1",
                        "--trace", "0"], cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_require_tpu_names_the_device_it_refuses():
    with pytest.raises(run.NoAccelerator, match="no TPU"):
        run.require_tpu(1)
