"""Tiny cells for the benchmark's CPU tests: the real configuration and mix
files with their sizes cut down, in a checkout-like directory of their own."""

from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for _p in (REPO, os.path.join(REPO, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TINY_DECODER = dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=4, head_dim=32, d_ff=256,
                    vocab=512, max_seq=128)
TINY_SERVE = dict(slots=4, seq_len=16, max_len=96, kv_block_size=8, kv_blocks=48)
TINY_OPEN = dict(rate_per_s=3.0, ramp_s=2, stratum=4, check={"requests": 3},
                 prompt_tokens={"median": 24, "min": 16, "max": 40, "sigma": 0.3},
                 output_tokens={"median": 8, "min": 4, "max": 12, "sigma": 0.3})


def _load(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


def make_root(path: str) -> str:
    """A directory holding BENCHMARK.json, bench/metrics, bench/generators
    and two tiny cells, ``tiny.serve`` (olmo-1b's file, cut down) and
    ``tiny.enc`` (whisper-tiny-encoder's)."""
    os.makedirs(os.path.join(path, "bench", "configs"))
    os.makedirs(os.path.join(path, "bench", "traffic"))
    for d in ("metrics", "generators"):
        shutil.copytree(os.path.join(REPO, "bench", d), os.path.join(path, "bench", d))
    shutil.copy(os.path.join(REPO, "bench", "peaks.json"), os.path.join(path, "bench"))
    bench = _load("BENCHMARK.json")
    bench["workloads"] = [
        {"name": "tiny.serve", "config": "tiny-dec", "traffic": "tiny-open", "chips": 1, "why": "t"},
        {"name": "tiny.enc", "config": "tiny-enc", "traffic": "tiny-closed", "chips": 1, "why": "t"},
    ]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            serving = any(w.startswith("olmo-1b") for w in m["workloads"])
            m["workloads"] = ["tiny.serve"] if serving else ["tiny.enc"]
    dec = _load("bench", "configs", "olmo-1b.json")
    dec["arch"].update(TINY_DECODER)
    dec["serve"].update(TINY_SERVE)
    enc = _load("bench", "configs", "whisper-tiny-encoder.json")
    enc["arch"].update(n_layers=2, d_model=128, n_heads=2, n_kv_heads=2, d_ff=256,
                       n_frames=256, max_seq=256)
    mix = _load("bench", "traffic", "reason.json")
    mix.update(TINY_OPEN)
    closed = _load("bench", "traffic", "b64.json")
    closed.update(batch=2, seq_len=256, distinct_batches=3, in_flight=2, check={"calls": 2})
    for rel, obj in (("BENCHMARK.json", bench), ("bench/configs/tiny-dec.json", dec),
                     ("bench/configs/tiny-enc.json", enc),
                     ("bench/traffic/tiny-open.json", mix),
                     ("bench/traffic/tiny-closed.json", closed)):
        with open(os.path.join(path, rel), "w") as f:
            json.dump(obj, f)
    return path


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("bench_root")))


CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
