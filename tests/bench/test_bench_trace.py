"""The reduction from trace records to busy time, idle gaps and kernel time."""

import gzip
import json
import os

import pytest

from bench import tracefile

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RECORDED = os.path.join(REPO, "bench", "traces")


def test_union_merges_overlaps_and_touching_intervals():
    assert tracefile.union_ns([(0, 10), (5, 10), (20, 5), (25, 5)]) == [(0, 15), (20, 30)]
    assert tracefile.union_ns([]) == []


def _rec():
    # ns: ops at [0,10) [5,15) [30,40) [60,70); host spans over the gaps
    ops = [["fusion.1", 0, 10, ""], ["fusion.2", 5, 10, ""],
           ["%int8_gemm_pallas.3 = s8[8,8] custom-call(s8[8,8] %fusion.2)", 30, 10, ""],
           ["%fusion.1 = s8[8,8] fusion(s8[8,8] %int8_gemm_pallas.3)", 60, 10, ""]]
    host = [["engine.step", 0, 100], ["sampling", 15, 10], ["session.decode", 40, 15]]
    return {"device": "/device:TPU:0", "ops": ops, "host": host}


def test_summary_of_a_small_trace():
    s = tracefile.summarize(_rec())
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["busy_s"] == pytest.approx(35e-9)
    idle = dict(s["idle_gaps"])
    # each piece of a gap goes to the innermost span open over it:
    # [15,25) sampling, [25,30) step; [40,55) decode, [55,60) step; [70,100) step
    assert idle == pytest.approx({"sampling": 10e-9, "session.decode": 15e-9,
                                  "engine.step": 40e-9})
    assert sorted(v for _, v in s["device_ops"]) == pytest.approx([10e-9] * 4)
    assert sum(v for _, v in s["idle_gaps"]) + s["busy_s"] == pytest.approx(s["window_s"])


def test_window_clips_events():
    s = tracefile.summarize(_rec(), window=(8, 35))
    assert s["busy_s"] == pytest.approx((15 - 8 + 35 - 30) * 1e-9)


def test_kernel_events_match_the_op_not_its_operands():
    evs = tracefile.kernel_events(_rec(), ("int8_gemm_pallas",))
    assert [tracefile.op_name(e) for e in evs] == ["int8_gemm_pallas.3"]


@pytest.mark.parametrize("name", sorted(os.listdir(RECORDED)) if os.path.isdir(RECORDED) else [])
def test_recorded_chip_trace(name):
    """A slice of a trace recorded on a TPU v5e: the reduction's busy time is
    the union of the op intervals, computed here by a plain sweep."""
    with gzip.open(os.path.join(RECORDED, name), "rt") as f:
        rec = json.load(f)
    s = tracefile.summarize(rec)
    marks = sorted([(o[1], 1) for o in rec["ops"]] + [(o[1] + o[2], -1) for o in rec["ops"]],
                   key=lambda m: (m[0], -m[1]))
    depth, busy, since = 0, 0, None
    for t, d in marks:
        if depth == 0 and d > 0:
            since = t
        depth += d
        if depth == 0:
            busy += t - since
    assert s["busy_s"] == pytest.approx(busy / 1e9)
    assert 0 < s["busy_s"] <= s["window_s"]
    assert sum(v for _, v in s["idle_gaps"]) == pytest.approx(s["window_s"] - s["busy_s"],
                                                               rel=1e-6)
    assert sum(v for _, v in s["device_ops"]) <= sum(o[2] for o in rec["ops"]) / 1e9 + 1e-12
    attn = tracefile.kernel_events(rec, ("ita_attention_pallas",))
    gemm = tracefile.kernel_events(rec, ("int8_gemm_pallas",))
    assert attn and gemm, "the recorded slice holds both Pallas kernels' events"
    assert all(o[0].startswith("%ita_attention_pallas") for o in attn)
