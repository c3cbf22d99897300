"""The traffic generators: same seed, same traffic; every seed the same work;
shared prefixes, bursts and mixed classes stated as data alone."""

import json
import os
from collections import Counter

import numpy as np
import pytest

from bench import spec

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
GENERATORS = os.path.join(REPO, "bench", "generators")
open_loop = spec._load_file(os.path.join(GENERATORS, "open_loop.py"), "test_gen_")
closed_loop = spec._load_file(os.path.join(GENERATORS, "closed_loop.py"), "test_gen_")

#: long prompts and short answers (the document-QA mix of PERF.md's open questions)
LONG_PROMPTS = {"rate_per_s": 1.0, "ramp_s": 10,
                "prompt_tokens": {"median": 1200, "min": 768, "max": 1920, "sigma": 0.25},
                "output_tokens": {"median": 32, "min": 16, "max": 64, "sigma": 0.35}}
#: a common system prompt of 1024 tokens before user turns of 128-256
SHARED_PREFIX = {"classes": [{"weight": 1, "prefix_tokens": 1024,
                              "prompt_tokens": {"median": 180, "min": 128, "max": 256, "sigma": 0.3},
                              "output_tokens": {"median": 128, "min": 64, "max": 256, "sigma": 0.4}}]}
#: the reason and long-prompt mixes in one queue, one long request in four
MIXED = {"stratum": 8, "classes": [
    {"weight": 3, "prompt_tokens": {"median": 192, "min": 128, "max": 384, "sigma": 0.35},
     "output_tokens": {"median": 512, "min": 256, "max": 1024, "sigma": 0.35}},
    {"weight": 1, "prompt_tokens": LONG_PROMPTS["prompt_tokens"],
     "output_tokens": LONG_PROMPTS["output_tokens"]}]}
#: on/off arrivals: 4x the rate for 2 s in every 10, nothing between
BURSTS = {"bursts": {"period_s": 10, "on_s": 2, "on_rate_per_s": 6.0, "off_rate_per_s": 0.0}}
ARCH = {"vocab": 50304, "d_model": 2048}


def _mix(name):
    extra = {"long-prompts": LONG_PROMPTS, "shared-prefix": SHARED_PREFIX, "mixed": MIXED,
             "bursts": BURSTS}
    if name in extra:
        return dict(_mix("reason"), **extra[name])
    with open(os.path.join(REPO, "bench", "traffic", name + ".json")) as f:
        return json.load(f)


def _bounds(mix, req):
    c = open_loop.classes(mix)[req.cls]
    return c["prompt_tokens"], c["output_tokens"], int(c.get("prefix_tokens", 0))


@pytest.mark.parametrize("name", ["reason", "long-prompts", "shared-prefix", "mixed", "bursts"])
def test_open_loop_is_deterministic_and_bounded(name):
    mix = _mix(name)
    a = open_loop.make(mix, 30, 2**31 + 7, ARCH)
    b = open_loop.make(mix, 30, 2**31 + 7, ARCH)
    assert a == b
    assert a and all(0 < r.due_s < mix["ramp_s"] + 30 for r in a)
    assert all(x.due_s < y.due_s for x, y in zip(a, a[1:]))
    for r in a:
        p, o, pre = _bounds(mix, r)
        assert p["min"] <= len(r.prompt) - pre <= p["max"]
        assert o["min"] <= r.max_new_tokens <= o["max"]
    assert all(0 <= t < ARCH["vocab"] for r in a for t in r.prompt)


@pytest.mark.parametrize("name", ["reason", "long-prompts", "mixed"])
def test_every_seed_gets_the_same_work_per_stratum(name):
    mix = {k: v for k, v in _mix(name).items() if k != "schedule_seed"}
    k = mix["stratum"]
    runs = [open_loop.make(mix, 60, seed, {"vocab": 1000}) for seed in (1, 2, 3**20)]
    n = min(len(r) for r in runs) // k * k
    assert n >= k
    for i in range(0, n, k):
        blocks = [r[i:i + k] for r in runs]
        assert len({tuple(sorted((x.cls, len(x.prompt)) for x in b)) for b in blocks}) == 1
        assert len({tuple(sorted((x.cls, x.max_new_tokens) for x in b)) for b in blocks}) == 1
    # ...in another order, with other tokens
    assert [len(x.prompt) for x in runs[0][:k]] != [len(x.prompt) for x in runs[1][:k]]
    assert runs[0][0].prompt != runs[1][0].prompt


def test_rate_and_medians_match_the_mix():
    mix = _mix("reason")
    reqs = open_loop.make(mix, 600, 5, {"vocab": 1000})
    span = mix["ramp_s"] + 600
    assert abs(len(reqs) / span - mix["rate_per_s"]) < 0.1 * mix["rate_per_s"]
    assert abs(np.median([len(r.prompt) for r in reqs]) - mix["prompt_tokens"]["median"]) <= 16


def test_strata_are_quantiles():
    dist = {"median": 100, "min": 10, "max": 1000, "sigma": 0.5}
    s = open_loop.lognormal_strata(dist, 5)
    assert s == sorted(s) and s[2] == 100
    assert Counter(open_loop.lognormal_strata({**dist, "max": 100}, 4))[100] == 2


def test_closed_loop_batches():
    mix = dict(_mix("b64"), seq_len=16)
    a = closed_loop.make(mix, 1, 9, {"vocab": 30522})
    assert a.shape == (mix["distinct_batches"], mix["batch"], 16)
    assert a.dtype == np.int32 and a.min() >= 0 and a.max() < 30522
    assert np.array_equal(a, closed_loop.make(mix, 1, 9, {"vocab": 30522}))
    assert not np.array_equal(a, closed_loop.make(mix, 1, 10, {"vocab": 30522}))


def test_closed_loop_feature_rows():
    mix = dict(_mix("b64"), batch=2, seq_len=8)
    a = closed_loop.make(mix, 1, 2**33 + 1, {"vocab": 0, "d_model": 384})
    assert a.shape == (mix["distinct_batches"], 2, 8, 384) and a.dtype == np.int8
    assert 8 < np.std(a.astype(np.float32)) < 24
    assert np.array_equal(a, closed_loop.make(mix, 1, 2**33 + 1, {"vocab": 0, "d_model": 384}))


def test_shared_prefix_is_one_prompt_for_every_request():
    reqs = open_loop.make(_mix("shared-prefix"), 60, 11, ARCH)
    assert len({r.prompt[:1024] for r in reqs}) == 1
    assert len({r.prompt[1024:] for r in reqs}) == len(reqs)
    other = open_loop.make(_mix("shared-prefix"), 60, 12, ARCH)
    assert other[0].prompt[:1024] != reqs[0].prompt[:1024]


def test_mixed_classes_share_each_stratum_by_weight():
    mix = _mix("mixed")
    assert open_loop.class_counts([3, 1], 8) == [6, 2]
    assert open_loop.class_counts([1, 1, 1], 4) in ([2, 1, 1], [1, 2, 1], [1, 1, 2])
    reqs = open_loop.make(mix, 120, 4, ARCH)
    for i in range(0, len(reqs) // 8 * 8, 8):
        assert Counter(r.cls for r in reqs[i:i + 8]) == {0: 6, 1: 2}


def test_bursts_follow_the_on_off_rate():
    mix = _mix("bursts")
    reqs = open_loop.make(mix, 980, 6, {"vocab": 100})
    phase = [r.due_s % 10 for r in reqs]
    assert all(p < 2 for p in phase)  # nothing due while the rate is 0
    mean = len(reqs) / (mix["ramp_s"] + 980)
    assert abs(mean - 6.0 * 2 / 10) < 0.1 * 1.2
    steady = open_loop.make(dict(mix, bursts={"period_s": 10, "on_s": 2, "on_rate_per_s": 1.5,
                                              "off_rate_per_s": 1.5}), 980, 6, {"vocab": 100})
    plain = open_loop.make(_mix("reason"), 980, 6, {"vocab": 100})
    n = min(len(steady), len(plain)) - 1
    assert n > 1000 and steady[:n] != [] and len(steady) - len(plain) in (-1, 0, 1)
    assert [r.prompt for r in steady[:n]] == [r.prompt for r in plain[:n]]
    assert [r.due_s for r in steady[:n]] == pytest.approx([r.due_s for r in plain[:n]])


def test_a_schedule_seed_fixes_arrivals_and_lengths_across_seeds():
    mix = _mix("reason")
    assert "schedule_seed" in mix
    runs = [open_loop.make(mix, 51, seed, ARCH) for seed in (1, 2**31 + 5)]
    assert [(r.due_s, len(r.prompt), r.max_new_tokens) for r in runs[0]] == [
        (r.due_s, len(r.prompt), r.max_new_tokens) for r in runs[1]]
    assert all(a.prompt != b.prompt for a, b in zip(*runs))
    other = open_loop.make(dict(mix, schedule_seed=2), 51, 1, ARCH)
    assert [r.due_s for r in other] != [r.due_s for r in runs[0]]
