"""The reader of ``sampling_share.serve`` on small synthetic records."""

import os
from types import SimpleNamespace

import pytest

from bench import spec

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
read = spec.load_reader(os.path.join(REPO, "bench", "metrics"), "sampling_share.serve")


def _ctx(host, window_s=100e-9):
    return SimpleNamespace(trace={"window_s": window_s,
                                  "record": {"device": "/device:TPU:0", "ops": [],
                                             "host": host}})


def test_union_of_sampling_spans_over_the_traced_window():
    # ns: the program's span [10, 30) holds the benchmark's own [12, 28);
    # a second call [50, 60); other spans do not count
    host = [["engine.step", 0, 100], ["sampling", 10, 20], ["sampling", 12, 16],
            ["session.decode", 30, 15], ["sampling", 50, 10]]
    assert read(_ctx(host)) == pytest.approx(30.0)


@pytest.mark.parametrize("ctx", [
    SimpleNamespace(trace=None),
    _ctx([["sampling", 10, 20]], window_s=0.0),     # a trace with no device
    _ctx([["engine.step", 0, 100]]),                # no sampling span
], ids=["untraced", "no-device", "no-span"])
def test_nothing_to_read(ctx):
    assert read(ctx) is None
