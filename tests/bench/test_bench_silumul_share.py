"""The reader of ``silumul_share.serve`` on small synthetic records."""

import os
from types import SimpleNamespace

import pytest

from bench import layer, spec

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
read = spec.load_reader(os.path.join(REPO, "bench", "metrics"), "silumul_share.serve")


def _ctx(ops, window_s=200e-9):
    ctx = SimpleNamespace(trace={"window_s": window_s,
                                 "record": {"device": "/device:TPU:0", "ops": ops,
                                            "host": []}})
    ctx.kernel_time = lambda prefixes: layer.Context.kernel_time(ctx, prefixes)
    return ctx


def test_device_time_of_the_kernel_over_the_traced_window():
    # ns: two kernel calls of 10 and 30; the fusion that reads the kernel's
    # output names it only as an operand and does not count
    ops = [["%silumul_pallas.3 = s8[32,8192] custom-call(s8[32,8192] %g, s8[32,8192] %u)",
            0, 10, ""],
           ["%fusion.7 = s8[32,8192] fusion(s8[32,8192] %silumul_pallas.3)", 10, 40, ""],
           ["%silumul_pallas.5 = s8[4096,8192] custom-call(s8[4096,8192] %g)", 100, 30, ""]]
    assert read(_ctx(ops)) == pytest.approx(20.0)


@pytest.mark.parametrize("ctx", [
    SimpleNamespace(trace=None, kernel_time=lambda prefixes: (0.0, [])),
    # the XLA runner: the gather fusions carry no kernel name
    _ctx([["%fusion.12 = s32[33554432] fusion(s8[4096,8192] %p)", 0, 50, ""]]),
    _ctx([["%silumul_pallas.1 = s8[32,8192] custom-call()", 0, 10, ""]], window_s=0.0),
], ids=["untraced", "no-kernel-events", "no-device"])
def test_nothing_to_read(ctx):
    assert read(ctx) is None
