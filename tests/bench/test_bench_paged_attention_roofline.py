"""The reader of ``paged_attention_roofline.serve`` on small synthetic
records, against a hand count."""

import os
from types import SimpleNamespace

import pytest

from bench import costs, layer, spec

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
read = spec.load_reader(os.path.join(REPO, "bench", "metrics"), "paged_attention_roofline.serve")

ARCH = {"n_heads": 2, "n_kv_heads": 1, "head_dim": 4}
PEAK = {"int8_ops_per_s": 4e9, "hbm_bytes_per_s": 1e9}


def _ctx(ops, decodes):
    ctx = SimpleNamespace(arch=ARCH, peak=PEAK, costs=costs, decodes=decodes,
                          trace={"window_s": 1.0,
                                 "record": {"device": "/device:TPU:0", "ops": ops, "host": []}})
    ctx.kernel_time = lambda prefixes: layer.Context.kernel_time(ctx, prefixes)
    return ctx


def test_least_time_of_the_calls_over_their_device_time():
    # one lane at 10 key rows: ops 4*2*4*10 = 320 (80 ns), bytes 2*1*4*10 K/V
    # + 2*1*2*4 q in, out = 96 (96 ns); two lanes at 30 rows in all: ops 960
    # (240 ns), bytes 240 + 32 = 272 (272 ns).  Bytes bind both: a mean call
    # needs 184 ns, and two calls ran 736 ns of device time.
    ops = [["%paged_attention_pallas.2 = s8[32,16,128] custom-call(s32[32] %l)", 0, 300, ""],
           ["%fusion.4 = s8[32,2048] fusion(s8[32,16,128] %paged_attention_pallas.2)",
            300, 500, ""],
           ["%paged_attention_pallas.9 = s8[32,16,128] custom-call(s32[32] %l)", 900, 436, ""]]
    assert read(_ctx(ops, [(1, 10), (2, 30)])) == pytest.approx(100.0 * 184 * 2 / 736)


@pytest.mark.parametrize("ctx", [
    SimpleNamespace(trace=None, decodes=[(1, 10)], kernel_time=lambda prefixes: (0.0, [])),
    # the XLA runner: the gather and its widening carry no kernel name
    _ctx([["%multiply_reduce_fusion = s32[32,128,16,16] fusion(s8[4097,16,16,128] %p)",
           0, 50, ""]], [(1, 10)]),
    _ctx([["%paged_attention_pallas.1 = s8[32,16,128] custom-call()", 0, 10, ""]], []),
], ids=["untraced", "no-kernel-events", "no-decode-steps"])
def test_nothing_to_read(ctx):
    assert read(ctx) is None
