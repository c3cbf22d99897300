"""The expert-layer cell's pieces on the CPU: its costs against hand counts,
its three per-layer readers, and its driver's ``correct`` on a tiny cell,
with the control and an altered token failing it.

The tiny cell is Qwen1.5-MoE's configuration file cut down, in a root of its
own built on ``conftest.make_root``.
"""

import json
import os
from types import SimpleNamespace

import pytest

from bench import layer, run, spec
from bench.reference import moe as R
from conftest import CPU, TINY_SERVE, make_root

ARCH = dict(n_layers=2, d_model=4, n_heads=2, n_kv_heads=2, head_dim=2, vocab=10,
            n_experts=6, top_k=2, d_ff_expert=3, n_shared_experts=2)
PEAK = {"int8_ops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
CELL = "tiny.moe"
METRICS = ("expert_gemm_roofline.moe", "expert_gemm_share.moe", "step_mfu.moe")
REAL_CELL = "qwen1.5-moe-a2.7b.docqa-sat"
TINY_MOE = dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=4, head_dim=32, d_ff=256,
                vocab=512, n_experts=8, top_k=2, d_ff_expert=128, n_shared_experts=2,
                max_seq=128)


def _read(name):
    return spec.load_reader(os.path.join(spec.BENCH_DIR, "metrics"), name)


# -- costs --------------------------------------------------------------------

def test_costs_against_hand_counts():
    from bench import costs_moe as C

    # a layer, one token: q/k/v 4 x 12, o 4 x 4, router 4 x 6, two experts
    # 2 x 3 x 4 x 3, shared (two of 3 fused) 3 x 4 x 6, gate 4
    assert C.expert_macs(ARCH) == 72
    assert C.layer_macs(ARCH) == 48 + 16 + 24 + 72 + 72 + 4
    assert C.token_ops(ARCH) == 2 * 2 * 236
    assert C.head_ops(ARCH) == 2 * 4 * 10
    # rows 0, 1 attend 1 and 2 keys: 4 x 2 layers x 2 heads x 2 dims = 32 a key
    assert C.chunk_ops(ARCH, 0, 2) == 2 * 944 + 32 * 3
    assert C.chunk_ops(ARCH, 0, 2, first_new=1) == 944 + 32 * 2
    assert C.chunk_ops(ARCH, 0, 2, first_new=2) == 0.0
    assert C.decode_ops(ARCH, lanes=3, key_rows=7) == 3 * 944 + 32 * 7
    ops, nbytes = C.expert_gemm_cost(ARCH, rows=10, groups=4)
    assert ops == 2 * 3 * 4 * 3 * 10 and nbytes == 3 * 4 * 3 * 4 + 2 * 4 * 10
    assert C.least_time(ops, nbytes, PEAK) == pytest.approx(max(ops / 100.0, nbytes / 10.0))


# -- readers on synthetic records ---------------------------------------------

def _ctx(ops=(), window_s=200e-9, counters=None, decodes=(), chunks=(), arch=ARCH):
    ctx = SimpleNamespace(
        trace={"window_s": window_s, "record": {"device": "/device:TPU:0", "ops": list(ops),
                                                "host": []}},
        counters=counters or {}, decodes=list(decodes), chunks=list(chunks), arch=arch,
        serve={"seq_len": 2}, peak=PEAK, window_s=2.0)
    ctx.kernel_time = lambda prefixes: layer.Context.kernel_time(ctx, prefixes)
    return ctx


KERNEL_OPS = [["%expert_gemm_pallas.3 = s8[64,3] custom-call(s8[64,4] %x)", 0, 40, ""],
              ["%fusion.7 = s8[64,3] fusion(s8[64,3] %expert_gemm_pallas.3)", 40, 50, ""],
              ["%expert_gemm_pallas.5 = s8[64,4] custom-call(s8[64,3] %a)", 100, 60, ""]]


def test_share_is_kernel_time_over_the_traced_window():
    assert _read("expert_gemm_share.moe")(_ctx(KERNEL_OPS)) == pytest.approx(50.0)


def test_roofline_is_least_time_over_kernel_time():
    # 10 rows, 4 groups: 720 ops (7.2 s at 100/s) against 224 bytes (22.4 s
    # at 10/s): bytes bind; 100 ns of kernel time
    ctx = _ctx(KERNEL_OPS, counters={"expert_rows": 10, "expert_groups": 4},
               arch=dict(ARCH))
    ctx.peak = {"int8_ops_per_s": 100e9, "hbm_bytes_per_s": 10e9}
    assert _read("expert_gemm_roofline.moe")(ctx) == pytest.approx(100.0 * 22.4e-9 / 100e-9)


def test_step_mfu_counts_prompt_rows_decode_lanes_and_sampled_heads():
    from bench import costs_moe as C

    ctx = _ctx(chunks=[[(0, 0), (2, 3)]], decodes=[(3, 7)],
               counters={"tokens_generated": 4})
    want = (C.chunk_ops(ARCH, 0, 2) + C.chunk_ops(ARCH, 2, 2, first_new=3)
            + C.decode_ops(ARCH, 3, 7) + 4 * C.head_ops(ARCH))
    assert _read("step_mfu.moe")(ctx) == pytest.approx(100.0 * want / 2.0 / 100.0)


@pytest.mark.parametrize("name,ctx", [
    ("expert_gemm_share.moe", SimpleNamespace(trace=None, kernel_time=lambda p: (0.0, []))),
    ("expert_gemm_share.moe", _ctx([["%fusion.2 = s8[8] fusion()", 0, 5, ""]])),
    ("expert_gemm_roofline.moe", _ctx(KERNEL_OPS)),  # no expert counters
    ("expert_gemm_roofline.moe", _ctx(counters={"expert_rows": 4, "expert_groups": 2})),
    ("step_mfu.moe", _ctx(decodes=[(3, 7)], arch={"n_experts": 0})),  # a dense decoder
    ("step_mfu.moe", _ctx()),
], ids=["share-untraced", "share-no-kernel", "roofline-no-counters", "roofline-no-kernel",
        "mfu-dense", "mfu-no-steps"])
def test_nothing_to_read(name, ctx):
    assert _read(name)(ctx) is None


# -- the driver on a tiny cell ------------------------------------------------

@pytest.fixture(scope="module")
def moe_root(tmp_path_factory):
    root = make_root(str(tmp_path_factory.mktemp("bench_moe_root")))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": CELL, "config": "tiny-moe", "traffic": "tiny-open",
                               "chips": 1, "why": "t"})
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"] for m in json.load(f)["per_layer"]
                  if REAL_CELL in m.get("workloads", ())}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in METRICS:
            m["workloads"] = [CELL]
        elif m["name"] == "output_tokens_per_s" or m["name"] in listed:
            m["workloads"].append(CELL)
    with open(os.path.join(spec.BENCH_DIR, "configs", "qwen1.5-moe-a2.7b.json")) as f:
        cfg = json.load(f)
    cfg["arch"].update(TINY_MOE)
    cfg["serve"].update(TINY_SERVE)
    for rel, obj in (("BENCHMARK.json", bench), ("bench/configs/tiny-moe.json", cfg)):
        with open(os.path.join(root, rel), "w") as f:
            json.dump(obj, f)
    return root


def _run(root, name, seed, hooks=None, seconds=3.0, trace=False):
    cell = spec.load_cell(name, root=root)
    rec = {}
    line = run.run(cell, seed, seconds, trace, CPU, hooks=dict(hooks or {}, record=rec.update),
                   peaks=spec.load_peaks("TPU v5 lite", root=root))
    return cell, line, rec


@pytest.fixture(scope="module")
def served(moe_root):
    return _run(moe_root, CELL, 2**31 + 77)


def test_sound_run_is_correct_and_counts_experts(served):
    _cell, line, rec = served
    assert line["correct"] is True
    assert line["checks"]["max_logit_gap"]["value"] == 0.0
    assert line["checks"]["requests_checked"]["value"] >= 2
    assert set(line["metrics"]) == {"output_tokens_per_s", "setup_s"}
    c = rec["counters"]
    assert c["expert_rows"] > 0 and 0 < c["expert_groups"] <= c["expert_rows"]
    assert c["requests_done"] >= 0


def test_the_int4_control_is_not_correct(served):
    from bench import control, control_moe

    cell, _line, rec = served
    reading = control_moe.control_reading(cell, 2**31 + 77, rec["checked"])
    assert reading > rec["checks"][0][3]
    assert spec.judge(control.control_checks(rec["checks"], reading)) is False


def test_a_token_altered_where_it_is_sampled_is_not_correct(moe_root):
    def alter(engine):
        inner = engine.sampling

        class Altered:
            vocab = inner.vocab

            def __call__(self, row, rid, index):
                tok = inner(row, rid, index)
                return (tok + 1) % self.vocab if index == 1 else tok

        engine.sampling = Altered()

    _cell, line, _rec = _run(moe_root, CELL, 9, hooks={"engine": alter})
    assert line["correct"] is False
    assert line["checks"]["max_logit_gap"]["value"] > 0


def test_traced_run_reports_the_step_share(moe_root):
    """On the CPU no trace is taken, so of the cell's metrics only those fed
    by the probe, the counters and the host clock have something to read."""
    _cell, line, _rec = _run(moe_root, CELL, 17, trace=True)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"step_mfu.moe", "decode_occupancy", "itl_p95_ms.serve"}
    assert 0 < line["metrics"]["step_mfu.moe"]["value"] <= 100
    assert 0 < line["metrics"]["decode_occupancy"]["value"] <= 100


def test_readers_find_nothing_on_the_encoder_cell(tiny_root):
    """``conftest.make_root`` maps these metrics onto its encoder cell."""
    cell, line, _rec = _run(tiny_root, "tiny.enc", 2**32 + 11, seconds=1.0, trace=True)
    assert {m["name"] for m in cell.per_layer} >= set(METRICS)
    assert not set(line["metrics"]) & set(METRICS)
