"""Tests for the deployment flow: graph passes, tiler, memory planner.

Hypothesis property tests live in ``test_properties.py`` behind a
``pytest.importorskip`` guard, so this module collects without the
``[test]`` extra.
"""

import pytest

from repro.configs import get_config
from repro.core import heterogeneous as het
from repro.deploy import costmodel, memory, patterns, tiler
from repro.deploy.graph import build_encoder_graph


def _mobilebert_graph():
    return build_encoder_graph(get_config("mobilebert"), seq_len=128)


class TestGraph:
    def test_build_validates(self):
        g = _mobilebert_graph()
        cfg = get_config("mobilebert")
        # bottleneck in/out+add (3) + attention chain (9) + n_ffn x 5
        per_layer = (3 if cfg.d_bottleneck else 0) + 9 + 5 * cfg.n_ffn
        assert len(g.nodes) == cfg.n_layers * per_layer, len(g.nodes)
        assert g.validate()

    def test_fuse_mha(self):
        g = patterns.fuse_mha(_mobilebert_graph())
        mha = [n for n in g.nodes if n.op == "MHA"]
        assert len(mha) == 24
        assert all(n.attrs["heads"] == 4 for n in mha)

    def test_head_split_inserts_accum(self):
        g = patterns.split_heads(patterns.fuse_mha(_mobilebert_graph()))
        heads = [n for n in g.nodes if n.op == "MHAHead"]
        acc = [n for n in g.nodes if n.op == "HeadAccum"]
        assert len(heads) == 24 * 4 and len(acc) == 24

    def test_engine_mapping(self):
        g = patterns.deploy_pipeline(_mobilebert_graph())
        engines = {n.op: n.engine for n in g.nodes}
        assert engines["MHAHead"] == "ita"
        assert engines["LayerNorm"] == "cluster"
        assert engines["HeadAccum"] == "cluster"
        assert engines["Add"] == "cluster"
        # GELU fused into the GEMM epilogue
        assert not any(n.op == "GELU" for n in g.nodes)
        assert any(n.attrs.get("activation") == "gelu" for n in g.nodes)


class TestTiler:
    @pytest.mark.parametrize("m,n,k", [(128, 256, 128), (512, 512, 512), (241, 384, 384),
                                       (64, 64, 64), (4096, 1536, 384)])
    def test_gemm_tiling_fits_and_aligned(self, m, n, k):
        t = tiler.solve_gemm_tiling(m, n, k)
        assert t.l1_bytes <= tiler.ITA_L1_BYTES
        for d in (t.tile_m, t.tile_n, t.tile_k):
            assert d % het.ITA_GRANULE == 0 and d <= tiler.ITA_MAX_TILE

    def test_tiles_cover_matrix(self):
        t = tiler.solve_gemm_tiling(241, 384, 384)
        import math

        assert math.ceil(241 / t.tile_m) * t.tile_m >= 241
        assert t.padded_ops >= t.useful_ops

    def test_mha_tiling(self):
        t = tiler.solve_mha_tiling(512, 64)
        assert t.l1_bytes <= tiler.ITA_L1_BYTES
        assert t.tile_s % het.ITA_GRANULE == 0

    def test_tpu_mode(self):
        t = tiler.solve_gemm_tiling(
            4096, 8192, 8192, granule=het.TPU_GRANULE, budget=tiler.TPU_VMEM_BYTES
        )
        assert t.tile_m % 128 == 0 and t.l1_bytes <= tiler.TPU_VMEM_BYTES


class TestMemoryPlanner:
    def test_no_overlap_mobilebert(self):
        g = patterns.deploy_pipeline(_mobilebert_graph())
        plan = memory.plan_memory(g)
        assert plan.check_no_overlap()
        lb = memory.peak_lower_bound(g)
        assert plan.peak >= lb
        assert plan.peak <= 4 * lb  # greedy best-fit stays near the bound


class TestCostModelAnchors:
    """The calibrated model must reproduce the paper's microbenchmarks."""

    def test_gemm_utilization_851(self):
        u = costmodel.gemm_util(512, 512, 512)
        assert abs(u - 0.851) < 0.01, u

    def test_peak_throughput(self):
        hw = costmodel.HW
        peak = hw.ita_ops_per_cyc * hw.freq_hz / 1e9
        assert abs(peak - 870.4) < 1.0
        assert abs(peak * 0.851 - 741) < 6  # paper: 741 GOp/s

    def test_standalone_beats_integrated(self):
        u_int = costmodel.gemm_util(512, 512, 512)
        u_alone = costmodel.gemm_util(512, 512, 512, standalone=True)
        assert u_alone > u_int

    def test_cluster_only_rate(self):
        g = patterns.deploy_pipeline(_mobilebert_graph())
        c = costmodel.network_cost_cluster_only(g)
        assert abs(c.gop_per_s - 0.74) < 0.01
        assert abs(c.gop_per_j - 28.5) < 1.0  # paper: 28.9 GOp/J

    def test_hw_targets_single_source(self):
        from benchmarks import roofline
        assert roofline.PEAK_FLOPS == costmodel.TPU_V5E.peak_flops
        assert roofline.HBM_BW == costmodel.TPU_V5E.hbm_bw
        ita = costmodel.hw_target("ita")
        assert ita.peak_flops == costmodel.HW.ita_ops_per_cyc * costmodel.HW.freq_hz
        with pytest.raises(ValueError, match="unknown hw target"):
            costmodel.hw_target("gpu")
