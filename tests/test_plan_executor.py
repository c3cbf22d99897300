"""End-to-end tests of the executable deployment flow.

compile -> plan -> execute: the plan executor must be *bit-exact* against
the model-level ``forward_w8a8`` path (the integer arithmetic is fully
deterministic, so any mismatch is a lowering/dispatch bug, not numerics);
the plan must round-trip through its serialized form; and every scheduled
node's engine assignment must agree with ``ita_supports``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import heterogeneous as het
from repro.deploy import api
from repro.deploy.executor import _run_node, bind_encoder_weights, bind_plan, execute
from repro.deploy.lowering import build_runtime_encoder_graph, lower, schedule
from repro.deploy.patterns import deploy_pipeline, node_opdesc
from repro.deploy.plan import DeploymentPlan, PlanNode
from repro.models import encoder as EN
from repro.models import transformer as T


def plan_and_bind(cfg, seq_len=None, *, params=None, head_by_head=False,
                  backend=het.Backend.W8A8):
    """compile() + bind, unpacked to (plan, weights, qp) for these tests."""
    m = api.compile(cfg, backend=backend, seq_len=seq_len,
                    head_by_head=head_by_head, use_cache=False)
    weights, qp = m.bind(params=params)
    return m.artifact, weights, qp


@pytest.fixture(scope="module")
def mobilebert_setup():
    cfg = reduced(get_config("mobilebert"))
    key = jax.random.PRNGKey(2)
    params = EN.init_params(cfg, key)
    qp = EN.quantize_params(cfg, params)
    batch = {"tokens": jax.random.randint(key, (2, 64), 0, cfg.vocab, jnp.int32)}
    return cfg, params, qp, batch


class TestBitExactness:
    def test_w8a8_backend_matches_model(self, mobilebert_setup):
        cfg, params, qp, batch = mobilebert_setup
        plan, weights, _ = plan_and_bind(cfg, seq_len=64, params=params)
        ref = EN.forward_w8a8(cfg, qp, batch)
        got = execute(plan, weights, batch, backend=het.Backend.W8A8)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_ita_backend_matches_model(self, mobilebert_setup):
        """Pallas kernels (interpret on CPU) produce the identical ints."""
        cfg, params, qp, batch = mobilebert_setup
        plan, weights, _ = plan_and_bind(cfg, seq_len=64, params=params)
        ref = EN.forward_w8a8(cfg, qp, batch)
        got = execute(plan, weights, batch, backend=het.Backend.ITA)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_head_by_head_matches_model_schedule(self, mobilebert_setup):
        """The per-head split plan reproduces the model's ita_head_by_head
        branch exactly (int32 partial accumulation is associative)."""
        cfg, params, qp, batch = mobilebert_setup
        plan, weights, _ = plan_and_bind(cfg, seq_len=64, params=params,
                                         head_by_head=True)
        ref = EN.forward_w8a8(cfg.replace(ita_head_by_head=True), qp, batch)
        got = execute(plan, weights, batch, backend=het.Backend.W8A8)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_jitted_executor_and_features_output(self):
        """Patch-input encoder (no vocab): jitted plan == model features."""
        cfg = get_config("dinov2-small").replace(n_layers=1, n_patches=64, max_seq=64)
        key = jax.random.PRNGKey(3)
        params = EN.init_params(cfg, key)
        qp = EN.quantize_params(cfg, params)
        model = api.compile(cfg, seq_len=64, use_cache=False)
        session = model.session(1, params=params)  # jitted forward
        batch = {"patches": jax.random.randint(key, (1, 64, cfg.d_model), -64, 64, jnp.int8)}
        ref = EN.forward_w8a8(cfg, qp, batch)
        got = session.forward(batch)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


class TestPlanArtifact:
    def test_json_round_trip(self, mobilebert_setup):
        cfg, params, qp, batch = mobilebert_setup
        plan, weights, _ = plan_and_bind(cfg, seq_len=64, params=params)
        restored = DeploymentPlan.from_json(plan.to_json())
        assert restored == plan
        ref = execute(plan, weights, batch)
        got = execute(restored, weights, batch)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_plan_is_static_and_complete(self, mobilebert_setup):
        cfg, params, _, _ = mobilebert_setup
        plan, weights, _ = plan_and_bind(cfg, seq_len=64, params=params)
        plan.validate()
        # every accelerated geometry has a tiling solution
        for n in plan.nodes:
            if n.engine == "ita" and n.op in ("MatMul", "MHA", "MHAHead"):
                assert n.name in plan.tilings, n.name
        # every activation has a static offset; weights have none
        for t in plan.tensors.values():
            if t.weight:
                assert t.offset is None
                assert t.name in weights
        assert plan.memory_peak > 0

    def test_schedule_is_topological(self):
        cfg = reduced(get_config("whisper-tiny-encoder"))
        g = deploy_pipeline(build_runtime_encoder_graph(cfg, 64))
        order = schedule(g)
        assert len(order) == len(g.nodes)
        seen = set(g.inputs) | set(g.weights)
        for n in order:
            for t in n.inputs:
                assert t in seen, (n.name, t)
            seen.update(n.outputs)

    def test_schedule_duplicate_inputs_from_one_producer(self):
        """A node consuming the same tensor twice must still wait for ALL
        its producers (edge dedup regression)."""
        from repro.deploy.graph import Graph

        g = Graph()
        for t in ("in", "a", "b", "c"):
            g.add_tensor(t, (4,))
        g.inputs.append("in")
        g.add_node("LayerNorm", ["in"], ["b"], name="B", dims=(4,))
        g.add_node("LayerNorm", ["in"], ["a"], name="A", dims=(4,))
        g.add_node("Add", ["a", "a", "b"], ["c"], name="C", dims=(4,))
        order = [n.name for n in schedule(g)]
        assert order.index("C") > order.index("A")
        assert order.index("C") > order.index("B")


class TestEngineAssignment:
    @pytest.mark.parametrize("arch", ["mobilebert", "dinov2-small", "whisper-tiny-encoder"])
    def test_engines_agree_with_ita_supports(self, arch):
        """The plan's static engine column is exactly ita_supports."""
        cfg = get_config(arch)
        plan = lower(cfg, seq_len=min(cfg.max_seq, 128))
        for n in plan.nodes:
            want = "ita" if het.ita_supports(node_opdesc(n, plan.granule), plan.granule) \
                else "cluster"
            assert n.engine == want, (n.name, n.op, n.engine, want)

    def test_misaligned_head_dim_falls_back(self):
        """reduced() uses head_dim=32: MHA must land on the cluster."""
        cfg = reduced(get_config("mobilebert"))
        plan = lower(cfg, seq_len=64)
        mha = [n for n in plan.nodes if n.op == "MHA"]
        assert mha and all(n.engine == "cluster" for n in mha)
        # aligned GEMMs still accelerate
        assert any(n.engine == "ita" for n in plan.nodes if n.op == "MatMul")

    def test_full_head_dim_accelerates(self):
        cfg = get_config("mobilebert").replace(n_layers=1)
        plan = lower(cfg)
        mha = [n for n in plan.nodes if n.op == "MHA"]
        assert mha and all(n.engine == "ita" for n in mha)


class TestGemmActivations:
    """Satellite regression: the GEMM runner must execute every activation
    the plan vocabulary admits, and fail loudly on anything else — the old
    code silently mapped unknown activations to identity."""

    def _node_and_env(self, act):
        key = jax.random.PRNGKey(0)
        x = jax.random.randint(key, (1, 8, 64), -127, 128, jnp.int8)
        w = jax.random.randint(key, (64, 64), -127, 128, jnp.int8)
        node = PlanNode(
            name="g0", op="MatMul", kind="gemm", engine="cluster",
            inputs=("x", "w"), outputs=("y",),
            attrs={"dims": (8, 64, 64), "scales": (0.05, 0.01, 0.05),
                   "activation": act},
        )
        return node, {"x": x, "w": w}

    def test_relu_executes_relu(self):
        from repro.core.quant_linear import ACT_RELU, make_qlinear_params, qlinear_i8

        node, env = self._node_and_env("relu")
        got = _run_node(node, env, het.DEFAULT_TABLE, het.Backend.W8A8)
        want = qlinear_i8(env["x"], env["w"], None,
                          make_qlinear_params(0.05, 0.01, 0.05, ACT_RELU))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # and relu is genuinely not identity on this data
        iden, _ = self._node_and_env("identity")
        got_id = _run_node(iden, env, het.DEFAULT_TABLE, het.Backend.W8A8)
        assert not np.array_equal(np.asarray(got), np.asarray(got_id))

    def test_unknown_activation_raises(self):
        node, env = self._node_and_env("swish")
        with pytest.raises(NotImplementedError, match="swish"):
            _run_node(node, env, het.DEFAULT_TABLE, het.Backend.W8A8)


class TestDefaultTable:
    def test_populated_at_import(self):
        kinds = het.DEFAULT_TABLE.kinds()
        for kind in ("gemm", "mha", "softmax", "gelu", "layernorm", "add",
                     "headaccum", "embed", "classifier", "dequant"):
            assert kind in kinds, kind

    def test_ita_overrides_are_pallas(self):
        """ITA backend resolves to different callables than W8A8 for the
        accelerated kinds (Pallas vs XLA arithmetic)."""
        op = het.OpDesc("gemm", shapes=((128, 128), (128, 128)))
        _, fn_w8a8 = het.DEFAULT_TABLE.resolve(op, het.Backend.W8A8)
        _, fn_ita = het.DEFAULT_TABLE.resolve(op, het.Backend.ITA)
        assert fn_w8a8 is not fn_ita

    def test_float_backend_stays_on_cluster(self):
        op = het.OpDesc("gemm", shapes=((128, 128), (128, 128)))
        engine, _ = het.DEFAULT_TABLE.resolve(op, het.Backend.FLOAT)
        assert engine is het.Engine.CLUSTER


class TestWeightBinding:
    def test_all_plan_weights_bound(self, mobilebert_setup):
        cfg, params, qp, _ = mobilebert_setup
        plan = lower(cfg, seq_len=64)
        weights = bind_encoder_weights(plan, cfg, qp)
        assert set(weights) == set(plan.weight_names)
        # wq/wk/wv slices recompose the fused wqkv exactly
        h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        lp0 = jax.tree.map(lambda a: a[0], qp["layers"])
        fused = np.asarray(lp0["attn"]["wqkv"]["w_q"])
        cat = np.concatenate(
            [np.asarray(weights["l0_wq"]), np.asarray(weights["l0_wk"]),
             np.asarray(weights["l0_wv"])], axis=1)
        np.testing.assert_array_equal(cat, fused[:, : (h + 2 * hkv) * hd])


class TestBindOnce:
    def test_no_resolution_after_bind(self, monkeypatch):
        """The executor resolves each node's DispatchTable entry exactly
        once, at bind time — repeated execution never re-resolves."""
        from repro.deploy.executor import bind_decoder_weights, execute_prefill

        cfg = reduced(get_config("olmo-1b"))
        params = T.init_params(cfg, jax.random.PRNGKey(7))
        pair = api.compile(cfg, backend="w8a8", seq_len=8, max_len=24,
                           use_cache=False).artifact
        weights = bind_decoder_weights(pair.prefill, cfg,
                                       T.quantize_params(cfg, params))
        calls = []
        orig = het.DispatchTable.resolve

        def counting(self, op, backend):
            calls.append(op.kind)
            return orig(self, op, backend)

        monkeypatch.setattr(het.DispatchTable, "resolve", counting)
        program = bind_plan(pair.prefill, backend="w8a8")
        n_bind = len(calls)
        assert n_bind > 0
        # same plan object: bind is cached, no new resolution
        assert bind_plan(pair.prefill, backend="w8a8") is program
        toks = jax.random.randint(jax.random.PRNGKey(0), (1, 8), 0, cfg.vocab,
                                  jnp.int32)
        execute_prefill(pair, weights, {"tokens": toks}, backend="w8a8")
        execute_prefill(pair, weights, {"tokens": toks}, backend="w8a8")
        assert len(calls) == n_bind, (
            f"execute() re-resolved {len(calls) - n_bind} entries after bind")

    def test_run_node_shim_still_single_shot(self):
        # _run_node survives as the compile-and-run helper tests use
        from repro.deploy.executor import _run_node  # noqa: F401
