"""Heterogeneous dispatch: the runtime 'ITA or cluster' decision."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import heterogeneous as het


def _table():
    t = het.DispatchTable()
    t.register("gemm", het.Engine.ACCELERATOR, lambda x, w: ("ita", x @ w))
    t.register("gemm", het.Engine.CLUSTER, lambda x, w: ("cluster", x @ w))
    t.register("layernorm", het.Engine.CLUSTER, lambda x: ("cluster", x))
    return t


class TestSupportPredicate:
    def test_aligned_int8_gemm_supported(self):
        op = het.OpDesc("gemm", shapes=((128, 256), (256, 64)))
        assert het.ita_supports(op)

    def test_misaligned_rejected(self):
        op = het.OpDesc("gemm", shapes=((100, 256), (256, 60)))
        assert not het.ita_supports(op)

    def test_float_rejected(self):
        op = het.OpDesc("gemm", shapes=((128, 128),), dtype="float32")
        assert not het.ita_supports(op)

    def test_unsupported_kind_rejected(self):
        assert not het.ita_supports(het.OpDesc("layernorm", shapes=((128, 128),)))

    def test_tpu_granule_stricter(self):
        op = het.OpDesc("gemm", shapes=((192, 192),))
        assert het.ita_supports(op, granule=het.ITA_GRANULE)
        assert not het.ita_supports(op, granule=het.TPU_GRANULE)


class TestDispatch:
    def test_supported_goes_to_accelerator(self):
        t = _table()
        op = het.OpDesc("gemm", shapes=((128, 128), (128, 128)))
        engine, fn = t.resolve(op, het.Backend.W8A8)
        assert engine is het.Engine.ACCELERATOR
        tag, _ = fn(jnp.zeros((128, 128)), jnp.zeros((128, 128)))
        assert tag == "ita"

    def test_misaligned_falls_back(self):
        t = _table()
        op = het.OpDesc("gemm", shapes=((100, 100), (100, 100)))
        engine, _ = t.resolve(op, het.Backend.W8A8)
        assert engine is het.Engine.CLUSTER

    def test_float_backend_always_cluster(self):
        t = _table()
        op = het.OpDesc("gemm", shapes=((128, 128), (128, 128)))
        engine, _ = t.resolve(op, het.Backend.FLOAT)
        assert engine is het.Engine.CLUSTER

    def test_cluster_only_op(self):
        t = _table()
        engine, _ = t.resolve(het.OpDesc("layernorm", shapes=((128, 128),)), het.Backend.W8A8)
        assert engine is het.Engine.CLUSTER


class TestSilumulDispatch:
    """SwiGLU's gated product stays a cluster op on every backend; under
    ``ita`` the cluster slot runs the Pallas kernel, elsewhere the XLA
    runner."""

    @pytest.mark.parametrize("backend", list(het.Backend), ids=lambda b: b.value)
    def test_runner_per_backend(self, backend):
        from repro.kernels.silumul import silumul, silumul_ref

        op = het.OpDesc("silumul", shapes=((32, 128, 8192), (32, 128, 8192)))
        engine, fn = het.DEFAULT_TABLE.resolve(op, backend)
        assert engine is het.Engine.CLUSTER
        assert fn is (silumul if backend is het.Backend.ITA else silumul_ref)
        assert "silumul" not in het.ACCEL_KINDS

    def test_ita_serves_the_w8a8_greedy_tokens(self):
        """A tiny SwiGLU decoder served through the paged engine (chunked
        prompts, decode steps) gives the same greedy tokens on both
        backends."""
        import jax

        from repro.configs import get_config, reduced
        from repro.deploy import api
        from repro.deploy.engine import Engine
        from repro.models import transformer as T

        cfg = reduced(get_config("olmo-1b"))
        assert cfg.mlp == "swiglu"
        params = T.init_params(cfg, jax.random.PRNGKey(5))
        key = jax.random.PRNGKey(11)
        prompts = [[int(t) for t in jax.random.randint(jax.random.fold_in(key, i), (n,), 0,
                                                        cfg.vocab)]
                   for i, n in enumerate((8, 19, 12))]

        def serve(backend):
            model = api.compile(cfg, backend=backend, seq_len=8, max_len=40, use_cache=False,
                                kv_block_size=8, kv_blocks=16)
            engine = Engine(model, 2, params=params)
            handles = [engine.submit(p, 4) for p in prompts]
            engine.run_until_idle(max_steps=200)
            return [h.tokens for h in handles]

        w8a8 = serve("w8a8")
        assert all(len(t) == 4 for t in w8a8)
        assert serve("ita") == w8a8


class TestPagedAttentionDispatch:
    """``attn_paged`` stays a cluster op on every backend; under ``ita`` a
    decode step (one query row per lane) runs the Pallas paged-decode
    kernel, and a prefill chunk, ``w8a8`` and ``float`` the XLA runner."""

    @pytest.mark.parametrize("backend,rows", [
        (het.Backend.ITA, 1), (het.Backend.ITA, 128), (het.Backend.W8A8, 1),
        (het.Backend.FLOAT, 1),
    ], ids=["ita-decode", "ita-chunk", "w8a8-decode", "float-decode"])
    def test_runner_per_backend_and_query_rows(self, backend, rows):
        from repro.kernels.paged_attention import paged_attention, paged_attention_ref

        op = het.OpDesc("attn_paged", shapes=((rows, 16 * 128),))
        engine, fn = het.DEFAULT_TABLE.resolve(op, backend)
        assert engine is het.Engine.CLUSTER
        kernel = backend is het.Backend.ITA and rows == 1
        assert fn is (paged_attention if kernel else paged_attention_ref)

    def test_ita_serves_w8a8_greedy_tokens_through_shared_prefixes(self):
        """The tiny paged SwiGLU decoder with the prefix cache on: a repeated
        prompt attaches its cached blocks, and its first decode append into
        the shared partial block forks a copy; ``ita``'s decode kernel reads
        the forked and shared blocks and gives ``w8a8``'s greedy tokens."""
        import jax

        from repro.configs import get_config, reduced
        from repro.deploy import api
        from repro.deploy.engine import Engine
        from repro.models import transformer as T

        cfg = reduced(get_config("olmo-1b"))
        params = T.init_params(cfg, jax.random.PRNGKey(5))
        key = jax.random.PRNGKey(13)
        base = [int(t) for t in jax.random.randint(key, (19,), 0, cfg.vocab)]
        longer = base + [int(t) for t in jax.random.randint(jax.random.fold_in(key, 1),
                                                            (5,), 0, cfg.vocab)]

        def serve(backend):
            model = api.compile(cfg, backend=backend, seq_len=8, max_len=40, use_cache=False,
                                kv_block_size=8, kv_blocks=24, prefix_cache=True)
            engine = Engine(model, 2, params=params)
            first = engine.submit(base, 6)
            engine.run_until_idle(max_steps=200)
            later = [engine.submit(base, 6), engine.submit(longer, 4)]
            engine.run_until_idle(max_steps=200)
            assert engine.stats.prefix_hits == 2 and engine.stats.cow_copies >= 1
            return [h.tokens for h in (first, *later)]

        w8a8 = serve("w8a8")
        assert [len(t) for t in w8a8] == [6, 6, 4]
        assert serve("ita") == w8a8
