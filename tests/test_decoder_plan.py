"""Decoder deployment plans: linked prefill/decode schedules + KV region.

Acceptance contract (ISSUE 2): plan-executed decoder inference is
*bit-exact* against ``prefill_w8a8`` + chained ``decode_step_w8a8`` on the
same quantized params — fused-vs-sliced QKV, GQA, RoPE — on both backends;
the two schedules share one statically planned persistent KV-cache region;
and engine placement follows ``ita_supports`` (prefill GEMMs accelerate,
M=1 decode GEMVs fall to the cluster).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.configs.base import ArchConfig
from repro.core import heterogeneous as het
from repro.deploy import api
from repro.deploy.executor import (
    execute_decode,
    execute_decode_paged,
    execute_prefill,
    execute_prefill_paged,
)
from repro.deploy.lowering import lower, lower_decoder
from repro.deploy.patterns import node_opdesc
from repro.deploy.plan import DecoderPlanPair
from repro.models import transformer as T


def plan_and_bind_decoder(cfg, seq_len=None, *, max_len=None, params=None,
                          backend=het.Backend.W8A8):
    """compile() + bind, unpacked to (pair, weights, qp) for these tests."""
    m = api.compile(cfg, backend=backend, seq_len=seq_len, max_len=max_len,
                    use_cache=False)
    weights, qp = m.bind(params=params)
    return m.artifact, weights, qp

SEQ, GEN = 16, 3
MAX_LEN = SEQ + GEN + 1


@pytest.fixture(scope="module")
def olmo_setup():
    """reduced olmo-1b: GQA (4 q / 2 kv heads), RoPE, SwiGLU,
    non-parametric LN, tied embeddings."""
    cfg = reduced(get_config("olmo-1b"))
    key = jax.random.PRNGKey(7)
    params = T.init_params(cfg, key)
    pair, weights, qp = plan_and_bind_decoder(cfg, SEQ, max_len=MAX_LEN, params=params)
    batch = {"tokens": jax.random.randint(key, (2, SEQ), 0, cfg.vocab, jnp.int32)}
    return cfg, pair, weights, qp, batch


def _assert_chain_bit_exact(cfg, pair, weights, qp, batch, backend, steps=GEN):
    """Prefill then `steps` chained decode steps, plan vs model, all exact."""
    logits, cache = execute_prefill(pair, weights, batch, backend=backend)
    ref_logits, ref_cache = T.prefill_w8a8(cfg, qp, batch, pair.max_len)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))
    np.testing.assert_array_equal(np.asarray(cache["k"]), np.asarray(ref_cache["k"]))
    np.testing.assert_array_equal(np.asarray(cache["v"]), np.asarray(ref_cache["v"]))
    tok = jnp.argmax(ref_logits[:, -1:], axis=-1).astype(jnp.int32)
    for _ in range(steps):
        logits, cache = execute_decode(pair, weights, cache, tok, backend=backend)
        ref_logits, ref_cache = T.decode_step_w8a8(cfg, qp, ref_cache, tok)
        np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))
        np.testing.assert_array_equal(np.asarray(cache["k"]), np.asarray(ref_cache["k"]))
        np.testing.assert_array_equal(np.asarray(cache["v"]), np.asarray(ref_cache["v"]))
        assert int(cache["len"]) == int(ref_cache["len"])
        tok = jnp.argmax(ref_logits[:, -1:], axis=-1).astype(jnp.int32)


class TestBitExactness:
    def test_w8a8_backend_matches_model_chain(self, olmo_setup):
        cfg, pair, weights, qp, batch = olmo_setup
        _assert_chain_bit_exact(cfg, pair, weights, qp, batch, het.Backend.W8A8)

    def test_ita_backend_matches_model_chain(self):
        """Pallas kernels (interpret on CPU) on the prefill GEMMs produce
        the identical ints through the whole prefill+decode trajectory."""
        cfg = reduced(get_config("olmo-1b"))
        pair, weights, qp = plan_and_bind_decoder(
            cfg, SEQ, max_len=MAX_LEN, backend=het.Backend.ITA
        )
        key = jax.random.PRNGKey(3)
        batch = {"tokens": jax.random.randint(key, (1, SEQ), 0, cfg.vocab, jnp.int32)}
        _assert_chain_bit_exact(cfg, pair, weights, qp, batch, het.Backend.ITA, steps=2)

    def test_jitted_executors(self, olmo_setup):
        """The jit-compiled closures produce the same ints as eager."""
        cfg, pair, weights, qp, batch = olmo_setup
        prefill_fn = jax.jit(lambda w, b: execute_prefill(pair, w, b))
        decode_fn = jax.jit(lambda w, c, t: execute_decode(pair, w, c, t))
        logits, cache = prefill_fn(weights, batch)
        ref_logits, ref_cache = T.prefill_w8a8(cfg, qp, batch, pair.max_len)
        np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))
        tok = jnp.argmax(ref_logits[:, -1:], axis=-1).astype(jnp.int32)
        logits, cache = decode_fn(weights, cache, tok)
        ref_logits, _ = T.decode_step_w8a8(cfg, qp, ref_cache, tok)
        np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))

    @pytest.mark.parametrize("kw", [
        dict(qkv_bias=True, mlp="gelu", norm="layernorm", tie_embeddings=False),
        dict(mlp="swiglu", norm="rmsnorm", tie_embeddings=True, rope=False),
    ], ids=["qkv-bias-gelu-untied", "rmsnorm-norope-tied"])
    def test_config_variants(self, kw):
        """Biased QKV slicing, fused-GELU MLP, untied LM head, no-RoPE."""
        cfg = ArchConfig(name="variant", family="dense", n_layers=2, d_model=128,
                         n_heads=4, n_kv_heads=2, head_dim=32, d_ff=256, vocab=512,
                         max_seq=64, **kw)
        pair, weights, qp = plan_and_bind_decoder(cfg, 12, max_len=16)
        key = jax.random.PRNGKey(1)
        batch = {"tokens": jax.random.randint(key, (2, 12), 0, cfg.vocab, jnp.int32)}
        _assert_chain_bit_exact(cfg, pair, weights, qp, batch, het.Backend.W8A8, steps=2)


# -- the session's jitted steps against an eager run of the same plan -------

JIT_SEQ, JIT_MAX, JIT_BLOCK, JIT_BLOCKS = 8, 24, 4, 14


def _eager_step(sess, step):
    """The executor call behind one session step, run outside any jit."""
    pair, be = sess._pair, sess.backend
    if not pair.paged:
        if step == "prefill":
            return lambda w, b: execute_prefill(pair, w, b, backend=be)
        return lambda w, c, t, p: execute_decode(pair, w, c, t, pos=p, backend=be)
    fn = execute_prefill_paged if step == "prefill" else execute_decode_paged

    def run(*args):
        *args, acc = args
        logits, pool, n = fn(pair, *args, backend=be)
        return logits, pool, None if n is None else acc + n

    return run


def _hook(sess, step):
    """Run every call of one session step eagerly too, before the jitted
    step (which may consume its arguments); returns the (jitted, eager)
    result pairs as they come."""
    if step == "decode":
        attr = "_decode_fn"
    else:
        attr = "_chunk_fn" if sess.paged else "_prefill_fn"
    jitted, eager, calls = getattr(sess, attr), _eager_step(sess, step), []

    def both(*args):
        want = eager(*args)
        calls.append((jitted(*args), want))
        return calls[-1][0]

    setattr(sess, attr, both)
    return calls


def _drive_and_compare(sess, cfg, step):
    """Prefill both slots (paged: then a second chunk of slot 0, slot 1
    parked on the scratch table), then two decode steps (paged: the
    second with slot 1 inactive); the hooked step's jitted and eager
    results must agree leaf for leaf."""
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, JIT_SEQ), 0, cfg.vocab,
                              jnp.int32)
    calls = _hook(sess, step) if step == "prefill" else None
    logits = sess.prefill(toks)
    if sess.paged:
        logits = sess.prefill_chunks({0: (toks[1:], JIT_SEQ)})
    if step == "decode":
        calls = _hook(sess, step)
    for active in ([True, True], [True, False]):
        tok = jnp.argmax(logits[:, -1:, : cfg.vocab], axis=-1).astype(jnp.int32)
        logits = sess.decode(tok, active=active if sess.paged else None)
    assert len(calls) == (2 if sess.paged or step == "decode" else 1)
    for got, want in calls:
        got, want = jax.tree.leaves(got), jax.tree.leaves(want)
        assert len(got) == len(want)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


class TestSessionStepsMatchEager:
    """The session jits each whole chunk or decode step; the executor run
    eagerly on the same arguments gives the same ints: logits, KV state,
    and an expert layer's counters."""

    @pytest.mark.parametrize("step", ["prefill", "decode"],
                             ids=["prefill-or-chunk", "decode"])
    @pytest.mark.parametrize("region", ["dense", "paged"])
    @pytest.mark.parametrize("backend", ["w8a8", "ita"])
    def test_dense_decoder(self, backend, region, step):
        cfg = reduced(get_config("olmo-1b"))
        kw = (dict(kv_block_size=JIT_BLOCK, kv_blocks=JIT_BLOCKS)
              if region == "paged" else {})
        model = api.compile(cfg, backend=backend, seq_len=JIT_SEQ, max_len=JIT_MAX,
                            use_cache=False, **kw)
        _drive_and_compare(model.session(2, key=jax.random.PRNGKey(7)), cfg, step)

    @pytest.mark.parametrize("step", ["prefill", "decode"],
                             ids=["prefill-or-chunk", "decode"])
    @pytest.mark.parametrize("backend", ["w8a8", "ita"])
    def test_expert_decoder(self, backend, step):
        cfg = reduced(get_config("qwen2-moe-a2.7b"))
        model = api.compile(cfg, backend=backend, seq_len=JIT_SEQ, max_len=JIT_MAX,
                            kv_block_size=JIT_BLOCK, kv_blocks=JIT_BLOCKS,
                            use_cache=False)
        assert model.artifact.paged
        _drive_and_compare(model.session(2, key=jax.random.PRNGKey(7)), cfg, step)


class TestKVRegion:
    def test_shared_static_offsets(self, olmo_setup):
        """The link: every cache tensor has identical offset/size in both
        plans, and the decode in-place update aliases its input."""
        _, pair, _, _, _ = olmo_setup
        pair.validate()
        assert pair.kv_tensors  # 2 per layer
        offsets = []
        for name in pair.kv_tensors:
            a, b = pair.prefill.tensors[name], pair.decode.tensors[name]
            assert a.offset == b.offset and a.size == b.size
            offsets.append((a.offset, a.size))
            out = pair.decode.tensors[name + "_new"]
            assert out.offset == a.offset and out.size == a.size
        # the persistent region is contiguous from offset 0, no overlap
        offsets.sort()
        assert offsets[0][0] == 0
        for (o1, s1), (o2, _) in zip(offsets, offsets[1:]):
            assert o1 + s1 <= o2

    def test_persistent_lifetimes_span_schedule(self):
        """In the lowered graphs the cache tensors must never be recycled:
        whole-schedule lifetimes, disjoint from every transient."""
        from repro.deploy import memory as memlib
        from repro.deploy.lowering import build_runtime_decoder_graph
        from repro.deploy.lowering import schedule as topo

        cfg = reduced(get_config("olmo-1b"))
        for phase in ("prefill", "decode"):
            g, kv_state = build_runtime_decoder_graph(cfg, SEQ, phase=phase,
                                                      max_len=MAX_LEN)
            g.nodes = topo(g)
            persistent = tuple(cin or cout for cin, cout in kv_state)
            aliases = {cout: cin for cin, cout in kv_state if cin}
            mem = memlib.plan_memory(g, persistent=persistent, aliases=aliases)
            assert mem.check_no_overlap()
            for t in persistent:
                a = mem.allocations[t]
                assert (a.start, a.end) == (0, len(g.nodes) - 1)

    def test_pair_json_round_trip(self, olmo_setup):
        _, pair, _, _, _ = olmo_setup
        restored = DecoderPlanPair.from_json(pair.to_json())
        assert restored == pair

    def test_lower_dispatches_to_pair(self):
        cfg = reduced(get_config("olmo-1b"))
        art = lower(cfg, SEQ, max_len=MAX_LEN)
        assert isinstance(art, DecoderPlanPair)
        with pytest.raises(NotImplementedError):
            lower(reduced(get_config("mamba2-370m")))


class TestEnginePlacement:
    def test_prefill_accelerates_decode_falls_back(self, olmo_setup):
        """The paper split at both phases: aligned prefill GEMMs on ITA;
        M=1 decode GEMVs (pad_m: False) on the cluster."""
        _, pair, _, _, _ = olmo_setup
        pre_gemms = [n for n in pair.prefill.nodes if n.op == "MatMul"]
        dec_gemms = [n for n in pair.decode.nodes if n.op == "MatMul"]
        assert pre_gemms and all(n.engine == "ita" for n in pre_gemms)
        assert dec_gemms and all(n.engine == "cluster" for n in dec_gemms)
        # attention / rope / cache ops are cluster kernels in both phases
        for plan in (pair.prefill, pair.decode):
            for n in plan.nodes:
                if n.op in ("Rope", "AttnPrefill", "AttnDecode", "CacheWrite",
                            "SiluMul", "LastTok", "LMHead"):
                    assert n.engine == "cluster", (n.name, n.engine)

    @pytest.mark.parametrize("backend", [het.Backend.W8A8, het.Backend.ITA])
    def test_static_engines_agree_with_runtime_resolve(self, backend):
        """Satellite: the plan's static engine column must equal what
        ``DispatchTable.resolve`` does at run time, per backend granule —
        the naming-trap regression (PALLAS vs ASIC granule)."""
        cfg = reduced(get_config("olmo-1b"))
        granule = het.backend_granule(backend)
        pair = lower_decoder(cfg, SEQ, max_len=MAX_LEN, granule=granule)
        for plan in (pair.prefill, pair.decode):
            for n in plan.nodes:
                desc = node_opdesc(n, granule)
                engine, _ = het.DEFAULT_TABLE.resolve(desc, backend)
                assert n.engine == engine.value, (plan.phase, n.name, n.engine,
                                                  engine.value)

    def test_backend_granule_aliases(self):
        """ITA backend == Pallas kernels == TPU granule; W8A8 == ASIC."""
        assert het.backend_granule(het.Backend.ITA) == het.TPU_GRANULE == 128
        assert het.backend_granule(het.Backend.W8A8) == het.ITA_GRANULE == 64
        assert het.backend_granule(het.Backend.FLOAT) == het.ITA_GRANULE


class TestModelPathParity:
    def test_prefill_vs_decode_parity(self):
        """The two integer paths cannot drift: prefilling N+1 tokens equals
        prefilling N then decoding the (N+1)-th, bit for bit (same flash
        blocking at these sizes; satellite regression for the swiglu
        dtype-promotion split)."""
        cfg = reduced(get_config("olmo-1b"))
        key = jax.random.PRNGKey(11)
        qp = T.quantize_params(cfg, T.init_params(cfg, key))
        toks = jax.random.randint(key, (2, SEQ), 0, cfg.vocab, jnp.int32)

        full_logits, full_cache = T.prefill_w8a8(cfg, qp, {"tokens": toks}, MAX_LEN)
        part_logits, cache = T.prefill_w8a8(
            cfg, qp, {"tokens": toks[:, : SEQ - 1]}, MAX_LEN)
        step_logits, cache = T.decode_step_w8a8(cfg, qp, cache, toks[:, SEQ - 1 :])
        np.testing.assert_array_equal(np.asarray(full_logits), np.asarray(step_logits))
        np.testing.assert_array_equal(
            np.asarray(full_cache["k"][:, :, :, :SEQ]),
            np.asarray(cache["k"][:, :, :, :SEQ]))

    def test_decode_swiglu_matches_qlayer(self):
        """decode_step_w8a8 literally runs qlayer_fwd now — one source of
        truth for the swiglu integer product (no dtype-promotion drift)."""
        import inspect

        src = inspect.getsource(T.decode_step_w8a8)
        assert "qlayer_fwd" in src
        assert "isilu_i8" not in src  # no duplicated MLP arithmetic
