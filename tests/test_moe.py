"""The integer expert layer through compile() -> Engine, against the plain
integer reference of ``bench/reference/moe.py`` and the published float
equations.

A small Qwen1.5-MoE-shaped decoder (8 experts of 128, top-2 unrenormalised,
a sigmoid-gated shared expert of 256, q/k/v bias, untied head) is served on
both backends; every logits row the engine samples from must equal the
reference's, bit for bit, through chunked prefill, decode, a prefix-cache
hit and a preempted-and-resumed request.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench.reference import moe as R  # noqa: E402
from bench.serve_moe import program_params  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import ArchConfig  # noqa: E402
from repro.deploy import api  # noqa: E402
from repro.deploy.engine import Engine, Greedy  # noqa: E402
from repro.deploy.lowering import EXPERT_COUNTS  # noqa: E402
from repro.deploy.serving.scheduler import PriorityDeadline  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models import moe  # noqa: E402
from repro.models import transformer as T  # noqa: E402

SMALL = dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=4, head_dim=32, d_ff=256,
             vocab=512, n_experts=8, top_k=2, d_ff_expert=128, n_shared_experts=2,
             max_seq=128)
SERVE = dict(seq_len=16, max_len=96, kv_block_size=8, kv_blocks=48)


def _bench_cfg():
    with open(os.path.join(REPO, "bench", "configs", "qwen1.5-moe-a2.7b.json")) as f:
        cfg = json.load(f)
    cfg["arch"].update(SMALL)
    cfg["serve"].update(SERVE)
    return cfg


@pytest.fixture(scope="module")
def small():
    cfg = _bench_cfg()
    arch = R.ref_arch(cfg)
    return cfg, ArchConfig(**cfg["arch"]), arch, R.make_codes(arch, cfg["weights"], 2**33 + 5)


def _compile(acfg, backend):
    return api.compile(acfg, backend=backend, prefix_cache=True, use_cache=False, **SERVE)


class Recorder:
    """Greedy sampling that keeps every logits row it samples from."""

    def __init__(self, vocab):
        self.inner, self.vocab, self.rows = Greedy(vocab), vocab, {}

    def __call__(self, row, rid, index):
        self.rows[(rid, index)] = np.asarray(row)[: self.vocab]
        return self.inner(row, rid, index)


class _Clock:
    t = 0.0

    def __call__(self):
        return self.t


def _assert_rows_match_reference(small, handles, rec):
    cfg, _, arch, codes = small
    for h in handles:
        tokens, _, n, _ = R._request_arrays(arch, cfg["serve"], h.prompt, h.tokens)
        ref = np.asarray(R._logits(arch, cfg["serve"], codes, tokens, n))
        for i in range(len(h.tokens)):
            np.testing.assert_array_equal(rec.rows[(h.rid, i)], ref[len(h.prompt) - 1 + i])


@pytest.mark.parametrize("backend", ["w8a8", "ita"])
def test_served_logits_are_the_reference_logits(small, backend):
    """Chunked prefill (prompts over the 16-token chunk, one not a multiple
    of it), decode, an exact prompt repeat (a prefix-cache hit) and a
    request preempted mid-decode and resumed: every sampled row equals the
    reference's."""
    cfg, acfg, arch, codes = small
    model = _compile(acfg, backend)
    rec, clk = Recorder(acfg.vocab), _Clock()
    eng = Engine(model, 2, params=program_params(codes), sampling=rec,
                 scheduler=PriorityDeadline(aging_s=1e9), clock=clk)
    rng = np.random.default_rng(0)
    shared = rng.integers(0, acfg.vocab, size=37).tolist()
    low = eng.submit(rng.integers(0, acfg.vocab, size=20).tolist(), 10, priority=5,
                     deadline_ms=100)
    for _ in range(5):
        eng.step()
    assert low.tokens, "setup: nothing generated before the preemption"
    clk.t = 1.0  # past the low-priority request's deadline
    handles = [low, eng.submit(shared, 6, priority=0), eng.submit(shared, 6, priority=0),
               eng.submit(rng.integers(0, acfg.vocab, size=32).tolist(), 7, priority=0)]
    eng.run_until_idle(max_steps=500)
    assert low.preemptions >= 1
    assert eng.stats.prefix_hits >= 1
    assert all(h.finish_reason == "length" for h in handles)
    _assert_rows_match_reference(small, handles, rec)
    for h in handles:
        assert R.decoder_gap(arch, cfg["serve"], codes, h.prompt, h.tokens) == 0.0


def test_expert_counters_count_every_routed_row(small):
    """``expert_rows`` is top_k x layers x the rows routed: the prompt rows
    of every chunk (no prompt here overlaps a chunk) and the active decode
    lanes, and never a parked lane's; ``expert_groups`` lies between one
    and E experts a layer and dispatch.  Both reach ``/v1/stats``."""
    from repro.deploy.serving import AsyncEngine
    from repro.deploy.serving.frontend import _stats_payload

    _, acfg, _, codes = small
    eng = Engine(_compile(acfg, "w8a8"), 3, params=program_params(codes))
    rng = np.random.default_rng(1)
    for n, g in ((32, 5), (16, 3), (48, 4)):
        eng.submit(rng.integers(0, acfg.vocab, size=n).tolist(), g)
    eng.run_until_idle(max_steps=200)
    s = eng.stats
    per_row = acfg.top_k * acfg.n_layers
    assert s.expert_rows == per_row * (s.prompt_tokens_prefilled + s.slot_steps_busy)
    dispatches = acfg.n_layers * (s.prefill_dispatches + s.decode_dispatches)
    assert dispatches <= s.expert_groups <= acfg.n_experts * dispatches
    assert eng.session.expert_counts is not None
    with AsyncEngine(eng) as aeng:
        payload = _stats_payload(aeng)
    assert (payload["expert_rows"], payload["expert_groups"]) == (s.expert_rows,
                                                                   s.expert_groups)
    eng.reset_stats()
    assert (eng.stats.expert_rows, eng.stats.expert_groups) == (0, 0)


def test_a_dense_plan_counts_and_fetches_nothing_more():
    from repro.configs import reduced

    model = api.compile(reduced(get_config("olmo-1b")), use_cache=False, **SERVE)
    assert EXPERT_COUNTS not in model.artifact.prefill.outputs
    eng = Engine(model, 2)
    eng.submit(list(range(20)), 3)
    eng.run_until_idle(max_steps=50)
    assert eng.session.expert_counts is None
    assert (eng.stats.expert_rows, eng.stats.expert_groups) == (0, 0)


def test_plan_pair_passes_verify_and_names_the_moe_scopes(small):
    _, acfg, _, _ = small
    model = _compile(acfg, "ita")
    assert model.diagnostics == () or all(d.severity == "warning" for d in model.diagnostics)
    for plan in (model.artifact.prefill, model.artifact.decode):
        ops = {n.op: n for n in plan.nodes}
        for op in ("MoeRouter", "MoeRoute", "MoeDispatch", "ExpertGemm", "MoeCombine",
                   "SharedGate", "MoeCount"):
            assert ops[op].kind == "moe"
        assert ops["ExpertGemm"].engine == "ita"
        assert plan.outputs[-1] == EXPERT_COUNTS


def test_saved_plan_reloads_and_the_audit_reads_the_grouped_gemm(small, tmp_path):
    """A saved MoE artifact reloads through the verifier; a grouped GEMM
    whose requant multiplier underflows is refused like any GEMM's."""
    import dataclasses

    from repro.deploy.verify import verify

    _, acfg, _, _ = small
    model = _compile(acfg, "ita")
    path = str(tmp_path / "moe.json")
    model.save(path)
    loaded = api.CompiledModel.load(path, acfg)
    assert loaded.artifact.prefill.outputs == model.artifact.prefill.outputs
    plan = model.artifact.decode
    bad = [dataclasses.replace(n, attrs=dict(n.attrs, scales=(1e-6, 1e-6, 10.0)))
           if n.op == "ExpertGemm" else n for n in plan.nodes]
    broken = dataclasses.replace(plan, nodes=bad, schedule=tuple(n.name for n in bad))
    assert any(d.rule == "QNT001" and d.severity == "error" for d in verify(broken))


def test_model_path_and_plan_give_one_answer(small):
    """``transformer.prefill_w8a8`` (the model path, through
    ``moe_ffn_w8a8``) and the plan's chunk step agree bit for bit."""
    _, acfg, _, codes = small
    model = _compile(acfg, "w8a8")
    sess = model.session(2, params=program_params(codes))
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, SERVE["seq_len"]), 0, acfg.vocab)
    plan_logits = sess.prefill(toks)
    ref_logits, _ = T.prefill_w8a8(acfg, sess.qp | {"layers": _stack(sess.qp["layers"])},
                                   {"tokens": toks}, SERVE["max_len"])
    np.testing.assert_array_equal(np.asarray(plan_logits), np.asarray(ref_logits))


def _stack(layers):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def test_float_params_bind_through_ptq(small):
    """The float path is unchanged: float parameters are quantized by PTQ
    (experts sliced from their padded training layout) and served."""
    _, acfg, _, _ = small
    eng = Engine(_compile(acfg, "w8a8"), 2)
    h = eng.submit(list(range(18)), 4)
    eng.run_until_idle(max_steps=50)
    assert h.finish_reason == "length" and len(h.tokens) == 4
    assert eng.session.qp["layers"]["mlp"]["experts"]["gate_q"].shape == (
        acfg.n_layers, acfg.n_experts, acfg.d_model, acfg.d_ff_expert)


def test_expert_layer_needs_the_paged_region(small):
    _, acfg, _, _ = small
    with pytest.raises(ValueError, match="paged KV region"):
        api.compile(acfg, seq_len=16, max_len=96, use_cache=False)


def test_published_configuration():
    cfg = get_config("qwen2-moe-a2.7b")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        24, 2048, 16, 16, 128)
    assert (cfg.n_experts, cfg.top_k, cfg.d_ff_expert, cfg.shared_width) == (60, 4, 1408, 5632)
    assert cfg.n_shared_experts == 4 and cfg.qkv_bias and not cfg.norm_topk_prob
    assert (cfg.rope_theta, cfg.max_seq, cfg.vocab, cfg.tie_embeddings) == (1e6, 8192, 151936,
                                                                            False)
    bench = _bench_cfg()
    assert bench["quant"]["s_router"] == moe.S_ROUTER
    assert bench["quant"]["s_gate"] == moe.S_GATE


# -- the integer layer against the published float equations ---------------

Q = L.QuantConfig()


def _float_layer(lp_f, h, top_k):
    """Qwen1.5-MoE's expert layer as published, in float32: softmax over
    every router logit, top-k without renormalisation, SwiGLU experts, a
    shared SwiGLU expert scaled by sigmoid(h . w_g)."""
    probs = jax.nn.softmax(h @ lp_f["router"], axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)

    def swiglu(x, g, u, d):
        return (jax.nn.silu(x @ g) * (x @ u)) @ d

    ex = jax.vmap(swiglu, in_axes=(None, 0, 0, 0))(
        h, lp_f["gate"], lp_f["up"], lp_f["down"])  # [E, R, D]
    routed = jnp.einsum("rk,rkd->rd", top_p,
                        jnp.take_along_axis(ex.transpose(1, 0, 2), top_i[..., None], axis=1))
    sh = swiglu(h, lp_f["sg"], lp_f["su"], lp_f["sd"]) * jax.nn.sigmoid(h @ lp_f["wg"])
    return routed + sh


def _int_layer(cfg, lq, h_q):
    lp = {"router": {"w_q": lq["router"]},
          "experts": {"gate_q": lq["gate"], "up_q": lq["up"], "down_q": lq["down"]},
          "shared": {"gate": {"w_q": lq["sg"]}, "up": {"w_q": lq["su"]},
                     "down": {"w_q": lq["sd"]}},
          "shared_gate": {"w_q": lq["wg"]}}
    return moe.moe_ffn_w8a8(cfg, lp, h_q[None], Q)[0]


def _relative_error(cfg, codes, h_q, float_codes):
    with jax.default_matmul_precision("highest"):
        want = _float_layer({k: v.astype(jnp.float32) * Q.s_w for k, v in float_codes.items()},
                            h_q.astype(jnp.float32) * Q.s_act, cfg.top_k)
    got = _int_layer(cfg, codes, h_q).astype(jnp.float32) * Q.s_act
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


#: relative L2 error of the integer layer against the float equations on
#: the same codes.  Its part: every int8 rounding inside the layer (the
#: expert and shared outputs on the s_act grid, Q0.7 router weights, the
#: Q0.8 sigmoid, the integer SiLU).  Measured 0.076-0.093 over six seeds;
#: int4 weights (the control, the next precision below) read 0.37-0.41.
TOLERANCE = 0.2


@pytest.mark.parametrize("seed", [0, 1])
def test_integer_layer_tracks_the_float_equations(seed):
    cfg = ArchConfig(name="t", family="moe", n_layers=1, d_model=256, n_heads=2,
                     n_kv_heads=2, head_dim=128, d_ff=512, vocab=256, n_experts=16,
                     top_k=4, n_shared_experts=4, d_ff_expert=128, norm_topk_prob=False)
    d, f, fs, e = cfg.d_model, cfg.d_ff_expert, cfg.shared_width, cfg.n_experts
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)

    def codes(k, shape):
        return jnp.clip(jnp.rint(jax.random.normal(k, shape) / np.sqrt(shape[-2]) / Q.s_w),
                        -127, 127).astype(jnp.int8)

    lq = {"router": codes(ks[0], (d, e)), "gate": codes(ks[1], (e, d, f)),
          "up": codes(ks[2], (e, d, f)), "down": codes(ks[3], (e, f, d)),
          "sg": codes(ks[4], (d, fs)), "su": codes(ks[5], (d, fs)), "sd": codes(ks[6], (fs, d)),
          "wg": codes(ks[7], (d, 1))}
    # RMSNorm output: unit RMS on the s_act grid
    h_q = jnp.clip(jnp.rint(jax.random.normal(ks[8], (64, d)) / Q.s_act), -127, 127
                   ).astype(jnp.int8)
    err = _relative_error(cfg, lq, h_q, lq)
    assert err < TOLERANCE
    control = jax.tree.map(lambda c: R.int4_codes({"w": c})["w"], lq)
    assert _relative_error(cfg, control, h_q, lq) > TOLERANCE
