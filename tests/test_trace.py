"""Spans, named device programs and per-node scopes.

The serving stack opens host spans (``repro.deploy.trace``) where the work
happens, the session jits named step functions, and the executor runs
every plan node under a named scope.  A profiler trace of a small paged
engine holds every span, nested as the engine nests the work; the compiled
steps carry their names and every plan node's scope in ``op_name``.
"""

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config, reduced
from repro.deploy import api
from repro.deploy.engine import Engine
from repro.deploy.serving import AsyncEngine
from repro.models import transformer as T

SEQ = 8
ENGINE_SPANS = ("engine.step", "engine.admit", "session.prefill_chunks", "session.decode",
                "engine.device_wait", "engine.fetch", "sampling", "engine.stream",
                "engine.no_work")


@pytest.fixture(scope="module")
def olmo():
    cfg = reduced(get_config("olmo-1b"))
    return cfg, T.init_params(cfg, jax.random.PRNGKey(7))


def _paged(cfg):
    return api.compile(cfg, backend="w8a8", seq_len=SEQ, max_len=4 * SEQ, kv_block_size=SEQ,
                       kv_blocks=16, use_cache=False)


def _host_spans(trace_dir: str, names) -> list[tuple[str, int, int, str]]:
    """(name, start ns, end ns, thread) of the trace's host events named
    in ``names``."""
    [path] = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, line.name)
                    for ev in line.events if ev.name in names]
    return out


def _inside(span, outer) -> bool:
    return span[3] == outer[3] and outer[1] <= span[1] and span[2] <= outer[2]


def test_engine_spans_nest_in_a_profiler_trace(olmo, tmp_path):
    cfg, params = olmo
    engine = Engine(_paged(cfg), 2, params=params)
    prompt = list(range(1, 2 * SEQ + 3))  # chunks at 0, 8 and the pinned tail
    engine.submit(prompt, 2)
    engine.run_until_idle(max_steps=50)  # compile every step outside the trace
    engine.reset_stats()
    streamed = []
    jax.profiler.start_trace(str(tmp_path))
    try:
        with AsyncEngine(engine) as aeng:
            h = aeng.submit([t + 1 for t in prompt], 4, on_token=streamed.append)
            h.result(timeout=120)
    finally:
        jax.profiler.stop_trace()
    assert streamed == h.tokens and len(streamed) == 4
    spans = _host_spans(str(tmp_path), ENGINE_SPANS)
    found = {s[0] for s in spans}
    assert found == set(ENGINE_SPANS)
    steps = [s for s in spans if s[0] == "engine.step"]
    assert len(steps) == len(engine.stats.step_times_s)
    for name in ("engine.admit", "session.prefill_chunks", "session.decode",
                 "engine.device_wait", "engine.fetch", "sampling", "engine.stream"):
        for s in (s for s in spans if s[0] == name):
            assert any(_inside(s, st) for st in steps), (name, s)
    # one logits fetch per decode step, after the step's decode dispatch
    # (the step whose chunk finishes the prompt fetches that row first)
    decode_steps = [st for st in steps
                    if any(s[0] == "session.decode" and _inside(s, st) for s in spans)]
    assert len(decode_steps) == engine.stats.decode_dispatches == 3
    for st in decode_steps:
        [dec] = [s for s in spans if s[0] == "session.decode" and _inside(s, st)]
        after = [s for s in spans if s[0] == "engine.fetch" and _inside(s, st)
                 and s[1] >= dec[2]]
        assert len(after) == 1
    fetches = [s for s in spans if s[0] == "engine.fetch"]
    assert len(fetches) == engine.stats.decode_dispatches + 1
    # a wait for each dispatch: three chunk steps and three decode steps
    waits = [s for s in spans if s[0] == "engine.device_wait"]
    assert len(waits) == engine.stats.prefill_dispatches + engine.stats.decode_dispatches == 6
    # the loop's wait for work lies outside every step
    assert not any(_inside(s, st) for s in spans if s[0] == "engine.no_work" for st in steps)


def test_encoder_forward_opens_its_span(tmp_path):
    cfg = reduced(get_config("mobilebert"))
    sess = api.compile(cfg, use_cache=False).session(1, key=jax.random.PRNGKey(0))
    x = jnp.zeros((1, sess._plan.seq_len), jnp.int32)
    jax.block_until_ready(sess.forward(x))
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(sess.forward(x))
    finally:
        jax.profiler.stop_trace()
    assert [s[0] for s in _host_spans(str(tmp_path), ("session.forward",))] == \
        ["session.forward"]


def _scopes(plan) -> set[str]:
    return {f"{n.kind}.{n.name}" for n in plan.nodes}


def _paged_args(sess, step):
    b = sess.batch_size
    tables = jnp.asarray(sess._tables)
    if step == "decode_step":
        return (sess.weights, sess._pool, jnp.zeros((b, 1), jnp.int32),
                jnp.zeros((b,), jnp.int32), tables, jnp.ones((b,), bool))
    return (sess.weights, sess._pool, jnp.zeros((b, SEQ), jnp.int32),
            jnp.zeros((b,), jnp.int32), tables)


@pytest.mark.parametrize("step", ["decode_step", "chunk_step"])
def test_compiled_steps_are_named_and_carry_node_scopes(olmo, step):
    """The HLO module is named after the session's step function, and every
    plan node (fused regions and their bodies too) and the pool's
    rebuild and slicing leave their scope in the ops' ``op_name``; the
    scopes are metadata, so no kernel is renamed."""
    cfg, params = olmo
    model = _paged(cfg)
    sess = model.session(2, params=params)
    fn = sess._decode_fn if step == "decode_step" else sess._chunk_fn
    text = fn.lower(*_paged_args(sess, step)).compile().as_text()
    assert text.startswith(f"HloModule jit_{step},")
    plan = model.artifact.decode if step == "decode_step" else model.artifact.prefill
    op_names = set(re.findall(rf'op_name="jit\({step}\)/([^"]*)"', text))
    path = "/" + "\n/".join(op_names)
    expect = _scopes(plan) | {"kv_pool.slice", "kv_pool.stack"}
    assert {s for s in expect if f"/{s}/" in path} == expect


@pytest.mark.parametrize("step", ["decode_step", "chunk_step"])
def test_every_op_sits_directly_under_a_node_or_pool_scope(olmo, step):
    """A plan is a flat schedule: the first scope under the step program
    is a plan node's ``<kind>.<node>`` or the pool's slice or rebuild,
    with no level in between, and every node opens one."""
    cfg, params = olmo
    model = _paged(cfg)
    sess = model.session(2, params=params)
    fn = sess._decode_fn if step == "decode_step" else sess._chunk_fn
    text = fn.lower(*_paged_args(sess, step)).compile().as_text()
    plan = model.artifact.decode if step == "decode_step" else model.artifact.prefill
    first = {o.split("/")[0]
             for o in re.findall(rf'op_name="jit\({step}\)/([^"]*)"', text)}
    nodes = _scopes(plan)
    assert first - nodes == {"kv_pool.slice", "kv_pool.stack"}
    assert nodes <= first


def test_other_programs_are_named(olmo):
    """The dense prefill and decode steps, the copy-on-write block copy and
    the encoder forward lower to named modules."""
    cfg, params = olmo
    dense = api.compile(cfg, backend="w8a8", seq_len=SEQ, max_len=2 * SEQ,
                        use_cache=False).session(1, params=params)
    tokens = jnp.zeros((1, SEQ), jnp.int32)
    assert dense._prefill_fn.lower(dense.weights, {"tokens": tokens}).as_text().startswith(
        "module @jit_prefill_step ")
    _, cache = dense._prefill_fn(dense.weights, {"tokens": tokens})
    kv = {"k": cache["k"], "v": cache["v"]}
    lowered = dense._decode_fn.lower(dense.weights, kv, jnp.zeros((1, 1), jnp.int32),
                                     jnp.asarray(np.full((1,), SEQ, np.int32)))
    assert lowered.as_text().startswith("module @jit_decode_step ")
    paged = _paged(cfg).session(1, params=params)
    assert paged._copy_fn.lower(paged._pool, jnp.int32(1), jnp.int32(2)).as_text().startswith(
        "module @jit_cow_copy ")
    enc = reduced(get_config("mobilebert"))
    esess = api.compile(enc, use_cache=False).session(1, key=jax.random.PRNGKey(0))
    batch = {esess._plan.inputs[0]: jnp.zeros((1, esess._plan.seq_len), jnp.int32)}
    assert esess._forward_fn.lower(esess.weights, batch).as_text().startswith(
        "module @jit_encoder_forward ")
