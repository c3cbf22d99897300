"""The unified inference API (ISSUE 3).

Acceptance contract: a batch of B requests at *distinct* ``pos`` values
decoded through one ``InferenceSession.decode`` call is bit-exact vs B
independent single-request ``decode_step_w8a8`` trajectories, on both
``w8a8`` and ``ita`` backends; a second ``compile()`` of the same config
is a cache hit and the deserialized plan executes bit-exactly vs the
freshly lowered one; backend names normalize once at the API boundary;
``lower()`` on unsupported families raises one clear
``UnsupportedFamilyError`` naming the family.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import heterogeneous as het
from repro.deploy import api
from repro.deploy.lowering import UnsupportedFamilyError, lower
from repro.deploy.plan import DecoderPlanPair
from repro.models import transformer as T

SEQ, GEN = 8, 3
MAX_LEN = SEQ + GEN + 2


@pytest.fixture(scope="module")
def olmo():
    """reduced olmo-1b (GQA, RoPE, SwiGLU, tied embeddings) + params."""
    cfg = reduced(get_config("olmo-1b"))
    params = T.init_params(cfg, jax.random.PRNGKey(7))
    return cfg, params


def _compile(cfg, **kw):
    kw.setdefault("use_cache", False)
    kw.setdefault("seq_len", SEQ)
    kw.setdefault("max_len", MAX_LEN)
    return api.compile(cfg, **kw)


def _mixed_depth_session(cfg, params, backend, batch_size=3):
    """Drive a session into genuinely mixed per-slot depths, mirroring B
    independent single-request reference trajectories at every step.

    Returns ``(session, refs, tok)`` where ``refs[b] = [logits, cache]``
    is request b's own ``prefill_w8a8``/``decode_step_w8a8`` state and
    ``tok`` the next per-slot token to decode.
    """
    model = _compile(cfg, backend=backend)
    session = model.session(batch_size, params=params)
    qp = session.qp
    key = jax.random.PRNGKey(0)
    toks = jax.random.randint(key, (batch_size, SEQ), 0, cfg.vocab, jnp.int32)

    refs = []
    for b in range(batch_size):
        lg, cache = T.prefill_w8a8(cfg, qp, {"tokens": toks[b : b + 1]}, MAX_LEN)
        refs.append([lg, cache])
    logits = session.prefill(toks)
    for b in range(batch_size):
        np.testing.assert_array_equal(np.asarray(logits[b : b + 1]),
                                      np.asarray(refs[b][0]))

    # advance every slot twice (uniform depths, one dispatch per step)
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    for _ in range(2):
        logits = session.decode(tok)
        for b in range(batch_size):
            rlg, refs[b][1] = T.decode_step_w8a8(cfg, qp, refs[b][1], tok[b : b + 1])
            np.testing.assert_array_equal(np.asarray(logits[b : b + 1]),
                                          np.asarray(rlg))
            refs[b][0] = rlg
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)

    # continuous batching: admit a fresh request into the last slot while
    # the others stay mid-generation -> distinct per-slot depths
    last = batch_size - 1
    new_toks = jax.random.randint(jax.random.PRNGKey(9), (1, SEQ), 0,
                                  cfg.vocab, jnp.int32)
    rlg, rcache = T.prefill_w8a8(cfg, qp, {"tokens": new_toks}, MAX_LEN)
    refs[last] = [rlg, rcache]
    slot_logits = session.prefill_slot(last, new_toks)
    np.testing.assert_array_equal(np.asarray(slot_logits), np.asarray(rlg))
    tok = tok.at[last].set(jnp.argmax(rlg[:, -1], axis=-1).astype(jnp.int32))

    depths = sorted(set(int(p) for p in session.pos))
    assert len(depths) == 2, f"expected mixed depths, got {session.pos}"
    return session, refs, tok


class TestBatchedContinuousDecode:
    @pytest.mark.parametrize("backend", ["w8a8", "ita"])
    def test_mixed_depths_bit_exact(self, olmo, backend):
        """One decode dispatch, B requests at distinct pos values, each
        bit-exact vs its own single-request decode_step_w8a8 trajectory
        (logits AND per-slot KV rows)."""
        cfg, params = olmo
        session, refs, tok = _mixed_depth_session(cfg, params, backend)
        qp = session.qp
        for _ in range(2):  # keep decoding across mixed depths
            logits = session.decode(tok)
            for b in range(session.batch_size):
                rlg, refs[b][1] = T.decode_step_w8a8(cfg, qp, refs[b][1],
                                                     tok[b : b + 1])
                np.testing.assert_array_equal(np.asarray(logits[b : b + 1]),
                                              np.asarray(rlg))
                np.testing.assert_array_equal(
                    np.asarray(session.kv_cache["k"][:, b : b + 1]),
                    np.asarray(refs[b][1]["k"]))
                np.testing.assert_array_equal(
                    np.asarray(session.kv_cache["v"][:, b : b + 1]),
                    np.asarray(refs[b][1]["v"]))
                refs[b][0] = rlg
            tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)

    def test_explicit_pos_vector(self, olmo):
        """``decode(tokens, pos)`` with an explicit per-request vector
        equals the session's own tracked positions."""
        cfg, params = olmo
        session, refs, tok = _mixed_depth_session(cfg, params, "w8a8")
        pos = session.pos
        logits = session.decode(tok, pos)
        qp = session.qp
        for b in range(session.batch_size):
            rlg, _ = T.decode_step_w8a8(cfg, qp, refs[b][1], tok[b : b + 1])
            np.testing.assert_array_equal(np.asarray(logits[b : b + 1]),
                                          np.asarray(rlg))
        np.testing.assert_array_equal(np.asarray(session.pos), np.asarray(pos + 1))

    def test_session_guards(self, olmo):
        cfg, params = olmo
        model = _compile(cfg)
        session = model.session(2, params=params)
        with pytest.raises(RuntimeError, match="decode before prefill"):
            session.decode(jnp.zeros((2, 1), jnp.int32))
        with pytest.raises(ValueError, match="prefill tokens"):
            session.prefill(jnp.zeros((2, SEQ + 1), jnp.int32))
        with pytest.raises(RuntimeError, match="encoder method"):
            session.forward(jnp.zeros((2, SEQ), jnp.int32))
        with pytest.raises(IndexError):
            session.prefill_slot(5, jnp.zeros((1, SEQ), jnp.int32))

    def test_decode_past_kv_capacity_raises(self, olmo):
        """Past-capacity cache writes would silently clamp inside
        dynamic_update_slice; the session bounds them loudly instead."""
        cfg, params = olmo
        model = _compile(cfg)  # max_len = MAX_LEN
        session = model.session(2, params=params)
        toks = jax.random.randint(jax.random.PRNGKey(0), (2, SEQ), 0,
                                  cfg.vocab, jnp.int32)
        logits = session.prefill(toks)
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        for _ in range(MAX_LEN - SEQ):  # fill the region exactly
            logits = session.decode(tok)
            tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        with pytest.raises(ValueError, match="KV region full"):
            session.decode(tok)


class TestEncoderSession:
    def test_forward_matches_model(self):
        from repro.models import encoder as EN

        cfg = reduced(get_config("mobilebert"))
        model = api.compile(cfg, use_cache=False)
        assert model.kind == "encoder"
        session = model.session(2)
        key = jax.random.PRNGKey(0)
        x = jax.random.randint(key, (2, model.artifact.seq_len), 0, cfg.vocab,
                               jnp.int32)
        out = session.forward(x)
        ref = EN.forward_w8a8(cfg, session.qp, {"tokens": x})
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        with pytest.raises(ValueError, match="batch dim"):
            session.forward(x[:1])
        with pytest.raises(RuntimeError, match="decoder method"):
            session.prefill(x)


class TestPlanCache:
    def test_second_compile_hits_and_is_bit_exact(self, olmo, tmp_path):
        """Miss -> store -> hit; the cache-loaded plan equals the fresh one
        structurally AND executes bit-exactly (same session outputs)."""
        cfg, params = olmo
        kw = dict(seq_len=SEQ, max_len=MAX_LEN, cache_dir=str(tmp_path))
        m1 = api.compile(cfg, **kw)
        assert not m1.cache_hit
        m2 = api.compile(cfg, **kw)
        assert m2.cache_hit and m2.fingerprint == m1.fingerprint
        assert m2.artifact == m1.artifact  # lossless JSON round trip

        key = jax.random.PRNGKey(0)
        toks = jax.random.randint(key, (2, SEQ), 0, cfg.vocab, jnp.int32)
        out1 = m1.session(2, params=params).prefill(toks)
        out2 = m2.session(2, params=params).prefill(toks)
        np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))

    def test_compiler_version_bump_invalidates(self, olmo, tmp_path, monkeypatch):
        cfg, _ = olmo
        kw = dict(seq_len=SEQ, max_len=MAX_LEN, cache_dir=str(tmp_path))
        api.compile(cfg, **kw)
        monkeypatch.setattr(api, "COMPILER_VERSION", api.COMPILER_VERSION + 1)
        m = api.compile(cfg, **kw)
        assert not m.cache_hit  # stale version recompiles in place
        assert api.compile(cfg, **kw).cache_hit  # re-stored under new version

    def test_config_change_changes_fingerprint(self, olmo, tmp_path):
        cfg, _ = olmo
        kw = dict(seq_len=SEQ, max_len=MAX_LEN, cache_dir=str(tmp_path))
        m1 = api.compile(cfg, **kw)
        cfg2 = dataclasses.replace(cfg, rope_theta=cfg.rope_theta * 2)
        m2 = api.compile(cfg2, **kw)
        assert m2.fingerprint != m1.fingerprint and not m2.cache_hit
        # options change the key too (a different max_len is a different plan)
        m3 = api.compile(cfg, seq_len=SEQ, max_len=MAX_LEN + 4,
                         cache_dir=str(tmp_path))
        assert m3.fingerprint != m1.fingerprint

    def test_corrupt_cache_entry_is_a_miss(self, olmo, tmp_path):
        cfg, _ = olmo
        kw = dict(seq_len=SEQ, max_len=MAX_LEN, cache_dir=str(tmp_path))
        m1 = api.compile(cfg, **kw)
        with open(m1.cache_path, "w") as f:
            f.write("{not json")
        m2 = api.compile(cfg, **kw)
        assert not m2.cache_hit
        assert api.compile(cfg, **kw).cache_hit  # repaired on the miss

    def test_save_load_round_trip(self, olmo, tmp_path):
        cfg, _ = olmo
        m1 = _compile(cfg)
        path = str(tmp_path / "model.json")
        m1.save(path)
        m2 = api.CompiledModel.load(path, cfg)
        assert m2.artifact == m1.artifact and m2.backend == m1.backend
        wrong = dataclasses.replace(cfg, rope_theta=cfg.rope_theta * 2)
        with pytest.raises(ValueError, match="fingerprint mismatch"):
            api.CompiledModel.load(path, wrong)

    def test_load_rejects_stale_compiler_version(self, olmo, tmp_path):
        """Explicit save/load enforces the same semantic-invalidation rule
        as the cache: a version bump means plan semantics may differ."""
        cfg, _ = olmo
        path = str(tmp_path / "model.json")
        _compile(cfg).save(path)
        payload = json.load(open(path))
        payload["compiler_version"] -= 1
        json.dump(payload, open(path, "w"))
        with pytest.raises(ValueError, match="compiler version"):
            api.CompiledModel.load(path, cfg)


class TestPlanCacheConcurrency:
    """Satellite: the on-disk cache is multi-process safe — concurrent
    writers of the same fingerprint publish via temp-file + atomic
    ``os.replace``, so no reader ever observes a torn JSON entry."""

    def test_simultaneous_compiles_never_tear(self, olmo, tmp_path):
        import json as _json
        import threading
        from concurrent.futures import ThreadPoolExecutor

        cfg, _ = olmo
        kw = dict(seq_len=SEQ, max_len=MAX_LEN, cache_dir=str(tmp_path))
        probe = api.compile(cfg, seq_len=SEQ, max_len=MAX_LEN, use_cache=False)
        path = api._cache_path(str(tmp_path), cfg, probe.fingerprint)
        stop = threading.Event()
        torn: list[Exception] = []

        def reader():
            # hammer the entry while writers race on os.replace: every
            # observed state must be "absent" or "one complete document"
            while not stop.is_set():
                try:
                    with open(path) as f:
                        payload = _json.load(f)
                    assert payload["format"] == api._PAYLOAD_FORMAT
                except FileNotFoundError:
                    pass
                except Exception as e:  # torn JSON shows up here
                    torn.append(e)
                    return

        t = threading.Thread(target=reader)
        t.start()
        try:
            with ThreadPoolExecutor(max_workers=4) as ex:
                models = list(ex.map(lambda _: api.compile(cfg, **kw), range(6)))
        finally:
            stop.set()
            t.join()
        assert not torn, torn
        # whichever writer landed last, the entry is whole and a hit
        assert all(m.artifact == models[0].artifact for m in models)
        final = api.compile(cfg, **kw)
        assert final.cache_hit
        assert final.artifact == models[0].artifact
        # no stray temp files left behind by the racing writers
        assert not [p for p in tmp_path.iterdir() if p.suffix == ".tmp"]


class TestPairRoundTrip:
    """Satellite: DecoderPlanPair JSON round trip preserves the KV link."""

    def test_offsets_aliases_engines_survive(self, olmo):
        cfg, _ = olmo
        pair = _compile(cfg).artifact
        restored = DecoderPlanPair.from_json(pair.to_json())
        assert restored == pair
        restored.validate()
        for name in restored.kv_tensors:
            for plan, orig in ((restored.prefill, pair.prefill),
                               (restored.decode, pair.decode)):
                assert plan.tensors[name].offset == orig.tensors[name].offset
                assert plan.tensors[name].size == orig.tensors[name].size
            # decode's in-place alias: *_new at the identical offset
            a = restored.decode.tensors[name]
            b = restored.decode.tensors[name + "_new"]
            assert (a.offset, a.size) == (b.offset, b.size)
        for plan, orig in ((restored.prefill, pair.prefill),
                           (restored.decode, pair.decode)):
            assert [n.engine for n in plan.nodes] == [n.engine for n in orig.nodes]
            assert plan.kv_state == orig.kv_state

    def test_cache_loaded_pair_executes_bit_exactly(self, olmo, tmp_path):
        """Deserialized-from-disk pair vs freshly lowered pair: identical
        prefill + chained decode trajectory."""
        cfg, params = olmo
        kw = dict(seq_len=SEQ, max_len=MAX_LEN, cache_dir=str(tmp_path))
        fresh = api.compile(cfg, **kw)
        loaded = api.compile(cfg, **kw)
        assert loaded.cache_hit
        s_fresh = fresh.session(2, params=params)
        s_loaded = loaded.session(2, params=params)
        toks = jax.random.randint(jax.random.PRNGKey(2), (2, SEQ), 0,
                                  cfg.vocab, jnp.int32)
        lg_f, lg_l = s_fresh.prefill(toks), s_loaded.prefill(toks)
        np.testing.assert_array_equal(np.asarray(lg_f), np.asarray(lg_l))
        tok = jnp.argmax(lg_f[:, -1:], axis=-1).astype(jnp.int32)
        for _ in range(GEN):
            lg_f, lg_l = s_fresh.decode(tok), s_loaded.decode(tok)
            np.testing.assert_array_equal(np.asarray(lg_f), np.asarray(lg_l))
            np.testing.assert_array_equal(np.asarray(s_fresh.kv_cache["k"]),
                                          np.asarray(s_loaded.kv_cache["k"]))
            tok = jnp.argmax(lg_f[:, -1:], axis=-1).astype(jnp.int32)


class TestUnsupportedFamily:
    @pytest.mark.parametrize("arch", ["zamba2-2.7b", "llava-next-34b",
                                      "seamless-m4t-large-v2", "mamba2-370m"])
    def test_one_clear_error_naming_the_family(self, arch):
        cfg = reduced(get_config(arch))
        with pytest.raises(UnsupportedFamilyError) as ei:
            lower(cfg)
        assert cfg.family in str(ei.value) and cfg.name in str(ei.value)
        assert ei.value.family == cfg.family
        # same class through compile(), and it IS a NotImplementedError
        with pytest.raises(UnsupportedFamilyError):
            api.compile(cfg, use_cache=False)
        assert issubclass(UnsupportedFamilyError, NotImplementedError)


class TestBackendNormalization:
    """Satellite: ``backend`` as string or enum, normalized once."""

    def test_compile_accepts_strings_and_enums(self, olmo):
        cfg, _ = olmo
        m1 = _compile(cfg, backend="w8a8")
        m2 = _compile(cfg, backend=het.Backend.W8A8)
        assert m1.backend is m2.backend is het.Backend.W8A8
        assert m1.fingerprint == m2.fingerprint
        assert _compile(cfg, backend="ITA").backend is het.Backend.ITA

    def test_executor_entry_points_accept_strings(self, olmo):
        cfg, params = olmo
        model = _compile(cfg)
        session = model.session(1, params=params)
        toks = jax.random.randint(jax.random.PRNGKey(0), (1, SEQ), 0,
                                  cfg.vocab, jnp.int32)
        ref = session.prefill(toks)
        from repro.deploy.executor import execute_prefill

        weights, _ = model.bind(params=params)
        out, _ = execute_prefill(model.artifact, weights, {"tokens": toks},
                                 backend="w8a8")
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_unknown_backend_fails_with_vocabulary(self, olmo):
        cfg, _ = olmo
        with pytest.raises(ValueError, match="unknown backend 'tpu'"):
            _compile(cfg, backend="tpu")
        with pytest.raises(TypeError):
            het.as_backend(64)
        assert het.as_backend("W8A8") is het.Backend.W8A8

    def test_pre_api_shims_are_gone(self):
        """The PR-3 deprecation shims were promised for one release."""
        from repro.deploy import executor

        for name in ("plan_and_bind", "plan_and_bind_decoder",
                     "make_jit_executor", "make_decoder_executors"):
            assert not hasattr(executor, name), name


class TestDryrunHeadByHead:
    def test_decoder_ignores_encoder_only_flag(self, tmp_path, capsys):
        """--head-by-head on a decoder arch is ignored with a note (the
        pre-API behavior), not a crash."""
        from repro.launch.dryrun import run_via_plan

        rc = run_via_plan(
            "olmo-1b", reduced_cfg=True, backend="w8a8", batch_size=1,
            seq_len=SEQ, head_by_head=True, gen_steps=1,
            out_dir=str(tmp_path), use_cache=False,
        )
        assert rc == 0
        assert "encoder-only" in capsys.readouterr().out


class TestSharedCli:
    """Satellite: one argparse block, one backend-name validator."""

    def test_backend_names_come_from_dispatch_vocabulary(self):
        from repro.launch.cli import plan_backend_names

        assert plan_backend_names() == ("w8a8", "ita")

    @pytest.mark.parametrize("build_parser", [
        lambda: __import__("argparse").ArgumentParser(),
    ])
    def test_parser_validates_and_normalizes(self, build_parser):
        from repro.launch.cli import add_plan_args

        ap = build_parser()
        add_plan_args(ap, via_plan_help="x")
        args = ap.parse_args(["--via-plan", "--backend", "ita"])
        assert args.via_plan and args.backend is het.Backend.ITA
        assert ap.parse_args([]).backend is het.Backend.W8A8
        with pytest.raises(SystemExit):
            ap.parse_args(["--backend", "bogus"])
        with pytest.raises(SystemExit):
            ap.parse_args(["--backend", "float"])  # model-path only

    def test_serve_and_dryrun_share_the_block(self):
        import inspect

        from repro.launch import dryrun, serve

        assert "add_plan_args" in inspect.getsource(serve.main)
        assert "add_plan_args" in inspect.getsource(dryrun.main)


class TestFingerprint:
    def test_stable_across_processes(self, olmo):
        """Pure function of (config, options): recomputing gives the same
        hex — the property the on-disk cache key relies on."""
        cfg, _ = olmo
        opts = {"backend": "w8a8", "granule": 64}
        fp1 = api.config_fingerprint(cfg, opts)
        fp2 = api.config_fingerprint(cfg, dict(reversed(list(opts.items()))))
        assert fp1 == fp2 and len(fp1) == 64
        blob = json.dumps({"config": dataclasses.asdict(cfg)}, sort_keys=True)
        assert isinstance(blob, str)  # config is JSON-serializable by design

    def test_identical_in_a_fresh_process(self, olmo):
        """ISSUE 5 regression: the old ``json.dumps(default=repr)``
        fallback could embed object identity (``<... at 0x7f...>``) and
        fingerprint differently every process — a permanent cache miss
        nobody notices.  A subprocess must now reproduce the hash."""
        import os
        import subprocess
        import sys

        import repro

        cfg, _ = olmo
        opts = {"backend": "w8a8", "granule": 64, "seq_len": SEQ}
        here = api.config_fingerprint(cfg, opts)
        prog = (
            "from repro.configs import get_config, reduced\n"
            "from repro.deploy import api\n"
            "cfg = reduced(get_config('olmo-1b'))\n"
            f"print(api.config_fingerprint(cfg, {opts!r}))\n"
        )
        env = dict(os.environ)
        # repro is a namespace package (no __init__.py): locate via __path__
        env["PYTHONPATH"] = os.path.dirname(list(repro.__path__)[0])
        env.setdefault("JAX_PLATFORMS", "cpu")
        out = subprocess.run(
            [sys.executable, "-c", prog], capture_output=True, text=True,
            check=True, env=env,
        )
        assert out.stdout.strip() == here

    def test_non_json_stable_values_fail_loudly(self, olmo):
        """Anything whose serialization would depend on object identity
        raises TypeError instead of silently keying the cache on it."""
        cfg, _ = olmo

        class Opaque:
            pass

        with pytest.raises(TypeError, match="not JSON-stable"):
            api.config_fingerprint(cfg, {"table": Opaque()})
        with pytest.raises(TypeError, match="not JSON-stable"):
            api.config_fingerprint(cfg, {"fn": lambda x: x})
        with pytest.raises(TypeError, match="non-finite"):
            api.config_fingerprint(cfg, {"scale": float("nan")})
        with pytest.raises(TypeError, match="key"):
            api.config_fingerprint(cfg, {"deep": {1: "non-str-key"}})
        # tuples/lists/dicts of scalars stay fingerprintable
        fp = api.config_fingerprint(cfg, {"shape": (1, 2), "f": 0.5,
                                          "flag": True, "none": None})
        assert len(fp) == 64
        # and a tuple fingerprints like its list form (JSON normal form)
        assert fp == api.config_fingerprint(cfg, {"shape": [1, 2], "f": 0.5,
                                                  "flag": True, "none": None})
