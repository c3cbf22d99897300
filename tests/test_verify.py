"""Static plan verifier: mutation suite + shipping-config cleanliness.

The contract under test (ISSUE 7): every statically decidable hazard
class is caught with the right rule id, and every artifact the flow
actually ships verifies clean — including under ``--strict``.

The mutation tests work on the JSON form (``to_dict`` -> surgical edit ->
``from_dict(validate=False)``): that is exactly the CLI's threat model
(artifacts corrupted on disk or by hand), and ``validate=False`` keeps
the constructor's asserts from dying before the verifier can report.
"""

import json

import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.deploy import api
from repro.deploy.plan import DecoderPlanPair
from repro.deploy.verify import (
    PlanVerificationError,
    check,
    load_artifact,
    main,
    verify,
    verify_plan,
)


@pytest.fixture(scope="module")
def cfg():
    return reduced(get_config("olmo-1b"))


@pytest.fixture(scope="module")
def dense_pair(cfg):
    return api.compile(cfg, seq_len=8, max_len=14, use_cache=False).artifact


@pytest.fixture(scope="module")
def paged_pair(cfg):
    return api.compile(cfg, seq_len=8, max_len=14, kv_block_size=4,
                       kv_blocks=8, use_cache=False).artifact


def _mutated(pair, mutate, which="decode"):
    d = pair.to_dict()
    mutate(d[which] if which else d)
    return DecoderPlanPair.from_dict(d, validate=False)


def _error_rules(artifact):
    return {d.rule for d in verify(artifact) if d.severity == "error"}


# ---------------------------------------------------------------------------
# shipping configs verify clean (strict: zero diagnostics)
# ---------------------------------------------------------------------------

class TestShippingClean:
    def test_dense_pairs_clean(self, dense_pair):
        assert verify(dense_pair) == []

    def test_paged_pair_clean(self, paged_pair):
        assert verify(paged_pair) == []

    def test_encoder_clean(self):
        m = api.compile(reduced(get_config("mobilebert")), seq_len=64,
                        use_cache=False)
        assert verify(m.artifact) == []

    def test_check_strict_passes_shipping(self, dense_pair, paged_pair):
        assert check(dense_pair, strict=True) == []
        assert check(paged_pair, strict=True) == []


# ---------------------------------------------------------------------------
# mutation suite: one defect class -> its rule id
# ---------------------------------------------------------------------------

class TestMutations:
    def test_offset_overlap_mem001(self, dense_pair):
        def overlap(p):
            kv = {n for kv_pair in p["kv_state"] for n in kv_pair if n}
            for n in p["nodes"]:
                cands = [
                    t for t in n["inputs"]
                    if t in p["tensors"] and not p["tensors"][t]["weight"]
                    and p["tensors"][t]["offset"] is not None
                    and p["tensors"][t]["size"] > 0 and t not in kv
                ]
                if len(cands) >= 2 and (p["tensors"][cands[0]]["offset"]
                                        != p["tensors"][cands[1]]["offset"]):
                    p["tensors"][cands[0]]["offset"] = \
                        p["tensors"][cands[1]]["offset"]
                    return
            raise AssertionError("no co-live activation pair found")

        rules = _error_rules(_mutated(dense_pair, overlap))
        assert "MEM001" in rules

    def test_def_before_use_df001(self, dense_pair):
        def swap_dependent(p):
            nodes = p["nodes"]
            for i in range(len(nodes) - 1):
                if set(nodes[i]["outputs"]) & set(nodes[i + 1]["inputs"]):
                    nodes[i], nodes[i + 1] = nodes[i + 1], nodes[i]
                    sched = p["schedule"]
                    sched[i], sched[i + 1] = sched[i + 1], sched[i]
                    return
            raise AssertionError("no adjacent dependent nodes")

        rules = _error_rules(_mutated(dense_pair, swap_dependent))
        assert "DF001" in rules

    def test_kv_war_hazard_kv001(self, dense_pair):
        def stale_read(p):
            cin, cout = p["kv_state"][0]
            for n in p["nodes"]:
                if cout in n["inputs"]:
                    n["inputs"] = [cin if t == cout else t
                                   for t in n["inputs"]]
                    return
            raise AssertionError(f"no reader of {cout}")

        rules = _error_rules(_mutated(dense_pair, stale_read))
        assert "KV001" in rules

    def test_pair_offset_mismatch_kv002(self, dense_pair):
        def swap_cache_offsets(p):
            (k_in, k_out), (v_in, v_out) = p["kv_state"][0], p["kv_state"][1]
            t = p["tensors"]
            ko, vo = t[k_in]["offset"], t[v_in]["offset"]
            for name in (k_in, k_out):
                t[name]["offset"] = vo
            for name in (v_in, v_out):
                t[name]["offset"] = ko

        rules = _error_rules(_mutated(dense_pair, swap_cache_offsets))
        assert "KV002" in rules

    def test_scale_overflow_qnt001(self, dense_pair):
        def blow_up_weight_scale(p):
            for n in p["nodes"]:
                if n["kind"] == "gemm":
                    s = n["attrs"]["scales"]
                    n["attrs"]["scales"] = [s[0], 1e6, s[2]]
                    return
            raise AssertionError("no gemm node")

        rules = _error_rules(_mutated(dense_pair, blow_up_weight_scale))
        assert "QNT001" in rules

    def test_illegal_engine_eng001(self, dense_pair):
        def flip_engine(p):
            for n in p["nodes"]:
                if n["kind"] == "cache_write":
                    n["engine"] = "ita"
                    return
            raise AssertionError("no cache_write node")

        rules = _error_rules(_mutated(dense_pair, flip_engine))
        assert "ENG001" in rules

    def test_paged_scratch_read_kv004(self, paged_pair):
        def direct_pool_access(p):
            for n in p["nodes"]:
                if n["kind"] == "attn_paged":
                    n["kind"] = "attn_cached"
                    return
            raise AssertionError("no attn_paged node")

        rules = _error_rules(_mutated(paged_pair, direct_pool_access))
        assert "KV004" in rules

    # -- beyond the required eight ----------------------------------------

    def test_accumulator_overflow_qnt002(self, dense_pair):
        def deepen_contraction(p):
            for n in p["nodes"]:
                if n["kind"] == "gemm":
                    m, _, nn = n["attrs"]["dims"]
                    n["attrs"]["dims"] = [m, 150_000, nn]
                    return

        rules = _error_rules(_mutated(dense_pair, deepen_contraction))
        assert "QNT002" in rules

    def test_paged_geometry_kv005(self, paged_pair):
        def corrupt_pool_shape(p):
            cin, _ = p["kv_state"][0]
            shape = p["tensors"][cin]["shape"]
            p["tensors"][cin]["shape"] = [shape[0] + 1] + list(shape[1:])

        rules = _error_rules(_mutated(paged_pair, corrupt_pool_shape))
        assert "KV005" in rules

    def test_beyond_peak_mem002(self, dense_pair):
        def move_past_peak(p):
            for name, t in p["tensors"].items():
                if not t["weight"] and t["offset"] is not None and t["size"]:
                    t["offset"] = p["memory_peak"] + 64
                    return

        rules = _error_rules(_mutated(dense_pair, move_past_peak))
        assert "MEM002" in rules

    def test_schedule_desync_df004(self, dense_pair):
        def rename_in_schedule(p):
            p["schedule"][0] = "bogus_node"

        rules = _error_rules(_mutated(dense_pair, rename_in_schedule))
        assert "DF004" in rules

    def test_unknown_kind_eng002(self, dense_pair):
        def alien_kind(p):
            p["nodes"][0]["kind"] = "quantum_annealer"

        rules = _error_rules(_mutated(dense_pair, alien_kind))
        assert "ENG002" in rules

    def test_v5_fused_region_node_eng002(self, dense_pair, tmp_path):
        """A plan written before region fusion was removed may hold a
        ``fused_region`` node with a ``body``; read from JSON, the audit
        names it as an unknown kind instead of crashing or passing."""
        d = dense_pair.to_dict()
        nodes = d["decode"]["nodes"]
        i = next(i for i in range(len(nodes) - 1)
                 if nodes[i]["engine"] == nodes[i + 1]["engine"])
        body = nodes[i:i + 2]
        produced = set(body[0]["outputs"])
        region = {
            "name": "fused0_" + body[0]["engine"], "op": "FusedRegion",
            "kind": "fused_region", "engine": body[0]["engine"],
            "inputs": list(dict.fromkeys(
                t for b in body for t in b["inputs"] if t not in produced)),
            "outputs": [o for b in body for o in b["outputs"]],
            "attrs": {"n_body": 2}, "body": body,
        }
        nodes[i:i + 2] = [region]
        d["decode"]["schedule"] = [n["name"] for n in nodes]
        path = tmp_path / "v5.json"
        path.write_text(json.dumps(d))
        diags = verify(load_artifact(str(path)))
        assert [(x.rule, x.severity) for x in diags if x.node == region["name"]] \
            == [("ENG002", "error")]
        assert main([str(path)]) == 1


# ---------------------------------------------------------------------------
# severities, check(), compile()/load() wiring
# ---------------------------------------------------------------------------

def _decomp_warning_pair(dense_pair):
    """k=16384 keeps the int32 accumulator legal but provably exceeds the
    exact requant decomposition bound for any maximized multiplier."""
    def widen(p):
        for n in p["nodes"]:
            if n["kind"] == "gemm":
                m, _, nn = n["attrs"]["dims"]
                n["attrs"]["dims"] = [m, 16_384, nn]
                return
    return _mutated(dense_pair, widen)


class TestSeveritiesAndWiring:
    def test_decomposition_bound_is_warning_not_error(self, dense_pair):
        mutant = _decomp_warning_pair(dense_pair)
        diags = verify(mutant)
        assert diags and all(d.severity == "warning" for d in diags)
        assert {d.rule for d in diags} == {"QNT002"}
        # non-strict check returns them; strict check raises
        assert check(mutant) == diags
        with pytest.raises(PlanVerificationError):
            check(mutant, strict=True)

    def test_error_raises_with_all_diagnostics(self, dense_pair):
        def two_defects(p):
            p["schedule"][0] = "bogus_node"
            for n in p["nodes"]:
                if n["kind"] == "gemm":
                    s = n["attrs"]["scales"]
                    n["attrs"]["scales"] = [s[0], 1e6, s[2]]
                    break

        mutant = _mutated(dense_pair, two_defects)
        with pytest.raises(PlanVerificationError) as ei:
            check(mutant, context="unit-test")
        rules = {d.rule for d in ei.value.diagnostics}
        assert {"DF004", "QNT001"} <= rules
        assert "unit-test" in str(ei.value)

    def test_compile_records_verification(self, cfg):
        m = api.compile(cfg, seq_len=8, max_len=14, use_cache=False)
        assert m.diagnostics == ()
        assert m.verify_ms > 0.0

    def test_compile_verify_false_skips(self, cfg):
        m = api.compile(cfg, seq_len=8, max_len=14, use_cache=False,
                        verify=False)
        assert m.diagnostics == () and m.verify_ms == 0.0

    def test_cache_hit_is_reverified(self, cfg, tmp_path):
        """A cached artifact edited on disk (in a way the constructor's
        asserts cannot see — an engine flip) must fail the re-verifying
        cache-hit path, not execute on the wrong engine."""
        cache = str(tmp_path / "plans")
        kw = dict(seq_len=8, max_len=14, cache_dir=cache)
        m = api.compile(cfg, **kw)
        assert not m.cache_hit and m.cache_path
        payload = json.loads(open(m.cache_path).read())
        for n in payload["artifact"]["decode"]["nodes"]:
            if n["kind"] == "cache_write":
                n["engine"] = "ita"
                break
        with open(m.cache_path, "w") as f:
            json.dump(payload, f)
        with pytest.raises(PlanVerificationError):
            api.compile(cfg, **kw)
        # verify=False still loads it (debugging escape hatch)
        m2 = api.compile(cfg, **kw, verify=False)
        assert m2.cache_hit

    def test_model_load_reverifies(self, cfg, tmp_path, dense_pair):
        m = api.compile(cfg, seq_len=8, max_len=14, use_cache=False)
        path = str(tmp_path / "model.json")
        m.save(path)
        loaded = api.CompiledModel.load(path, cfg)
        assert loaded.verify_ms > 0.0
        payload = json.loads(open(path).read())
        for n in payload["artifact"]["decode"]["nodes"]:
            if n["kind"] == "cache_write":
                n["engine"] = "ita"
                break
        with open(path, "w") as f:
            json.dump(payload, f)
        with pytest.raises(PlanVerificationError):
            api.CompiledModel.load(path, cfg)
        assert api.CompiledModel.load(path, cfg, verify=False) is not None


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

class TestCli:
    def test_clean_artifacts_pass(self, dense_pair, paged_pair, tmp_path):
        a = str(tmp_path / "dense.json")
        b = str(tmp_path / "paged.json")
        dense_pair.save(a)
        paged_pair.save(b)
        assert main([a, b]) == 0
        assert main([a, b, "--strict"]) == 0

    def test_corrupt_artifact_fails(self, dense_pair, tmp_path, capsys):
        d = dense_pair.to_dict()
        d["decode"]["schedule"][0] = "bogus_node"
        path = str(tmp_path / "corrupt.json")
        with open(path, "w") as f:
            json.dump(d, f)
        assert main([path]) == 1
        out = capsys.readouterr().out
        assert "DF004" in out and "FAIL" in out

    def test_warnings_fail_only_under_strict(self, dense_pair, tmp_path):
        mutant = _decomp_warning_pair(dense_pair)
        path = str(tmp_path / "warn.json")
        mutant.save(path)
        assert main([path]) == 0
        assert main([path, "--strict"]) == 1

    def test_compiled_model_envelope_loads(self, cfg, tmp_path):
        m = api.compile(cfg, seq_len=8, max_len=14, use_cache=False)
        path = str(tmp_path / "model.json")
        m.save(path)
        artifact = load_artifact(path)
        assert isinstance(artifact, DecoderPlanPair)
        assert main([path, "--strict"]) == 0

    def test_unreadable_path_is_rc2(self, tmp_path):
        assert main([str(tmp_path / "missing.json")]) == 2


# ---------------------------------------------------------------------------
# satellite: structured binding errors
# ---------------------------------------------------------------------------

class TestBindingChecks:
    def test_weight_bind_lists_all_mismatches(self, cfg):
        from repro.deploy.executor import PlanBindingError, check_bindings

        m = api.compile(cfg, seq_len=8, max_len=14, use_cache=False)
        weights, _ = m.bind()
        plan = m.artifact.prefill
        names = plan.weight_names[:2]
        broken = dict(weights)
        del broken[names[0]]
        broken[names[1]] = np.zeros((1, 1), np.int8)  # wrong shape
        with pytest.raises(PlanBindingError) as ei:
            check_bindings(plan, weights=broken)
        msg = str(ei.value)
        assert names[0] in msg and names[1] in msg
        assert len(ei.value.mismatches) == 2

    def test_clean_weights_bind(self, cfg):
        m = api.compile(cfg, seq_len=8, max_len=14, use_cache=False)
        weights, _ = m.bind()  # _check_bound ran inside without raising
        assert weights

    def test_input_bind_rejects_bad_batch(self, cfg):
        from repro.deploy.executor import PlanBindingError, execute

        m = api.compile(cfg, seq_len=8, max_len=14, use_cache=False)
        weights, _ = m.bind()
        plan = m.artifact.prefill
        with pytest.raises(PlanBindingError) as ei:
            execute(plan, weights, {"tokens": np.zeros((2, 9), np.int32)})
        assert "tokens" in str(ei.value)
        with pytest.raises(PlanBindingError) as ei:
            execute(plan, weights, {})
        assert "missing from the batch" in str(ei.value)


# ---------------------------------------------------------------------------
# satellite: structured memory-plan overlap reporting
# ---------------------------------------------------------------------------

class TestMemoryPlanError:
    def test_violations_name_pairs_and_ranges(self):
        from repro.deploy.memory import Allocation, MemoryPlan, MemoryPlanError

        a = Allocation("x", 0, 64, 0, 3)
        b = Allocation("y", 32, 64, 2, 5)
        plan = MemoryPlan({"x": a, "y": b}, peak=96)
        assert plan.overlap_violations() == [(a, b)]
        assert not plan.check_no_overlap()
        with pytest.raises(MemoryPlanError) as ei:
            plan.check()
        msg = str(ei.value)
        assert "x" in msg and "y" in msg and "[0, 64)" in msg \
            and "[32, 96)" in msg
        assert ei.value.violations == [(a, b)]

    def test_clean_plan_checks_through(self):
        from repro.deploy.memory import Allocation, MemoryPlan

        plan = MemoryPlan(
            {"x": Allocation("x", 0, 64, 0, 1),
             "y": Allocation("y", 0, 64, 2, 3)},  # disjoint lifetimes
            peak=64,
        )
        assert plan.check() is plan


# ---------------------------------------------------------------------------
# satellite: engine surfaces the one-time verification cost
# ---------------------------------------------------------------------------

class TestEngineVerifyMs:
    def test_stats_carry_verify_ms(self, cfg):
        from repro.deploy.engine import Engine

        m = api.compile(cfg, seq_len=8, max_len=14, use_cache=False)
        eng = Engine(m, max_batch=1)
        assert eng.stats.verify_ms == m.verify_ms > 0.0
        assert "verified" in eng.stats.summary()
        assert eng.reset_stats().verify_ms == m.verify_ms


# ---------------------------------------------------------------------------
# label plumbing
# ---------------------------------------------------------------------------

class TestDiagnosticShape:
    def test_labels_and_format(self, dense_pair):
        d = dense_pair.to_dict()
        d["decode"]["schedule"][0] = "bogus_node"
        mutant = DecoderPlanPair.from_dict(d, validate=False)
        diags = [x for x in verify(mutant) if x.rule == "DF004"]
        assert diags and diags[0].plan == "decode"
        line = diags[0].format()
        assert "ERROR" in line and "DF004" in line and "decode" in line

    def test_verify_plan_standalone(self, dense_pair):
        assert verify_plan(dense_pair.decode, "decode") == []
