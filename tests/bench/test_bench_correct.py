"""``correct`` on tiny cells on the CPU: sound runs pass; the control and a
token or an answer altered where it is produced fail.

These drive the whole run (set-up, open or closed loop, the window, the
check against the plain reference) past the harness's look for a chip.
"""

import jax.numpy as jnp
import pytest

from bench import control, run, spec
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def _run(root, name, seed, hooks=None, seconds=3.0, trace=False):
    cell = spec.load_cell(name, root=root)
    rec = {}
    hooks = dict(hooks or {}, record=rec.update)
    line = run.run(cell, seed, seconds, trace, CPU, hooks=hooks,
                   peaks=spec.load_peaks("TPU v5 lite", root=root))
    return cell, line, rec


def _alter_token(engine):
    """Every request's second token comes out one id higher."""
    inner = engine.sampling

    class Altered:
        vocab = inner.vocab

        def __call__(self, row, rid, index):
            tok = inner(row, rid, index)
            return (tok + 1) % self.vocab if index == 1 else tok

    engine.sampling = Altered()


def _alter_answer(session):
    forward = session.forward
    session.forward = lambda x: forward(x).at[0, 0, 0].add(jnp.float32(0.05))


@pytest.fixture(scope="module")
def served(tiny_root):
    return _run(tiny_root, "tiny.serve", 2**31 + 99)


def test_sound_serving_run_is_correct(served):
    _cell, line, rec = served
    assert line["correct"] is True
    assert line["checks"]["max_logit_gap"]["value"] == 0.0
    assert line["checks"]["requests_checked"]["value"] >= 2
    assert list(line)[-1] == "checks"
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"] == dict(CPU, memory_peak_bytes=0)
    assert line["attempted"] > 0 and line["failed"] == 0


def test_serving_control_fails_the_limit(tiny_root, served):
    cell, line, rec = served
    reading = control.control_reading(cell, 2**31 + 99, rec["checked"])
    assert reading > line["checks"]["max_logit_gap"]["limit"]
    assert spec.judge(rec["checks"]) is True
    assert spec.judge(control.control_checks(rec["checks"], reading)) is False


def test_a_token_altered_where_it_is_sampled_is_not_correct(tiny_root):
    _cell, line, _rec = _run(tiny_root, "tiny.serve", 5, hooks={"engine": _alter_token})
    assert line["correct"] is False
    assert line["checks"]["max_logit_gap"]["value"] > 0


@pytest.fixture(scope="module")
def encoded(tiny_root):
    return _run(tiny_root, "tiny.enc", 2**32 + 3, seconds=1.5)


def test_sound_encoder_run_is_correct(encoded):
    _cell, line, rec = encoded
    assert line["correct"] is True
    assert line["checks"]["max_code_diff"]["value"] == 0
    assert set(line["metrics"]) == {"encoder_seq_per_s", "setup_s"}
    # every call sent ahead is fetched before the clock closes the window
    assert rec["counters"]["calls_done"] == rec["counters"]["calls"] == line["attempted"]
    assert rec["counters"]["drain_s"] >= 0


def test_encoder_control_fails_the_limit(encoded):
    cell, line, rec = encoded
    reading = control.control_reading(cell, 2**32 + 3, rec["checked"])
    assert reading > line["checks"]["max_code_diff"]["limit"]
    assert spec.judge(control.control_checks(rec["checks"], reading)) is False


def test_an_answer_altered_where_it_is_produced_is_not_correct(tiny_root):
    _cell, line, _rec = _run(tiny_root, "tiny.enc", 8, hooks={"session": _alter_answer},
                             seconds=1.0)
    assert line["correct"] is False
    assert line["checks"]["max_code_diff"]["value"] >= 1


def test_traced_run_reports_per_layer_metrics(tiny_root):
    _cell, line, _rec = _run(tiny_root, "tiny.serve", 17, seconds=3.0, trace=True)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"decode_occupancy", "decode_step_mfu", "prefill_step_mfu",
                                    "step_mfu.serve", "itl_p95_ms.serve"}
    assert all(0 < m["value"] <= 100 for k, m in line["metrics"].items() if m["unit"] == "%")
    assert {"busy_s", "window_s"} <= set(line["device"]) and "breakdown" in line
