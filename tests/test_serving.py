"""Async serving frontend + SLO-aware scheduling (ISSUE 8).

Acceptance contract: :class:`AsyncEngine` token streams are bit-exact vs
the synchronous :class:`Engine` on identical request sets — ``w8a8`` and
``ita``, dense and paged KV — *including* preemption + requeue (a
requeued request's final stream is identical to an uninterrupted run);
``PriorityDeadline`` ordering is deterministic under a fake clock,
starvation-free under aging, and preempts exactly the over-budget
outranked residents; bounded queues shed with a structured
:class:`QueueFullError` or by displacing the worst-ranked queued request
when the newcomer outranks it; N producer threads submitting into one engine
all complete-or-shed with no duplicated or lost tokens; and the stdlib
HTTP frontend streams, reports status/stats, maps errors to structured
4xx/5xx and drains gracefully.
"""

import json
import threading
import urllib.error

import jax
import pytest

from repro.configs import get_config, reduced
from repro.deploy import api
from repro.deploy.engine import Engine, Temperature
from repro.deploy.serving.async_engine import AsyncEngine
from repro.deploy.serving.frontend import ServingFrontend
from repro.deploy.serving.scheduler import (
    FIFO,
    PriorityDeadline,
    QueueFullError,
    effective_deadline,
    make_scheduler,
)
from repro.launch.cli import http_generate, http_get_json
from repro.models import transformer as T

SEQ = 8
MAX_LEN = 24


@pytest.fixture(scope="module")
def olmo():
    cfg = reduced(get_config("olmo-1b"))
    params = T.init_params(cfg, jax.random.PRNGKey(7))
    return cfg, params


def _compile(cfg, backend="w8a8", *, paged=False, max_len=MAX_LEN,
             kv_blocks=14):
    kw = dict(kv_block_size=4, kv_blocks=kv_blocks) if paged else {}
    return api.compile(cfg, backend=backend, seq_len=SEQ, max_len=max_len,
                       use_cache=False, **kw)


@pytest.fixture(scope="module")
def dense_model(olmo):
    return _compile(olmo[0])


def _prompts(cfg, n, *, lengths=(SEQ, SEQ + 2), seed=0):
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed)
    return [
        [int(t) for t in jax.random.randint(jax.random.fold_in(key, i),
                                            (lengths[i % len(lengths)],), 0,
                                            cfg.vocab, jnp.int32)]
        for i in range(n)
    ]


class _FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


def _mk(rid, *, priority=0, ttft=None, deadline=None, arrival=0.0):
    """Bare handle stand-in for scheduler unit tests (no engine)."""

    class H:
        pass

    h = H()
    h.rid = rid
    h.priority = priority
    h.ttft_slo_ms = ttft
    h.deadline_ms = deadline
    h.arrival_t = arrival
    h.deadline_t = None if deadline is None else arrival + deadline / 1e3
    h.admit_deadline_t = effective_deadline(arrival, ttft, deadline)
    return h


class TestSchedulerPolicies:
    def test_fifo_orders_by_submission_and_default_unbounded(self):
        s = FIFO()
        hs = [_mk(i) for i in range(50)]
        for h in hs:
            s.add(h, 0.0)
        assert [s.pop(0.0).rid for _ in range(50)] == list(range(50))
        assert s.pop(0.0) is None and s.peek(0.0) is None

    def test_bounded_queue_sheds_with_structured_error(self):
        s = FIFO(max_queue=2)
        s.add(_mk(0), 0.0)
        s.add(_mk(1), 0.1)
        with pytest.raises(QueueFullError) as ei:
            s.add(_mk(2), 0.2)
        e = ei.value
        assert e.queue_depth == 2 and e.max_queue == 2
        assert e.retry_after_s > 0
        # requeues are NOT shed: admission already happened once
        s.requeue(_mk(3), 0.3)
        assert len(s) == 3

    def test_displacement_sheds_worst_queued_for_outranking_arrival(self):
        s = PriorityDeadline(max_queue=2, aging_s=1e9)
        bg = [_mk(0, priority=5, ttft=900.0), _mk(1, priority=5, ttft=100.0)]
        assert s.add(bg[0], 0.0) is None and s.add(bg[1], 0.0) is None
        # an urgent newcomer displaces the WORST-ranked queued request
        # (bg[0]: later deadline), not whoever arrived last
        urgent = _mk(2, priority=0, ttft=50.0)
        assert s.add(urgent, 0.0) is bg[0]
        assert len(s) == 2
        assert [s.pop(0.0).rid for _ in range(2)] == [2, 1]
        # a newcomer that outranks nobody still sheds via QueueFullError
        s2 = PriorityDeadline(max_queue=1, aging_s=1e9)
        s2.add(_mk(0, priority=0, ttft=50.0), 0.0)
        with pytest.raises(QueueFullError):
            s2.add(_mk(1, priority=5), 0.0)
        # EXPIRED queued work is displaced first, for ANY newcomer —
        # past its admission deadline the shed can never cost goodput
        s3 = PriorityDeadline(max_queue=2, aging_s=1e9)
        doomed = _mk(0, priority=0, ttft=50.0)   # urgent, dead by now=1.0
        fresh = _mk(1, priority=5, ttft=5000.0)
        s3.add(doomed, 0.0)
        s3.add(fresh, 0.0)
        late_bg = _mk(2, priority=9)             # outranks nobody
        assert s3.add(late_bg, 1.0) is doomed
        assert sorted(h.rid for h in (s3.pop(1.0), s3.pop(1.0))) == [1, 2]
        # FIFO never displaces — equal-depth overflow is always a refusal
        f = FIFO(max_queue=1)
        assert f.add(_mk(0), 0.0) is None
        with pytest.raises(QueueFullError):
            f.add(_mk(1, priority=-10, ttft=1.0), 0.0)

    def test_engine_finishes_displaced_handle_as_shed(self, olmo,
                                                      dense_model):
        cfg, params = olmo
        eng = Engine(dense_model, 1, params=params,
                     scheduler=PriorityDeadline(max_queue=1))
        p = _prompts(cfg, 1)[0]
        bg = eng.submit(p, 2, priority=5)
        urgent = eng.submit(p, 2, priority=0, ttft_slo_ms=50.0)
        assert bg.done and bg.finish_reason == "shed"
        assert eng.stats.shed_requests == 1
        assert eng.stats.requests_evicted == 1
        eng.run_until_idle(max_steps=100)
        assert urgent.finish_reason == "length" and len(urgent.tokens) == 2

    def test_priority_dominates_then_deadline_then_arrival(self):
        s = PriorityDeadline(aging_s=1e9)  # aging off for this test
        urgent = _mk(2, priority=0, ttft=500.0)
        sooner = _mk(1, priority=5, ttft=100.0)
        later = _mk(0, priority=5, ttft=900.0)
        for h in (later, sooner, urgent):
            s.add(h, 0.0)
        assert [s.pop(0.0).rid for _ in range(3)] == [2, 1, 0]

    def test_arrival_breaks_exact_ties(self):
        s = PriorityDeadline(aging_s=1e9)
        a, b = _mk(0, priority=1), _mk(1, priority=1)
        s.add(b, 0.0)
        s.add(a, 0.0)
        assert s.pop(0.0).rid == 0  # same aged priority, same (inf)
        assert s.pop(0.0).rid == 1  # deadline -> submission order wins

    def test_aging_promotes_waiting_requests(self):
        s = PriorityDeadline(aging_s=1.0)
        old_low = _mk(0, priority=5, arrival=0.0)
        fresh_high = _mk(1, priority=0, arrival=9.0)
        s.add(old_low, 0.0)
        s.add(fresh_high, 9.0)
        # at t=9 old_low has aged 9 levels: 5-9=-4 < 0 -> admitted first
        assert s.pop(9.0).rid == 0

    def test_victims_only_over_budget_and_outranked(self):
        s = PriorityDeadline(aging_s=1e9)
        resident_ok = _mk(0, priority=5)                     # no deadline
        resident_over = _mk(1, priority=5, deadline=100.0)   # blown at t=1
        assert s.victims([resident_ok, resident_over], 1.0) == []  # queue empty
        s.add(_mk(2, priority=0), 1.0)  # strictly outranks rid=1
        v = s.victims([resident_ok, resident_over], 1.0)
        assert [h.rid for h in v] == [1]  # never the no-deadline resident
        # a queued request that does NOT outrank preempts nothing
        s2 = PriorityDeadline(aging_s=1e9)
        s2.add(_mk(3, priority=9), 1.0)
        assert s2.victims([resident_over], 1.0) == []

    def test_victims_capped_by_outranking_queue_depth(self):
        s = PriorityDeadline(aging_s=1e9)
        residents = [_mk(i, priority=5, deadline=100.0) for i in range(3)]
        s.add(_mk(10, priority=0), 1.0)  # ONE outranker
        assert len(s.victims(residents, 1.0)) == 1

    def test_registry_and_validation(self):
        assert isinstance(make_scheduler("fifo"), FIFO)
        pd = make_scheduler("priority-deadline", max_queue=4, aging_s=2.0)
        assert isinstance(pd, PriorityDeadline)
        assert pd.max_queue == 4 and pd.aging_s == 2.0
        with pytest.raises(ValueError, match="unknown scheduler"):
            make_scheduler("lifo")
        with pytest.raises(ValueError, match="aging_s"):
            PriorityDeadline(aging_s=0.0)
        with pytest.raises(ValueError, match="max_queue"):
            FIFO(max_queue=-1)

    def test_effective_deadline(self):
        import math

        assert effective_deadline(1.0, None, None) == math.inf
        assert effective_deadline(1.0, 500.0, None) == pytest.approx(1.5)
        assert effective_deadline(1.0, 500.0, 200.0) == pytest.approx(1.2)

    def test_engine_rejects_used_scheduler(self, olmo, dense_model):
        cfg, params = olmo
        s = FIFO()
        s.add(_mk(0), 0.0)
        with pytest.raises(ValueError, match="fresh"):
            Engine(dense_model, 1, params=params, scheduler=s)


class TestAsyncBitExact:
    @pytest.mark.parametrize("backend,paged", [
        ("w8a8", False), ("w8a8", True), ("ita", False), ("ita", True),
    ], ids=["w8a8-dense", "w8a8-paged", "ita-dense", "ita-paged"])
    def test_async_streams_match_sync_engine(self, olmo, backend, paged):
        """The background loop thread changes *when* steps happen, never
        what they compute: same request set, identical per-request
        streams vs the synchronous engine on every backend/KV combo."""
        cfg, params = olmo
        model = _compile(cfg, backend, paged=paged)
        n = 4 if backend == "w8a8" else 3
        prompts = _prompts(cfg, n, seed=3)
        gens = [3, 4, 2, 3][:n]

        sync = Engine(model, 2, params=params)
        ref = [sync.submit(p, g) for p, g in zip(prompts, gens)]
        sync.run_until_idle(max_steps=300)

        with AsyncEngine(model, 2, params=params) as eng:
            hs = [eng.submit(p, g) for p, g in zip(prompts, gens)]
            streams = [[t for t in h] for h in hs]  # blocking iteration
            for h, r, stream in zip(hs, ref, streams):
                raw = h.result(timeout=120)
                assert raw.tokens == r.tokens
                assert stream == r.tokens
                assert raw.finish_reason == r.finish_reason

    @pytest.mark.parametrize("paged", [False, True],
                             ids=["dense", "paged"])
    def test_preempted_requeued_stream_is_bit_exact(self, olmo, paged):
        """A resident evicted back to the queue resumes with its full
        prefix teacher-forced and the sampling index unchanged — the
        final stream equals an uninterrupted run (temperature sampling,
        so any index/slot drift would diverge instantly)."""
        cfg, params = olmo
        model = _compile(cfg, paged=paged)

        ref_eng = Engine(model, 1, params=params,
                         sampling=Temperature(0.8, jax.random.PRNGKey(3)))
        ref = ref_eng.submit(list(range(10)), 8)
        ref_eng.run_until_idle(max_steps=200)

        clk = _FakeClock()
        eng = Engine(model, 1, params=params,
                     sampling=Temperature(0.8, jax.random.PRNGKey(3)),
                     scheduler=PriorityDeadline(), clock=clk)
        h = eng.submit(list(range(10)), 8, priority=5, deadline_ms=100)
        for _ in range(6):  # admit + generate a few tokens
            eng.step()
        assert h.tokens, "setup: nothing generated before preemption"
        clk.t = 1.0  # blow h's completion budget
        hi = eng.submit(list(range(8)), 2, priority=0)
        eng.run_until_idle(max_steps=300)
        assert h.preemptions >= 1
        assert h.tokens == ref.tokens
        assert h.finish_reason == ref.finish_reason
        assert hi.finish_reason == "length" and len(hi.tokens) == 2
        assert eng.stats.preemptions == eng.stats.requeues == h.preemptions

    def test_requeued_request_streams_each_token_once(self, olmo):
        """Preemption must not re-fire on_token for already-streamed
        tokens: the resumed prefix is teacher-forced, not re-sampled."""
        cfg, params = olmo
        model = _compile(cfg)
        seen = []
        clk = _FakeClock()
        eng = Engine(model, 1, params=params,
                     scheduler=PriorityDeadline(), clock=clk)
        h = eng.submit(list(range(10)), 6, priority=5, deadline_ms=100,
                       on_token=seen.append)
        for _ in range(5):
            eng.step()
        clk.t = 1.0
        eng.submit(list(range(8)), 1, priority=0)
        eng.run_until_idle(max_steps=200)
        assert h.preemptions >= 1
        assert seen == h.tokens  # every token exactly once, in order


class TestAsyncLifecycle:
    def test_idle_engine_does_not_busy_spin(self, olmo, dense_model):
        cfg, params = olmo
        with AsyncEngine(dense_model, 1, params=params) as eng:
            eng.submit(_prompts(cfg, 1)[0], 2).result(timeout=120)
            steps_after_drain = len(eng.stats.step_times_s)
            import time

            time.sleep(0.25)  # idle: the loop must be waiting, not stepping
            assert len(eng.stats.step_times_s) == steps_after_drain

    def test_result_timeout_raises(self, olmo, dense_model):
        cfg, params = olmo
        with AsyncEngine(dense_model, 1, params=params) as eng:
            h = eng.submit(_prompts(cfg, 1)[0], 14)
            with pytest.raises(TimeoutError, match="not finished"):
                h.result(timeout=1e-4)
            assert h.result(timeout=120).finish_reason == "length"

    def test_submit_after_close_raises(self, olmo, dense_model):
        cfg, params = olmo
        eng = AsyncEngine(dense_model, 1, params=params)
        eng.close()
        with pytest.raises(RuntimeError, match="closed"):
            eng.submit(_prompts(cfg, 1)[0], 2)

    def test_close_without_drain_cancels_live_work(self, olmo, dense_model):
        cfg, params = olmo
        eng = AsyncEngine(dense_model, 1, params=params)
        hs = [eng.submit(p, 30) for p in _prompts(cfg, 3)]
        eng.close(drain=False, timeout=60)
        assert all(h.done for h in hs)
        assert any(h.finish_reason == "cancelled" for h in hs)

    def test_cancel_from_other_thread(self, olmo, dense_model):
        cfg, params = olmo
        with AsyncEngine(dense_model, 1, params=params) as eng:
            hs = [eng.submit(p, 10) for p in _prompts(cfg, 3)]
            hs[2].cancel()   # still queued behind hs[1]
            hs[0].cancel()   # possibly resident: routed to the loop thread
            done = hs[1].result(timeout=120)
            assert done.finish_reason == "length"
            for h in (hs[0], hs[2]):
                assert h.result(timeout=120).finish_reason == "cancelled"

    def test_threaded_producers_all_complete_or_shed(self, olmo, dense_model):
        """N producer threads hammer one bounded-queue engine: every
        submission either completes with its exact single-request
        reference stream (no lost/duplicated/cross-wired tokens) or is
        shed with QueueFullError — and the stats account for all of it."""
        cfg, params = olmo
        prompts = _prompts(cfg, 4, seed=5)

        ref_eng = Engine(dense_model, 2, params=params)
        refs = [ref_eng.submit(p, 4) for p in prompts]
        ref_eng.run_until_idle(max_steps=300)
        expect = {i: r.tokens for i, r in enumerate(refs)}

        results: dict[tuple[int, int], list] = {}
        shed = []
        with AsyncEngine(dense_model, 2, params=params,
                         scheduler=FIFO(max_queue=6)) as eng:
            def producer(t):
                for j in range(4):
                    try:
                        h = eng.submit(prompts[j], 4)
                    except QueueFullError:
                        shed.append((t, j))
                        continue
                    raw = h.result(timeout=120)
                    results[(t, j)] = (raw.tokens, raw.finish_reason)

            threads = [threading.Thread(target=producer, args=(t,))
                       for t in range(4)]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            eng.drain(timeout=120)
            stats = eng.stats

        assert len(results) + len(shed) == 16
        for (_, j), (tokens, reason) in results.items():
            assert reason == "length"
            assert tokens == expect[j]  # greedy folds rid-free: exact match
        assert stats.requests_completed == len(results)
        assert stats.shed_requests == len(shed)
        assert stats.requests_submitted == len(results)

    def test_adopting_busy_engine_rejected(self, olmo, dense_model):
        cfg, params = olmo
        sync = Engine(dense_model, 1, params=params)
        sync.submit(_prompts(cfg, 1)[0], 2)
        with pytest.raises(ValueError, match="live work"):
            AsyncEngine(sync)


class TestSubmitValidation:
    # empty-prompt / short / over-max_len / pool-impossible refusals are
    # regression-tested in tests/test_engine.py; here only the SLO
    # contract fields added by this layer
    def test_negative_slo_rejected(self, olmo, dense_model):
        cfg, params = olmo
        eng = Engine(dense_model, 1, params=params)
        with pytest.raises(ValueError, match="ttft_slo_ms"):
            eng.submit([1] * SEQ, 2, ttft_slo_ms=-1.0)
        with pytest.raises(ValueError, match="deadline_ms"):
            eng.submit([1] * SEQ, 2, deadline_ms=-5.0)


class TestLatencyStats:
    def test_ttft_tpot_recorded_per_generated_token(self, olmo, dense_model):
        cfg, params = olmo
        eng = Engine(dense_model, 2, params=params)
        hs = [eng.submit(p, 3) for p in _prompts(cfg, 2)]
        eng.run_until_idle(max_steps=200)
        s = eng.stats
        assert len(s.ttft_s) == 2                      # one per request
        assert len(s.tpot_s) == sum(len(h.tokens) for h in hs) - 2
        assert all(t >= 0 for t in s.ttft_s + s.tpot_s)
        assert s.ttft(50) <= s.ttft(99)
        for h in hs:
            assert h.ttft_s is not None and h.finish_t is not None

    def test_goodput_under_slo_with_fake_clock(self, olmo, dense_model):
        cfg, params = olmo
        clk = _FakeClock()
        eng = Engine(dense_model, 1, params=params, clock=clk)
        met = eng.submit([1] * SEQ, 2, ttft_slo_ms=1e6)
        missed = eng.submit([2] * SEQ, 2, ttft_slo_ms=1.0)
        while not eng.idle:
            clk.t += 0.050  # 50 ms per scheduler step
            eng.step()
        assert met.ttft_s is not None and met.ttft_s <= 1e3
        assert missed.ttft_s > 1e-3
        assert eng.stats.goodput_under_slo() == pytest.approx(0.5)

    def test_summary_mentions_slo_and_preemption_counters(self, olmo,
                                                          dense_model):
        cfg, params = olmo
        eng = Engine(dense_model, 1, params=params)
        eng.submit([1] * SEQ, 2)
        eng.run_until_idle(max_steps=100)
        s = eng.stats.summary()
        assert "ttft p50/p99" in s and "tpot p50/p99" in s
        eng.stats.preemptions = 2
        eng.stats.requeues = 2
        eng.stats.shed_requests = 1
        assert "2 preemptions / 2 requeues / 1 shed" in eng.stats.summary()


class TestSessionThreadAffinity:
    def test_mutation_from_second_thread_rejected(self, olmo, dense_model):
        cfg, params = olmo
        eng = Engine(dense_model, 1, params=params)
        eng.submit(_prompts(cfg, 1)[0], 2)
        eng.run_until_idle(max_steps=100)  # binds the session to this thread
        errors = []

        def intruder():
            try:
                eng.session.free_slot(0)
            except RuntimeError as e:
                errors.append(str(e))

        t = threading.Thread(target=intruder)
        t.start()
        t.join()
        assert len(errors) == 1 and "rebind_thread" in errors[0]

    def test_rebind_transfers_ownership(self, olmo, dense_model):
        cfg, params = olmo
        session = dense_model.session(1, params=params)
        session.free_slot(0)  # binds here
        ok = []

        def new_owner():
            session.rebind_thread()
            session.free_slot(0)
            ok.append(True)

        t = threading.Thread(target=new_owner)
        t.start()
        t.join()
        assert ok == [True]


class TestFrontend:
    @pytest.fixture()
    def served(self, olmo, dense_model):
        cfg, params = olmo
        eng = AsyncEngine(dense_model, 2, params=params,
                          scheduler=PriorityDeadline(max_queue=32))
        fe = ServingFrontend(eng, port=0)
        host, port = fe.start()
        yield cfg, host, port, fe
        if fe._thread.is_alive():
            fe.shutdown(drain=False, timeout=60)

    def test_streaming_matches_final_summary(self, olmo, served):
        cfg, host, port, _ = served
        prompt = _prompts(cfg, 1)[0]
        events = list(http_generate(host, port, prompt, 4))
        toks = [e["token"] for e in events if "token" in e]
        final = events[-1]
        assert final["done"] and final["finish_reason"] == "length"
        assert final["tokens"] == toks and len(toks) == 4
        assert [e["index"] for e in events if "token" in e] == [0, 1, 2, 3]

    def test_unary_status_stats_roundtrip(self, olmo, served):
        cfg, host, port, _ = served
        out = http_generate(host, port, _prompts(cfg, 1)[0], 3, stream=False,
                            priority=1, ttft_slo_ms=60_000.0)
        assert out["finish_reason"] == "length" and len(out["tokens"]) == 3
        st = http_get_json(host, port, f"/v1/status/{out['rid']}")
        assert st["status"] == "done" and st["tokens_generated"] == 3
        stats = http_get_json(host, port, "/v1/stats")
        assert stats["requests_completed"] >= 1
        assert stats["goodput_under_slo"] == pytest.approx(1.0)
        # sampling time and queue waits, from the engine's counters
        assert stats["sample_time_s"] > 0
        assert 0 <= stats["queue_wait_p50_ms"] <= stats["queue_wait_p99_ms"]
        assert http_get_json(host, port, "/healthz")["status"] == "ok"

    def test_bad_request_is_structured_400(self, olmo, served):
        cfg, host, port, _ = served
        with pytest.raises(urllib.error.HTTPError) as ei:
            http_generate(host, port, [], 3, stream=False)
        assert ei.value.code == 400
        body = json.loads(ei.value.read().decode())
        assert body["type"] == "ValueError" and "empty prompt" in body["error"]

    def test_unknown_rid_is_404(self, served):
        _, host, port, _ = served
        with pytest.raises(urllib.error.HTTPError) as ei:
            http_get_json(host, port, "/v1/status/999999")
        assert ei.value.code == 404

    def test_shed_is_429_with_retry_after(self, olmo, dense_model):
        cfg, params = olmo
        eng = AsyncEngine(dense_model, 1, params=params,
                          scheduler=FIFO(max_queue=0))  # sheds everything
        fe = ServingFrontend(eng, port=0)
        host, port = fe.start()
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                http_generate(host, port, _prompts(cfg, 1)[0], 2, stream=False)
            assert ei.value.code == 429
            assert int(ei.value.headers["Retry-After"]) >= 1
            body = json.loads(ei.value.read().decode())
            assert body["type"] == "QueueFullError"
            assert body["retry_after_s"] > 0 and body["max_queue"] == 0
            assert eng.stats.shed_requests == 1
        finally:
            fe.shutdown(drain=False, timeout=60)

    def test_graceful_drain_finishes_streams_then_refuses(self, olmo,
                                                          dense_model):
        cfg, params = olmo
        eng = AsyncEngine(dense_model, 2, params=params)
        fe = ServingFrontend(eng, port=0)
        host, port = fe.start()
        h = eng.submit(_prompts(cfg, 1)[0], 6)
        fe.draining = True  # the first phase of shutdown()
        with pytest.raises(urllib.error.HTTPError) as ei:
            http_generate(host, port, _prompts(cfg, 1)[0], 2, stream=False)
        assert ei.value.code == 503
        assert http_get_json(host, port, "/healthz")["status"] == "draining"
        fe.shutdown(drain=True, timeout=120)   # in-flight request finishes
        assert h.done and h.finish_reason == "length"
        with pytest.raises(urllib.error.URLError):
            http_get_json(host, port, "/healthz")  # listener gone
