"""The request-level serving engine (ISSUE 4).

Acceptance contract: an ``Engine`` run with staggered submits, mid-flight
admissions, mixed prompt lengths and slot evictions yields per-request
token streams *bit-exact* vs independent single-request
``prefill_w8a8``/``decode_step_w8a8`` trajectories, on both ``w8a8`` and
``ita`` backends; greedy sampling is deterministic across batch
orderings and ``max_batch`` choices; KV-capacity eviction uses the
structured :class:`KVCapacityError` to evict exactly the overflowing
slots; and streaming callbacks observe every token in order.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduced
from repro.deploy import api
from repro.deploy.engine import (
    Engine,
    Greedy,
    RequestStatus,
    Temperature,
)
from repro.models import transformer as T

SEQ = 8
MAX_LEN = SEQ + 8


@pytest.fixture(scope="module")
def olmo():
    """reduced olmo-1b (GQA, RoPE, SwiGLU, tied embeddings) + params."""
    cfg = reduced(get_config("olmo-1b"))
    params = T.init_params(cfg, jax.random.PRNGKey(7))
    return cfg, params


def _compile(cfg, backend="w8a8", max_len=MAX_LEN):
    return api.compile(cfg, backend=backend, seq_len=SEQ, max_len=max_len,
                       use_cache=False)


def _prompts(cfg, n, *, lengths, seed=0):
    key = jax.random.PRNGKey(seed)
    return [
        [int(t) for t in jax.random.randint(jax.random.fold_in(key, i),
                                            (lengths[i % len(lengths)],), 0,
                                            cfg.vocab, jnp.int32)]
        for i in range(n)
    ]


def reference_trajectory(cfg, qp, prompt, max_new, max_len, eos_id=None):
    """One request's independent greedy trajectory on the model path —
    the oracle the engine's scheduled stream must match bit-for-bit.
    Mirrors the engine's lifecycle: static prefill of the first SEQ
    tokens, teacher-forced prompt tail, then greedy generation until
    eos / max_new / KV capacity."""
    lg, cache = T.prefill_w8a8(
        cfg, qp, {"tokens": jnp.asarray(prompt[:SEQ], jnp.int32)[None]}, max_len)
    out, depth = [], SEQ
    while True:
        if depth < len(prompt):
            nxt = prompt[depth]
        else:
            # the engine's Greedy policy masks the LM head's padding lanes
            nxt = int(jnp.argmax(lg[0, -1, : cfg.vocab]))
            out.append(nxt)
            if eos_id is not None and nxt == eos_id:
                return out, "eos"
            if len(out) >= max_new:
                return out, "length"
        if depth >= max_len:
            return out, "kv_capacity"
        lg, cache = T.decode_step_w8a8(cfg, qp, cache,
                                       jnp.asarray([[nxt]], jnp.int32))
        depth += 1


class TestSchedulerBitExact:
    @pytest.mark.parametrize("backend,n,max_batch,gens", [
        ("w8a8", 6, 2, (2, 4, 1, 3)),
        ("ita", 3, 2, (2, 1, 2)),
    ], ids=["w8a8", "ita"])
    def test_random_schedule_bit_exact(self, olmo, backend, n, max_batch, gens):
        """Staggered submits, mixed prompt lengths, mid-flight admissions
        and recycled slots: every request's stream equals its own
        single-request reference trajectory, token for token."""
        cfg, params = olmo
        engine = Engine(_compile(cfg, backend), max_batch, params=params)
        qp = engine.session.qp
        prompts = _prompts(cfg, n, lengths=(SEQ, SEQ + 2, SEQ + 1), seed=3)
        budgets = [gens[i % len(gens)] for i in range(n)]

        # one request stops on EOS: pick its reference's 2nd token as eos
        eos_ids = [None] * n
        if budgets[1] >= 2:
            toks, _ = reference_trajectory(cfg, qp, prompts[1], budgets[1],
                                           MAX_LEN)
            eos_ids[1] = toks[1]
        refs = [reference_trajectory(cfg, qp, prompts[i], budgets[i], MAX_LEN,
                                     eos_id=eos_ids[i]) for i in range(n)]

        # staggered arrival: half up front, the rest mid-flight
        handles = [engine.submit(prompts[i], budgets[i], eos_id=eos_ids[i])
                   for i in range(n // 2)]
        engine.step()
        engine.step()
        handles += [engine.submit(prompts[i], budgets[i], eos_id=eos_ids[i])
                    for i in range(n // 2, n)]
        engine.run_until_idle(max_steps=300)

        for h, (ref_tokens, ref_reason) in zip(handles, refs):
            assert h.status is RequestStatus.DONE
            assert h.tokens == ref_tokens, (h.rid, h.tokens, ref_tokens)
            assert h.finish_reason == ref_reason
        assert engine.stats.tokens_generated == sum(len(h.tokens)
                                                    for h in handles)
        if n > max_batch:
            assert engine.stats.slots_recycled >= 1
        # mixed prompt lengths really exercised the teacher-forced path
        assert engine.stats.prompt_tokens_forced >= 1

    def test_eos_stops_early(self, olmo):
        cfg, params = olmo
        engine = Engine(_compile(cfg), 1, params=params)
        qp = engine.session.qp
        [prompt] = _prompts(cfg, 1, lengths=(SEQ,), seed=5)
        free_run, _ = reference_trajectory(cfg, qp, prompt, 4, MAX_LEN)
        h = engine.submit(prompt, 4, eos_id=free_run[0])
        engine.run_until_idle(max_steps=50)
        assert h.finish_reason == "eos"
        assert h.tokens == free_run[:1]  # EOS recorded, nothing after


class TestKVCapacityEviction:
    def test_structured_error_names_slots(self, olmo):
        """Satellite: the session error carries exactly which slots are
        out of capacity, not one aggregate string."""
        cfg, params = olmo
        model = _compile(cfg, max_len=SEQ + 2)
        session = model.session(2, params=params)
        toks = jnp.asarray(_prompts(cfg, 2, lengths=(SEQ,), seed=1), jnp.int32)
        session.prefill(toks)
        tok = jnp.zeros((2, 1), jnp.int32)
        session.decode(tok)
        session.decode(tok)  # region now full on both slots
        with pytest.raises(api.KVCapacityError) as ei:
            session.decode(tok)
        assert ei.value.slots == (0, 1)
        assert ei.value.pos == (SEQ + 2, SEQ + 2)
        assert ei.value.max_len == SEQ + 2
        # only slot 1 past capacity -> only slot 1 reported
        with pytest.raises(api.KVCapacityError) as ei:
            session.decode(tok, jnp.asarray([0, SEQ + 2], jnp.int32))
        assert ei.value.slots == (1,)

    def test_engine_evicts_precisely_and_recycles(self, olmo):
        """Requests overflowing the KV region finish with reason
        ``kv_capacity`` and their exact reference prefix; the freed slots
        are recycled for the queue."""
        cfg, params = olmo
        max_len = SEQ + 3
        engine = Engine(_compile(cfg, max_len=max_len), 2, params=params)
        qp = engine.session.qp
        prompts = _prompts(cfg, 3, lengths=(SEQ, SEQ + 1), seed=9)
        refs = [reference_trajectory(cfg, qp, p, 10, max_len) for p in prompts]
        assert {r[1] for r in refs} == {"kv_capacity"}  # budget can't fit
        handles = [engine.submit(p, 10) for p in prompts]
        engine.run_until_idle(max_steps=100)
        for h, (ref_tokens, ref_reason) in zip(handles, refs):
            assert h.status is RequestStatus.DONE
            assert h.finish_reason == ref_reason
            assert h.tokens == ref_tokens
        assert engine.stats.slots_recycled >= 1

    def test_submit_rejects_impossible_prompts(self, olmo):
        cfg, params = olmo
        engine = Engine(_compile(cfg), 1, params=params)
        with pytest.raises(ValueError, match="seq_len"):
            engine.submit([1] * (SEQ - 1), 2)
        with pytest.raises(ValueError, match="max_len"):
            engine.submit([1] * (MAX_LEN + 1), 2)
        with pytest.raises(ValueError, match="max_new_tokens"):
            engine.submit([1] * SEQ, 0)

    def test_submit_rejects_empty_prompt(self, olmo):
        """Regression (ISSUE 8): ``submit([])`` used to fall through to
        the generic short-prompt message; it is its own structured
        refusal now, and no engine state changes."""
        cfg, params = olmo
        engine = Engine(_compile(cfg), 1, params=params)
        with pytest.raises(ValueError, match="empty prompt"):
            engine.submit([], 2)
        assert engine.stats.requests_submitted == 0 and engine.idle

    def test_submit_rejects_prompt_larger_than_paged_pool(self, olmo):
        """Regression (ISSUE 8): a prompt needing more KV blocks than
        the whole paged pool is refused at submit time with a structured
        ``KVCapacityError(reason="pool")`` — it used to be accepted and
        then die (or stall admission) inside the step loop."""
        cfg, params = olmo
        model = api.compile(cfg, backend="w8a8", seq_len=SEQ, max_len=40,
                            use_cache=False, kv_block_size=4, kv_blocks=4)
        engine = Engine(model, 1, params=params)
        with pytest.raises(api.KVCapacityError, match="pool holds") as ei:
            engine.submit([1] * 20, 2)  # needs 5 blocks; the pool has 4
        assert ei.value.reason == "pool"
        assert engine.stats.requests_submitted == 0 and engine.idle


class TestDeterminism:
    def test_greedy_across_batch_orderings(self, olmo):
        """The same request set, submitted in a different order onto a
        different slot count, produces identical per-request streams —
        slot placement is invisible (slot isolation is exact)."""
        cfg, params = olmo
        model = _compile(cfg)
        prompts = _prompts(cfg, 4, lengths=(SEQ, SEQ + 1), seed=11)

        def run(order, max_batch):
            engine = Engine(model, max_batch, params=params)
            handles = {i: engine.submit(prompts[i], 3) for i in order}
            engine.run_until_idle(max_steps=200)
            return {i: h.tokens for i, h in handles.items()}

        a = run(range(4), 2)
        b = run(reversed(range(4)), 3)
        assert a == b

    def test_temperature_deterministic_and_order_free(self, olmo):
        """Temperature sampling folds the caller key with (request id,
        token index) — never the slot — so streams are reproducible and
        independent of max_batch."""
        cfg, params = olmo
        model = _compile(cfg)

        shared_policy = Temperature(0.8, jax.random.PRNGKey(4))

        def run(max_batch):
            engine = Engine(model, max_batch, params=params,
                            sampling=shared_policy)
            # the engine binds vocab on its own copy, never on the
            # caller's (possibly shared) policy object
            assert engine.sampling.vocab == cfg.vocab
            assert shared_policy.vocab is None
            prompts = _prompts(cfg, 3, lengths=(SEQ,), seed=2)
            handles = [engine.submit(p, 3) for p in prompts]
            engine.run_until_idle(max_steps=100)
            return [h.tokens for h in handles]

        a = run(1)
        b = run(3)
        assert a == b
        assert all(0 <= t < cfg.vocab for toks in a for t in toks)
        with pytest.raises(ValueError, match="temperature"):
            Temperature(0.0, jax.random.PRNGKey(0))


class TestLifecycle:
    def test_streaming_callback_sees_every_token_in_order(self, olmo):
        cfg, params = olmo
        engine = Engine(_compile(cfg), 2, params=params)
        streams = {}
        prompts = _prompts(cfg, 3, lengths=(SEQ,), seed=6)
        handles = [
            engine.submit(p, 3, on_token=streams.setdefault(i, []).append)
            for i, p in enumerate(prompts)
        ]
        assert all(h.status is RequestStatus.QUEUED for h in handles)
        engine.run_until_idle(max_steps=100)
        for i, h in enumerate(handles):
            assert streams[i] == h.tokens and len(h.tokens) == 3

    def test_cancel_queued_and_resident(self, olmo):
        cfg, params = olmo
        engine = Engine(_compile(cfg), 1, params=params)
        prompts = _prompts(cfg, 3, lengths=(SEQ,), seed=8)
        handles = [engine.submit(p, 4) for p in prompts]
        engine.step()  # request 0 resident, 1 and 2 queued
        assert handles[0].status in (RequestStatus.PREFILLING,
                                     RequestStatus.DECODING)
        handles[1].cancel()  # queued -> never scheduled
        assert handles[1].status is RequestStatus.EVICTED
        assert handles[1].finish_reason == "cancelled"
        handles[0].cancel()  # resident -> slot freed for request 2
        assert handles[0].status is RequestStatus.EVICTED
        engine.run_until_idle(max_steps=100)
        assert handles[1].tokens == []
        assert handles[2].status is RequestStatus.DONE
        assert len(handles[2].tokens) == 4
        assert engine.stats.requests_evicted == 2
        assert engine.stats.requests_completed == 1
        cancelled = handles[1]
        cancelled.cancel()  # idempotent on finished handles
        assert engine.stats.requests_evicted == 2

    def test_cancel_from_streaming_callback(self, olmo):
        """A streaming callback may cancel requests mid-step — its own or
        a neighbor's — without crashing the consume loop or
        double-finishing the handle."""
        cfg, params = olmo
        engine = Engine(_compile(cfg), 2, params=params)
        # prompt tails keep both requests teacher-forcing through the
        # first dispatch, so the first sampled token (and the cancel)
        # lands inside the decode consume loop with both slots resident
        prompts = _prompts(cfg, 2, lengths=(SEQ + 1,), seed=12)
        handles = []

        def cancel_both(tok):
            handles[1].cancel()  # neighbor slot, not yet consumed this step
            handles[0].cancel()  # the very request being consumed

        handles.append(engine.submit(prompts[0], 4, on_token=cancel_both))
        handles.append(engine.submit(prompts[1], 4))
        engine.run_until_idle(max_steps=50)
        for h in handles:
            assert h.status is RequestStatus.EVICTED
            assert h.finish_reason == "cancelled"
        assert len(handles[0].tokens) == 1  # the token that fired the hook
        assert handles[1].tokens == []  # evicted before its consume turn
        assert engine.stats.requests_evicted == 2
        assert engine.stats.requests_completed == 0

    def test_engine_guards(self, olmo):
        cfg, params = olmo
        enc = api.compile(reduced(get_config("mobilebert")), use_cache=False)
        with pytest.raises(ValueError, match="decoder"):
            Engine(enc, 2)
        model = _compile(cfg)
        with pytest.raises(ValueError, match="max_batch"):
            Engine(model, 0)
        session = model.session(2, params=params)
        with pytest.raises(ValueError, match="batch_size"):
            Engine(session, 3)
        adopted = Engine(session)  # adopting a fresh session infers max_batch
        assert adopted.max_batch == 2
        assert adopted.run_until_idle() is adopted.stats  # idle engine no-ops
        with pytest.raises(ValueError, match="bound weights"):
            Engine(session, params=params)  # silently ignoring them would
            # serve from the session's weights, not the caller's
        used = model.session(2, params=params)
        used.prefill(jnp.asarray(_prompts(cfg, 2, lengths=(SEQ,)), jnp.int32))
        with pytest.raises(ValueError, match="live KV state"):
            Engine(used)  # the engine must own its slots exclusively

    def test_decode_hot_path_stays_on_host(self, olmo):
        """ISSUE 5 regression: the per-token scheduler loop performs no
        per-slot device fetches — the session tracks ``pos`` host-side
        (numpy) and the engine hands ``_consume_logits`` rows of ONE
        whole-step ``jax.device_get``, while the streams stay bit-exact
        vs the reference trajectories."""
        import numpy as np

        cfg, params = olmo
        engine = Engine(_compile(cfg), 2, params=params)
        qp = engine.session.qp
        seen_types = []
        orig = engine.sampling

        class Spy:
            vocab = cfg.vocab

            def __call__(self, row, rid, index):
                seen_types.append(type(row))
                return orig(row, rid, index)

        engine.sampling = Spy()
        prompts = _prompts(cfg, 3, lengths=(SEQ, SEQ + 2), seed=21)
        refs = [reference_trajectory(cfg, qp, p, 3, MAX_LEN) for p in prompts]
        handles = [engine.submit(p, 3) for p in prompts]
        engine.run_until_idle(max_steps=100)
        for h, (ref_tokens, _) in zip(handles, refs):
            assert h.tokens == ref_tokens
        # every logits row consumed by sampling was already host memory
        assert seen_types and all(t is np.ndarray for t in seen_types)
        # and the session's depth bookkeeping is host-side numpy, not a
        # device array that syncs per int() read
        assert isinstance(engine.session.pos, np.ndarray)

    def test_stats_split_prompt_vs_generated_throughput(self, olmo):
        """ISSUE 5: teacher-forced prompt tokens consume decode
        dispatches but generate nothing — the stats report them as
        prompt throughput instead of silently deflating tok/s."""
        cfg, params = olmo
        engine = Engine(_compile(cfg), 2, params=params)
        prompts = _prompts(cfg, 2, lengths=(SEQ + 3,), seed=22)
        handles = [engine.submit(p, 2) for p in prompts]
        engine.run_until_idle(max_steps=100)
        s = engine.stats
        assert all(h.status is RequestStatus.DONE for h in handles)
        assert s.prompt_tokens_forced == 2 * 3  # the tails
        assert s.prompt_tokens_prefilled == 2 * SEQ  # the static prefills
        total_time = s.prefill_time_s + s.decode_time_s
        assert s.tokens_per_s() == pytest.approx(
            s.tokens_generated / total_time)
        assert s.prompt_tokens_per_s() == pytest.approx(
            (s.prompt_tokens_prefilled + s.prompt_tokens_forced) / total_time)
        assert "gen tok/s" in s.summary() and "prompt tok/s" in s.summary()

    def test_failed_dispatch_time_is_accounted(self, olmo, monkeypatch):
        """ISSUE 5: the dispatch that dies on KVCapacityError still costs
        wall time; dropping it made capacity-churny traces look faster
        than the clock."""
        import time as time_mod

        cfg, params = olmo
        max_len = SEQ + 2
        engine = Engine(_compile(cfg, max_len=max_len), 1, params=params)
        orig_decode = engine.session.decode
        calls = {"n": 0}

        def slow_decode(tokens, pos=None, **kw):
            calls["n"] += 1
            time_mod.sleep(0.01)  # make the failed dispatch's cost visible
            return orig_decode(tokens, pos, **kw)

        monkeypatch.setattr(engine.session, "decode", slow_decode)
        [p] = _prompts(cfg, 1, lengths=(SEQ,), seed=23)
        h = engine.submit(p, 10)
        engine.run_until_idle(max_steps=100)
        assert h.finish_reason == "kv_capacity"
        # every decode call (including the one that raised) >= 10ms
        assert engine.stats.decode_time_s >= 0.01 * calls["n"]

    def test_stats_record_shape(self, olmo):
        cfg, params = olmo
        engine = Engine(_compile(cfg), 2, params=params)
        prompts = _prompts(cfg, 4, lengths=(SEQ,), seed=10)
        handles = [engine.submit(p, 2) for p in prompts]
        stats = engine.run_until_idle(max_steps=100)
        assert stats.requests_completed == 4
        assert stats.peak_queue_depth >= 2
        assert stats.queue_depth == 0 and stats.slots_busy == 0
        assert 0.0 < stats.occupancy() <= 1.0
        assert stats.tokens_per_s() > 0
        assert stats.tokens_generated == sum(len(h.tokens) for h in handles)
        assert isinstance(Greedy()(jnp.zeros(4), 0, 0), int)
        assert "slot occupancy" in stats.summary()
        # reset_stats clears the counters AND the slot-reuse bookkeeping:
        # the next admission reuses a slot but is not counted as a recycle
        fresh = engine.reset_stats()
        assert fresh is engine.stats and fresh.requests_completed == 0
        h = engine.submit(_prompts(cfg, 1, lengths=(SEQ,), seed=13)[0], 1)
        engine.run_until_idle(max_steps=20)
        assert h.status is RequestStatus.DONE
        assert engine.stats.slots_recycled == 0


class TestStepLatencyStats:
    def test_percentile_accounting(self):
        """Nearest-rank percentiles over recorded step wall times: empty
        record reads 0, a single sample is every percentile, and p50/p99
        land on the 50th/99th ranked sample regardless of append order."""
        from repro.deploy.engine import EngineStats

        s = EngineStats(max_batch=2)
        assert s.step_latency_p50() == 0.0 and s.step_latency_p99() == 0.0
        s.step_times_s.append(0.25)
        assert s.step_latency_p50() == 0.25 and s.step_latency_p99() == 0.25
        s.step_times_s[:] = [i / 1000.0 for i in range(100, 0, -1)]
        assert s.step_latency_p50() == pytest.approx(0.050)
        assert s.step_latency_p99() == pytest.approx(0.099)
        assert s.step_latency_s(100.0) == pytest.approx(0.100)
        # the summary carries the sampling time and the queue waits
        s.sample_time_s = 1.25
        s.queue_wait_s[:] = [0.002, 0.004]
        assert "1.250 s sampling" in s.summary()
        assert "queue wait p50/p99 2.0/4.0 ms" in s.summary()

    def test_engine_records_steps_and_dispatches(self, olmo):
        cfg, params = olmo
        clock = iter(float(t) for t in range(10**6))
        engine = Engine(_compile(cfg), 2, params=params, clock=lambda: next(clock))
        prompts = _prompts(cfg, 3, lengths=(SEQ,), seed=3)
        handles = [engine.submit(p, 2) for p in prompts]
        engine.run_until_idle(max_steps=50)
        s = engine.stats
        assert len(s.step_times_s) > 0
        assert s.step_latency_p99() >= s.step_latency_p50() > 0
        assert s.decode_dispatches > 0 and s.prefill_dispatches == 3
        # every sampled token passed through the policy's clock
        assert s.tokens_generated == 6 and s.sample_time_s > 0
        # one queue wait per admission, on the engine's clock: the third
        # request waited for a slot two requests held
        assert all(h.admitted_t is not None for h in handles)
        assert s.queue_wait_s == [h.admitted_t - h.arrival_t for h in handles]
        assert handles[2].admitted_t - handles[2].arrival_t > \
            handles[0].admitted_t - handles[0].arrival_t >= 0
        # a snapshot copies the counters and lists
        snap = engine.stats_snapshot()
        assert snap.sample_time_s == s.sample_time_s
        assert snap.queue_wait_s == s.queue_wait_s
        assert snap.queue_wait_s is not s.queue_wait_s
        # reset starts a fresh record
        fresh = engine.reset_stats()
        assert fresh.step_times_s == [] and fresh.queue_wait_s == []
        assert fresh.sample_time_s == 0.0


def test_sample_lists_keep_the_newest_samples():
    """A long-running server keeps the newest SAMPLES step times, TTFT,
    TPOT and queue waits, trimmed in place."""
    from repro.deploy import engine as E

    s = E.EngineStats(max_batch=1)
    lists = (s.step_times_s, s.ttft_s, s.tpot_s, s.queue_wait_s)
    for xs in lists:
        for i in range(E.SAMPLES + 10):
            E._record(xs, float(i))
    for xs in lists:
        assert len(xs) == E.SAMPLES
        assert xs[0] == 10.0 and xs[-1] == float(E.SAMPLES + 9)
    assert (s.step_times_s, s.ttft_s, s.tpot_s, s.queue_wait_s) == lists
