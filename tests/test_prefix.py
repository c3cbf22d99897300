"""Radix prefix cache + refcounted copy-on-write paged KV (ISSUE 9).

Acceptance contract: requests sharing a prompt prefix attach resident
pool blocks (``PrefixIndex`` match -> ``attach_prefix``) and prefill only
the novel suffix — an exact-prompt repeat admits with *zero* prefill
dispatches — while every trajectory stays bit-exact vs its independent
unshared reference on both ``w8a8`` and ``ita``; the first write into a
shared block copy-on-writes it, so siblings and the index never observe
a neighbour's decode; eviction respects refcounts
(``KVCapacityError.evictable`` never names a slot whose blocks are all
shared; reclaim never frees a block a live request holds); and the
KV-sharing audit (rules KV006/KV007) is clean after any schedule.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.deploy import api
from repro.deploy.engine import Engine, RequestStatus
from repro.deploy.paging import BlockAllocator, PoolExhausted, blocks_for_rows
from repro.deploy.prefix import PrefixIndex, PrefixMatch
from repro.deploy.verify import (
    KVSharingState,
    KVWrite,
    PlanVerificationError,
    check_sharing,
    verify_sharing,
)
from repro.models import transformer as T

SEQ = 8
MAX_LEN = 40
BLOCK = 4


@pytest.fixture(scope="module")
def olmo():
    # Running late in the full suite, the process carries hundreds of live
    # jitted executables; on a single-core box the XLA backend has been
    # observed to segfault compiling this module's scan-based reference
    # oracle under that load.  Dropping the accumulated caches first keeps
    # the heavy compiles in this module starting from a clean JIT arena.
    jax.clear_caches()
    cfg = reduced(get_config("olmo-1b"))
    params = T.init_params(cfg, jax.random.PRNGKey(7))
    return cfg, params


def _compile(cfg, backend="w8a8", *, max_len=MAX_LEN, kv_blocks=30,
             kv_block_size=BLOCK, prefix_cache=True):
    return api.compile(cfg, backend=backend, seq_len=SEQ, max_len=max_len,
                       kv_block_size=kv_block_size, kv_blocks=kv_blocks,
                       prefix_cache=prefix_cache, use_cache=False)


def _tokens(cfg, n, seed=0):
    return [int(t) for t in jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 0, cfg.vocab, jnp.int32)]


def reference_trajectory(cfg, qp, prompt, max_new, max_len, eos_id=None):
    """Independent single-request greedy oracle (same as test_engine)."""
    lg, cache = T.prefill_w8a8(
        cfg, qp, {"tokens": jnp.asarray(prompt[:SEQ], jnp.int32)[None]},
        max_len)
    out, depth = [], SEQ
    while True:
        if depth < len(prompt):
            nxt = prompt[depth]
        else:
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


# ---------------------------------------------------------------------------
# Allocator: refcounts, fork, copy-on-write
# ---------------------------------------------------------------------------

class TestAllocatorSharing:
    def test_fork_shares_and_free_decrements(self):
        a = BlockAllocator(6)
        blocks = a.allocate(3)
        assert [a.refcount(b) for b in blocks] == [1, 1, 1]
        assert a.fork(blocks[:2]) == blocks[:2]
        assert a.n_shared == 2 and a.n_free == 3
        # first free: refcounts drop, nothing returns to the pool
        a.free(blocks[:2])
        assert a.n_free == 3
        assert [a.refcount(b) for b in blocks] == [1, 1, 1]
        # last reference out: blocks rejoin the free list, lowest-id first
        a.free(blocks)
        assert a.n_free == 6 and a.n_shared == 0
        assert a.allocate(3) == blocks  # deterministic reissue

    def test_fork_dead_block_is_loud_and_atomic(self):
        a = BlockAllocator(4)
        (b,) = a.allocate(1)
        with pytest.raises(ValueError, match="not allocated"):
            a.fork([b, 99])
        assert a.refcount(b) == 1  # all-or-nothing: b was not bumped

    def test_cow_exclusive_is_in_place(self):
        a = BlockAllocator(4)
        (b,) = a.allocate(1)
        assert a.cow(b) == (b, False)
        assert a.n_free == 3 and a.refcount(b) == 1

    def test_cow_shared_materializes_private_copy(self):
        a = BlockAllocator(4)
        (b,) = a.allocate(1)
        a.fork([b])
        fresh, copied = a.cow(b)
        assert copied and fresh != b
        assert a.refcount(b) == 1 and a.refcount(fresh) == 1
        assert a.n_shared == 0
        # conservation: 2 live + 2 free
        assert a.n_free == 2

    def test_cow_exhausted_pool_is_loud_without_mutation(self):
        a = BlockAllocator(1)
        (b,) = a.allocate(1)
        a.fork([b])
        with pytest.raises(PoolExhausted):
            a.cow(b)
        assert a.refcount(b) == 2  # untouched

    def test_double_free_still_loud(self):
        a = BlockAllocator(2)
        (b,) = a.allocate(1)
        a.free([b])
        with pytest.raises(ValueError, match="double free"):
            a.free([b])


# ---------------------------------------------------------------------------
# PrefixIndex: match / insert / LRU reclaim
# ---------------------------------------------------------------------------

class TestPrefixIndex:
    def _index(self, n_blocks=12):
        return PrefixIndex(BlockAllocator(n_blocks), BLOCK)

    def test_empty_index_misses(self):
        idx = self._index()
        m = idx.match(list(range(10)))
        assert m == PrefixMatch((), 0) and not m.hit

    def test_insert_then_full_and_partial_match(self):
        idx = self._index()
        alloc = idx._alloc
        toks = list(range(10))  # 2 full blocks + 2-row tail
        chain = alloc.allocate(blocks_for_rows(10, BLOCK))
        logits = np.arange(8, dtype=np.float32)
        assert idx.insert(toks, chain, logits) == 3
        assert idx.n_blocks == 3
        assert [alloc.refcount(b) for b in chain] == [2, 2, 2]

        full = idx.match(toks)
        assert full.full and full.rows == 10 and full.blocks == tuple(chain)
        np.testing.assert_array_equal(full.logits, logits)
        # longer prompt with the same leading blocks: partial hit on the
        # full-block prefix only (the tail rows are not row-addressable)
        part = idx.match(toks + [77, 78])
        assert not part.full and part.rows == 8
        assert part.blocks == tuple(chain[:2])
        # diverging first block: miss
        assert not idx.match([99] * 10).hit

    def test_insert_validates_chain_and_logits(self):
        idx = self._index()
        chain = idx._alloc.allocate(2)
        with pytest.raises(ValueError, match="chain"):
            idx.insert(list(range(10)), chain, np.zeros(4))
        with pytest.raises(ValueError, match="logits"):
            idx.insert(list(range(8)), chain, None)

    def test_duplicate_insert_keeps_incumbents(self):
        idx = self._index()
        alloc = idx._alloc
        toks = list(range(8))
        first = alloc.allocate(2)
        idx.insert(toks, first, np.zeros(4))
        second = alloc.allocate(2)
        assert idx.insert(toks, second, np.ones(4)) == 0
        assert idx.match(toks).blocks == tuple(first)
        assert [alloc.refcount(b) for b in second] == [1, 1]

    def test_reclaim_is_lru_and_respects_refcounts(self):
        idx = self._index()
        alloc = idx._alloc
        cold, hot = list(range(8)), list(range(100, 108))
        cold_chain = alloc.allocate(2)
        idx.insert(cold, cold_chain, np.zeros(4))
        hot_chain = alloc.allocate(2)
        idx.insert(hot, hot_chain, np.zeros(4))
        alloc.free(cold_chain)
        alloc.free(hot_chain)  # index is now the only holder of all 4
        idx.match(hot)  # refresh hot's ticks
        assert idx.reclaimable() == 4
        assert idx.reclaim(1) >= 1
        # the cold prompt lost (part of) its chain first; hot is intact
        assert not idx.match(cold).full
        assert idx.match(hot).full

        # a block a live request still shares is never reclaimed
        alloc.fork([idx.match(hot).blocks[0]])
        freed = idx.reclaim()
        assert all(alloc.refcount(b) != 1 or b not in idx.pinned_blocks()
                   for b in range(1, alloc.n_blocks + 1))
        m = idx.match(hot)
        assert not m.full  # terminal + leaf went; shared node block stayed
        assert m.rows == 4 and freed >= 1

    def test_reclaim_frees_deep_chains_leaf_first_in_lru_order(self):
        # chains deeper than Python's recursion limit: the walk is iterative
        depth = sys.getrecursionlimit() + 50
        alloc = BlockAllocator(3 * depth)
        idx = PrefixIndex(alloc, BLOCK)
        prompts = [[p * 10_000 + i for i in range(depth * BLOCK)] for p in range(3)]
        chains = []
        for toks in prompts:
            chain = alloc.allocate(depth)
            idx.insert(toks, chain, np.zeros(4))
            alloc.free(chain)
            chains.append(chain)
        idx.match(prompts[0])  # prompt 1 is now the coldest
        assert idx.reclaim(depth // 2) == depth // 2
        # the coldest chain lost its deepest blocks first; the others are whole
        assert idx.match(prompts[1]).rows == (depth - depth // 2) * BLOCK
        assert idx.match(prompts[0]).full and idx.match(prompts[2]).full
        assert set(idx.pinned_blocks()) == set(chains[0]) | set(chains[2]) | set(
            chains[1][: depth - depth // 2])
        assert idx.reclaim() == 2 * depth + depth - depth // 2
        assert idx.n_blocks == 0 and alloc.n_free == alloc.n_blocks

    def test_reclaim_protect_guard(self):
        idx = self._index()
        alloc = idx._alloc
        chain = alloc.allocate(2)
        idx.insert(list(range(8)), chain, np.zeros(4))
        alloc.free(chain)
        assert idx.reclaim(protect=chain) == 0
        assert idx.reclaim() == 2
        assert alloc.n_free == alloc.n_blocks

    def test_drop_all_releases_everything(self):
        idx = self._index()
        alloc = idx._alloc
        chain = alloc.allocate(3)
        idx.insert(list(range(10)), chain, np.zeros(4))
        alloc.free(chain)
        assert idx.drop_all() == 3
        assert alloc.n_free == alloc.n_blocks and idx.n_blocks == 0


# ---------------------------------------------------------------------------
# KV-sharing audit: KV006 / KV007 mutation tests
# ---------------------------------------------------------------------------

class TestSharingAudit:
    def _clean(self):
        # slot0 shares blocks 1,2 with the index; block 3 is private
        return KVSharingState(
            n_blocks=8,
            refcounts={1: 2, 2: 2, 3: 1},
            tables={0: (1, 2, 3)},
            index_blocks=(1, 2),
        )

    def test_clean_state_passes(self):
        assert verify_sharing(self._clean()) == []
        assert check_sharing(self._clean(), strict=True) == []

    @pytest.mark.parametrize("state,rule", [
        # dead block referenced by a table
        (KVSharingState(n_blocks=8, refcounts={}, tables={0: (3,)}), "KV006"),
        # out-of-pool (and scratch) ids referenced
        (KVSharingState(n_blocks=8, refcounts={1: 1}, tables={0: (1,)},
                        index_blocks=(0,)), "KV006"),
        (KVSharingState(n_blocks=8, refcounts={1: 1}, tables={0: (1, 9)}),
         "KV006"),
        # refcount leak (2 recorded, 1 held) and underflow (1 recorded,
        # 2 held)
        (KVSharingState(n_blocks=8, refcounts={1: 2}, tables={0: (1,)}),
         "KV006"),
        (KVSharingState(n_blocks=8, refcounts={1: 1},
                        tables={0: (1,), 1: (1,)}), "KV006"),
        # write outside the writer's own table
        (KVSharingState(n_blocks=8, refcounts={1: 1, 2: 1},
                        tables={0: (1,), 1: (2,)},
                        writes=(KVWrite(0, 2),)), "KV007"),
        # in-place write into a shared block (no COW)
        (KVSharingState(n_blocks=8, refcounts={1: 2},
                        tables={0: (1,), 1: (1,)},
                        writes=(KVWrite(0, 1, cow=False),)), "KV007"),
        # COW write whose target is still shared
        (KVSharingState(n_blocks=8, refcounts={1: 2},
                        tables={0: (1,), 1: (1,)},
                        writes=(KVWrite(0, 1, cow=True),)), "KV007"),
    ], ids=["dead-block", "scratch-ref", "out-of-range", "refcount-leak",
            "refcount-underflow", "foreign-write", "shared-write-no-cow",
            "cow-still-shared"])
    def test_each_mutation_caught_by_exact_rule(self, state, rule):
        diags = verify_sharing(state)
        assert diags and all(d.rule == rule for d in diags), \
            [str(d) for d in diags]
        assert all(d.severity == "error" for d in diags)
        with pytest.raises(PlanVerificationError) as ei:
            check_sharing(state, context="mutation")
        assert rule in str(ei.value)

    def test_cowed_exclusive_write_is_legal(self):
        state = KVSharingState(
            n_blocks=8, refcounts={1: 2, 4: 1},
            tables={0: (4,), 1: (1,)}, index_blocks=(1,),
            writes=(KVWrite(0, 4, cow=True),),
        )
        assert verify_sharing(state) == []


# ---------------------------------------------------------------------------
# Session: attach_prefix + copy-on-write before any shared write
# ---------------------------------------------------------------------------

class TestSessionSharing:
    def test_attach_cow_isolates_siblings_bit_exactly(self, olmo):
        """Slot 1 attaches slot 0's whole chain; both then decode their
        own continuations.  The divergent writes must COW — afterwards
        the two trajectories differ while slot 0's original rows are
        untouched, and the sharing audit stays clean throughout."""
        cfg, params = olmo
        sess = _compile(cfg, kv_blocks=12).session(2, params=params)
        alloc = sess.allocator
        # 10 rows: 2 full blocks + a half-filled tail block — the tail is
        # where attach-then-write MUST copy-on-write
        prompt = _tokens(cfg, SEQ + 2, seed=11)
        sess.prefill_chunk(0, jnp.asarray([prompt[:SEQ]], jnp.int32), 0)
        lg = sess.prefill_chunk(0, jnp.asarray([prompt[2:]], jnp.int32), 2)
        chain = sess.block_chain(0)
        assert len(chain) == blocks_for_rows(SEQ + 2, BLOCK)

        sess.attach_prefix(1, chain, SEQ + 2)
        assert sess.block_chain(1) == chain
        assert int(sess.pos[1]) == SEQ + 2
        assert alloc.n_shared == len(chain)
        assert verify_sharing(sess.sharing_state()) == []

        # identical next token on both slots: the decode writes land in
        # the shared tail block -> each writer COWs before writing
        nxt = int(jnp.argmax(lg[0, -1, : cfg.vocab]))
        before = sess.cow_copies
        lg2 = sess.decode(jnp.asarray([[nxt], [nxt]], jnp.int32))
        assert sess.cow_copies > before
        assert alloc.n_shared < len(chain)  # tail block(s) privatized
        # both lanes read identical context -> identical logits rows
        np.testing.assert_array_equal(np.asarray(lg2[0, -1]),
                                      np.asarray(lg2[1, -1]))
        assert verify_sharing(sess.sharing_state()) == []
        # freeing the sharer returns only its private copies
        held = sess.blocks_held(1)
        free_before = sess.blocks_free
        sess.free_slot(1)
        assert sess.blocks_free < free_before + held  # shared stayed live
        assert verify_sharing(sess.sharing_state()) == []

    def test_attach_validations(self, olmo):
        cfg, params = olmo
        sess = _compile(cfg, kv_blocks=8).session(2, params=params)
        prompt = _tokens(cfg, SEQ, seed=3)
        sess.prefill_chunk(0, jnp.asarray([prompt], jnp.int32), 0)
        chain = sess.block_chain(0)
        with pytest.raises(RuntimeError, match="live slot"):
            sess.attach_prefix(0, chain, SEQ)
        with pytest.raises(ValueError):
            sess.attach_prefix(1, chain, SEQ + 1)  # chain/rows mismatch

    def test_evictable_excludes_all_shared_slots(self, olmo):
        """The regression the tentpole guards: a slot whose blocks are
        ALL shared frees nothing when evicted, so the structured
        capacity error must not name it."""
        cfg, params = olmo
        sess = _compile(cfg, kv_blocks=4).session(3, params=params)
        prompt = _tokens(cfg, SEQ, seed=5)
        sess.prefill_chunk(0, jnp.asarray([prompt], jnp.int32), 0)  # 2 blocks
        sess.attach_prefix(1, sess.block_chain(0), SEQ)  # all-shared slot
        # slot 2 wants 2 blocks; 2 free -> fits.  Then growing past the
        # pool must name ONLY slot 0 (exclusive owner is... both 0 and 1
        # share everything; neither holds an exclusive block!).  Fill the
        # pool with slot 2 instead and let 0 hold the only private block.
        sess.prefill_chunk(2, jnp.asarray([prompt], jnp.int32), 0)
        assert sess.blocks_free == 0
        with pytest.raises(api.KVCapacityError) as ei:
            sess.decode(jnp.asarray([[1], [1], [1]], jnp.int32),
                        active=jnp.asarray([True, False, False]))
        e = ei.value
        assert e.reason == "pool" and e.slots == (0,)
        # slot 1 shares everything it holds -> not evictable; slot 2's
        # blocks are exclusively its own -> evictable
        assert e.evictable == (2,)


# ---------------------------------------------------------------------------
# Engine: shared-prefix serving, bit-exact on both backends
# ---------------------------------------------------------------------------

class TestEnginePrefixBitExact:
    @pytest.mark.parametrize("backend", ["w8a8", "ita"])
    def test_shared_prompt_trajectories_bit_exact(self, olmo, backend):
        """Sequential re-submissions of a shared prompt: the repeat is a
        zero-prefill full hit, the extended prompt a partial hit that
        prefills only its suffix — all three token streams equal their
        independent unshared references."""
        cfg, params = olmo
        engine = Engine(_compile(cfg, backend), 2, params=params)
        qp = engine.session.qp
        base = _tokens(cfg, 2 * SEQ + 2, seed=21)  # 18 rows: 4 blocks + tail
        longer = base + _tokens(cfg, 6, seed=22)  # shares base verbatim
        plans = [(base, 3), (base, 3), (longer, 2)]
        refs = [reference_trajectory(cfg, qp, p, n, MAX_LEN)
                for p, n in plans]

        h0 = engine.submit(*plans[0])
        engine.run_until_idle(max_steps=200)
        h1 = engine.submit(*plans[1])
        h2 = engine.submit(*plans[2])
        engine.run_until_idle(max_steps=200)

        for h, (toks, reason) in zip([h0, h1, h2], refs):
            assert h.status is RequestStatus.DONE
            assert h.tokens == toks, (h.rid, h.tokens, toks)
            assert h.finish_reason == reason
        s = engine.stats
        assert s.prefix_lookups == 3 and s.prefix_hits == 2
        assert s.full_prefix_hits == 1  # the exact repeat skipped prefill
        assert s.prefix_hit_blocks >= 5 + 4  # full chain + base's 4 nodes
        assert s.prefix_hit_rate() == pytest.approx(2 / 3)
        assert s.cow_copies >= 1  # decode into the shared tail block
        assert engine.audit_sharing() == []

    def test_concurrent_identical_prompts_defer_then_hit(self, olmo):
        """All-at-once identical submissions: the head prefills once,
        admission defers the rest until the prefix lands, and they admit
        as zero-prefill full hits — 1x prefill cost for N requests."""
        cfg, params = olmo
        engine = Engine(_compile(cfg), 2, params=params)
        qp = engine.session.qp
        prompt = _tokens(cfg, 4 * SEQ, seed=31)
        ref, _ = reference_trajectory(cfg, qp, prompt, 2, MAX_LEN)

        handles = [engine.submit(prompt, 2) for _ in range(3)]
        engine.run_until_idle(max_steps=300)
        for h in handles:
            assert h.status is RequestStatus.DONE and h.tokens == ref
        s = engine.stats
        assert s.full_prefix_hits == 2
        # exactly ONE request's worth of prompt tokens hit the prefill path
        assert s.prompt_tokens_prefilled == 4 * SEQ
        assert engine.audit_sharing() == []

    def test_eviction_under_pressure_never_corrupts_siblings(self, olmo):
        """Undersized pool + shared prefixes: some requests finish with
        kv_capacity, but every token any request DID emit must match its
        independent reference — eviction decrements refcounts, it never
        reclaims a sibling's shared rows."""
        cfg, params = olmo
        engine = Engine(_compile(cfg, kv_blocks=9), 2, params=params)
        qp = engine.session.qp
        base = _tokens(cfg, 2 * SEQ, seed=41)
        prompts = [base + _tokens(cfg, 4, seed=s) for s in (42, 43, 44)]
        budgets = [2, 6, 6]  # the head fits outright; the rest squeeze
        refs = [reference_trajectory(cfg, qp, p, n, MAX_LEN)[0]
                for p, n in zip(prompts, budgets)]

        handles = [engine.submit(p, n) for p, n in zip(prompts, budgets)]
        engine.run_until_idle(max_steps=400)
        for h, ref in zip(handles, refs):
            assert h.status is RequestStatus.DONE
            assert h.finish_reason in ("length", "kv_capacity")
            # every token any request DID emit is its reference's — a
            # sibling's eviction never rewrote shared rows underneath it
            assert h.tokens == ref[: len(h.tokens)], (h.rid, h.tokens, ref)
        assert handles[0].finish_reason == "length"
        assert engine.audit_sharing() == []

    def test_prefix_cache_off_by_default_and_fingerprinted(self, olmo):
        cfg, _ = olmo
        on = _compile(cfg)
        off = _compile(cfg, prefix_cache=False)
        assert on.fingerprint != off.fingerprint
        with pytest.raises(ValueError, match="prefix_cache"):
            api.compile(cfg, seq_len=SEQ, max_len=MAX_LEN,
                        prefix_cache=True, use_cache=False)  # dense decoder

    def test_engine_without_prefix_cache_has_no_index(self, olmo):
        cfg, params = olmo
        engine = Engine(_compile(cfg, prefix_cache=False), 1, params=params)
        assert engine.prefix_index is None
        prompt = _tokens(cfg, SEQ, seed=51)
        h = engine.submit(prompt, 2)
        engine.run_until_idle(max_steps=100)
        assert h.status is RequestStatus.DONE
        assert engine.stats.prefix_lookups == 0
        assert engine.audit_sharing() == []
