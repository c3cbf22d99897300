"""Hypothesis property tests (all modules), gathered behind one guard.

The ``[test]`` extra installs hypothesis; where it is missing this module
skips at collection (``pytest.importorskip``) and the deterministic suites
keep running — the suite degrades gracefully instead of breaking
collection.
"""

import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need the [test] extra")

import jax.numpy as jnp
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import ilayernorm as iln
from repro.core import itamax as im
from repro.deploy import memory, tiler
from repro.deploy.graph import Graph
from repro.quant.qparams import (
    MULT_MAX,
    SHIFT_MAX,
    SHIFT_MIN,
    requantize,
    requantize_wide,
    rounding_rshift,
)


def _requant_gold(acc, mult, shift, zp=0):
    """Arbitrary-precision (python int) reference of requantize."""
    out = (int(acc) * int(mult) + (1 << (shift - 1))) >> shift
    return int(np.clip(out + zp, -128, 127))


class TestTilerProperties:
    @given(
        m=st.integers(1, 2048), n=st.integers(1, 2048), k=st.integers(1, 2048)
    )
    @settings(max_examples=60, deadline=None)
    def test_property_always_feasible(self, m, n, k):
        t = tiler.solve_gemm_tiling(m, n, k)
        assert t.l1_bytes <= tiler.ITA_L1_BYTES
        assert t.useful_ops == 2 * m * n * k


def _random_graph(rng) -> Graph:
    """Random branching DAG over 2-D int8 tensors (shared helper)."""
    g = Graph()
    live = [g.add_tensor("in", (int(rng.integers(1, 64)), 32))]
    g.inputs.append("in")
    for i in range(int(rng.integers(2, 25))):
        src = [live[int(rng.integers(0, len(live)))]]
        if rng.random() < 0.4 and len(live) > 1:
            src.append(live[int(rng.integers(0, len(live)))])
        out = g.add_tensor(f"t{i}", (int(rng.integers(1, 64)), 32))
        g.add_node("Add" if len(src) > 1 else "LayerNorm", src, [out],
                   dims=g.tensors[out].shape)
        live.append(out)
    g.outputs.append(live[-1])
    return g


class TestMemoryPlannerProperties:
    @given(seed=st.integers(0, 10_000))
    @settings(max_examples=25, deadline=None)
    def test_property_random_graphs_no_overlap(self, seed):
        """Random branching DAGs: planner must never alias live tensors."""
        g = _random_graph(np.random.default_rng(seed))
        plan = memory.plan_memory(g)
        assert plan.check_no_overlap()
        assert plan.peak >= memory.peak_lower_bound(g)

    @given(seed=st.integers(0, 10_000), n_pers=st.integers(1, 6))
    @settings(max_examples=25, deadline=None)
    def test_property_persistent_no_overlap_and_bounded(self, seed, n_pers):
        """KV-cache-style persistent tensors (whole-schedule lifetimes):
        no aliasing with any transient, peak bracketed by the lower bound
        and the everything-is-live upper bound."""
        rng = np.random.default_rng(seed)
        g = _random_graph(rng)
        names = list(g.tensors)
        persistent = tuple(
            names[int(rng.integers(0, len(names)))] for _ in range(n_pers)
        )
        plan = memory.plan_memory(g, persistent=persistent)
        assert plan.check_no_overlap()
        last = len(g.nodes) - 1
        for t in set(persistent):
            a = plan.allocations[t]
            assert (a.start, a.end) == (0, last)
        lb = memory.peak_lower_bound(g, persistent=persistent)
        total = sum(
            (max(g.tensors[t].bytes, 1) + 15) // 16 * 16 for t in plan.allocations
        )
        assert lb <= plan.peak <= total

    @given(seed=st.integers(0, 10_000))
    @settings(max_examples=15, deadline=None)
    def test_property_alias_shares_allocation(self, seed):
        """An aliased output (in-place cache update) maps onto the exact
        allocation record of its source."""
        rng = np.random.default_rng(seed)
        g = _random_graph(rng)
        # pretend the graph output updates the input in place
        plan = memory.plan_memory(
            g, persistent=("in",), aliases={g.outputs[0]: "in"}
        )
        assert plan.check_no_overlap()
        assert plan.allocations[g.outputs[0]] == plan.allocations["in"]


class TestISqrtProperties:
    @given(v=st.integers(0, 2**31 - 1))
    @settings(max_examples=300, deadline=None)
    def test_floor_sqrt(self, v):
        got = int(iln.isqrt(jnp.int32(v)))
        want = max(1, int(np.floor(np.sqrt(v))))
        assert got == want


class TestRequantizeProperties:
    @given(
        acc=st.integers(-(1 << 25), (1 << 25) - 1),
        mult=st.integers(1, MULT_MAX),
        shift=st.integers(SHIFT_MIN, SHIFT_MAX),
    )
    @settings(max_examples=300, deadline=None)
    def test_bit_exact_vs_python_int(self, acc, mult, shift):
        got = int(requantize(jnp.int32(acc), mult, shift))
        assert got == _requant_gold(acc, mult, shift)

    @given(
        acc=st.integers(-(1 << 25), (1 << 25) - 1),
        mult=st.integers(1, MULT_MAX),
        shift=st.integers(SHIFT_MIN, SHIFT_MAX),
    )
    @settings(max_examples=200, deadline=None)
    def test_wide_matches_float(self, acc, mult, shift):
        got = int(requantize_wide(jnp.int32(acc), mult, shift, out_bits=31))
        gold = (acc * mult + (1 << (shift - 1))) >> shift
        gold = int(np.clip(gold, -(1 << 30), (1 << 30) - 1))
        assert got == gold

    @given(x=st.integers(-(1 << 29), (1 << 29)), s=st.integers(1, 20))
    @settings(max_examples=200, deadline=None)
    def test_rounding_shift_matches_python(self, x, s):
        got = int(rounding_rshift(jnp.int32(x), s))
        assert got == (x + (1 << (s - 1))) >> s


class TestBlockAllocatorProperties:
    @given(
        n_blocks=st.integers(1, 24),
        ops=st.lists(
            st.tuples(st.integers(0, 5), st.integers(0, 3), st.booleans()),
            max_size=40,
        ),
    )
    @settings(max_examples=60, deadline=None)
    def test_property_no_block_owned_twice_and_frees_return(self, n_blocks, ops):
        """Random allocate/free traffic from interleaved owners: no
        physical block is ever owned by two live slots, the scratch block
        is never issued, failed allocations mutate nothing, and freed
        blocks genuinely return to the pool."""
        from repro.deploy.paging import (
            SCRATCH_BLOCK,
            BlockAllocator,
            PoolExhausted,
        )

        alloc = BlockAllocator(n_blocks)
        held: dict[int, list[int]] = {}
        for owner, n, do_free in ops:
            if do_free and held.get(owner):
                alloc.free(held.pop(owner))
            else:
                before = alloc.n_free
                try:
                    got = alloc.allocate(n, owner=owner)
                except PoolExhausted:
                    assert alloc.n_free == before  # all-or-nothing
                    continue
                held.setdefault(owner, []).extend(got)
            live = [b for blocks in held.values() for b in blocks]
            assert len(live) == len(set(live))  # no double ownership
            assert SCRATCH_BLOCK not in live
            assert all(1 <= b <= n_blocks for b in live)
            assert alloc.n_free + len(live) == n_blocks  # conservation
        for blocks in held.values():
            alloc.free(blocks)
        assert alloc.n_free == n_blocks  # everything returned

    @given(
        n_blocks=st.integers(1, 16),
        ops=st.lists(
            st.tuples(st.integers(0, 3),  # holder id
                      st.sampled_from(["alloc", "fork", "cow", "free"]),
                      st.integers(0, 3)),  # count / source holder / pick
            max_size=60,
        ),
    )
    @settings(max_examples=60, deadline=None)
    def test_property_fork_cow_free_interleavings(self, n_blocks, ops):
        """Random fork/cow/free traffic (the prefix-cache access
        pattern): the allocator's refcount for every live block always
        equals the references the model actually holds, conservation
        (``n_free + live == n_blocks``) holds after every operation,
        failed cow/alloc mutate nothing, and draining every reference —
        shared blocks freed once per holder — returns the whole pool
        without ever double-freeing."""
        from repro.deploy.paging import BlockAllocator, PoolExhausted

        alloc = BlockAllocator(n_blocks)
        held: dict[int, list[int]] = {}  # holder -> refs (list = multiset)

        def refs_of(b):
            return sum(blocks.count(b) for blocks in held.values())

        for holder, op, k in ops:
            if op == "alloc":
                before = alloc.n_free
                try:
                    got = alloc.allocate(k, owner=holder)
                except PoolExhausted:
                    assert alloc.n_free == before  # all-or-nothing
                    continue
                held.setdefault(holder, []).extend(got)
            elif op == "fork":
                src = held.get(k)
                if not src:
                    continue
                take = src[: max(1, holder)]
                assert alloc.fork(take) == take
                held.setdefault(holder, []).extend(take)
            elif op == "cow":
                mine = held.get(holder)
                if not mine:
                    continue
                b = mine[k % len(mine)]
                before = alloc.n_free
                shared = refs_of(b) > 1
                try:
                    fresh, copied = alloc.cow(b, owner=holder)
                except PoolExhausted:
                    # loud and mutation-free: the share survives intact
                    assert alloc.n_free == before
                    assert alloc.refcount(b) == refs_of(b)
                    continue
                assert copied == shared
                if copied:
                    mine[mine.index(b)] = fresh  # one ref moved over
                else:
                    assert fresh == b  # exclusive: write in place
            elif op == "free":
                if held.get(holder):
                    alloc.free(held.pop(holder))
            live = {b for blocks in held.values() for b in blocks}
            assert alloc.n_free + len(live) == n_blocks  # conservation
            for b in live:
                assert alloc.refcount(b) == refs_of(b) >= 1
            assert alloc.n_shared == sum(refs_of(b) > 1 for b in live)
        for blocks in held.values():
            alloc.free(blocks)  # would raise on any double-free
        assert alloc.n_free == n_blocks


class TestPagedPlanProperties:
    @given(
        seq=st.sampled_from([4, 8]),
        block=st.sampled_from([2, 4, 8]),
        blocks=st.integers(2, 9),
    )
    @settings(max_examples=8, deadline=None)
    def test_property_pool_offsets_identical_across_pair(self, seq, block, blocks):
        """Any paged geometry: the prefill and decode schedules allocate
        every pool tensor at the same static offset/size (the linked
        plans literally share one region), and validate() holds."""
        from repro.configs import get_config, reduced
        from repro.deploy.lowering import lower_decoder

        cfg = reduced(get_config("olmo-1b"))
        pair = lower_decoder(cfg, seq, max_len=seq + block * 2,
                             kv_block_size=block, kv_blocks=blocks)
        assert pair.paged
        names = pair.kv_tensors
        assert names  # pools exist
        assert not memory.shared_persistent_offsets(
            pair.prefill.tensors, pair.decode.tensors, names
        )
        # pools are stacked contiguously from offset 0 (sorted-name order)
        offsets = sorted(pair.prefill.tensors[n].offset for n in names)
        assert offsets[0] == 0

    @given(
        depths=st.lists(st.integers(0, 11), min_size=2, max_size=3),
        seed=st.integers(0, 500),
    )
    @settings(max_examples=12, deadline=None)
    def test_property_paged_runners_bit_exact_vs_dense(self, depths, seed):
        """Random per-slot depths and block tables: the paged
        ``cache_write`` + ``attn_cached`` runner pair computes exactly
        the dense runners' ints (the block-table gather is a layout
        change, not an arithmetic one)."""
        import jax

        from repro.core.heterogeneous import DEFAULT_TABLE, Backend, Engine, OpDesc
        from repro.deploy.paging import SCRATCH_BLOCK

        hkv, heads, d, block, max_len = 2, 4, 8, 4, 12
        b = len(depths)
        rng = np.random.default_rng(seed)

        def rand8(*shape):
            return jnp.asarray(rng.integers(-128, 128, shape), jnp.int8)

        def cluster(kind):
            return DEFAULT_TABLE._lookup(kind, Engine.CLUSTER, Backend.W8A8)

        # dense cache with each slot's first `depth` rows populated
        dense_k = np.zeros((b, hkv, max_len, d), np.int8)
        dense_v = np.zeros((b, hkv, max_len, d), np.int8)
        nblk = max_len // block
        pool_k = np.zeros((b * nblk + 1, hkv, block, d), np.int8)
        pool_v = np.zeros_like(pool_k)
        table = np.full((b, nblk), SCRATCH_BLOCK, np.int32)
        next_free = 1
        for i, depth in enumerate(depths):
            rows_k = rng.integers(-128, 128, (hkv, depth, d))
            rows_v = rng.integers(-128, 128, (hkv, depth, d))
            dense_k[i, :, :depth] = rows_k
            dense_v[i, :, :depth] = rows_v
            # blocks cover the append target row `depth` too — the session
            # allocates the crossed-into block before dispatching
            for blk_i in range(-(-(depth + 1) // block)):
                table[i, blk_i] = next_free
                lo = blk_i * block
                pool_k[next_free, :, : max(0, min(depth - lo, block))] = (
                    rows_k[:, lo : lo + block])
                pool_v[next_free, :, : max(0, min(depth - lo, block))] = (
                    rows_v[:, lo : lo + block])
                next_free += 1

        pos = jnp.asarray(depths, jnp.int32)
        kv_new = rand8(b, 1, hkv * d)
        q_new = rand8(b, 1, heads * d)

        # cache_write: dense row-append vs paged block scatter
        dk = cluster("cache_write")(kv_new, jnp.asarray(dense_k), pos,
                                    kv_heads=hkv, head_dim=d, max_len=max_len)
        pk = cluster("cache_write_paged")(kv_new, jnp.asarray(pool_k), pos,
                                          jnp.asarray(table), None,
                                          kv_heads=hkv, head_dim=d,
                                          block_size=block)
        # compare through each slot's logical view (gather its blocks)
        gathered = np.asarray(pk)[table].transpose(0, 2, 1, 3, 4).reshape(
            b, hkv, max_len, d)
        for i, depth in enumerate(depths):
            np.testing.assert_array_equal(
                np.asarray(dk)[i, :, : depth + 1], gathered[i, :, : depth + 1])

        # attn: dense cache-masked vs paged gathered, same ints
        dv = cluster("cache_write")(kv_new, jnp.asarray(dense_v), pos,
                                    kv_heads=hkv, head_dim=d, max_len=max_len)
        pv = cluster("cache_write_paged")(kv_new, jnp.asarray(pool_v), pos,
                                          jnp.asarray(table), None,
                                          kv_heads=hkv, head_dim=d,
                                          block_size=block)
        dense_out = cluster("attn_cached")(
            q_new, dk, dv, pos, heads=heads, head_dim=d,
            s_act=0.05, s_out=0.05, block_k=2048)
        paged_out = cluster("attn_paged")(
            q_new, pk, pv, pos, jnp.asarray(table), heads=heads,
            kv_heads=hkv, head_dim=d, s_act=0.05, s_out=0.05, block_k=2048)
        np.testing.assert_array_equal(np.asarray(dense_out),
                                      np.asarray(paged_out))


class TestItamaxProperties:
    @given(data=st.data())
    @settings(max_examples=30, deadline=None)
    def test_property_monotone(self, data):
        """Larger logit -> no smaller attention weight (within a row)."""
        n = data.draw(st.integers(8, 96))
        row = data.draw(
            st.lists(st.integers(-128, 127), min_size=n, max_size=n)
        )
        x = jnp.asarray([row], jnp.int8)
        a = np.asarray(im.itamax_rowwise(x))[0]
        order = np.argsort(row, kind="stable")
        assert (np.diff(a[order]) >= 0).all()


def _plan_from_graph(g, mem):
    """Assemble a synthetic DeploymentPlan from a scheduled graph + its
    static memory layout (shared helper for the verifier properties).
    ``g`` must already have every sink in ``g.outputs`` (see
    :func:`_mark_sinks`) so the allocator and the verifier agree on
    output lifetimes."""
    from repro.deploy.patterns import KIND_BY_OP
    from repro.deploy.plan import DeploymentPlan, PlanNode, TensorSpec

    nodes = [
        PlanNode(name=n.name, op=n.op, kind=KIND_BY_OP[n.op],
                 engine="cluster", inputs=tuple(n.inputs),
                 outputs=tuple(n.outputs), attrs=dict(n.attrs))
        for n in g.nodes
    ]
    tensors = {}
    for name, ti in g.tensors.items():
        a = mem.allocations.get(name)
        tensors[name] = TensorSpec(
            name=name, shape=tuple(ti.shape), dtype=ti.dtype,
            offset=None if a is None else a.offset,
            size=0 if a is None else a.size,
        )
    return DeploymentPlan(
        arch="synthetic", seq_len=1, granule=64, head_by_head=False,
        quant={}, nodes=nodes, tensors=tensors, inputs=tuple(g.inputs),
        outputs=tuple(g.outputs),
        schedule=tuple(n.name for n in nodes), memory_peak=mem.peak,
    )


def _mark_sinks(g):
    """Promote every never-consumed tensor to a graph output, so the
    allocator keeps it live to the end of the schedule — exactly the
    lifetime contract the verifier enforces on plan outputs (and no dead
    intermediates remain to trip the DF002 lint)."""
    consumed = {t for n in g.nodes for t in n.inputs}
    g.outputs = [t for t in g.tensors
                 if t not in consumed and t not in g.inputs] or g.outputs
    return g


class TestVerifierProperties:
    @given(seed=st.integers(0, 10_000))
    @settings(max_examples=25, deadline=None)
    def test_property_valid_random_schedules_verify_clean(self, seed):
        """Soundness of the verifier's clean direction: any topologically
        scheduled graph with a correct static layout must produce ZERO
        diagnostics — the lint never cries wolf on valid plans."""
        from repro.deploy.verify import verify_plan

        g = _mark_sinks(_random_graph(np.random.default_rng(seed)))
        plan = _plan_from_graph(g, memory.plan_memory(g))
        assert verify_plan(plan) == []

    @given(seed=st.integers(0, 10_000))
    @settings(max_examples=25, deadline=None)
    def test_property_forced_aliasing_is_flagged(self, seed):
        """Completeness on the memory-race class: force any two co-live
        allocations onto the same offset and MEM001 must fire."""
        from dataclasses import replace

        from repro.deploy.verify import verify_plan

        g = _mark_sinks(_random_graph(np.random.default_rng(seed)))
        mem = memory.plan_memory(g)
        plan = _plan_from_graph(g, mem)
        allocs = list(dict.fromkeys(mem.allocations.values()))
        colive = next(
            ((a, b) for i, a in enumerate(allocs) for b in allocs[i + 1:]
             if not (a.end < b.start or b.end < a.start)
             and a.offset != b.offset),
            None,
        )
        if colive is None:
            return  # degenerate chain graph: nothing is ever co-live
        a, b = colive
        plan.tensors[a.tensor] = replace(plan.tensors[a.tensor],
                                         offset=b.offset)
        rules = {d.rule for d in verify_plan(plan)
                 if d.severity == "error"}
        assert "MEM001" in rules

    @given(seed=st.integers(0, 10_000), drop=st.integers(0, 30))
    @settings(max_examples=25, deadline=None)
    def test_property_dropped_producer_is_flagged(self, seed, drop):
        """Completeness on the dataflow class: delete any node whose
        output is consumed downstream and DF001 (or DF003 for a dropped
        output producer) must fire."""
        from repro.deploy.verify import verify_plan

        g = _mark_sinks(_random_graph(np.random.default_rng(seed)))
        plan = _plan_from_graph(g, memory.plan_memory(g))
        consumed = {t for n in plan.nodes for t in n.inputs}
        keep = set(plan.outputs)
        victims = [i for i, n in enumerate(plan.nodes)
                   if set(n.outputs) & (consumed | keep)]
        i = victims[drop % len(victims)]
        del plan.nodes[i]
        plan.schedule = tuple(n.name for n in plan.nodes)
        rules = {d.rule for d in verify_plan(plan)
                 if d.severity == "error"}
        assert rules & {"DF001", "DF003"}


def _sched_handle(rid, priority, ttft_slo_ms, deadline_ms, arrival_t):
    """Minimal object satisfying the Scheduler handle contract."""
    from repro.deploy.serving.scheduler import effective_deadline

    class H:
        pass

    h = H()
    h.rid = rid
    h.priority = priority
    h.ttft_slo_ms = ttft_slo_ms
    h.deadline_ms = deadline_ms
    h.arrival_t = arrival_t
    h.deadline_t = (None if deadline_ms is None
                    else arrival_t + deadline_ms / 1e3)
    h.admit_deadline_t = effective_deadline(arrival_t, ttft_slo_ms,
                                            deadline_ms)
    return h


_handle_st = st.builds(
    _sched_handle,
    rid=st.integers(0, 10_000),
    priority=st.integers(-5, 20),
    ttft_slo_ms=st.none() | st.floats(0.0, 1e5, allow_nan=False),
    deadline_ms=st.none() | st.floats(0.0, 1e5, allow_nan=False),
    arrival_t=st.floats(0.0, 1e4, allow_nan=False),
)


class TestSchedulerProperties:
    @given(hs=st.lists(_handle_st, min_size=2, max_size=12,
                       unique_by=lambda h: h.rid),
           now=st.floats(0.0, 2e4, allow_nan=False))
    @settings(max_examples=80, deadline=None)
    def test_property_key_is_strict_total_order(self, hs, now):
        """The PriorityDeadline sort key never ties on distinct rids
        (the rid tiebreak makes it a strict total order), and popping
        drains the queue in exactly sorted-key order."""
        from repro.deploy.serving.scheduler import PriorityDeadline

        s = PriorityDeadline(aging_s=3.0)
        for h in hs:
            s.add(h, now)
        keys = [s.key(h, now) for h in hs]
        assert len(set(keys)) == len(keys)
        want = [h.rid for h in sorted(hs, key=lambda h: s.key(h, now))]
        got = [s.pop(now).rid for _ in range(len(hs))]
        assert got == want and s.pop(now) is None

    @given(hs=st.lists(_handle_st, min_size=2, max_size=12,
                       unique_by=lambda h: h.rid))
    @settings(max_examples=80, deadline=None)
    def test_property_order_matches_contract_when_aging_is_off(self, hs):
        """With aging effectively disabled, the admitted order is exactly
        lexicographic (priority, effective deadline, rid) — the
        documented scheduler contract."""
        from repro.deploy.serving.scheduler import PriorityDeadline

        s = PriorityDeadline(aging_s=1e12)
        now = max(h.arrival_t for h in hs)
        for h in hs:
            s.add(h, now)
        want = [h.rid for h in
                sorted(hs, key=lambda h: (h.priority, h.admit_deadline_t,
                                          h.rid))]
        assert [s.pop(now).rid for _ in range(len(hs))] == want

    @given(old_priority=st.integers(0, 20),
           fresh_priority=st.integers(0, 20),
           aging_s=st.floats(0.1, 60.0, allow_nan=False))
    @settings(max_examples=80, deadline=None)
    def test_property_aging_guarantees_starvation_freedom(
            self, old_priority, fresh_priority, aging_s):
        """Any queued request eventually outranks ANY fresh arrival: by
        ``(old_priority - fresh_priority + 1) * aging_s`` seconds of
        waiting, the aged key is strictly smaller even against a fresh
        request with a tight (earlier-deadline) SLO."""
        from repro.deploy.serving.scheduler import PriorityDeadline

        s = PriorityDeadline(aging_s=aging_s)
        old = _sched_handle(0, old_priority, None, None, arrival_t=0.0)
        wait = (max(0, old_priority - fresh_priority) + 1) * aging_s
        now = wait * 1.0000001  # strictly past the promotion boundary
        fresh = _sched_handle(1, fresh_priority, 1.0, None, arrival_t=now)
        assert s.key(old, now) < s.key(fresh, now)
