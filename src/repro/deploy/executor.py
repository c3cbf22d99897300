"""Plan executor — runs a DeploymentPlan as a jitted JAX function.

The closing of the deploy loop: every scheduled node resolves through the
runtime :class:`~repro.core.heterogeneous.DispatchTable`, so accelerator
nodes hit the Pallas kernels (``Backend.ITA``) or the paper-faithful XLA
integer arithmetic (``Backend.W8A8``), and cluster nodes always hit the
XLA fallback kernels — exactly as ``ita_supports`` decides.

Bit-exactness contract: ``execute(plan, bind_encoder_weights(...), batch,
backend=Backend.W8A8)`` equals ``repro.models.encoder.forward_w8a8`` on
the same quantized params, element for element.  The integer arithmetic
is column-separable, so the plan's sliced Q/K/V projections reproduce the
model's fused QKV GEMM exactly; the per-head schedule reproduces the
``ita_head_by_head`` branch the same way.
"""

from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.heterogeneous import (
    DEFAULT_TABLE,
    Backend,
    DispatchTable,
    OpDesc,
    as_backend,
    backend_granule,
)
from repro.core.quant_linear import ACT_GELU, ACT_IDENTITY, ACT_RELU
from repro.deploy.plan import DecoderPlanPair, DeploymentPlan, PlanNode

#: fused-activation vocabulary the GEMM runner can lower; anything else in
#: a plan is a compile/runtime mismatch and must fail loudly (a silent
#: identity fallback executes the wrong function).
_GEMM_ACTS = {"identity": ACT_IDENTITY, "relu": ACT_RELU, "gelu": ACT_GELU}


def _ceil_to(d: int, g: int) -> int:
    return math.ceil(d / g) * g


def _gemm_desc(
    m: int, k: int, n: int, granule: int, act: str = "identity", pad_m: bool = True
) -> OpDesc:
    mm = _ceil_to(m, granule) if pad_m else m
    return OpDesc("gemm", shapes=((mm, k), (k, n)), act=act)


def _mha_desc(seq: int, head_dim: int, granule: int) -> OpDesc:
    return OpDesc("mha", shapes=((_ceil_to(seq, granule), head_dim),))


def _resolve(table: DispatchTable, desc: OpDesc, backend: Backend) -> Callable:
    return table.resolve(desc, backend)[1]


# ---------------------------------------------------------------------------
# Per-kind node compilers
#
# Every scheduled node is *bound* once per (plan, backend, table): attrs
# are unpacked, shapes described, and the DispatchTable entry resolved at
# bind time, producing a ``run(env) -> out`` closure.  ``execute`` then
# walks pre-compiled closures — no per-step dict lookups, no per-step
# ``resolve`` calls (the decode hot path dispatches in a tight loop).
# ---------------------------------------------------------------------------

def _compile_gemm(node: PlanNode, table, backend) -> Callable:
    if "heads" in node.attrs:
        raise NotImplementedError(
            f"{node.name}: un-fused attention MatMul cannot execute; lower with "
            "fuse_mha (deploy_pipeline) so attention runs as an MHA node"
        )
    a = node.attrs
    m, k, n = a["dims"]
    act_name = a.get("activation", "identity")
    if act_name not in _GEMM_ACTS:
        raise NotImplementedError(
            f"{node.name}: no GEMM lowering for fused activation {act_name!r} "
            f"(supported: {sorted(_GEMM_ACTS)})"
        )
    act = _GEMM_ACTS[act_name]
    scales = tuple(a["scales"])
    s_preact = a.get("s_preact")
    if act == ACT_GELU and s_preact is None:
        s_preact = scales[2]
    g = backend_granule(backend)
    desc = _gemm_desc(m, k, n, g, act_name, pad_m=a.get("pad_m", True))
    fn = _resolve(table, desc, backend)
    x_t, w_t = node.inputs[0], node.inputs[1]
    b_t = node.inputs[2] if len(node.inputs) > 2 else None

    def run(env):
        b = env[b_t] if b_t is not None else None
        return fn(env[x_t], env[w_t], b, scales=scales, act=act, s_preact=s_preact)

    return run


def _split(x, heads, head_dim):
    b, s, _ = x.shape
    return x.reshape(b, s, heads, head_dim).transpose(0, 2, 1, 3)


def _mha_weights(node: PlanNode, env):
    wq, wk, wv, wo = (env[t] for t in node.inputs[1:5])
    if node.attrs.get("has_bias"):
        bq, bk, bv, bo = (env[t] for t in node.inputs[5:9])
    else:
        bq = bk = bv = bo = None
    return wq, wk, wv, wo, bq, bk, bv, bo


def _compile_mha(node: PlanNode, table, backend) -> Callable:
    """Fused MHA: QKV projections -> attention core -> output projection."""
    a = node.attrs
    s, e = a["seq"], a["d_model"]
    h, hkv, hd = a["heads"], a["kv_heads"], a["head_dim"]
    proj = tuple(a["proj_scales"])
    outp = tuple(a["out_scales"])
    g = backend_granule(backend)

    gemm_q = _resolve(table, _gemm_desc(s, e, h * hd, g), backend)
    gemm_kv = _resolve(table, _gemm_desc(s, e, hkv * hd, g), backend)
    attn = _resolve(table, _mha_desc(s, hd, g), backend)
    gemm_o = _resolve(table, _gemm_desc(s, h * hd, e, g), backend)

    def run(env):
        x = env[node.inputs[0]]
        wq, wk, wv, wo, bq, bk, bv, bo = _mha_weights(node, env)
        q = gemm_q(x, wq, bq, scales=proj, act=ACT_IDENTITY, s_preact=None)
        k = gemm_kv(x, wk, bk, scales=proj, act=ACT_IDENTITY, s_preact=None)
        v = gemm_kv(x, wv, bv, scales=proj, act=ACT_IDENTITY, s_preact=None)
        at = attn(_split(q, h, hd), _split(k, hkv, hd), _split(v, hkv, hd),
                  s_act=proj[2], s_out=outp[0])
        a_m = at.transpose(0, 2, 1, 3).reshape(*x.shape[:2], h * hd)
        return gemm_o(a_m, wo, bo, scales=outp, act=ACT_IDENTITY, s_preact=None)

    return run


def _compile_mha_head(node: PlanNode, table, backend) -> Callable:
    """One head of the paper schedule: per-head Q/K/V projection slices,
    single-head attention, *raw int32* partial output projection (the
    cluster HeadAccum requantizes once after summing all heads)."""
    a = node.attrs
    s, e = a["seq"], a["d_model"]
    h, hkv, hd = a["heads"], a["kv_heads"], a["head_dim"]
    head = a["head"]
    kvh = head // (h // hkv)
    proj = tuple(a["proj_scales"])
    outp = tuple(a["out_scales"])
    g = backend_granule(backend)

    gemm_h = _resolve(table, _gemm_desc(s, e, hd, g), backend)
    attn = _resolve(table, _mha_desc(s, hd, g), backend)

    def slc(w, b, idx):
        lo = idx * hd
        return w[:, lo : lo + hd], None if b is None else b[lo : lo + hd]

    def run(env):
        x = env[node.inputs[0]]
        wq, wk, wv, wo, bq, bk, bv, bo = _mha_weights(node, env)
        q1 = gemm_h(x, *slc(wq, bq, head), scales=proj, act=ACT_IDENTITY, s_preact=None)
        k1 = gemm_h(x, *slc(wk, bk, kvh), scales=proj, act=ACT_IDENTITY, s_preact=None)
        v1 = gemm_h(x, *slc(wv, bv, kvh), scales=proj, act=ACT_IDENTITY, s_preact=None)
        a1 = attn(q1[:, None], k1[:, None], v1[:, None], s_act=proj[2], s_out=outp[0])
        wo_h = wo[head * hd : (head + 1) * hd, :]
        return jnp.matmul(a1[:, 0], wo_h, preferred_element_type=jnp.int32)

    return run


def _compile_cluster(node: PlanNode, table, backend) -> Callable:
    """Bind one cluster-engine node: resolve the runtime kernel for the
    node's own shape description once, close over unpacked attrs."""
    kind = node.kind
    a = node.attrs
    desc = OpDesc(kind, shapes=(tuple(a.get("dims", ())),))
    fn = _resolve(table, desc, backend)
    ins = node.inputs
    if kind == "layernorm":
        norm, s_gamma, s_out = a["norm"], a["s_gamma"], a["s_out"]
        params = list(ins[1:])
        g_t = params[0] if norm != "np_layernorm" and params else None
        b_t = params[1] if norm == "layernorm" and len(params) > 1 else None

        def run(env):
            pq = {}
            if g_t is not None:
                pq["g_q"] = env[g_t]
            if b_t is not None:
                pq["beta_q"] = env[b_t]
            return fn(norm, pq, env[ins[0]], s_gamma, s_out)

        return run
    if kind == "add":
        scales = tuple(a["scales"])
        return lambda env: fn(env[ins[0]], env[ins[1]], scales=scales)
    if kind == "gelu":
        s_in, s_out = a["scales"]
        return lambda env: fn(env[ins[0]], s_in=s_in, s_out=s_out)
    if kind == "embed":
        return lambda env: fn(env[ins[0]], env[ins[1]])
    if kind == "headaccum":
        h = a["heads"]
        out_scales = tuple(a["out_scales"])
        bias_t = ins[h] if len(ins) > h else None

        def run(env):
            parts = [env[t] for t in ins[:h]]
            bias = env[bias_t] if bias_t is not None else None
            return fn(parts, bias, scales=out_scales)

        return run
    if kind == "classifier":
        scale = a["scale"]
        return lambda env: fn(env[ins[0]], env[ins[1]], scale=scale)
    if kind == "dequant":
        scale = a["scale"]
        return lambda env: fn(env[ins[0]], scale=scale)
    # decoder / KV-cache kinds
    if kind == "rope":
        rows = a["dims"][0]
        heads, head_dim, theta = a["heads"], a["head_dim"], a["theta"]
        if len(ins) <= 1:
            # numpy on purpose: this constant is built at BIND time, which
            # can happen inside a caller's jit trace — a jnp.arange here
            # would be staged as that trace's tracer and leak through the
            # cached bound program into the next trace
            positions = np.arange(rows)  # prefill: static 0..S
            return lambda env: fn(env[ins[0]], positions, heads=heads,
                                  head_dim=head_dim, theta=theta)
        if rows > 1:
            # prefill chunk: S absolute angles at each lane's global
            # offset.  Scalar pos broadcasts one offset (single-lane
            # chunk dispatch); a [B] pos vector shifts per lane (the
            # engine's batched multi-slot chunk dispatch).
            def run(env):
                pos = jnp.asarray(env[ins[1]], jnp.int32)
                if pos.size == 1:
                    positions = pos.reshape(()) + jnp.arange(rows)
                else:
                    positions = pos.reshape(-1)[:, None] + jnp.arange(rows)
                return fn(env[ins[0]], positions, heads=heads,
                          head_dim=head_dim, theta=theta)

            return run
        return lambda env: fn(env[ins[0]], env[ins[1]], heads=heads,
                              head_dim=head_dim, theta=theta)
    if kind == "attn_causal":
        kw = dict(heads=a["heads"], kv_heads=a["kv_heads"], head_dim=a["head_dim"],
                  s_act=a["s_act"], s_out=a["s_out"], block_k=a["block_k"])
        return lambda env: fn(env[ins[0]], env[ins[1]], env[ins[2]], **kw)
    if kind == "attn_cached":
        kw = dict(heads=a["heads"], head_dim=a["head_dim"],
                  s_act=a["s_act"], s_out=a["s_out"], block_k=a["block_k"])
        return lambda env: fn(env[ins[0]], env[ins[1]], env[ins[2]], env[ins[3]], **kw)
    if kind == "cache_write":
        kw = dict(kv_heads=a["kv_heads"], head_dim=a["head_dim"], max_len=a["max_len"])
        cache_t = ins[1] if len(ins) > 1 else None
        pos_t = ins[2] if len(ins) > 2 else None

        def run(env):
            cache = env[cache_t] if cache_t is not None else None
            pos = env[pos_t] if pos_t is not None else None
            return fn(env[ins[0]], cache, pos, **kw)

        return run
    if kind == "attn_paged":
        kw = dict(heads=a["heads"], kv_heads=a["kv_heads"], head_dim=a["head_dim"],
                  s_act=a["s_act"], s_out=a["s_out"], block_k=a["block_k"])
        return lambda env: fn(env[ins[0]], env[ins[1]], env[ins[2]], env[ins[3]],
                              env[ins[4]], **kw)
    if kind == "cache_write_paged":
        kw = dict(kv_heads=a["kv_heads"], head_dim=a["head_dim"],
                  block_size=a["block_size"])
        active_t = ins[4] if len(ins) > 4 else None

        def run(env):
            active = env[active_t] if active_t is not None else None
            return fn(env[ins[0]], env[ins[1]], env[ins[2]], env[ins[3]], active, **kw)

        return run
    if kind == "silumul":
        scales = tuple(a["scales"])
        if len(ins) > 2:  # an expert layer's grouped rows: skip the dead tiles
            from repro.models import moe

            tile = a["row_tile"]
            return lambda env: fn(env[ins[0]], env[ins[1]], scales=scales,
                                  live_rows=moe.live_rows(env[ins[2]], tile))
        return lambda env: fn(env[ins[0]], env[ins[1]], scales=scales)
    if kind == "lasttok":
        return lambda env: fn(env[ins[0]])
    if kind == "lmhead":
        scale, tied = a["scale"], a["tied"]
        return lambda env: fn(env[ins[0]], env[ins[1]], scale=scale, tied=tied)
    raise NotImplementedError(f"no runner for op kind {kind!r} ({node.op})")


def _compile_moe(node: PlanNode, table, backend) -> Callable:
    """Bind one node of an expert layer: the integer functions of
    :mod:`repro.models.moe`; the grouped GEMM resolves through the
    dispatch table (Pallas ``expert_gemm`` on ``ita``, its XLA runner
    otherwise)."""
    from repro.deploy.paging import SCRATCH_BLOCK
    from repro.models import moe

    a, ins, op = node.attrs, node.inputs, node.op
    if op == "MoeRouter":
        return lambda env: moe.router_logits(env[ins[0]], env[ins[1]])
    if op == "MoeRoute":
        s_in, s_w, s_out = a["scales"]
        qp = moe.make_qparams(s_in, s_w, s_out)
        kw = dict(top_k=a["top_k"], norm_topk=a["norm_topk"], mult=qp.mult, shift=qp.shift)
        lanes = a["lanes"]

        def run(env):
            logits = env[ins[0]]
            valid = None
            if lanes == "active":
                valid = jnp.asarray(env[ins[1]]).astype(bool).reshape(-1)
            elif lanes == "block_table":
                # a lane parked on an all-scratch table carries no request
                bt = jnp.asarray(env[ins[1]], jnp.int32)
                valid = bt.reshape(-1, bt.shape[-1])[:, 0] != SCRATCH_BLOCK
            if valid is not None:
                valid = jnp.broadcast_to(valid[:, None], logits.shape[:-1])
            return moe.route_int(logits, valid, **kw)

        return run
    if op == "MoeDispatch":
        n_e, tile = a["n_experts"], a["row_tile"]

        def run(env):
            h, ids = env[ins[0]], env[ins[1]]
            return moe.dispatch(h.reshape(-1, h.shape[-1]), ids.reshape(-1, ids.shape[-1]),
                                n_e, tile)

        return run
    if op == "ExpertGemm":
        _, k, n = a["dims"]
        fn = _resolve(table, OpDesc("expert_gemm", shapes=((k, n),)), backend)
        scales, tile = tuple(a["scales"]), a["row_tile"]
        return lambda env: fn(env[ins[0]], env[ins[1]], env[ins[2]], scales=scales,
                              row_tile=tile)
    if op == "MoeCombine":
        qp = moe.make_qparams(*a["scales"])

        def run(env):
            w = env[ins[2]]
            out = moe.combine_int(env[ins[0]], env[ins[1]], w.reshape(-1, w.shape[-1]),
                                  qp.mult, qp.shift)
            return out.reshape(*w.shape[:-1], out.shape[-1])

        return run
    if op == "SharedGate":
        qp = moe.make_qparams(*a["scales"])
        table_q = tuple(int(v) for v in moe.sigmoid_table(a["s_gate"]))
        return lambda env: moe.shared_gate_int(env[ins[0]], env[ins[1]], table_q,
                                               qp.mult, qp.shift)
    if op == "MoeCount":
        return lambda env: sum(moe.expert_counts(env[t]) for t in ins)
    raise NotImplementedError(f"no runner for expert-layer op {op!r} ({node.name})")


def _compile_region(node: PlanNode, table, backend) -> Callable:
    """Bind a FusedRegion: compile every body node, then close the whole
    region into ONE jitted callable — a single dispatch executes the
    entire same-engine run (cluster closures trace into one XLA
    computation; ita bodies trace their Pallas kernels into one fused
    program).  Nested under an outer jit the inner jit inlines, so
    region plans stay trace-compatible."""
    body = tuple((b, _compile_node(b, table, backend)) for b in node.body)
    in_names, out_names = node.inputs, node.outputs

    def region_fn(*args):
        env = dict(zip(in_names, args))
        for b, run in body:
            with jax.named_scope(_scope(b)):
                _store(env, b, run(env))
        return tuple(env[t] for t in out_names)

    jitted = jax.jit(region_fn)

    def run(env):
        args = tuple(env[t] for t in in_names)
        if any(isinstance(x, jax.core.Tracer) for x in args):
            # already under a caller's jit (the session wraps the whole
            # schedule): inline the body so the region costs nothing —
            # a nested pjit call boundary here measurably slows the
            # decode step without buying a dispatch back
            return region_fn(*args)
        return jitted(*args)

    return run


def _store(env: dict, node: PlanNode, value) -> None:
    """A node's result into ``env``: one array, or a tuple for a fused
    region and for a node with several outputs (the expert layer's route
    and dispatch)."""
    if node.fused or len(node.outputs) > 1:
        env.update(zip(node.outputs, value))
    else:
        env[node.outputs[0]] = value


def _scope(node: PlanNode) -> str:
    """The named scope a node's device ops carry in their ``op_name``."""
    return f"{node.kind}.{node.name}"


def _compile_node(node: PlanNode, table, backend) -> Callable:
    if node.fused:
        return _compile_region(node, table, backend)
    kind = node.kind
    if kind == "gemm":
        return _compile_gemm(node, table, backend)
    if kind == "mha":
        if node.op == "MHAHead":
            return _compile_mha_head(node, table, backend)
        return _compile_mha(node, table, backend)
    if kind == "moe":
        return _compile_moe(node, table, backend)
    return _compile_cluster(node, table, backend)


def _run_node(node: PlanNode, env, table, backend):
    """Compile-and-run one node (single-shot helper; the execute path
    binds the whole schedule once via :func:`bind_plan`)."""
    return _compile_node(node, table, backend)(env)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def bind_plan(
    plan: DeploymentPlan,
    *,
    backend: Backend | str = Backend.W8A8,
    table: DispatchTable | None = None,
) -> tuple:
    """Resolve every scheduled node to its runner ONCE, cached per plan.

    Returns the bound program: a tuple of ``(node, run)`` pairs in
    schedule order.  The cache lives on the plan instance keyed by
    ``(backend, id(table))`` (the table object is retained, so its id
    cannot be reused); repeated ``execute`` calls — the decode loop —
    never touch :meth:`DispatchTable.resolve` again.
    """
    backend = as_backend(backend)
    table = DEFAULT_TABLE if table is None else table
    cache = plan.__dict__.setdefault("_bound_programs", {})
    key = (backend, id(table))
    hit = cache.get(key)
    if hit is not None:
        return hit[1]
    program = tuple((n, _compile_node(n, table, backend)) for n in plan.nodes)
    cache[key] = (table, program)
    return program


def execute(
    plan: DeploymentPlan,
    weights: dict,
    batch: dict,
    *,
    backend: Backend | str = Backend.W8A8,
    table: DispatchTable | None = None,
):
    """Run one forward pass of the plan (trace-compatible: jit freely).

    ``batch`` maps the plan's input names (``tokens`` / ``patches`` /
    ``frames``) to arrays with a leading batch dim; every runner
    broadcasts over that dim exactly like the model path.
    """
    program = bind_plan(plan, backend=backend, table=table)
    # trace-time only under jit: retraces are keyed on shape/dtype, so a
    # shape that passed once never re-pays this check in the decode loop
    check_bindings(plan, batch=batch)
    env = dict(weights)
    for name in plan.inputs:
        env[name] = batch[name]
    for node, run in program:
        with jax.named_scope(_scope(node)):
            _store(env, node, run(env))
    outs = [env[name] for name in plan.outputs]
    return outs[0] if len(outs) == 1 else tuple(outs)


def _weight_binder(weights: dict):
    """(put, put_norm) closures writing non-None params into ``weights``."""

    def put(name, arr):
        if arr is not None:
            weights[name] = arr

    def put_norm(prefix, pq):
        put(prefix + "_g", pq.get("g_q"))
        put(prefix + "_b", pq.get("beta_q"))

    return put, put_norm


def _bind_attn_layer(put, put_norm, pre: str, cfg: ArchConfig, lp: dict) -> None:
    """Shared per-layer attention/norm binding: the fused ``wqkv`` weight
    (and bias) is column-sliced into the plan's wq/wk/wv tensors —
    bit-identical to the fused GEMM (integer accumulation is
    column-separable)."""
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    qd, kd = h * hd, hkv * hd
    wqkv, bqkv = lp["attn"]["wqkv"]["w_q"], lp["attn"]["wqkv"].get("b_q")
    put(pre + "wq", wqkv[:, :qd])
    put(pre + "wk", wqkv[:, qd : qd + kd])
    put(pre + "wv", wqkv[:, qd + kd : qd + 2 * kd])
    if bqkv is not None:
        put(pre + "wq_b", bqkv[:qd])
        put(pre + "wk_b", bqkv[qd : qd + kd])
        put(pre + "wv_b", bqkv[qd + kd : qd + 2 * kd])
    put(pre + "wo", lp["attn"]["wo"]["w_q"])
    put(pre + "wo_b", lp["attn"]["wo"].get("b_q"))
    put_norm(pre + "norm1", lp["norm1"])
    put_norm(pre + "norm2", lp["norm2"])


class PlanBindingError(ValueError):
    """Bound arrays contradict the plan's static ``TensorSpec`` contract.

    Raised at *bind time* (weights) or *trace time* (batch inputs) with
    **every** mismatch listed — shape, dtype, missing binding — so one
    failed bind names the whole delta instead of dying on the first
    offender per rerun.  Under ``jax.jit`` the input check runs only
    while tracing, so the decode hot path pays nothing steady-state.
    """

    def __init__(self, mismatches: list[str], *, what: str = "binding"):
        self.mismatches = list(mismatches)
        lines = "; ".join(self.mismatches)
        super().__init__(
            f"plan {what} rejects {len(self.mismatches)} tensor(s): {lines}"
        )


#: spec dtype -> array dtypes accepted for it.  int32 specs accept bool
#: arrays (lane masks like ``active`` are carried as bools host-side and
#: widened inside the kernels); everything else binds exactly.
_BIND_DTYPES = {
    "int8": {"int8"},
    "int32": {"int32", "bool"},
    "float32": {"float32"},
}


def _spec_mismatch(spec, arr, *, batched: bool) -> str | None:
    """One mismatch line, or None if ``arr`` satisfies ``spec``.

    ``batched`` specs additionally accept one leading batch dimension
    (the session dispatches every plan at ``[B, ...]``; the plan's specs
    describe a single request slot).
    """
    shape = tuple(getattr(arr, "shape", ()))
    ok_shape = shape == spec.shape or (batched and shape[1:] == spec.shape)
    dt = str(getattr(arr, "dtype", type(arr).__name__))
    ok_dtype = dt in _BIND_DTYPES.get(spec.dtype, {spec.dtype})
    if ok_shape and ok_dtype:
        return None
    want = f"{spec.dtype}{list(spec.shape)}"
    got = f"{dt}{list(shape)}"
    return f"{spec.name}: spec {want} vs bound {got}"


def check_bindings(
    plan: DeploymentPlan,
    *,
    weights: dict | None = None,
    batch: dict | None = None,
) -> None:
    """Pre-flight every provided binding against the plan's ``TensorSpec``s.

    ``weights``: every declared plan weight must be present with the
    spec's exact shape and a compatible dtype.  ``batch``: every plan
    input must be present, matching its spec exactly or with one leading
    batch dimension.  All violations raise together as one
    :class:`PlanBindingError`.
    """
    bad: list[str] = []
    if weights is not None:
        for name in plan.weight_names:
            if name not in weights:
                bad.append(f"{name}: declared plan weight never bound")
                continue
            m = _spec_mismatch(plan.tensors[name], weights[name], batched=False)
            if m:
                bad.append(m)
        what = "weight binding"
    if batch is not None:
        for name in plan.inputs:
            if name not in batch:
                bad.append(f"{name}: plan input missing from the batch")
                continue
            m = _spec_mismatch(plan.tensors[name], batch[name], batched=True)
            if m:
                bad.append(m)
        what = "input binding"
    if bad:
        raise PlanBindingError(bad, what=what)


def _check_bound(plan: DeploymentPlan, weights: dict) -> dict:
    """Keep only the plan's declared weights; fail on unbound/misshaped ones."""
    bound = {k: v for k, v in weights.items() if k in plan.tensors and plan.tensors[k].weight}
    check_bindings(plan, weights=bound)
    return bound


def bind_encoder_weights(plan: DeploymentPlan, cfg: ArchConfig, qp: dict) -> dict:
    """Map plan weight names onto the model's quantized param pytree.

    ``qp`` is ``repro.models.encoder.quantize_params`` output (stacked
    layers from vmap).
    """
    weights: dict = {}
    put, put_norm = _weight_binder(weights)

    for l in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[l], qp["layers"])
        pre = f"l{l}_"
        _bind_attn_layer(put, put_norm, pre, cfg, lp)
        put(pre + "up", lp["mlp"]["up"]["w_q"])
        put(pre + "up_b", lp["mlp"]["up"].get("b_q"))
        put(pre + "down", lp["mlp"]["down"]["w_q"])
        put(pre + "down_b", lp["mlp"]["down"].get("b_q"))

    put("pos", qp["pos_q"][: plan.seq_len])
    put_norm("final_norm", qp["final_norm"])
    if "embed" in qp:
        put("embed_table", qp["embed"]["table_q"])
    return _check_bound(plan, weights)


# ---------------------------------------------------------------------------
# Decoder plans: weight binding + KV-cache-threading executors
# ---------------------------------------------------------------------------

def _layer(qp: dict, l: int) -> dict:
    """Layer ``l`` of a quantized pytree: its ``layers`` stacked over a
    leading axis, or a list of per-layer trees (bound without a copy)."""
    layers = qp["layers"]
    if isinstance(layers, (list, tuple)):
        return layers[l]
    return jax.tree.map(lambda a: a[l], layers)


def bind_decoder_weights(plan: DeploymentPlan, cfg: ArchConfig, qp: dict) -> dict:
    """Map decoder plan weight names onto ``transformer.quantize_params``.

    Shares the encoder binder's fused-QKV column slicing; the prefill and
    decode plans declare one weight set, so binding against either plan
    yields the same dict.
    """
    weights: dict = {}
    put, put_norm = _weight_binder(weights)

    for l in range(cfg.n_layers):
        lp = _layer(qp, l)
        pre = f"l{l}_"
        _bind_attn_layer(put, put_norm, pre, cfg, lp)
        mlp = lp["mlp"]
        for mname in ("gate", "up", "down"):
            if mname in mlp:
                put(pre + mname, mlp[mname]["w_q"])
                put(pre + mname + "_b", mlp[mname].get("b_q"))
        if cfg.n_experts:
            put(pre + "router", mlp["router"]["w_q"])
            for mname in ("gate", "up", "down"):
                put(pre + "experts_" + mname, mlp["experts"][mname + "_q"])
            if "shared" in mlp:
                for mname, p in mlp["shared"].items():
                    put(pre + "shared_" + mname + "_proj", p["w_q"])
                put(pre + "shared_expert_gate", mlp["shared_gate"]["w_q"])

    put_norm("final_norm", qp["final_norm"])
    put("embed_table", qp["embed"]["table_q"])
    if "lm_head" in qp:
        put("lm_head", qp["lm_head"]["w_q"])
    return _check_bound(plan, weights)


def _stack_cache(plan: DeploymentPlan, outs_by_name: dict, length) -> dict:
    """Per-layer cache outputs -> the model-shaped cache pytree
    ``{"k": [L, B, Hkv, M, D], "v": ..., "len": int32}``."""
    ks = [outs_by_name[out] for _, out in plan.kv_state[0::2]]
    vs = [outs_by_name[out] for _, out in plan.kv_state[1::2]]
    with jax.named_scope("kv_pool.stack"):
        return {"k": jnp.stack(ks), "v": jnp.stack(vs),
                "len": jnp.asarray(length, jnp.int32)}


def execute_prefill(
    pair: DecoderPlanPair,
    weights: dict,
    batch: dict,
    *,
    backend: Backend | str = Backend.W8A8,
    table: DispatchTable | None = None,
):
    """Run the prefill schedule. Returns ``(logits, cache)`` with the same
    cache pytree as ``transformer.prefill_w8a8`` (bit-comparable)."""
    plan = pair.prefill
    outs = execute(plan, weights, batch, backend=backend, table=table)
    outs_by_name = dict(zip(plan.outputs, outs))
    return outs_by_name[plan.outputs[0]], _stack_cache(plan, outs_by_name, plan.seq_len)


def execute_decode(
    pair: DecoderPlanPair,
    weights: dict,
    cache: dict,
    token,
    *,
    pos=None,
    backend: Backend | str = Backend.W8A8,
    table: DispatchTable | None = None,
):
    """Advance one token per request through the decode schedule.

    ``pos`` is the generation depth fed to RoPE, the cache append and the
    attention mask: a scalar (every request at the same depth — the
    classic chained-decode loop, default ``cache["len"]``) or a **[B]
    per-request vector** (continuous batching: one dispatch advances a
    batch of requests at distinct depths, each against its own rows of
    the statically planned KV region).
    """
    plan = pair.decode
    if pos is None:
        pos = cache["len"]
    batch = {"token": token, "pos": pos}
    with jax.named_scope("kv_pool.slice"):
        for i, (cin, _) in enumerate(plan.kv_state):
            batch[cin] = cache["k" if i % 2 == 0 else "v"][i // 2]
    outs = execute(plan, weights, batch, backend=backend, table=table)
    outs_by_name = dict(zip(plan.outputs, outs))
    cache_out = _stack_cache(plan, outs_by_name, pos + 1)
    return outs_by_name[plan.outputs[0]], cache_out


# ---------------------------------------------------------------------------
# Paged decoder plans: pool-threading executors
# ---------------------------------------------------------------------------

def _stack_pool(plan: DeploymentPlan, outs_by_name: dict) -> dict:
    """Per-layer pool outputs -> the session pool pytree
    ``{"k": [L, P+1, Hkv, block_size, D] int8, "v": ...}`` (no batch dim:
    the pool is shared across request slots by construction)."""
    ks = [outs_by_name[out] for _, out in plan.kv_state[0::2]]
    vs = [outs_by_name[out] for _, out in plan.kv_state[1::2]]
    with jax.named_scope("kv_pool.stack"):
        return {"k": jnp.stack(ks), "v": jnp.stack(vs)}


def _paged_batch(plan: DeploymentPlan, pool: dict, extra: dict) -> dict:
    batch = dict(extra)
    with jax.named_scope("kv_pool.slice"):
        for i, (cin, _) in enumerate(plan.kv_state):
            batch[cin] = pool["k" if i % 2 == 0 else "v"][i // 2]
    return batch


def execute_prefill_paged(
    pair: DecoderPlanPair,
    weights: dict,
    pool: dict,
    tokens,
    start,
    block_table,
    *,
    backend: Backend | str = Backend.W8A8,
    table: DispatchTable | None = None,
):
    """Run one chunk of the paged prefill schedule.

    ``tokens`` int32 [B, S] (S = the lowered prompt length), ``start``
    the chunk's global token offset (scalar; 0 for the first chunk),
    ``block_table`` int32 [B, blocks_per_slot].  Writes rows
    ``[start, start + S)`` of each lane's logical cache through its block
    table and returns ``(last-token logits, updated pool, expert
    counts)``; the counts (:data:`~repro.deploy.lowering.EXPERT_COUNTS`)
    are None for a plan without expert layers.
    """
    plan = pair.prefill
    batch = _paged_batch(plan, pool, {
        "tokens": tokens, "pos": start, "block_table": block_table,
    })
    outs = execute(plan, weights, batch, backend=backend, table=table)
    return _paged_outputs(plan, outs)


def _paged_outputs(plan: DeploymentPlan, outs) -> tuple:
    from repro.deploy.lowering import EXPERT_COUNTS

    outs_by_name = dict(zip(plan.outputs, outs))
    return (outs_by_name[plan.outputs[0]], _stack_pool(plan, outs_by_name),
            outs_by_name.get(EXPERT_COUNTS))


def execute_decode_paged(
    pair: DecoderPlanPair,
    weights: dict,
    pool: dict,
    token,
    pos,
    block_table,
    active,
    *,
    backend: Backend | str = Backend.W8A8,
    table: DispatchTable | None = None,
):
    """Advance one token per active lane through the paged decode schedule.

    ``pos`` int32 [B] per-lane depths, ``block_table`` int32
    [B, blocks_per_slot], ``active`` bool/int32 [B] — inactive lanes
    (free slots, slots mid-chunked-prefill) dispatch anyway (the batch
    shape is static) but their cache writes land in the scratch block and
    their logits are discarded by the caller.  Returns what
    :func:`execute_prefill_paged` returns.
    """
    plan = pair.decode
    batch = _paged_batch(plan, pool, {
        "token": token, "pos": pos, "block_table": block_table,
        "active": active,
    })
    outs = execute(plan, weights, batch, backend=backend, table=table)
    return _paged_outputs(plan, outs)
