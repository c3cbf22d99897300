"""Heterogeneous dispatch — the "ITA or cluster" decision, per operator.

The paper's template maps each DNN operator either to the accelerator
(GEMM / MHA / supported activations, when shapes satisfy the geometric
constraints) or to fallback kernels on the cluster cores.  Here the
"accelerator" is the Pallas kernel path (or the w8a8 XLA integer path on
non-TPU hosts) and the "cluster" is plain XLA.

``repro.deploy`` makes the static mapping decision per graph node; this
module holds the runtime registry and the geometric support predicate the
planner queries — the direct analogue of Deeploy's accelerator model
("first, the accelerator model must specify the geometrical tiling
constraints for operators it can run").

``DEFAULT_TABLE`` is populated at import time from the kernel packages:
every op kind the plan executor (``repro.deploy.executor``) can schedule
has a CLUSTER fallback (XLA integer kernels), and the accelerated kinds
additionally carry per-backend ACCELERATOR implementations — the
paper-faithful XLA arithmetic for ``Backend.W8A8`` and the Pallas kernels
for ``Backend.ITA``.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Callable


class Backend(enum.Enum):
    FLOAT = "float"  # bf16/f32 reference ("cluster-only" at model level)
    W8A8 = "w8a8"  # XLA integer path (paper-faithful arithmetic)
    ITA = "ita"  # Pallas kernels (TPU target / interpret on CPU)


class Engine(enum.Enum):
    ACCELERATOR = "ita"
    CLUSTER = "cluster"


# ITA geometric constraints (Section IV-B): vector length M=64, dimensions
# up to 512, 64-granule tiles.  The TPU adaptation aligns to the MXU/VMEM
# granule of 128 instead; both are checked by the planner.
ITA_GRANULE = 64
ITA_MAX_DIM = 512
TPU_GRANULE = 128


@dataclasses.dataclass(frozen=True)
class OpDesc:
    """Shape/type description of one operator instance."""

    kind: str  # "gemm" | "mha" | "layernorm" | "rmsnorm" | "softmax" | ...
    shapes: tuple[tuple[int, ...], ...]
    dtype: str = "int8"
    act: str = "identity"


#: ops the accelerator datapath supports at all
ACCEL_KINDS = {"gemm", "expert_gemm", "mha", "relu", "gelu", "identity"}


def backend_granule(backend: "Backend") -> int:
    """Alignment granule at which ``resolve`` judges ``ita_supports``.

    ``Backend.ITA`` runs the *Pallas* kernels and hence aligns to the TPU
    MXU granule, while ``Backend.W8A8`` runs the paper-faithful arithmetic
    at the ASIC's granule.  The raw pairing ("ITA backend -> TPU granule")
    reads inverted at call sites, so derive it here, not by hand.
    """
    return TPU_GRANULE if backend is Backend.ITA else ITA_GRANULE


def as_backend(backend: "Backend | str") -> "Backend":
    """Normalize a backend given as enum or name string, once, at the API
    boundary.  Every executor/compile entry point routes through this, so
    ``backend="ita"`` and ``backend=Backend.ITA`` are interchangeable
    everywhere and unknown names fail with the valid vocabulary."""
    if isinstance(backend, Backend):
        return backend
    if isinstance(backend, str):
        try:
            return Backend(backend.lower())
        except ValueError:
            raise ValueError(
                f"unknown backend {backend!r}; expected one of "
                f"{sorted(b.value for b in Backend)}"
            ) from None
    raise TypeError(f"backend must be a Backend or name string, got {type(backend)!r}")


def ita_supports(op: OpDesc, granule: int = ITA_GRANULE) -> bool:
    """Would ITA (resp. the Pallas kernel set) accept this op?

    The ASIC requires int8 operands and 64-aligned dims; dims beyond 512
    are handled by *tiling*, so only alignment matters here.  Non-int8 or
    unsupported kinds fall back to the cluster.

    MHA is special-cased: the single-head datapath fixes the P dimension
    at the ITA granule (the paper's P=64 vector length) regardless of the
    host granule — the attention runner pads the sequence itself, so only
    the head dim gates acceptance.
    """
    if op.kind not in ACCEL_KINDS:
        return False
    if op.dtype != "int8":
        return False
    if op.kind == "mha":
        # shapes = ((seq, head_dim),): seq is padded by the runner/tiler,
        # head_dim must match the single-head datapath width.
        return all(s[-1] % ITA_GRANULE == 0 for s in op.shapes)
    for shape in op.shapes:
        for d in shape[-2:]:  # contracting/output dims must be aligned
            if d % granule != 0:
                return False
    return True


@dataclasses.dataclass
class DispatchTable:
    """Runtime registry: op kind -> {engine -> callable}.

    ``register(..., backend=...)`` installs a backend-specific override —
    the mechanism by which the accelerator slot holds the paper-faithful
    XLA arithmetic under ``Backend.W8A8`` and the Pallas kernel under
    ``Backend.ITA`` simultaneously.  A cluster slot can be overridden the
    same way (``silumul`` runs a Pallas kernel under ``Backend.ITA``)
    without moving the op to the accelerator.  ``when`` limits an
    override to the op shapes it accepts (``attn_paged`` runs its kernel
    at one query row); other shapes keep the slot's runner.
    """

    table: dict[str, dict[Engine, Callable]] = dataclasses.field(default_factory=dict)
    overrides: dict[tuple[str, Engine, Backend], tuple[Callable, Callable | None]] = (
        dataclasses.field(default_factory=dict)
    )

    def register(
        self, kind: str, engine: Engine, fn: Callable, backend: Backend | None = None,
        when: Callable[[OpDesc], bool] | None = None,
    ) -> None:
        if backend is None:
            self.table.setdefault(kind, {})[engine] = fn
        else:
            self.table.setdefault(kind, {})
            self.overrides[(kind, engine, backend)] = (fn, when)

    def kinds(self) -> set[str]:
        return set(self.table)

    def _lookup(self, kind: str, engine: Engine, backend: Backend,
                op: OpDesc | None = None) -> Callable:
        fn, when = self.overrides.get((kind, engine, backend), (None, None))
        if fn is None or (when is not None and (op is None or not when(op))):
            fn = self.table[kind][engine]
        return fn

    def _has_accelerator(self, kind: str, backend: Backend) -> bool:
        return Engine.ACCELERATOR in self.table.get(kind, {}) or (
            (kind, Engine.ACCELERATOR, backend) in self.overrides
        )

    def resolve(self, op: OpDesc, backend: Backend) -> tuple[Engine, Callable]:
        if backend is Backend.FLOAT:
            return Engine.CLUSTER, self._lookup(op.kind, Engine.CLUSTER, backend, op)
        granule = backend_granule(backend)
        if ita_supports(op, granule) and self._has_accelerator(op.kind, backend):
            return Engine.ACCELERATOR, self._lookup(op.kind, Engine.ACCELERATOR, backend, op)
        return Engine.CLUSTER, self._lookup(op.kind, Engine.CLUSTER, backend, op)


DEFAULT_TABLE = DispatchTable()


def _pick_block(dim: int, prefs: tuple[int, ...] = (512, 256, 128)) -> int:
    """Largest preferred Pallas block dividing ``dim`` (whole dim otherwise)."""
    for p in prefs:
        if dim % p == 0:
            return p
    return dim


def populate_default_table(table: DispatchTable | None = None) -> DispatchTable:
    """Fill a dispatch table from the kernel packages + XLA fallbacks.

    Called at import time on ``DEFAULT_TABLE`` (the plan is only as real
    as its runnable kernels), so importing this module pulls in jax and
    the kernel packages; the imports stay local to keep the module's
    declarations usable before population.  Registered callables have one
    uniform signature per kind (the plan executor prepares arguments
    once, whatever the engine):

      gemm:       fn(x, w, b, *, scales, act, s_preact) -> int8
      mha:        fn(qh, kh, vh, *, s_act, s_out) -> int8  [B, H, S, D]
      softmax:    fn(logits_q) -> int8
      gelu:       fn(x_q, *, s_in, s_out) -> int8
      layernorm:  fn(kind, pq, x_q, s_gamma, s_out) -> int8
      add:        fn(a_q, b_q, *, scales) -> int8
      headaccum:  fn(parts, bias_q, *, scales) -> int8
      embed:      fn(table_q, tokens) -> int8
      classifier: fn(h_q, table_q, *, scale) -> float32
      dequant:    fn(h_q, *, scale) -> float32

    Decoder / KV-cache kinds (all cluster: integer RoPE, SiLU and cache
    maintenance are Snitch software kernels in the paper's template, and
    the ITA attention datapath has no causal/cache-mask mode):

      rope:        fn(x_q, positions, *, heads, head_dim, theta) -> int8
      attn_causal: fn(q, k, v, *, heads, kv_heads, head_dim, s_act, s_out,
                      block_k) -> int8  [B, S, H*D] merged layout
      attn_cached: fn(q, k_cache, v_cache, pos, *, heads, head_dim, s_act,
                      s_out, block_k) -> int8  [B, 1, H*D]
      cache_write: fn(kv, cache | None, pos | None, *, kv_heads, head_dim,
                      max_len) -> int8  [B, Hkv, max_len, D]
      attn_paged:  fn(q, k_pool, v_pool, pos, block_table, *, heads,
                      kv_heads, head_dim, s_act, s_out, block_k)
                      -> int8  [B, S, H*D] (block-table gather; S = 1 for
                      decode, S = seq_len for a prefill chunk; under ``ita``
                      at S = 1 a kernel reads only the live blocks)
      cache_write_paged: fn(kv, pool, pos, block_table, active | None, *,
                      kv_heads, head_dim, block_size)
                      -> int8 pool [P+1, Hkv, block_size, D] (scatter at
                      per-lane rows; inactive lanes land in scratch)
      silumul:     fn(gate_q, up_q, *, scales, live_rows=None) -> int8
                      (rows from live_rows on are zero: an expert
                      layer's padding, skipped by the kernel)
      lasttok:     fn(x_q) -> int8 (last sequence position)
      lmhead:      fn(h_q, w_q, *, scale, tied) -> float32

    The expert layer's grouped GEMM (the plan's ``ExpertGemm``; the other
    expert-layer nodes are cluster code in ``repro.models.moe``):

      expert_gemm: fn(x_q, tiles, w_q, *, scales, row_tile) -> int8 [P, N]
                   (rows grouped by expert, ``w_q`` [E, K, N])
    """
    table = DEFAULT_TABLE if table is None else table

    import jax
    import jax.numpy as jnp

    from repro.core import itamax as im
    from repro.core.attention import (
        MhaQParams,
        attention_decode_i8,
        attention_flash_i8,
        attention_rowwise_i8,
    )
    from repro.core.igelu import igelu_int, make_igelu_params
    from repro.core.quant_linear import ACT_IDENTITY, make_qlinear_params, qlinear_i8
    from repro.kernels import igelu as igelu_pallas
    from repro.kernels import int8_gemm as int8_gemm_pallas
    from repro.kernels import ita_attention as ita_attention_pallas
    from repro.kernels import paged_attention as paged_attention_pallas
    from repro.kernels import paged_attention_ref
    from repro.kernels import expert_gemm as expert_gemm_pallas
    from repro.kernels import expert_gemm_ref
    from repro.kernels import silumul as silumul_pallas
    from repro.kernels import silumul_ref
    from repro.kernels.paged_attention.ref import norm_table
    from repro.models import layers as L
    from repro.quant.qparams import make_qparams, requantize

    # -- gemm: ITA's GEMM mode (int8 matmul + bias + requant + activation)
    def _gemm_xla(x_q, w_q, b_q, *, scales, act=ACT_IDENTITY, s_preact=None):
        s_in, s_w, s_out = scales
        return qlinear_i8(x_q, w_q, b_q, make_qlinear_params(s_in, s_w, s_out, act, s_preact))

    def _gemm_ita(x_q, w_q, b_q, *, scales, act=ACT_IDENTITY, s_preact=None):
        s_in, s_w, s_out = scales
        *lead, k = x_q.shape
        m = 1
        for d in lead:
            m *= d
        n = w_q.shape[1]
        if m % TPU_GRANULE == 0:
            bm, pad = _pick_block(m, (256, 128)), 0
        else:
            # pad rows up to the MXU granule (zero rows, exact: they are
            # sliced away after the requant) — unaligned block_m would not
            # compile on real TPUs even though interpret mode accepts it
            bm = TPU_GRANULE
            pad = bm - m % bm
        x2 = x_q.reshape(m, k)
        if pad:
            x2 = jnp.concatenate([x2, jnp.zeros((pad, k), x_q.dtype)], axis=0)
        out = int8_gemm_pallas(
            x2, w_q, b_q, s_in=s_in, s_w=s_w, s_out=s_out, act=act, s_preact=s_preact,
            block_m=bm, block_n=_pick_block(n), block_k=_pick_block(k),
        )
        if pad:
            out = out[:m]
        return out.reshape(*lead, n)

    table.register("gemm", Engine.CLUSTER, _gemm_xla)
    table.register("gemm", Engine.ACCELERATOR, _gemm_xla, backend=Backend.W8A8)
    table.register("gemm", Engine.ACCELERATOR, _gemm_ita, backend=Backend.ITA)

    # -- mha: the fused attention core (projections dispatch as gemm)
    def _mha_xla(qh, kh, vh, *, s_act, s_out):
        p = MhaQParams.make(s_act, s_act, s_act, s_out, qh.shape[-1])
        return attention_rowwise_i8(qh, kh, vh, p)

    def _mha_ita(qh, kh, vh, *, s_act, s_out):
        # Pallas kernel wants 128-aligned sequence tiles; pad + mask the
        # KV tail (same recipe as the model-level ita backend).
        sq = qh.shape[2]
        pad = (-sq) % TPU_GRANULE
        if pad:
            qh = jnp.pad(qh, ((0, 0), (0, 0), (0, pad), (0, 0)))
            kh = jnp.pad(kh, ((0, 0), (0, 0), (0, pad), (0, 0)))
            vh = jnp.pad(vh, ((0, 0), (0, 0), (0, pad), (0, 0)))
        out = ita_attention_pallas(
            qh, kh, vh, s_q=s_act, s_k=s_act, s_v=s_act, s_out=s_out,
            block_q=TPU_GRANULE, block_k=TPU_GRANULE, kv_valid=sq if pad else None,
        )
        return out[:, :, :sq] if pad else out

    table.register("mha", Engine.CLUSTER, _mha_xla)
    table.register("mha", Engine.ACCELERATOR, _mha_xla, backend=Backend.W8A8)
    table.register("mha", Engine.ACCELERATOR, _mha_ita, backend=Backend.ITA)

    # -- softmax: standalone rowwise ITAMax, cluster only — like the ASIC,
    # the ITAMax unit accelerates softmax only inside the MHA datapath
    # ("softmax" is deliberately absent from ACCEL_KINDS)
    table.register("softmax", Engine.CLUSTER, im.itamax_rowwise)

    # -- gelu: standalone i-GeLU (survives only when the producing GEMM
    # was not accelerated, so the epilogue fusion could not fold it)
    def _igelu_xla(x_q, *, s_in: float, s_out: float):
        gp = make_igelu_params(s_in)
        qp = make_qparams(gp.out_scale, 1.0, s_out)
        return requantize(igelu_int(x_q, gp), qp.mult, qp.shift)

    def _igelu_ita(x_q, *, s_in: float, s_out: float):
        return igelu_pallas(x_q, in_scale=s_in, out_scale=s_out)

    table.register("gelu", Engine.CLUSTER, _igelu_xla)
    table.register("gelu", Engine.ACCELERATOR, _igelu_xla, backend=Backend.W8A8)
    table.register("gelu", Engine.ACCELERATOR, _igelu_ita, backend=Backend.ITA)

    # -- cluster-only auxiliaries (the paper's Snitch fallback kernels)
    table.register("layernorm", Engine.CLUSTER, L.norm_apply_i8)

    def _iadd(a_q, b_q, *, scales):
        return L.iadd_i8(a_q, b_q, *L.make_iadd_params(*scales))

    table.register("add", Engine.CLUSTER, _iadd)
    table.register("embed", Engine.CLUSTER, lambda table_q, tokens: table_q[tokens])

    def _head_accum(parts, bias_q, *, scales):
        # exact model-path arithmetic: int32 sum of the per-head partial
        # output projections, one requant, then the bias fold-in
        s_in, s_w, s_out = scales
        acc = jnp.asarray(parts[0], jnp.int32)
        for p in parts[1:]:
            acc = acc + jnp.asarray(p, jnp.int32)
        qp_o = make_qparams(s_in, s_w, s_out)
        out = requantize(acc, qp_o.mult, qp_o.shift)
        if bias_q is not None:
            qb = make_qparams(s_in, 1.0, s_out)
            out = requantize(
                jnp.asarray(out, jnp.int32) + requantize(bias_q, qp_o.mult, qp_o.shift),
                qb.mult, qb.shift,
            )
        return out

    table.register("headaccum", Engine.CLUSTER, _head_accum)

    def _classifier(h_q, table_q, *, scale: float):
        acc = jnp.matmul(
            h_q.astype(jnp.int8), table_q.astype(jnp.int8).T,
            preferred_element_type=jnp.int32,
        )
        return acc.astype(jnp.float32) * scale

    table.register("classifier", Engine.CLUSTER, _classifier)
    table.register("dequant", Engine.CLUSTER, lambda h_q, *, scale: h_q.astype(jnp.float32) * scale)

    # -- decoder / KV-cache cluster kinds (serving path; see docstring).
    # Plan tensors keep the merged [S, H*D] layout between nodes; the
    # runners split/merge heads internally — reshapes are free and exact.
    def _split(x_q, heads, head_dim):
        b, s, _ = x_q.shape
        return x_q.reshape(b, s, heads, head_dim).transpose(0, 2, 1, 3)

    def _merge(x_q):
        b, h, s, d = x_q.shape
        return x_q.transpose(0, 2, 1, 3).reshape(b, s, h * d)

    def _rope(x_q, positions, *, heads, head_dim, theta):
        positions = jnp.asarray(positions)
        if positions.ndim == 2:
            # per-lane window positions [B, S] (batched prefill chunks):
            # tables [B, S, D/2] -> [B, 1, S, D/2] broadcast over heads.
            # Each lane's rows see exactly the angles the single-lane
            # dispatch would (the tables are elementwise in position).
            c_q, s_q = L.rope_tables_i8(positions, head_dim, theta)
            return _merge(L.apply_rope_i8(_split(x_q, heads, head_dim),
                                          c_q[:, None], s_q[:, None]))
        positions = positions.reshape(-1)
        c_q, s_q = L.rope_tables_i8(positions, head_dim, theta)
        if x_q.shape[1] == 1 and positions.shape[0] == x_q.shape[0]:
            # per-request decode positions: row b rotates by its own angle
            # tables [B, D/2] -> [B, 1, 1, D/2] (broadcast over heads, S=1);
            # for B = 1 this is the same broadcast as the scalar-pos path,
            # bit for bit.
            c_q, s_q = c_q[:, None, None, :], s_q[:, None, None, :]
        return _merge(L.apply_rope_i8(_split(x_q, heads, head_dim), c_q, s_q))

    table.register("rope", Engine.CLUSTER, _rope)

    def _attn_causal(q_m, k_m, v_m, *, heads, kv_heads, head_dim, s_act, s_out, block_k):
        p = MhaQParams.make_flash(s_act, s_act, s_act, s_out, max(head_dim, 1))
        kh = _split(k_m, kv_heads, head_dim)
        out = attention_flash_i8(
            _split(q_m, heads, head_dim), kh, _split(v_m, kv_heads, head_dim),
            p, causal=True, block_k=min(block_k, kh.shape[2]),
        )
        return _merge(out)

    table.register("attn_causal", Engine.CLUSTER, _attn_causal)

    def _attn_cached(q_m, k_cache, v_cache, pos, *, heads, head_dim, s_act, s_out, block_k):
        p = MhaQParams.make_flash(s_act, s_act, s_act, s_out, max(head_dim, 1))
        qh = _split(q_m, heads, head_dim)
        # pos may be a scalar (every request at the same depth) or a [B]
        # per-request vector (continuous batching); either way request b
        # attends exactly its first pos_b + 1 cache rows.
        kv_len = jnp.broadcast_to(
            jnp.asarray(pos, jnp.int32).reshape(-1) + 1, (qh.shape[0],)
        )
        out = attention_decode_i8(
            qh, k_cache, v_cache, kv_len, p, block_k=min(block_k, k_cache.shape[2])
        )
        return _merge(out)

    table.register("attn_cached", Engine.CLUSTER, _attn_cached)

    def _cache_write(kv_m, cache, pos, *, kv_heads, head_dim, max_len):
        kh = _split(kv_m, kv_heads, head_dim)
        if cache is None:  # prefill: fresh cache, rows [0, S) written
            cache = jnp.zeros((kh.shape[0], kv_heads, max_len, head_dim), jnp.int8)
            pos = 0
        if jnp.ndim(pos) == 1:
            # per-request write rows: slot b appends at its own depth
            return jax.vmap(
                lambda c, k, p: jax.lax.dynamic_update_slice(c, k, (0, p, 0))
            )(cache, kh, jnp.asarray(pos, jnp.int32))
        return jax.lax.dynamic_update_slice(cache, kh, (0, 0, pos, 0))

    table.register("cache_write", Engine.CLUSTER, _cache_write)

    # -- paged KV region (shared block pool + per-slot block tables).
    # Pool layout [P+1, Hkv, block_size, D]: physical block 0 is scratch
    # (repro.deploy.paging.SCRATCH_BLOCK) — unallocated table entries and
    # inactive dispatch lanes route there, so a batched dispatch can carry
    # parked lanes without touching any live slot's rows.
    def _cache_write_paged(kv_m, pool, pos, table_q, active=None, *,
                           kv_heads, head_dim, block_size):
        from repro.deploy.paging import SCRATCH_BLOCK

        kh = _split(kv_m, kv_heads, head_dim)  # [B, Hkv, S, D]
        b, _, s, _ = kh.shape
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
        table_q = norm_table(table_q, b)
        rows = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None]  # [B, S]
        phys = jnp.take_along_axis(table_q, rows // block_size, axis=1)
        if active is not None:
            act = jnp.asarray(active).astype(bool).reshape(-1)[:, None]
            phys = jnp.where(act, phys, jnp.int32(SCRATCH_BLOCK))
        # one scatter, unique (block, row) targets across live lanes (the
        # allocator never maps one block to two slots; scratch duplicates
        # are dont-care rows)
        vals = kh.transpose(0, 2, 1, 3).reshape(b * s, kv_heads, head_dim)
        return pool.at[phys.reshape(-1), :, (rows % block_size).reshape(-1), :].set(
            vals
        )

    table.register("cache_write_paged", Engine.CLUSTER, _cache_write_paged)

    # -- attn_paged: a cluster op on every backend; under ``ita`` a decode
    # step (one query row per lane) runs the Pallas paged-decode kernel,
    # which reads only each lane's live blocks, and a prefill chunk keeps
    # the XLA runner (the whole block-table gather)
    table.register("attn_paged", Engine.CLUSTER, paged_attention_ref)
    table.register("attn_paged", Engine.CLUSTER, paged_attention_pallas,
                   backend=Backend.ITA, when=lambda op: op.shapes[0][0] == 1)

    # -- silumul: SwiGLU's gated product, a cluster op on every backend;
    # under ``ita`` it runs as a Pallas kernel (the XLA runner reads the
    # exp2 table through a gather over the whole activation)
    table.register("silumul", Engine.CLUSTER, silumul_ref)
    table.register("silumul", Engine.CLUSTER, silumul_pallas, backend=Backend.ITA)
    table.register("lasttok", Engine.CLUSTER, lambda x_q: x_q[:, -1:])

    # -- expert_gemm: the dropless grouped int8 GEMM of an expert layer
    table.register("expert_gemm", Engine.CLUSTER, expert_gemm_ref)
    table.register("expert_gemm", Engine.ACCELERATOR, expert_gemm_ref, backend=Backend.W8A8)
    table.register("expert_gemm", Engine.ACCELERATOR, expert_gemm_pallas, backend=Backend.ITA)

    def _lm_head(h_q, w_q, *, scale, tied):
        w = w_q.astype(jnp.int8).T if tied else w_q.astype(jnp.int8)
        acc = jnp.matmul(h_q.astype(jnp.int8), w, preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32) * scale

    table.register("lmhead", Engine.CLUSTER, _lm_head)
    return table


populate_default_table(DEFAULT_TABLE)
