"""One inference API: ``compile() -> CompiledModel -> InferenceSession``.

The paper's deployment flow (§IV) ends in a *single* deployable artifact;
this module is that artifact's programming surface.  ``compile(cfg)``
lowers a config through the pass pipeline into its deployment artifact —
an encoder :class:`~repro.deploy.plan.DeploymentPlan` or a decoder
:class:`~repro.deploy.plan.DecoderPlanPair` — wrapped in a
:class:`CompiledModel` that carries a stable config fingerprint and the
``COMPILER_VERSION`` it was produced by, serializes to JSON, and is
cached on disk: a second ``compile()`` of the same (config, options,
compiler version) deserializes the plan instead of re-lowering it, and a
bump of either the compiler version or the config hash invalidates the
entry.

``CompiledModel.session(batch_size)`` binds quantized weights and
returns an :class:`InferenceSession` — the one runtime surface for both
families:

* encoder: ``forward(x)`` — batched plan execution;
* decoder: ``prefill(tokens)`` / ``decode(tokens, pos)`` where ``pos``
  is a **per-request vector**: a batch of requests at *different*
  generation depths advances in one dispatch against one statically
  planned, batched KV region (continuous batching from a single plan,
  cf. the prefill/decode phase split of arXiv 2405.19284).
  ``prefill_slot(i, tokens)`` admits a new request into a finished slot
  while the others keep decoding.

Everything here is bit-exact against the model-level ``w8a8`` integer
path — including a cache-loaded plan vs a freshly lowered one (the JSON
round trip is lossless; tested).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import tempfile
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.deploy.paging import (
    SCRATCH_BLOCK,
    BlockAllocator,
    PoolExhausted,
    blocks_for_rows,
    chunk_starts,
)
from repro.deploy.paging import (
    blocks_per_slot as _blocks_per_slot,
)

from repro.configs.base import ArchConfig
from repro.core.heterogeneous import (
    Backend,
    DispatchTable,
    as_backend,
    backend_granule,
)
from repro.deploy.lowering import (  # noqa: F401 (re-exports)
    EXPERT_COUNTS,
    UnsupportedFamilyError,
    is_decoder_lm,
    lower,
)
from repro.deploy.plan import DecoderPlanPair, DeploymentPlan
from repro.deploy.trace import span

#: Bumped whenever lowering/executor changes can alter plan *content* or
#: *semantics*.  Cached plans from other versions are recompiled.
#: v4: paged KV region (kv_block_size/kv_blocks options, pool-shaped
#: cache tensors) + strict fingerprint canonicalization.
#: v5: FusedRegion mega-nodes (region-fusion pass, ``fuse`` option) +
#: cost-model autotuning (``autotune`` option folds the tuned knobs —
#: kv_block_size, fusion boundary, GEMM macro-tiles — into the
#: fingerprint and records them in the plan's ``autotune`` payload).
COMPILER_VERSION = 5

_PAYLOAD_FORMAT = "repro.deploy.api/compiled-model"


class KVCapacityError(ValueError):
    """A decode dispatch (or prefill chunk) cannot fit the KV region.

    Carries exactly *which* request slots are out of capacity so a
    scheduler (:class:`repro.deploy.engine.Engine`) can evict precisely —
    finish those requests, recycle their slots — and re-dispatch the
    survivors, instead of tearing down the whole batch.

    Two causes share this type (callers branch on the attributes, not
    the message):

    * ``reason == "max_len"`` — dense region or block-table width: a
      slot's depth reached the compiled ``max_len``.
    * ``reason == "pool"`` — paged region only: the shared block pool is
      exhausted; ``slots`` are the requests that could not grow and
      ``evictable`` names the *other* live slots whose eviction would
      actually free capacity — slots holding at least one exclusively
      owned block.  A slot whose blocks are ALL shared (refcount > 1:
      a prefix-cache sibling or the index still references every one)
      is excluded: evicting it only decrements refcounts and returns
      nothing to the pool.

    Attributes: ``slots`` (tuple of offending slot indices), ``pos``
    (their per-slot depths, same order), ``max_len`` (the region's
    planned per-slot capacity), ``reason``, ``evictable``.
    """

    def __init__(self, slots, pos, max_len: int, *, reason: str = "max_len",
                 evictable=(), message: str | None = None):
        self.slots = tuple(int(s) for s in slots)
        self.pos = tuple(int(p) for p in pos)
        self.max_len = int(max_len)
        self.reason = reason
        self.evictable = tuple(int(s) for s in evictable)
        if message is not None:
            # submit-time raisers (Engine.validate_request) describe the
            # refusal in request terms; the structured attributes above
            # still drive any programmatic handling
            msg = message
        elif reason == "pool":
            msg = (
                f"paged KV pool exhausted: slot(s) {list(self.slots)} at pos "
                f"{list(self.pos)} need new blocks and none are free; "
                f"evictable slot(s) holding blocks: {list(self.evictable)}"
            )
        else:
            msg = (
                f"KV region full: slot(s) {list(self.slots)} at pos "
                f"{list(self.pos)} >= max_len {self.max_len}; re-admit via "
                f"prefill_slot or compile with a larger max_len"
            )
        super().__init__(msg)


# ---------------------------------------------------------------------------
# Fingerprint + on-disk plan cache
# ---------------------------------------------------------------------------

def default_cache_dir() -> str:
    """``$REPRO_PLAN_CACHE`` or ``~/.cache/repro/plans``."""
    return os.environ.get("REPRO_PLAN_CACHE") or os.path.join(
        os.path.expanduser("~"), ".cache", "repro", "plans"
    )


def _canonical(obj, path: str = "payload"):
    """JSON-stable normal form of a fingerprint payload value.

    Strict on purpose: a value serialized through a fallback like
    ``repr`` can embed object identity (``<object at 0x7f...>``) — the
    fingerprint then differs every process and the plan cache silently
    becomes a permanent miss.  Anything that is not a plain JSON scalar /
    list / tuple / str-keyed dict fails loudly instead.
    """
    if obj is None or isinstance(obj, (bool, int, str)):
        return obj
    if isinstance(obj, float):
        if obj != obj or obj in (float("inf"), float("-inf")):
            raise TypeError(f"{path}: non-finite float {obj!r} is not JSON-stable")
        return obj
    if isinstance(obj, (list, tuple)):
        return [_canonical(v, f"{path}[{i}]") for i, v in enumerate(obj)]
    if isinstance(obj, dict):
        out = {}
        for k, v in obj.items():
            if not isinstance(k, str):
                raise TypeError(
                    f"{path}: dict key {k!r} is not a string — fingerprint "
                    f"payloads must be JSON-stable"
                )
            out[k] = _canonical(v, f"{path}.{k}")
        return out
    raise TypeError(
        f"{path}: {type(obj).__name__} value {obj!r} is not JSON-stable; "
        f"config/options entries must be None/bool/int/float/str or "
        f"lists/tuples/str-dicts of those (a repr fallback would embed "
        f"object identity and silently break cross-process cache hits)"
    )


def config_fingerprint(cfg: ArchConfig, options: dict | None = None) -> str:
    """Stable hash of (full config, resolved lowering options).

    The payload is canonicalized strictly (:func:`_canonical` raises
    ``TypeError`` on any value JSON cannot represent stably), so two
    processes — today or after a restart — always fingerprint the same
    (config, options) identically.

    The compiler version is deliberately *not* part of the fingerprint —
    it is stored (and checked) separately in the cache payload, so a
    version bump invalidates entries in place instead of leaking stale
    files under new keys.
    """
    payload = _canonical({
        "config": dataclasses.asdict(cfg),
        "options": dict(sorted((options or {}).items())),
    })
    blob = json.dumps(payload, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()


def _cache_path(cache_dir: str, cfg: ArchConfig, fingerprint: str) -> str:
    safe = "".join(c if (c.isalnum() or c in "-_.") else "_" for c in cfg.name)
    return os.path.join(cache_dir, f"{safe}-{fingerprint[:16]}.plan.json")


def _artifact_from_payload(payload: dict) -> DeploymentPlan | DecoderPlanPair:
    if payload["kind"] == "pair":
        return DecoderPlanPair.from_dict(payload["artifact"])
    return DeploymentPlan.from_dict(payload["artifact"])


def _cache_load(path: str, fingerprint: str):
    """Deserialized artifact on a hit; None on any miss (absent, stale
    compiler version, fingerprint mismatch, or corrupt file)."""
    try:
        with open(path) as f:
            payload = json.load(f)
        if payload.get("format") != _PAYLOAD_FORMAT:
            return None
        if payload.get("compiler_version") != COMPILER_VERSION:
            return None
        if payload.get("fingerprint") != fingerprint:
            return None
        return _artifact_from_payload(payload)
    except (OSError, ValueError, KeyError, AssertionError):
        return None


def _cache_store(path: str, payload: dict) -> None:
    """Publish one cache entry atomically (multi-process safe).

    Each writer dumps into its own ``mkstemp`` file in the destination
    directory, fsyncs, then ``os.replace``s it over the final name — so a
    reader only ever sees no file or one complete JSON document, never a
    torn entry.  Concurrent writers of the *same* fingerprint race on the
    replace; whichever lands last wins, which is harmless because the
    payload is a pure function of (config, options, compiler version) —
    both candidates carry identical content.
    """
    os.makedirs(os.path.dirname(path), exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path), suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(payload, f, indent=1)
            f.flush()
            os.fsync(f.fileno())  # a crash can't leave a short file published
        os.replace(tmp, path)  # atomic publish: readers never see partial JSON
    except BaseException:
        try:
            os.unlink(tmp)  # tolerate a concurrent cleaner: ENOENT is fine
        except OSError:
            pass
        raise


# ---------------------------------------------------------------------------
# CompiledModel
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CompiledModel:
    """The single deployable artifact: plan(s) + identity + weights binder."""

    cfg: ArchConfig
    backend: Backend
    artifact: DeploymentPlan | DecoderPlanPair
    fingerprint: str
    compiler_version: int
    options: dict
    cache_hit: bool = False
    cache_path: str | None = None
    # static-verifier results (runtime-only: not serialized — a reloaded
    # artifact is re-verified, not trusted).  ``diagnostics`` holds the
    # WARNING-severity findings of the verification pass (errors raise
    # PlanVerificationError at compile/load time instead); ``verify_ms``
    # is the one-time wall-clock cost of that pass.
    diagnostics: tuple = ()
    verify_ms: float = 0.0

    @property
    def kind(self) -> str:
        return "decoder" if isinstance(self.artifact, DecoderPlanPair) else "encoder"

    def counts(self) -> dict:
        return self.artifact.counts()

    # -- weights -----------------------------------------------------------

    def bind(self, params: dict | None = None, *, key=None) -> tuple[dict, dict]:
        """(float init ->) PTQ quantize -> bind onto the plan's weight names.

        ``params`` may also be an already quantized pytree (the layout
        ``quantize_params`` gives: int8 codes, int32 biases, int8 norm
        gammas, on its grids; ``layers`` stacked or a list of per-layer
        trees), recognised by its ``*_q`` tables: it is bound as it is,
        so int8 weights never travel as floats.

        Returns ``(weights, qp)``; ``qp`` is the quantized param pytree so
        callers can run the model-level reference path on identical ints.
        """
        from repro.deploy.executor import bind_decoder_weights, bind_encoder_weights

        if self.kind == "decoder":
            from repro.models import transformer as M

            bind_fn, plan = bind_decoder_weights, self.artifact.prefill
        else:
            from repro.models import encoder as M

            bind_fn, plan = bind_encoder_weights, self.artifact
        if params is None:
            key = jax.random.PRNGKey(0) if key is None else key
            params = M.init_params(self.cfg, key)
        if "pos_q" in params or "table_q" in params.get("embed", {}):
            qp = params
        else:
            qp = M.quantize_params(self.cfg, params)
        return bind_fn(plan, self.cfg, qp), qp

    def session(
        self,
        batch_size: int,
        *,
        params: dict | None = None,
        key=None,
        table: DispatchTable | None = None,
    ) -> "InferenceSession":
        return InferenceSession(self, batch_size, params=params, key=key, table=table)

    # -- serialization -----------------------------------------------------

    def to_dict(self) -> dict:
        return {
            "format": _PAYLOAD_FORMAT,
            "compiler_version": self.compiler_version,
            "fingerprint": self.fingerprint,
            "arch": self.cfg.name,
            "backend": self.backend.value,
            "options": dict(self.options),
            "kind": "pair" if self.kind == "decoder" else "plan",
            "artifact": self.artifact.to_dict(),
        }

    def to_json(self, indent: int | None = 1) -> str:
        return json.dumps(self.to_dict(), indent=indent)

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.to_json())

    @staticmethod
    def load(path: str, cfg: ArchConfig, *, verify: bool = True) -> "CompiledModel":
        """Rehydrate a saved model.  ``cfg`` must be the config it was
        compiled from (verified against the stored fingerprint), and the
        artifact must carry the current ``COMPILER_VERSION`` — version
        bumps mean plan content/semantics may have changed, so executing
        a stale artifact would silently compute the wrong function.

        The rehydrated artifact is re-run through the static verifier
        (``verify=True``): a file edited or corrupted on disk raises
        :class:`~repro.deploy.verify.PlanVerificationError` here instead
        of executing garbage."""
        with open(path) as f:
            payload = json.load(f)
        if payload.get("format") != _PAYLOAD_FORMAT:
            raise ValueError(f"{path}: not a CompiledModel payload")
        if payload.get("compiler_version") != COMPILER_VERSION:
            raise ValueError(
                f"{path}: compiled by compiler version "
                f"{payload.get('compiler_version')}, current is "
                f"{COMPILER_VERSION} — recompile with compile()"
            )
        fp = config_fingerprint(cfg, payload["options"])
        if fp != payload["fingerprint"]:
            raise ValueError(
                f"{path}: fingerprint mismatch — saved for config "
                f"{payload['arch']!r} with different contents/options"
            )
        model = CompiledModel(
            cfg=cfg,
            backend=as_backend(payload["backend"]),
            artifact=_artifact_from_payload(payload),
            fingerprint=payload["fingerprint"],
            compiler_version=int(payload["compiler_version"]),
            options=dict(payload["options"]),
            cache_path=path,
        )
        if verify:
            model.diagnostics, model.verify_ms = _verify_artifact(
                model.artifact, context=path
            )
        return model


# ---------------------------------------------------------------------------
# compile()
# ---------------------------------------------------------------------------

def _verify_artifact(artifact, *, context: str) -> tuple[tuple, float]:
    """Run the static plan verifier; return (warnings, elapsed ms).

    Errors raise :class:`~repro.deploy.verify.PlanVerificationError`
    (compile refuses to hand out a plan with a statically provable
    hazard); warnings are returned for the caller to surface.
    """
    import time

    from repro.deploy.verify import check

    t0 = time.perf_counter()
    diags = check(artifact, context=context)
    return tuple(diags), (time.perf_counter() - t0) * 1e3


def compile(  # noqa: A001 — torch.compile precedent
    cfg: ArchConfig,
    *,
    backend: Backend | str = Backend.W8A8,
    seq_len: int | None = None,
    max_len: int | None = None,
    kv_block_size: int | None = None,
    kv_blocks: int | None = None,
    head_by_head: bool = False,
    include_head: bool = True,
    fuse: bool = True,
    autotune: bool = False,
    prefix_cache: bool = False,
    cache_dir: str | None = None,
    use_cache: bool = True,
    verify: bool = True,
) -> CompiledModel:
    """Compile one config into its deployment artifact, cached on disk.

    The plan's static engine mapping is solved at the granule of the
    execution ``backend`` (64 for the ASIC-faithful W8A8 arithmetic, 128
    for the Pallas/TPU kernels), so the engine column matches what
    ``DispatchTable.resolve`` does at run time.

    ``kv_block_size`` + ``kv_blocks`` (decoder family only, both or
    neither) switch the KV region from dense per-slot ``max_len`` strips
    to a **paged** shared block pool with per-slot block tables — the
    pool budget is ``kv_blocks`` blocks *total* across all request
    slots, so long-context capacity is pooled instead of reserved
    worst-case per slot, and prompts beyond ``seq_len`` prefill in
    chunks (see DEPLOY.md "Paged KV region").

    ``fuse=True`` (the default; decoder family only — encoder plans
    always lower unfused) runs the region-fusion pass: contiguous
    same-engine schedule runs collapse into ``FusedRegion`` mega-nodes
    the executor dispatches as single jitted closures — bit-exact vs the
    unfused plans (tested both backends, dense and paged).  Pass
    ``fuse=False`` to force unfused plans (per-node dispatch, e.g. for
    per-operator debugging/tracing).

    ``autotune=True`` (decoder only) runs the cost-model-driven tuner
    (:mod:`repro.deploy.autotune`) over the bit-neutral plan knobs —
    ``kv_block_size`` (pool rows preserved), the fusion boundary, and
    the GEMM macro-tiles — picks the predicted-cost argmin, records the
    chosen knobs + predicted step cost in the plan's ``autotune``
    payload, and folds them into the fingerprint, so autotuned plans
    ride the same on-disk cache (the tuner is deterministic: a second
    ``compile(autotune=True)`` re-derives identical knobs and hits).

    Cache semantics: the key is ``config_fingerprint(cfg, options)`` —
    the *full* config plus every resolved lowering option (backend
    granule included).  A hit deserializes the stored plan (bit-exact vs
    re-lowering; tested); a ``COMPILER_VERSION`` bump or any config /
    option change misses and recompiles.  ``use_cache=False`` bypasses
    the disk entirely.  Raises :class:`UnsupportedFamilyError` for
    families the flow cannot lower yet.

    ``prefix_cache=True`` (decoder + paged only) declares the artifact
    will be served with the radix prefix cache
    (:mod:`repro.deploy.prefix`): the engine indexes finished prompt
    prefills, matches new submissions against resident block chains, and
    admits only the novel suffix.  The knob changes no plan content —
    sharing is block-table bookkeeping — but it *is* a serving-semantics
    option, so it enters the options dict and the fingerprint like any
    other lowering option (a prefix-cached artifact caches separately
    from an unshared one).

    ``verify=True`` (the default) runs the static plan verifier
    (:mod:`repro.deploy.verify`) over the artifact — freshly lowered OR
    cache-loaded (a cache hit deserializes bytes from disk; those bytes
    are audited, not trusted).  Error-severity findings raise
    :class:`~repro.deploy.verify.PlanVerificationError`; warnings land
    on ``CompiledModel.diagnostics`` and the one-time cost on
    ``CompiledModel.verify_ms``.  ``verify`` is a *checking* knob, not a
    lowering option: it never enters the fingerprint, so verified and
    unverified compiles share cache entries.
    """
    be = as_backend(backend)
    granule = backend_granule(be)
    s = seq_len or cfg.max_seq
    is_decoder = is_decoder_lm(cfg)
    if (kv_block_size is None) != (kv_blocks is None):
        raise ValueError(
            "kv_block_size and kv_blocks come as a pair (both set the "
            "paged KV region, both absent keeps the dense region)"
        )
    bs, nb = int(kv_block_size or 0), int(kv_blocks or 0)
    # (paged options on a non-decoder family are rejected by lower() —
    # one copy of that predicate and message, not two)
    if (bs < 0 or nb < 0) or (bool(bs) != bool(nb)):
        raise ValueError(
            f"kv_block_size/kv_blocks must both be positive, got "
            f"{kv_block_size}/{kv_blocks}"
        )
    # fusion targets the decode hot path; encoder artifacts ignore it so
    # the fused-by-default surface stays family-agnostic
    fuse = bool(fuse) and is_decoder
    if autotune and not is_decoder:
        raise ValueError(
            "autotune enumerates decode-step knobs (kv_block_size, fusion "
            f"boundary, decode GEMM tiles); {cfg.name} does not lower to a "
            "decoder plan pair"
        )
    if prefix_cache and not (is_decoder and nb):
        raise ValueError(
            "prefix_cache needs a paged decoder artifact: prefix sharing "
            "forks per-slot block-table entries, so compile with "
            "kv_block_size/kv_blocks on a decoder config"
        )
    cap = (max_len or s + 1) if is_decoder else 0
    tuned = None
    fuse_min_nodes = 2
    if autotune:
        from repro.deploy.autotune import tune_decoder

        tuned = tune_decoder(
            cfg, seq_len=s, max_len=cap, granule=granule,
            kv_block_size=bs, kv_blocks=nb, fuse=fuse,
        )
        bs = tuned.knobs["kv_block_size"]
        nb = tuned.knobs["kv_blocks"]
        fuse_min_nodes = tuned.knobs["fuse_min_nodes"]
    options = {
        "backend": be.value,
        "granule": granule,
        "seq_len": s,
        "max_len": cap,
        "kv_block_size": bs,
        "kv_blocks": nb,
        "head_by_head": head_by_head,
        "include_head": include_head,
        "fuse": fuse,
        "prefix_cache": bool(prefix_cache),
    }
    if autotune:
        # the *resolved* knobs key the cache: same (config, options) ->
        # same deterministic tuner outcome -> same fingerprint -> hit
        options["autotune"] = dict(tuned.knobs)
    fingerprint = config_fingerprint(cfg, options)
    cache_dir = cache_dir or default_cache_dir()
    path = _cache_path(cache_dir, cfg, fingerprint)

    if use_cache:
        artifact = _cache_load(path, fingerprint)
        if artifact is not None:
            model = CompiledModel(
                cfg, be, artifact, fingerprint, COMPILER_VERSION, options,
                cache_hit=True, cache_path=path,
            )
            if verify:
                # a hit is bytes deserialized from disk — audit them like
                # any other untrusted artifact before handing them out
                model.diagnostics, model.verify_ms = _verify_artifact(
                    artifact, context=path
                )
            return model

    artifact = lower(
        cfg, seq_len, head_by_head=head_by_head, include_head=include_head,
        max_len=max_len, kv_block_size=bs, kv_blocks=nb, granule=granule,
        fuse=fuse, fuse_min_nodes=fuse_min_nodes,
    )
    if tuned is not None:
        artifact.decode.autotune = tuned.payload()
    model = CompiledModel(
        cfg, be, artifact, fingerprint, COMPILER_VERSION, options,
        cache_path=path if use_cache else None,
    )
    if verify:
        model.diagnostics, model.verify_ms = _verify_artifact(
            artifact, context=f"compile({cfg.name})"
        )
    if use_cache:
        _cache_store(path, model.to_dict())
    return model


# ---------------------------------------------------------------------------
# InferenceSession
# ---------------------------------------------------------------------------

class InferenceSession:
    """Stateful runtime surface over one compiled artifact.

    Encoder: :meth:`forward`.  Decoder: :meth:`prefill` /
    :meth:`prefill_slot` fill the statically planned, batched KV region;
    :meth:`decode` advances **all** ``batch_size`` request slots by one
    token in a single plan dispatch, each slot at its *own* generation
    depth (``pos`` is a per-request vector) — continuous batching from a
    single static plan.  Slot isolation is exact: every runner is
    row-local, so slot ``b`` computes the same ints as an independent
    single-request trajectory at depth ``pos[b]`` (tested bit-exactly on
    both backends).

    **Thread affinity**: KV state, the block allocator and per-slot
    depths are plain host objects with no internal locking — a session
    belongs to exactly ONE thread at a time.  The first *mutating* call
    (prefill / decode / free_slot) binds the session to the calling
    thread; mutating from any other thread afterwards raises
    ``RuntimeError`` instead of silently corrupting KV state.  Hand a
    session across threads explicitly with :meth:`rebind_thread` — e.g.
    :class:`~repro.deploy.serving.async_engine.AsyncEngine` constructs
    the engine on the caller's thread and rebinds to its loop thread
    before the first step.  Reads (``pos``, capacity properties, stats)
    are unguarded.
    """

    def __init__(
        self,
        model: CompiledModel,
        batch_size: int,
        *,
        params: dict | None = None,
        key=None,
        table: DispatchTable | None = None,
    ):
        from repro.deploy.executor import (
            execute,
            execute_decode,
            execute_decode_paged,
            execute_prefill,
            execute_prefill_paged,
        )

        if batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        self.model = model
        self.cfg = model.cfg
        self.backend = model.backend
        self.batch_size = batch_size
        self.weights, self.qp = model.bind(params=params, key=key)
        be, tb = self.backend, table
        if model.kind == "decoder":
            pair = model.artifact
            self._pair = pair
            self._kv = None  # dense: {"k": [L,B,Hkv,M,D] int8, "v": ...}
            self._pos = None  # HOST int32 [B] per-slot depth (numpy: the
            # decode hot path must not round-trip to the device per token)
            if pair.paged:
                # ``acc``: the expert layers' counters, summed on the device
                # over steps until the engine fetches them with a step's
                # logits (None for a plan without expert layers)
                def chunk_step(w, pl, t, st, bt, acc=None):
                    logits, pool, n = execute_prefill_paged(
                        pair, w, pl, t, st, bt, backend=be, table=tb)
                    return logits, pool, None if n is None else acc + n

                def decode_step(w, pl, t, p, bt, act, acc=None):
                    logits, pool, n = execute_decode_paged(
                        pair, w, pl, t, p, bt, act, backend=be, table=tb)
                    return logits, pool, None if n is None else acc + n

                self._chunk_fn = jax.jit(chunk_step)
                self._decode_fn = jax.jit(decode_step)
                cfgm = model.cfg
                shape = (cfgm.n_layers, pair.kv_blocks + 1, cfgm.n_kv_heads,
                         pair.kv_block_size, cfgm.head_dim)
                self._pool = {"k": jnp.zeros(shape, jnp.int8),
                              "v": jnp.zeros(shape, jnp.int8)}
                self._alloc = BlockAllocator(pair.kv_blocks)
                self._table_width = _blocks_per_slot(pair.max_len,
                                                     pair.kv_block_size)
                self._tables = np.full((batch_size, self._table_width),
                                       SCRATCH_BLOCK, np.int32)
                self._slot_blocks: list[list[int]] = [
                    [] for _ in range(batch_size)
                ]
                # copy-on-write: one jitted whole-block pool copy with
                # *traced* src/dst indices, so every COW reuses the same
                # executable instead of retracing per block id
                def cow_copy(p, src, dst):
                    return {"k": p["k"].at[:, dst].set(p["k"][:, src]),
                            "v": p["v"].at[:, dst].set(p["v"][:, src])}

                self._copy_fn = jax.jit(cow_copy)
                self._cow_copies = 0
                self._expert_zero = (jnp.zeros((2,), jnp.int32)
                                     if EXPERT_COUNTS in pair.prefill.outputs else None)
                self._expert_acc = self._expert_zero
            else:
                def prefill_step(w, b):
                    return execute_prefill(pair, w, b, backend=be, table=tb)

                def decode_step(w, c, t, p):
                    return execute_decode(pair, w, c, t, pos=p, backend=be,
                                          table=tb)

                self._prefill_fn = jax.jit(prefill_step)
                self._decode_fn = jax.jit(decode_step)
        else:
            plan = model.artifact
            self._plan = plan

            def encoder_forward(w, b):
                return execute(plan, w, b, backend=be, table=tb)

            self._forward_fn = jax.jit(encoder_forward)
        self._owner_ident: int | None = None  # thread affinity (lazy bind)

    # -- shared ------------------------------------------------------------

    def _require(self, kind: str, method: str) -> None:
        if self.model.kind != kind:
            raise RuntimeError(
                f"InferenceSession.{method} is a {kind} method; this session "
                f"wraps a {self.model.kind} artifact ({self.cfg.name})"
            )

    def _affine(self, method: str) -> None:
        """Bind the session to the first mutating caller's thread; refuse
        mutation from any other thread (see the class docstring)."""
        ident = threading.get_ident()
        if self._owner_ident is None:
            self._owner_ident = ident
        elif self._owner_ident != ident:
            raise RuntimeError(
                f"InferenceSession.{method} called from thread {ident} but "
                f"the session is bound to thread {self._owner_ident}; KV "
                f"state has no internal locking — call rebind_thread() from "
                f"the new owning thread to transfer ownership explicitly"
            )

    def rebind_thread(self) -> None:
        """Transfer session ownership to the *calling* thread.

        The caller asserts the previous owner has stopped mutating (e.g.
        an engine handing its session to a background loop thread).
        """
        self._owner_ident = threading.get_ident()

    # -- encoder -----------------------------------------------------------

    def forward(self, x):
        """One batched forward pass of the encoder plan.

        ``x`` is the plan's input array (``tokens`` int32 [B, S] or int8
        features [B, S, D]) or a ready batch dict keyed by input name.
        """
        self._require("encoder", "forward")
        with span("session.forward"):
            batch = x if isinstance(x, dict) else {self._plan.inputs[0]: jnp.asarray(x)}
            lead = batch[self._plan.inputs[0]].shape[0]
            if lead != self.batch_size:
                raise ValueError(
                    f"batch dim {lead} != session batch_size {self.batch_size}"
                )
            return self._forward_fn(self.weights, batch)

    # -- decoder -----------------------------------------------------------

    @property
    def seq_len(self) -> int:
        """Prompt length the prefill schedule was lowered for."""
        self._require("decoder", "seq_len")
        return self._pair.seq_len

    @property
    def max_len(self) -> int:
        self._require("decoder", "max_len")
        return self._pair.max_len

    @property
    def paged(self) -> bool:
        """Is the KV region a shared block pool (vs dense per-slot strips)?"""
        self._require("decoder", "paged")
        return self._pair.paged

    @property
    def kv_block_size(self) -> int:
        self._require("decoder", "kv_block_size")
        return self._pair.kv_block_size

    @property
    def kv_blocks(self) -> int:
        self._require("decoder", "kv_blocks")
        return self._pair.kv_blocks

    @property
    def decode_dispatch_count(self) -> int:
        """Top-level dispatches per decode step — ``len(decode.nodes)``.

        Fused plans collapse same-engine runs into single FusedRegion
        dispatches, so this is the count the fusion pass moves.  The whole
        step is one device program (``decode_step``) either way."""
        self._require("decoder", "decode_dispatch_count")
        return len(self._pair.decode.nodes)

    @property
    def blocks_free(self) -> int:
        """Free blocks in the paged pool (0 for dense sessions)."""
        self._require("decoder", "blocks_free")
        return self._alloc.n_free if self._pair.paged else 0

    def blocks_held(self, slot: int) -> int:
        """Pool blocks currently owned by one slot (0 for dense)."""
        self._require("decoder", "blocks_held")
        if not 0 <= slot < self.batch_size:
            raise IndexError(f"slot {slot} out of range [0, {self.batch_size})")
        return len(self._slot_blocks[slot]) if self._pair.paged else 0

    def block_chain(self, slot: int) -> tuple[int, ...]:
        """One slot's physical block chain in logical row order (empty
        for dense sessions or a freed slot)."""
        self._require("decoder", "block_chain")
        if not 0 <= slot < self.batch_size:
            raise IndexError(f"slot {slot} out of range [0, {self.batch_size})")
        if not self._pair.paged:
            return ()
        return tuple(int(b) for b in self._tables[slot]
                     if b != SCRATCH_BLOCK)

    @property
    def allocator(self) -> BlockAllocator | None:
        """The paged session's block allocator (None for dense) — the
        refcount surface the prefix index and the engine share."""
        self._require("decoder", "allocator")
        return self._alloc if self._pair.paged else None

    @property
    def expert_counts(self):
        """Device int32 [2]: the (row, expert) pairs routed and the experts
        with rows, summed over layers and steps since the last
        :meth:`reset_expert_counts`; None for a plan without expert layers."""
        return getattr(self, "_expert_acc", None)

    def reset_expert_counts(self) -> None:
        self._affine("reset_expert_counts")
        if self.expert_counts is not None:
            self._expert_acc = self._expert_zero

    @property
    def cow_copies(self) -> int:
        """Copy-on-write block copies materialized so far (paged)."""
        self._require("decoder", "cow_copies")
        return self._cow_copies if self._pair.paged else 0

    @property
    def pos(self):
        """Per-slot generation depth, host int32 [batch_size] (numpy)."""
        self._require("decoder", "pos")
        return self._pos

    @property
    def kv_cache(self) -> dict | None:
        """The batched dense KV region: ``{"k": [L,B,Hkv,max_len,D], ...}``."""
        self._require("decoder", "kv_cache")
        return self._kv

    @property
    def kv_pool(self) -> dict | None:
        """The shared paged pool: ``{"k": [L,P+1,Hkv,block_size,D], ...}``."""
        self._require("decoder", "kv_pool")
        return self._pool if self._pair.paged else None

    def _check_tokens(self, tokens, rows: int):
        tokens = jnp.asarray(tokens, jnp.int32)
        if tokens.ndim == 1:
            tokens = tokens[None]
        if tokens.shape != (rows, self._pair.seq_len):
            raise ValueError(
                f"prefill tokens must be [{rows}, {self._pair.seq_len}] "
                f"(the lowered prompt length), got {tuple(tokens.shape)}"
            )
        return tokens

    def prefill(self, tokens):
        """Prefill every slot with one prompt each: tokens int32 [B, S].

        Returns the last-token logits [B, 1, vocab_padded] and resets all
        slots to depth ``S``.  Paged sessions release every slot's blocks
        first and allocate fresh ones for rows ``[0, S)`` (all slots, one
        batched chunk-0 dispatch).
        """
        self._require("decoder", "prefill")
        self._affine("prefill")
        tokens = self._check_tokens(tokens, self.batch_size)
        s = self._pair.seq_len
        if self._pair.paged:
            # capacity is statically decidable (every slot is about to be
            # released, so the whole pool would be free) — check BEFORE
            # the destructive release, or a failed prefill would leave
            # scratched tables under stale nonzero depths
            need = blocks_for_rows(s, self._pair.kv_block_size)
            if self._pair.kv_blocks < need * self.batch_size:
                raise KVCapacityError(
                    list(range(self.batch_size)), [0] * self.batch_size,
                    self._pair.max_len, reason="pool",
                )
            for b in range(self.batch_size):
                self._release_blocks(b)
            for b in range(self.batch_size):
                self._grow_table(b, need)
            for b in range(self.batch_size):
                self._note_writes(b, 0, s)
            logits, self._pool, self._expert_acc = self._chunk_fn(
                self.weights, self._pool, tokens, jnp.int32(0),
                jnp.asarray(self._tables), self._expert_acc,
            )
        else:
            logits, cache = self._prefill_fn(self.weights, {"tokens": tokens})
            self._kv = {"k": cache["k"], "v": cache["v"]}
        self._pos = np.full((self.batch_size,), s, np.int32)
        return logits

    def prefill_slot(self, slot: int, tokens):
        """Admit a new request into one slot (continuous batching).

        Dense: runs the prefill schedule at batch 1 and installs the
        resulting KV rows + depth into slot ``slot``; the other slots'
        cache rows and positions are untouched, so they keep decoding
        mid-flight.  Paged: additionally accepts prompts of any length
        ``seq_len <= T <= max_len`` — the static schedule runs in
        ``seq_len``-sized chunks writing through the slot's block table
        (see :meth:`prefill_chunk` to drive the chunks one dispatch at a
        time).  Returns the prompt's last-token logits
        [1, 1, vocab_padded].
        """
        self._require("decoder", "prefill_slot")
        self._affine("prefill_slot")
        if not 0 <= slot < self.batch_size:
            raise IndexError(f"slot {slot} out of range [0, {self.batch_size})")
        if self._pair.paged:
            tokens = jnp.asarray(tokens, jnp.int32)
            if tokens.ndim == 1:
                tokens = tokens[None]
            t = tokens.shape[-1]
            if tokens.shape != (1, t) or not (
                self._pair.seq_len <= t <= self._pair.max_len
            ):
                raise ValueError(
                    f"paged prefill_slot tokens must be [1, T] with "
                    f"{self._pair.seq_len} <= T <= {self._pair.max_len}, "
                    f"got {tuple(tokens.shape)}"
                )
            logits = None
            for start in chunk_starts(t, self._pair.seq_len):
                logits = self.prefill_chunk(
                    slot, tokens[:, start : start + self._pair.seq_len], start
                )
            return logits
        tokens = self._check_tokens(tokens, 1)
        logits, cache = self._prefill_fn(self.weights, {"tokens": tokens})
        if self._kv is None:
            l, _, hkv, m, d = cache["k"].shape
            zeros = jnp.zeros((l, self.batch_size, hkv, m, d), cache["k"].dtype)
            self._kv = {"k": zeros, "v": zeros}
            self._pos = np.zeros((self.batch_size,), np.int32)
        self._kv = {
            "k": self._kv["k"].at[:, slot].set(cache["k"][:, 0]),
            "v": self._kv["v"].at[:, slot].set(cache["v"][:, 0]),
        }
        self._pos[slot] = self._pair.seq_len
        return logits

    def prefill_chunk(self, slot: int, tokens, start: int):
        """One chunked-prefill dispatch (paged sessions only).

        Runs the static ``seq_len``-token prefill schedule at global
        token offset ``start``, writing cache rows ``[start, start +
        seq_len)`` of slot ``slot`` through its block table — so a
        prompt of ``T`` tokens prefills in ``<= ceil(T / seq_len)``
        dispatches (:func:`repro.deploy.paging.chunk_starts`) instead of
        ``T - seq_len`` teacher-forced decode steps.  ``start == 0``
        recycles the slot (frees its blocks) first; later chunks may
        overlap the previous one (the final chunk is pinned to the
        prompt tail), which is bit-neutral because every token's K/V is
        a pure function of its prefix.  A scheduler interleaves these
        dispatches with batched decodes of the resident slots.

        Returns the chunk's last-token logits [1, 1, vocab_padded];
        raises :class:`KVCapacityError` (``reason="pool"``) when the
        blocks for the chunk's rows cannot be allocated.
        """
        self._require("decoder", "prefill_chunk")
        self._affine("prefill_chunk")
        if not self._pair.paged:
            raise RuntimeError(
                "prefill_chunk needs a paged session; compile with "
                "kv_block_size/kv_blocks"
            )
        if not 0 <= slot < self.batch_size:
            raise IndexError(f"slot {slot} out of range [0, {self.batch_size})")
        tokens = self._check_tokens(tokens, 1)
        s = self._pair.seq_len
        if self._pos is None:
            self._pos = np.zeros((self.batch_size,), np.int32)
        if start == 0:
            self._release_blocks(slot)
        elif not 0 < start <= int(self._pos[slot]):
            raise ValueError(
                f"chunk at start {start} leaves a gap: slot {slot} has "
                f"{int(self._pos[slot])} rows (chunks must be contiguous "
                f"or overlapping)"
            )
        if start + s > self._pair.max_len:
            raise KVCapacityError([slot], [start], self._pair.max_len)
        need = blocks_for_rows(start + s, self._pair.kv_block_size)
        self._grow_table(slot, need)
        self._cow_range(slot, start, start + s)
        self._note_writes(slot, start, start + s)
        logits, self._pool, self._expert_acc = self._chunk_fn(
            self.weights, self._pool, tokens, jnp.int32(start),
            jnp.asarray(self._tables[slot : slot + 1]), self._expert_acc,
        )
        self._pos[slot] = start + s
        return logits

    def prefill_chunks(self, chunks: dict):
        """Batched chunked prefill: ONE multi-slot dispatch (paged only).

        ``chunks`` maps ``slot -> (tokens, start)`` — the same per-slot
        arguments :meth:`prefill_chunk` takes.  Every named slot's chunk
        runs in a single full-batch dispatch of the static prefill
        schedule (``pos`` becomes a per-lane offset vector), instead of
        one dispatch per mid-chunking slot per scheduler step — the
        engine's chunked-prefill hot-path fix.  Lanes *not* named in
        ``chunks`` are parked on all-scratch block tables, so their
        placeholder computation scatters into the scratch block and
        cannot touch any live slot's cache rows; their logits rows are
        garbage for the caller to ignore.

        All per-slot validation, block release (``start == 0``) and
        pool growth happen host-side BEFORE the dispatch — a
        :class:`KVCapacityError` leaves device state untouched, so the
        scheduler can evict the named slot and retry the survivors.
        Bit-exactness per lane vs the single-slot path is row-local
        (tested).

        Returns the batch's last-token logits [batch_size, 1,
        vocab_padded]; row ``b`` is meaningful only for ``b in chunks``.
        """
        self._require("decoder", "prefill_chunks")
        self._affine("prefill_chunks")
        with span("session.prefill_chunks"):
            if not self._pair.paged:
                raise RuntimeError(
                    "prefill_chunks needs a paged session; compile with "
                    "kv_block_size/kv_blocks"
                )
            if not chunks:
                raise ValueError("prefill_chunks needs at least one slot chunk")
            s = self._pair.seq_len
            if self._pos is None:
                self._pos = np.zeros((self.batch_size,), np.int32)
            checked: dict[int, tuple] = {}
            for slot, (tokens, start) in sorted(chunks.items()):
                slot = int(slot)
                if not 0 <= slot < self.batch_size:
                    raise IndexError(
                        f"slot {slot} out of range [0, {self.batch_size})")
                tokens = self._check_tokens(tokens, 1)
                start = int(start)
                if start != 0 and not 0 < start <= int(self._pos[slot]):
                    raise ValueError(
                        f"chunk at start {start} leaves a gap: slot {slot} has "
                        f"{int(self._pos[slot])} rows (chunks must be contiguous "
                        f"or overlapping)"
                    )
                if start + s > self._pair.max_len:
                    raise KVCapacityError([slot], [start], self._pair.max_len)
                checked[slot] = (tokens, start)
            # host-side state changes after ALL validation; release-then-grow
            # is idempotent per slot, so a KVCapacityError mid-loop (pool
            # exhaustion) is safely retried for the surviving slots
            for slot, (_, start) in checked.items():
                if start == 0:
                    self._release_blocks(slot)
            for slot, (_, start) in checked.items():
                self._grow_table(slot, blocks_for_rows(start + s,
                                                       self._pair.kv_block_size))
            for slot, (_, start) in checked.items():
                # a suffix chunk overlapping an attached shared prefix (the
                # pinned tail chunk of a near-full match) re-writes identical
                # rows — bit-neutral, but still a write: COW keeps the
                # no-write-into-shared-blocks invariant unconditional
                self._cow_range(slot, start, start + s)
            for slot, (_, start) in checked.items():
                self._note_writes(slot, start, start + s)
            batch_tokens = np.zeros((self.batch_size, s), np.int32)
            starts = np.zeros((self.batch_size,), np.int32)
            # parked lanes write through all-scratch tables — handing them
            # their live tables would scatter placeholder K/V into real rows
            tables = np.full_like(self._tables, SCRATCH_BLOCK)
            for slot, (tokens, start) in checked.items():
                batch_tokens[slot] = np.asarray(tokens[0])
                starts[slot] = start
                tables[slot] = self._tables[slot]
            logits, self._pool, self._expert_acc = self._chunk_fn(
                self.weights, self._pool, jnp.asarray(batch_tokens),
                jnp.asarray(starts), jnp.asarray(tables), self._expert_acc,
            )
            for slot, (_, start) in checked.items():
                self._pos[slot] = start + s
            return logits

    def free_slot(self, slot: int) -> None:
        """Release one slot's KV state (paged: return its blocks to the
        pool so other requests can grow into them).  The scheduler calls
        this on eviction/completion; dense sessions only reset the depth.
        """
        self._require("decoder", "free_slot")
        self._affine("free_slot")
        if not 0 <= slot < self.batch_size:
            raise IndexError(f"slot {slot} out of range [0, {self.batch_size})")
        if self._pair.paged:
            self._release_blocks(slot)
        if self._pos is not None:
            self._pos[slot] = 0

    def attach_prefix(self, slot: int, blocks, rows: int) -> None:
        """Install a shared prefix into a *free* slot (paged only).

        ``blocks`` is a resident block chain (e.g. a
        :class:`~repro.deploy.prefix.PrefixIndex` match) covering cache
        rows ``[0, rows)`` in logical order.  Every block is
        :meth:`~repro.deploy.paging.BlockAllocator.fork`-ed — refcount
        + 1, zero data movement — into the slot's table, and the slot's
        depth starts at ``rows``: chunked prefill then only runs on the
        novel suffix (``prefill_chunk(start >= rows - seq_len)``), or,
        on a full-prompt match, decode starts immediately.  The first
        write into any still-shared block copy-on-writes it
        (:meth:`_cow_range`), so siblings and the index never observe
        the attach.  :meth:`free_slot` releases the forked references
        like any other blocks.
        """
        self._require("decoder", "attach_prefix")
        self._affine("attach_prefix")
        if not self._pair.paged:
            raise RuntimeError(
                "attach_prefix needs a paged session; compile with "
                "kv_block_size/kv_blocks")
        if not 0 <= slot < self.batch_size:
            raise IndexError(f"slot {slot} out of range [0, {self.batch_size})")
        if self._pos is None:
            self._pos = np.zeros((self.batch_size,), np.int32)
        if self._slot_blocks[slot] or int(self._pos[slot]) != 0:
            raise RuntimeError(
                f"attach_prefix into live slot {slot} (pos "
                f"{int(self._pos[slot])}, {len(self._slot_blocks[slot])} "
                f"blocks held); free_slot it first")
        chain = [int(b) for b in blocks]
        rows = int(rows)
        if not 1 <= rows <= self._pair.max_len:
            raise ValueError(
                f"attach_prefix rows must be in [1, {self._pair.max_len}], "
                f"got {rows}")
        if len(chain) != blocks_for_rows(rows, self._pair.kv_block_size):
            raise ValueError(
                f"{rows} prefix rows cover "
                f"{blocks_for_rows(rows, self._pair.kv_block_size)} blocks "
                f"of size {self._pair.kv_block_size}, got a chain of "
                f"{len(chain)}")
        self._alloc.fork(chain, owner=slot)  # loud on any dead block
        for i, blk in enumerate(chain):
            self._tables[slot, i] = blk
        self._slot_blocks[slot] = chain
        self._pos[slot] = rows

    def sharing_state(self, index_blocks=()) -> "KVSharingState":
        """Snapshot of the pool's sharing structure for the KV-sharing
        audit (:func:`repro.deploy.verify.verify_sharing`): live block
        tables, per-block refcounts, and (caller-supplied) the prefix
        index's pinned blocks."""
        self._require("decoder", "sharing_state")
        if not self._pair.paged:
            raise RuntimeError("sharing_state needs a paged session")
        from repro.deploy.verify import KVSharingState

        return KVSharingState(
            n_blocks=self._pair.kv_blocks,
            refcounts={b: self._alloc.refcount(b)
                       for b in range(1, self._pair.kv_blocks + 1)
                       if self._alloc.refcount(b) > 0},
            tables={b: self.block_chain(b) for b in range(self.batch_size)
                    if self._slot_blocks[b]},
            index_blocks=tuple(int(b) for b in index_blocks),
        )

    # -- paged internals ---------------------------------------------------

    def _release_blocks(self, slot: int) -> None:
        if self._slot_blocks[slot]:
            self._alloc.free(self._slot_blocks[slot])
            self._slot_blocks[slot] = []
        self._tables[slot, :] = SCRATCH_BLOCK

    def _pool_capacity_error(self, slot: int) -> KVCapacityError:
        """Structured pool-exhaustion error for ``slot``, naming only the
        slots whose eviction would *actually* return blocks to the pool:
        holders of at least one exclusively owned (refcount == 1) block.
        A slot whose blocks are all shared contributes nothing when
        evicted — freeing it just decrements its siblings' refcounts —
        so reporting it evictable would let a scheduler churn evictions
        that can never make progress (and corrupt nothing, but starve)."""
        evictable = sorted(
            b for b in range(self.batch_size)
            if b != slot and any(self._alloc.refcount(blk) == 1
                                 for blk in self._slot_blocks[b])
        )
        pos = 0 if self._pos is None else int(self._pos[slot])
        return KVCapacityError(
            [slot], [pos], self._pair.max_len, reason="pool",
            evictable=evictable,
        )

    def _grow_table(self, slot: int, need: int) -> None:
        """Allocate blocks until slot's table covers ``need`` logical
        blocks; all-or-nothing, raising the structured pool-exhaustion
        error with the evictable block holders named."""
        missing = [i for i in range(need)
                   if self._tables[slot, i] == SCRATCH_BLOCK]
        if not missing:
            return
        try:
            got = self._alloc.allocate(len(missing), owner=slot)
        except PoolExhausted:
            raise self._pool_capacity_error(slot) from None
        for i, blk in zip(missing, got):
            self._tables[slot, i] = blk
        self._slot_blocks[slot].extend(got)

    def _cow_range(self, slot: int, lo: int, hi: int) -> None:
        """Copy-on-write every *shared* block ``slot`` is about to write
        in cache rows ``[lo, hi)`` — called before each write dispatch
        (decode append, prefill chunk), so a request that attached a
        shared prefix materializes a private copy before its first write
        into a partially filled shared block.  Whole-block device copy
        (bit-exact: int8 rows move verbatim), table + chain patched in
        place; pool exhaustion raises the structured capacity error
        before any state changes."""
        if hi <= lo:
            return
        bsz = self._pair.kv_block_size
        for i in range(lo // bsz, blocks_for_rows(hi, bsz)):
            blk = int(self._tables[slot, i])
            if blk == SCRATCH_BLOCK or self._alloc.refcount(blk) <= 1:
                continue
            try:
                fresh, copied = self._alloc.cow(blk, owner=slot)
            except PoolExhausted:
                raise self._pool_capacity_error(slot) from None
            assert copied, (slot, blk)
            self._pool = self._copy_fn(self._pool, jnp.int32(blk),
                                       jnp.int32(fresh))
            self._tables[slot, i] = fresh
            chain = self._slot_blocks[slot]
            chain[chain.index(blk)] = fresh
            self._cow_copies += 1

    def _note_writes(self, slot: int, lo: int, hi: int) -> None:
        """Tell the shadow block sanitizer (``REPRO_SANITIZE=1``) that
        the next dispatch writes ``slot``'s cache rows ``[lo, hi)`` —
        it fails with BLK001 (freed block) or BLK003 (still-shared
        block, i.e. a skipped COW) at this call site instead of letting
        the scatter corrupt another request's rows silently."""
        shadow = self._alloc.shadow
        if shadow is None or hi <= lo:
            return
        bsz = self._pair.kv_block_size
        for i in range(lo // bsz, blocks_for_rows(hi, bsz)):
            blk = int(self._tables[slot, i])
            if blk != SCRATCH_BLOCK:
                shadow.write(slot, blk, self._alloc)

    def decode(self, tokens, pos=None, *, active=None):
        """One batched continuous-decode dispatch.

        ``tokens`` int32 [B] or [B, 1] — the next token of each request.
        ``pos`` int32 [B] — each request's current depth (defaults to the
        session's tracked per-slot positions; tracked **host-side** as
        numpy, so the per-token scheduler loop never round-trips to the
        device for bookkeeping).  Slot ``b`` RoPE-rotates by ``pos[b]``,
        appends its K/V at cache row ``pos[b]`` and attends rows
        ``[0, pos[b]]`` — one dispatch, B depths.  Returns logits
        [B, 1, vocab_padded]; active positions advance to ``pos + 1``.

        ``active`` (paged sessions only) is a per-lane bool mask: a
        static-shape dispatch can carry parked lanes (free slots, slots
        mid-chunked-prefill) whose writes land in the scratch block, who
        skip capacity checks and whose depth does not advance.
        """
        self._require("decoder", "decode")
        self._affine("decode")
        with span("session.decode"):
            paged = self._pair.paged
            if (self._kv is None) if not paged else (self._pos is None):
                raise RuntimeError("decode before prefill: no KV state in the session")
            if active is not None and not paged:
                raise ValueError(
                    "active lane masks are a paged-session feature (dense "
                    "dispatches park free lanes at pos 0 instead)"
                )
            tokens = jnp.asarray(tokens, jnp.int32)
            if tokens.ndim == 1:
                tokens = tokens[:, None]
            if tokens.shape != (self.batch_size, 1):
                raise ValueError(
                    f"decode tokens must be [{self.batch_size}, 1], got "
                    f"{tuple(tokens.shape)}"
                )
            # pos/active stay on the host: capacity checks and the +1 advance
            # are numpy, so a decode step costs exactly one device dispatch
            # (an int(jnp.max(pos)) here would sync with the device per token).
            pos = self._pos if pos is None else np.asarray(pos, np.int32)
            if pos.shape != (self.batch_size,):
                raise ValueError(
                    f"pos must be a per-request vector [{self.batch_size}], got "
                    f"{tuple(pos.shape)}"
                )
            act = (np.ones((self.batch_size,), bool) if active is None
                   else np.asarray(active, bool).reshape(-1))
            if act.shape != (self.batch_size,):
                raise ValueError(
                    f"active must be a per-request mask [{self.batch_size}], "
                    f"got {tuple(act.shape)}"
                )
            # past-capacity writes would silently clamp inside the scatter and
            # corrupt the deepest cache row, so bound them loudly instead —
            # with the offending slots attached, so a scheduler can evict
            # exactly those and re-dispatch the rest.
            full = [b for b in range(self.batch_size)
                    if act[b] and int(pos[b]) >= self._pair.max_len]
            if full:
                raise KVCapacityError(full, [int(pos[b]) for b in full],
                                      self._pair.max_len)
            if paged:
                # crossing into a new logical block allocates it up front —
                # pool exhaustion surfaces as a structured error BEFORE any
                # device state changes, naming the evictable block holders
                bs = self._pair.kv_block_size
                for b in range(self.batch_size):
                    if act[b] and int(pos[b]) % bs == 0:
                        self._grow_table(b, int(pos[b]) // bs + 1)
                for b in range(self.batch_size):
                    if act[b]:
                        # first append into a shared partial block (an
                        # attached prefix whose tail block siblings/the index
                        # still reference) materializes a private copy
                        self._cow_range(b, int(pos[b]), int(pos[b]) + 1)
                        self._note_writes(b, int(pos[b]), int(pos[b]) + 1)
                logits, self._pool, self._expert_acc = self._decode_fn(
                    self.weights, self._pool, tokens, jnp.asarray(pos),
                    jnp.asarray(self._tables), jnp.asarray(act), self._expert_acc,
                )
            else:
                logits, cache = self._decode_fn(self.weights, self._kv, tokens,
                                                jnp.asarray(pos))
                self._kv = {"k": cache["k"], "v": cache["v"]}
            self._pos = np.where(act, pos + 1,
                                 self._pos if self._pos is not None else 0
                                 ).astype(np.int32)
            return logits
