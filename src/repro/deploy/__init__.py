"""Deployment flow (Deeploy analogue) + dry-run HLO analysis.

graph -> patterns (MHA fusion, head split, engine mapping) -> tiler
(geometric constraints) -> memory (static layout) -> costmodel
(calibrated Snitch+ITA cycles/energy).  ``hlo_analysis`` is the TPU-side
"profiler" reading compiled dry-run artifacts.

The executable half: ``lowering`` compiles an ArchConfig through the pass
pipeline into a serializable ``plan.DeploymentPlan`` (encoder family) or
a linked ``plan.DecoderPlanPair`` — prefill + decode-step schedules
sharing one persistent, statically planned KV-cache region (decoder
family); ``executor`` runs the plans as jitted JAX functions, resolving
every node through the runtime DispatchTable (Pallas kernels on the
accelerator engine, XLA fallbacks on the cluster).

``api`` is the one inference surface over all of it:
``compile(cfg) -> CompiledModel -> InferenceSession`` with an on-disk
plan cache keyed by (config fingerprint, compiler version) and batched
continuous decoding (per-request ``pos`` vectors).  ``engine`` is the
request-level serving layer on top: ``Engine.submit() -> RequestHandle``
runs a continuous-batching scheduler (pluggable admission, slot eviction
+ recycling, streaming) so no caller touches slot indices; the
slot-indexed ``InferenceSession`` remains the documented low-level
surface underneath.  ``serving`` stacks the async frontier on top:
``AsyncEngine`` runs the loop on a background thread, scheduler policies
(``FIFO`` / ``PriorityDeadline``) order admission with SLOs, preemption
and load shedding, and ``ServingFrontend`` speaks streaming JSON-lines
HTTP (``python -m repro.deploy.serving``).

``verify`` is the static plan-analysis pass guarding all of it: memory
hazards, KV ordering, quant ranges and engine legality are audited on
every ``compile()`` (and via ``python -m repro.deploy.verify`` for
artifacts on disk).

``prefix`` adds cross-request KV reuse over the paged pool: a radix
index (``PrefixIndex``) maps shared prompt prefixes to resident,
refcounted blocks; the engine attaches matches copy-on-write and
prefills only the novel suffix (``compile(cfg, ...,
prefix_cache=True)``).  ``verify.check_sharing`` audits the live pool's
refcount/COW invariants (rules KV006/KV007).

``sanitize`` is the concurrency & KV-lifetime sanitizer over the whole
serving stack: a static lock-order lint (the declared ``serving.cv ->
engine.lock -> frontend.hlock`` lattice, checked over the cross-module
acquisition graph) plus an ``InferenceSession`` thread-affinity lint
(``python -m repro.deploy.sanitize``), and — under ``REPRO_SANITIZE=1``
— a lockdep-style runtime order checker on every serving lock and a
shadow block-lifecycle tracker that turns use-after-free / double-free
/ skipped-COW / refcount-drift into structured ``BLK*`` diagnostics at
the offending call site.  Small-scope interleaving model checks of the
fork/COW/free and scheduler cancel protocols ride along
(``--interleavings``).
"""

from repro.deploy import (  # noqa: F401
    api,
    costmodel,
    engine,
    executor,
    graph,
    hlo_analysis,
    lowering,
    memory,
    paging,
    patterns,
    plan,
    prefix,
    sanitize,
    serving,
    tiler,
    verify,
)
from repro.deploy.api import (  # noqa: F401
    COMPILER_VERSION,
    CompiledModel,
    InferenceSession,
    KVCapacityError,
    UnsupportedFamilyError,
    compile,
    config_fingerprint,
    is_decoder_lm,
)
from repro.deploy.paging import (  # noqa: F401
    BlockAllocator,
    chunk_starts,
)
from repro.deploy.prefix import (  # noqa: F401
    PrefixIndex,
    PrefixMatch,
)
from repro.deploy.engine import (  # noqa: F401
    Engine,
    EngineStats,
    Greedy,
    RequestHandle,
    RequestStatus,
    Temperature,
)
from repro.deploy.executor import PlanBindingError  # noqa: F401
from repro.deploy.memory import MemoryPlanError  # noqa: F401
from repro.deploy.sanitize import (  # noqa: F401
    SanitizerDiagnostic,
    SanitizerError,
    ShadowPool,
    affinity_report,
    check_interleavings,
    lint_affinity,
    lint_lock_order,
)
from repro.deploy.verify import (  # noqa: F401
    KVSharingState,
    KVWrite,
    PlanDiagnostic,
    PlanVerificationError,
    check,
    check_sharing,
    verify_pair,
    verify_plan,
    verify_sharing,
)
