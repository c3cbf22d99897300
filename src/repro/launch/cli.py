"""Shared CLI plumbing for the plan-backed launch scripts.

One place defines the ``--via-plan`` / ``--backend`` / plan-cache
argument block and validates backend names, so ``serve.py``,
``dryrun.py`` and the benchmarks cannot drift apart.  Backend choices
are derived from the runtime dispatch registry: a name is valid iff
:func:`repro.core.heterogeneous.as_backend` resolves it to a backend the
plan executor dispatches (``FLOAT`` is model-path only — plans carry
integer quant scales).

``add_engine_args`` / ``make_sampling`` are the matching shared block
for the request-level serving engine (``repro.deploy.engine.Engine``):
request count, generation budget and the sampling policy — so the serve
CLI and the throughput benchmark present one surface.

``enable_compile_cache`` places JAX's persistent compilation cache for
every entry point that runs the model.
"""

from __future__ import annotations

import argparse
import os

from repro.core.heterogeneous import Backend, as_backend

#: The checkout this package runs from, when it runs from one (an editable
#: install or ``src`` on the path); ``None`` for an installed package.
_ROOT = os.path.normpath(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                      "..", "..", ".."))
CHECKOUT = _ROOT if os.path.isfile(os.path.join(_ROOT, "pyproject.toml")) else None


def enable_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory: JAX reads it
    itself and nothing here overrides it.  Otherwise the cache goes to one
    fixed path, ``.jax_cache/`` in the checkout, since a cache whose
    directory moves between runs is never found again.  An installed
    package has no checkout and gets no cache (``None``).  Call before the
    first compile.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path and CHECKOUT is not None:
        import jax

        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path or None


def plan_backend_names() -> tuple[str, ...]:
    """Backend names the plan executor accepts, in enum order."""
    return tuple(b.value for b in Backend if b is not Backend.FLOAT)


def parse_backend(name: str) -> Backend:
    """Validate + normalize a CLI backend name (argparse ``type=``)."""
    try:
        be = as_backend(name)
    except ValueError as e:
        raise argparse.ArgumentTypeError(str(e)) from None
    if be is Backend.FLOAT:
        raise argparse.ArgumentTypeError(
            f"backend {name!r} is model-path only; plan backends: "
            f"{', '.join(plan_backend_names())}"
        )
    return be


def add_plan_args(ap: argparse.ArgumentParser, *, via_plan_help: str) -> None:
    """Install the shared plan-execution argument block.

    ``--backend`` parses straight to a :class:`Backend` enum member
    (``args.backend.value`` prints the name); ``--plan-cache`` /
    ``--no-plan-cache`` control the ``compile()`` on-disk plan cache.
    """
    ap.add_argument("--via-plan", action="store_true", help=via_plan_help)
    ap.add_argument(
        "--backend", type=parse_backend, default=Backend.W8A8,
        metavar="|".join(plan_backend_names()),
        help="plan-executor backend: paper-faithful XLA integer path (w8a8) "
             "or Pallas kernels (ita; interpret on CPU, compiled on TPU)",
    )
    ap.add_argument(
        "--plan-cache", default=None, metavar="DIR",
        help="plan cache directory for compile() (default: $REPRO_PLAN_CACHE "
             "or ~/.cache/repro/plans)",
    )
    ap.add_argument(
        "--no-plan-cache", action="store_true",
        help="bypass the on-disk plan cache (always re-lower)",
    )


def add_engine_args(ap: argparse.ArgumentParser) -> None:
    """Install the shared serving-engine argument block.

    ``--batch`` is the engine's ``max_batch`` (KV-region slots);
    ``--requests`` how many to submit (default: a multiple of the batch
    via :func:`resolve_requests`, so the scheduler genuinely evicts and
    recycles slots); ``--sampling`` / ``--temperature`` /
    ``--sample-seed`` pick the token policy.
    """
    ap.add_argument("--batch", type=int, default=4,
                    help="engine max_batch: concurrent request slots")
    ap.add_argument("--requests", type=int, default=None,
                    help="requests to submit (default: a multiple of --batch "
                         "— see each tool's resolve_requests factor — so "
                         "slot eviction + recycling genuinely happen)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=8,
                    help="max_new_tokens per request")
    ap.add_argument("--sampling", choices=("greedy", "temperature"),
                    default="greedy")
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="PRNG seed for --sampling temperature")


def add_serving_args(ap: argparse.ArgumentParser) -> None:
    """Install the shared scheduler-policy argument block.

    One surface for ``serve.py``, ``python -m repro.deploy.serving`` and
    the throughput benchmark: ``--scheduler`` names a policy from
    :data:`repro.deploy.serving.scheduler.POLICIES`, ``--max-queue``
    bounds admission (shed with 429/``QueueFullError`` past it),
    ``--aging-s`` tunes priority aging (priority-deadline only).
    """
    from repro.deploy.serving.scheduler import POLICIES

    ap.add_argument("--scheduler", choices=tuple(POLICIES), default="fifo",
                    help="admission policy (fifo = historical behavior; "
                         "priority-deadline = SLO-aware ordering, preemption "
                         "and load shedding)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bound the admission queue; submissions past it are "
                         "shed with retry-after backpressure (default: "
                         "unbounded)")
    ap.add_argument("--aging-s", type=float, default=None,
                    help="priority-deadline aging interval: a queued request "
                         "gains one priority level per this many seconds "
                         "waited (starvation-freedom)")


def add_sanitize_args(ap: argparse.ArgumentParser) -> None:
    """Install the shared concurrency-sanitizer flag.

    ``--sanitize`` turns on the lockdep runtime checker and the shadow
    block-lifecycle tracker (:mod:`repro.deploy.sanitize`) for this
    process — equivalent to running with ``REPRO_SANITIZE=1``.
    """
    ap.add_argument(
        "--sanitize", action="store_true",
        help="enable the concurrency & KV-lifetime sanitizer (lockdep "
             "lock-order checking + shadow block tracking; same as "
             "REPRO_SANITIZE=1)")


def apply_sanitize_args(args) -> None:
    """Flip the sanitizer env switch from the parsed ``--sanitize`` flag.

    Must run *before* any engine/allocator is constructed — the lock
    wrappers and the shadow pool are chosen at construction time."""
    if getattr(args, "sanitize", False):
        import os

        os.environ["REPRO_SANITIZE"] = "1"


def make_scheduler_from_args(args):
    """Build the engine scheduler policy from the shared argument block."""
    from repro.deploy.serving.scheduler import make_scheduler

    return make_scheduler(args.scheduler, max_queue=args.max_queue,
                          aging_s=args.aging_s)


def make_sampling(args):
    """Build the engine sampling policy from the shared argument block."""
    from repro.deploy.engine import Greedy, Temperature

    if args.sampling == "temperature":
        import jax

        return Temperature(args.temperature, jax.random.PRNGKey(args.sample_seed))
    return Greedy()


def resolve_requests(args, *, factor: int = 2) -> int:
    """The ``--requests`` default: ``factor * batch`` keeps admissions
    outrunning the slot count so eviction + recycling genuinely happen
    (serve/example use 2x; the throughput benchmark asks for 3x)."""
    return args.requests if args.requests is not None else factor * args.batch


def http_generate(host: str, port: int, prompt, max_new_tokens: int, *,
                  stream: bool = True, timeout: float = 60.0, **slo):
    """Stdlib client for the serving frontend's ``POST /v1/generate``.

    Streaming (default) returns an iterator of decoded JSON-lines events
    — ``{"token": t, "index": i}`` per sampled token, then the final
    ``{"done": true, ...}`` summary.  Unary returns the summary dict.
    Extra keyword args (``priority``, ``ttft_slo_ms``, ``deadline_ms``,
    ``eos_id``) pass straight through to the request body.  HTTP errors
    surface as ``urllib.error.HTTPError`` — a shed request is ``429``
    with a ``Retry-After`` header and a structured JSON body.
    """
    import json as _json
    import urllib.request

    body = {"prompt": list(prompt), "max_new_tokens": int(max_new_tokens),
            "stream": stream, **{k: v for k, v in slo.items() if v is not None}}
    req = urllib.request.Request(
        f"http://{host}:{port}/v1/generate",
        data=_json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    resp = urllib.request.urlopen(req, timeout=timeout)
    if not stream:
        with resp:
            return _json.loads(resp.read().decode())

    def events():
        with resp:
            for line in resp:
                if line.strip():
                    yield _json.loads(line.decode())

    return events()


def http_get_json(host: str, port: int, path: str, *,
                  timeout: float = 10.0) -> dict:
    """Fetch one JSON endpoint (``/v1/stats``, ``/v1/status/<rid>``,
    ``/healthz``) from the serving frontend."""
    import json as _json
    import urllib.request

    with urllib.request.urlopen(f"http://{host}:{port}{path}",
                                timeout=timeout) as resp:
        return _json.loads(resp.read().decode())


def synthesize_prompts(vocab: int, *, n: int, prompt_len: int, extra: int = 0,
                       seed: int = 0) -> list[list[int]]:
    """``n`` random prompts with lengths staggered across
    ``[prompt_len, prompt_len + extra]`` — the tail past the static
    prefill length is teacher-forced through batched decode, so resident
    requests sit at genuinely mixed depths.  One implementation so the
    serve CLI, the example and the throughput benchmark drive the engine
    with the same traffic shape."""
    import jax

    key = jax.random.PRNGKey(seed)
    prompts = []
    for i in range(n):
        p = prompt_len + (i % (extra + 1))
        toks = jax.random.randint(jax.random.fold_in(key, i), (p,), 0, vocab)
        prompts.append([int(t) for t in toks])
    return prompts
