"""Serving driver: the request-level engine over the compiled artifact.

Everything serves from the deployment artifact (``repro.deploy.compile``
— the on-disk plan cache prints hit/miss).  Decoder families go through
the continuous-batching scheduler (``repro.deploy.engine.Engine``):
requests are *submitted*, the engine owns slot admission, the per-request
``pos`` vector, eviction and recycling — no caller here touches a slot
index.  Encoder families run batched ``InferenceSession.forward``.

Runnable directly:
  PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --reduced \
      --batch 4 --requests 8 --prompt-len 32 --gen 8
  PYTHONPATH=src python -m repro.launch.serve --arch mobilebert --reduced \
      --batch 8 --gen 16

``--via-plan`` is accepted for compatibility with the shared CLI block
(serving has been plan-backed since the unified API; the flag is now
implied).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced


def compile_for_serving(cfg, args, *, extra_prompt: int = 0):
    """One ``compile()`` call for both families (the shared CLI surface)."""
    from repro.deploy import api

    is_decoder = api.is_decoder_lm(cfg)
    t0 = time.time()
    model = api.compile(
        cfg,
        backend=args.backend,
        seq_len=args.prompt_len if is_decoder else None,
        max_len=(args.prompt_len + extra_prompt + args.gen + 1)
        if is_decoder else None,
        cache_dir=args.plan_cache,
        use_cache=not args.no_plan_cache,
    )
    t_compile = time.time() - t0
    print(
        f"compile [{model.backend.value}] {cfg.name}: {model.kind} artifact, "
        f"plan cache {'hit' if model.cache_hit else 'miss'} "
        f"({model.fingerprint[:12]}, v{model.compiler_version}) in {t_compile:.2f}s"
    )
    return model


def serve_encoder(model, *, batch_size: int, steps: int) -> None:
    """Batched encoder serving through ``InferenceSession.forward``."""
    cfg, plan = model.cfg, model.artifact
    t0 = time.time()
    session = model.session(batch_size)
    key = jax.random.PRNGKey(0)
    name = plan.inputs[0]
    s = plan.seq_len

    def make_batch(k):
        if name == "tokens":
            return jax.random.randint(k, (batch_size, s), 0, cfg.vocab, jnp.int32)
        return jax.random.randint(k, (batch_size, s, cfg.d_model), -64, 64, jnp.int8)

    # synthesize all request batches up front so the timed loop measures
    # the executor, not the input generator
    batches = [make_batch(k) for k in jax.random.split(key, steps + 1)]
    out = jax.block_until_ready(session.forward(batches[-1]))
    t_compile = time.time() - t0
    t0 = time.time()
    for batch in batches[:steps]:
        out = session.forward(batch)
    jax.block_until_ready(out)
    t_serve = time.time() - t0
    counts = plan.counts()
    print(
        f"plan-serving [{model.backend.value}] {cfg.name}: {counts['nodes']} nodes "
        f"({counts['ita']} ita / {counts['cluster']} cluster); "
        f"bind+compile {t_compile:.2f}s; {steps} batches of {batch_size}x{s} in "
        f"{t_serve:.3f}s ({steps * batch_size / max(t_serve, 1e-9):.1f} inf/s, "
        f"{steps * batch_size * s / max(t_serve, 1e-9):.0f} tok/s)"
    )


def serve_decoder(model, *, max_batch: int, requests: int, prompt_len: int,
                  extra_prompt: int, gen: int, sampling,
                  scheduler=None) -> int:
    """Request-level serving: submit → schedule → stream, engine-only.

    Returns the exit code: 0 when every request finished with ``eos`` or
    ``length``, 1 otherwise."""
    from repro.deploy.engine import Engine
    from repro.launch.cli import synthesize_prompts

    pair = model.artifact
    t0 = time.time()
    engine = Engine(model, max_batch=max_batch, sampling=sampling,
                    scheduler=scheduler)
    prompts = synthesize_prompts(model.cfg.vocab, n=requests,
                                 prompt_len=prompt_len, extra=extra_prompt)
    handles = [engine.submit(p, max_new_tokens=gen) for p in prompts]
    stats = engine.run_until_idle()
    t_total = time.time() - t0

    counts = pair.counts()
    print(
        f"engine-serving [{model.backend.value}] {model.cfg.name}: "
        f"decode plan {counts['decode']['nodes']} nodes "
        f"({counts['decode']['ita']} ita); KV region "
        f"{len(pair.kv_tensors)} tensors x {pair.max_len} tokens x "
        f"{max_batch} slots"
    )
    print(f"  {stats.summary()}")
    print(f"  bind+compile+serve wall time {t_total:.2f}s "
          f"(prefill {stats.prefill_time_s:.2f}s, decode {stats.decode_time_s:.2f}s); "
          f"peak queue depth {stats.peak_queue_depth}")
    for h in handles[:2]:
        print(f"  request {h.rid}: prompt {len(h.prompt)} tokens -> "
              f"{h.tokens[:8]} ({h.finish_reason})")
    unfinished = [h for h in handles if h.finish_reason not in ("eos", "length")]
    for h in unfinished:
        print(f"  request {h.rid} ended {h.finish_reason!r} after "
              f"{len(h.tokens)} of {gen} tokens")
    return 1 if unfinished else 0


def main(argv=None):
    from repro.deploy.lowering import UnsupportedFamilyError
    from repro.launch.cli import (
        add_engine_args,
        add_plan_args,
        add_sanitize_args,
        add_serving_args,
        apply_sanitize_args,
        enable_compile_cache,
        make_sampling,
        make_scheduler_from_args,
        resolve_requests,
    )

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--extra-prompt", type=int, default=2,
                    help="stagger prompt lengths up to this many tokens past "
                         "--prompt-len (teacher-forced through batched decode)")
    add_engine_args(ap)
    add_serving_args(ap)
    add_sanitize_args(ap)
    add_plan_args(ap, via_plan_help="accepted for compatibility; serving is "
                  "always plan-backed (compile() -> Engine/InferenceSession)")
    args = ap.parse_args(argv)
    apply_sanitize_args(args)  # before any engine/allocator exists
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    try:
        model = compile_for_serving(cfg, args, extra_prompt=args.extra_prompt)
    except UnsupportedFamilyError as e:
        raise SystemExit(f"cannot serve {cfg.name}: {e}")
    if model.kind == "encoder":
        serve_encoder(model, batch_size=args.batch, steps=args.gen)
        return 0
    return serve_decoder(
        model,
        max_batch=args.batch,
        requests=resolve_requests(args),
        prompt_len=args.prompt_len,
        extra_prompt=args.extra_prompt,
        gen=args.gen,
        sampling=make_sampling(args),
        scheduler=make_scheduler_from_args(args),
    )


if __name__ == "__main__":
    raise SystemExit(main())
