"""Multi-pod dry-run (deliverable e).

For every (architecture x input-shape) cell, lower + compile the real step
function — ``train_step`` (AdamW, remat, microbatching) for train cells,
``prefill`` / ``serve_step`` for inference cells — against the production
mesh, with full parameter/optimizer/batch/cache shardings.  Success proves
the distribution config is coherent; the compiled artifact provides
memory_analysis (fits?) and cost_analysis (FLOPs/bytes) plus the
collective schedule parsed from the partitioned HLO (§Roofline inputs).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod both
Outputs one JSON per cell under experiments/dryrun/.

Plan-backed model path (the paper's deployment flow, executable):
  PYTHONPATH=src python -m repro.launch.dryrun --arch mobilebert --reduced --via-plan
  PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b --reduced --via-plan
compiles the config through the unified API (``repro.deploy.api.compile``
with its on-disk plan cache -> ``CompiledModel.session``) into its
deployment artifact — an encoder DeploymentPlan, or a decoder
prefill/decode plan pair sharing a static KV region — executes it
through the InferenceSession (dispatch via the runtime DispatchTable),
and checks bit-exactness against the model-level ``forward_w8a8``
(encoder) or ``prefill_w8a8`` + chained ``decode_step_w8a8`` (decoder)
on the identical quantized params.
"""

import argparse
import json
import os
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ALL_SHAPES, get_config, list_archs, shape_applicable
from repro.deploy.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.models import build, input_specs
from repro.optim import adamw
from repro.runtime.activations import activation_policy
from repro.runtime.sharding import (
    batch_shardings,
    cache_shardings,
    opt_state_shardings,
    param_shardings,
)

# train-cell microbatch counts (memory fitting; the global batch is fixed)
MICROBATCHES = {
    "qwen1.5-110b": 16,
    "mistral-large-123b": 16,
    "llava-next-34b": 8,
    "seamless-m4t-large-v2": 4,
    "zamba2-2.7b": 4,
    "mamba2-370m": 2,
    "qwen2-moe-a2.7b": 2,
}



def build_cell(arch: str, shape_name: str, mesh, *, seed: int = 0):
    """Returns (fn, arg_specs, in_shardings, meta) for one cell."""
    cfg = get_config(arch)
    cell = next(c for c in ALL_SHAPES if c.name == shape_name)
    api = build(cfg)
    key = jax.random.PRNGKey(seed)

    if cell.kind == "train":
        from repro.launch.train import make_train_step

        params = jax.eval_shape(lambda: api.init_params(key, jnp.bfloat16))
        opt_state = jax.eval_shape(lambda: adamw.init(params))
        batch = input_specs(cfg, cell, jnp.bfloat16)
        mb = MICROBATCHES.get(arch, 1)
        step = make_train_step(api, microbatches=mb, remat=True)
        # ZeRO-3/FSDP: params + optimizer fully sharded (data axes included)
        p_sh = param_shardings(mesh, params, fsdp=True)
        o_sh = opt_state_shardings(mesh, opt_state, p_sh)
        b_sh = batch_shardings(mesh, batch)
        return step, (params, opt_state, batch), (p_sh, o_sh, b_sh), {
            "microbatches": mb,
            "kind": "train",
            "fsdp": True,
        }

    sparams = jax.eval_shape(lambda: api.init_serve_params(key))
    sp_sh = param_shardings(mesh, sparams)
    if cell.kind == "prefill":
        batch = input_specs(cfg, cell, jnp.bfloat16)
        b_sh = batch_shardings(mesh, batch)
        fn = lambda sp, b: api.prefill(sp, b, cell.seq_len)  # noqa: E731
        return fn, (sparams, batch), (sp_sh, b_sh), {"kind": "prefill"}

    # decode
    cache = jax.eval_shape(api.init_cache_shape(cell.global_batch, cell.seq_len))
    seq_shard = cell.name == "long_500k"
    c_sh = cache_shardings(mesh, cache, seq_shard=seq_shard)
    token = input_specs(cfg, cell)["token"]
    t_sh = batch_shardings(mesh, {"token": token})["token"]
    fn = api.decode_step
    return fn, (sparams, cache, token), (sp_sh, c_sh, t_sh), {"kind": "decode"}


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, out_dir: str) -> dict:
    cfg = get_config(arch)
    cell = next(c for c in ALL_SHAPES if c.name == shape_name)
    ok, reason = shape_applicable(cfg, cell)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        fn, specs, shardings, meta = build_cell(arch, shape_name, mesh)
        rec.update(meta)
        with mesh, activation_policy(mesh, sequence_parallel=(meta["kind"] == "train")):
            lowered = jax.jit(fn, in_shardings=shardings).lower(*specs)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = analyze_hlo(compiled.as_text())  # multiplicity-aware (per device)
        rec.update(
            status="ok",
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            xla_cost_flops=float(cost.get("flops", -1.0)) if cost else -1.0,
            flops=hlo["flops"],
            mem_bytes=hlo["mem_bytes"],
            collectives={
                "bytes_by_op": hlo["collective_by_op"],
                "op_counts": hlo["collective_counts"],
                "total_bytes": hlo["collective_bytes"],
            },
        )
        if mem is not None:
            for attr in (
                "generated_code_size_in_bytes",
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
                "alias_size_in_bytes",
            ):
                if hasattr(mem, attr):
                    rec[attr] = int(getattr(mem, attr))
    except Exception as e:  # noqa: BLE001 — failures are findings
        rec.update(status="failed", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch.replace('/', '_')}__{shape_name}__{mesh_name}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def run_decoder_via_plan(
    model,
    *,
    batch_size: int,
    gen_steps: int,
    out_dir: str,
) -> int:
    """CompiledModel -> InferenceSession -> prefill + batched continuous
    decode; verify the whole trajectory bit-exactly vs prefill_w8a8 /
    decode_step_w8a8 (the session's per-request ``pos`` path)."""
    import numpy as np

    from repro.models import transformer as T

    cfg, pair = model.cfg, model.artifact
    arch, max_len = cfg.name, model.artifact.max_len
    s = pair.seq_len
    counts = pair.counts()
    print(
        f"[plan   ] {arch}: prefill {counts['prefill']['nodes']} nodes "
        f"({counts['prefill']['ita']} ita), decode {counts['decode']['nodes']} "
        f"nodes ({counts['decode']['ita']} ita), KV region "
        f"{len(pair.kv_tensors)} tensors x {max_len} tokens, "
        f"plan cache {'hit' if model.cache_hit else 'miss'}"
    )

    session = model.session(batch_size)
    qp = session.qp
    key = jax.random.PRNGKey(0)
    tokens = jax.random.randint(key, (batch_size, s), 0, cfg.vocab, jnp.int32)

    def same_state(ref_cache):
        kv = session.kv_cache
        return bool(
            np.array_equal(np.asarray(kv["k"]), np.asarray(ref_cache["k"]))
            and np.array_equal(np.asarray(kv["v"]), np.asarray(ref_cache["v"]))
        )

    t0 = time.time()
    logits = session.prefill(tokens)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0
    ref_logits, ref_cache = T.prefill_w8a8(cfg, qp, {"tokens": tokens}, max_len)
    exact = bool(np.array_equal(np.asarray(logits), np.asarray(ref_logits)))
    exact = exact and same_state(ref_cache)
    tok = jnp.argmax(ref_logits[:, -1:], axis=-1).astype(jnp.int32)
    t0 = time.time()
    for _ in range(gen_steps):
        logits = session.decode(tok)
        ref_logits, ref_cache = T.decode_step_w8a8(cfg, qp, ref_cache, tok)
        exact = exact and bool(
            np.array_equal(np.asarray(logits), np.asarray(ref_logits))
        ) and same_state(ref_cache)
        tok = jnp.argmax(ref_logits[:, -1:], axis=-1).astype(jnp.int32)
    t_decode = time.time() - t0

    be = model.backend
    status = "ok" if exact else "MISMATCH"
    print(
        f"[{status:7s}] decoder plan pair [{be.value}] vs prefill_w8a8 + "
        f"{gen_steps} x decode_step_w8a8: bit-exact={exact}; "
        f"prefill {batch_size}x{s} in {t_prefill:.2f}s (compile incl.), "
        f"decode {t_decode:.3f}s"
    )
    os.makedirs(out_dir, exist_ok=True)
    rec = {
        "arch": arch, "backend": be.value,
        "status": "ok" if exact else "mismatch", "bit_exact": exact,
        "plan": counts, "max_len": max_len, "gen_steps": gen_steps,
        "memory_peak": {"prefill": pair.prefill.memory_peak,
                        "decode": pair.decode.memory_peak},
        "cache_hit": model.cache_hit,
        "fingerprint": model.fingerprint,
        "compiler_version": model.compiler_version,
    }
    with open(os.path.join(out_dir, f"{arch}__via_plan_decoder__{be.value}.json"), "w") as f:
        json.dump(rec, f, indent=1)
    pair.save(os.path.join(out_dir, f"{arch}__plan_pair.json"))
    return 0 if exact else 1


def run_via_plan(
    arch: str,
    *,
    reduced_cfg: bool,
    backend,
    batch_size: int,
    seq_len: int | None,
    head_by_head: bool,
    gen_steps: int,
    out_dir: str,
    cache_dir: str | None = None,
    use_cache: bool = True,
) -> int:
    """compile() -> CompiledModel -> InferenceSession for one arch; verify
    bit-exactness vs the model-level w8a8 path (both families)."""
    import numpy as np

    from repro.configs import reduced
    from repro.deploy import api
    from repro.models import encoder as EN

    cfg = get_config(arch)
    if reduced_cfg:
        cfg = reduced(cfg)
    is_decoder = api.is_decoder_lm(cfg)
    if is_decoder and head_by_head:
        print("[note   ] --head-by-head is encoder-only; decoder pairs always "
              "emit fused attention (flag ignored)")
    t0 = time.time()
    try:
        model = api.compile(
            cfg,
            backend=backend,
            seq_len=(seq_len or 32) if is_decoder else seq_len,
            max_len=(seq_len or 32) + gen_steps + 1 if is_decoder else None,
            head_by_head=head_by_head and not is_decoder,
            cache_dir=cache_dir,
            use_cache=use_cache,
        )
    except api.UnsupportedFamilyError as e:
        raise SystemExit(f"--via-plan: {e}")
    t_lower = time.time() - t0

    if model.kind == "decoder":
        return run_decoder_via_plan(
            model, batch_size=batch_size, gen_steps=gen_steps, out_dir=out_dir,
        )

    plan = model.artifact
    counts = plan.counts()
    print(
        f"[plan   ] {arch}: {counts['nodes']} nodes "
        f"({counts['ita']} ita / {counts['cluster']} cluster), "
        f"{len(plan.tilings)} tilings, static peak {plan.memory_peak / 1024:.0f} KiB, "
        f"{'plan cache hit' if model.cache_hit else 'lowered'} in {t_lower:.2f}s"
    )

    session = model.session(batch_size)
    qp = session.qp
    key = jax.random.PRNGKey(0)
    name = plan.inputs[0]
    if name == "tokens":
        x = jax.random.randint(key, (batch_size, plan.seq_len), 0, cfg.vocab, jnp.int32)
    else:
        x = jax.random.randint(
            key, (batch_size, plan.seq_len, cfg.d_model), -64, 64, jnp.int8)

    t0 = time.time()
    out = jax.block_until_ready(session.forward(x))
    t_first = time.time() - t0
    t0 = time.time()
    out = jax.block_until_ready(session.forward(x))
    t_steady = time.time() - t0

    ref = jax.block_until_ready(EN.forward_w8a8(cfg, qp, {name: x}))
    exact = bool(np.array_equal(np.asarray(out), np.asarray(ref)))
    max_diff = float(np.max(np.abs(np.asarray(out) - np.asarray(ref))))
    be = model.backend
    status = "ok" if exact else "MISMATCH"
    print(
        f"[{status:7s}] plan-executor [{be.value}] vs forward_w8a8: "
        f"bit-exact={exact} (max |diff| {max_diff:.3g}); "
        f"compile+run {t_first:.2f}s, steady {t_steady * 1e3:.1f}ms "
        f"for batch {batch_size} x seq {plan.seq_len}"
    )

    os.makedirs(out_dir, exist_ok=True)
    rec = {
        "arch": arch, "reduced": reduced_cfg, "backend": be.value,
        "status": "ok" if exact else "mismatch", "bit_exact": exact,
        "plan": counts, "memory_peak": plan.memory_peak,
        "lower_s": round(t_lower, 3), "steady_s": round(t_steady, 4),
        "head_by_head": head_by_head,
        "cache_hit": model.cache_hit,
        "fingerprint": model.fingerprint,
        "compiler_version": model.compiler_version,
    }
    path = os.path.join(out_dir, f"{arch}__via_plan__{be.value}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    plan.save(os.path.join(out_dir, f"{arch}__plan.json"))
    return 0 if exact else 1


#: Host devices the multi-pod dry run lays its production meshes over.
DRYRUN_HOST_DEVICES = 512


def main(argv=None):
    from repro.launch.cli import add_plan_args, enable_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=["off", "on", "both"], default="off")
    ap.add_argument("--out-dir", default="experiments/dryrun")
    add_plan_args(ap, via_plan_help="compile --arch to its deployment "
                  "artifact and execute it, verifying bit-exactness vs the "
                  "model-level w8a8 path (both families)")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced (CPU smoke) variant of --arch")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--gen", type=int, default=2,
                    help="decoder --via-plan: number of chained decode steps "
                         "to verify against decode_step_w8a8")
    ap.add_argument("--head-by-head", action="store_true",
                    help="lower with the paper's per-head MHA schedule")
    args = ap.parse_args(argv)

    if args.via_plan:
        if not args.arch:
            raise SystemExit("--via-plan requires --arch")
        enable_compile_cache()
        return run_via_plan(
            args.arch,
            reduced_cfg=args.reduced,
            backend=args.backend,
            batch_size=args.batch,
            seq_len=args.seq,
            head_by_head=args.head_by_head,
            gen_steps=args.gen,
            out_dir=args.out_dir,
            cache_dir=args.plan_cache,
            use_cache=not args.no_plan_cache,
        )

    # the production meshes need 512 host devices; the flag is read when
    # the backend first starts, so it is appended here, before any device use
    os.environ["XLA_FLAGS"] = " ".join(filter(None, (
        os.environ.get("XLA_FLAGS"),
        f"--xla_force_host_platform_device_count={DRYRUN_HOST_DEVICES}",
    )))
    archs = [args.arch] if args.arch else [a for a in list_archs()[:10]]
    shapes = [args.shape] if args.shape else [c.name for c in ALL_SHAPES]
    pods = {"off": [False], "on": [True], "both": [False, True]}[args.multi_pod]

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                rec = run_cell(arch, shape, multi_pod=mp, out_dir=args.out_dir)
                status = rec["status"]
                extra = (
                    f"flops={rec.get('flops', 0):.3e} "
                    f"coll={rec.get('collectives', {}).get('total_bytes', 0):.3e}B "
                    f"compile={rec.get('compile_s', 0)}s"
                    if status == "ok"
                    else rec.get("reason", rec.get("error", ""))[:120]
                )
                print(f"[{status:7s}] {arch:22s} {shape:12s} {rec['mesh']:8s} {extra}",
                      flush=True)
                results.append(rec)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_fail = sum(r["status"] == "failed" for r in results)
    print(f"\ndry-run: {n_ok} ok, {n_skip} skipped (documented), {n_fail} failed")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
