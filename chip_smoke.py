#!/usr/bin/env python3
"""Smoke run of the deployment flow on one TPU, at published widths.

Runs the main path once through the entry points a user calls, in one
process, phase by phase:

  device         JAX's first device must be a TPU; there is no CPU fallback
  decoder-http   olmo-1b, w8a8: compile() -> AsyncEngine -> ServingFrontend,
                 greedy requests over HTTP on 127.0.0.1
  decoder-ita    olmo-1b, ita (Pallas int8 GEMMs): compile() -> Engine
  decoder-paged  olmo-1b, w8a8, paged KV + prefix cache: prompts longer than
                 the prefill length (chunked prefill) and a repeated prompt
  encoder-ita    mobilebert, ita (Pallas attention + GEMMs): batched
                 InferenceSession.forward

Every generated stream must equal, token for token, an independent greedy
trajectory of ``prefill_w8a8`` + chained ``decode_step_w8a8`` on the same
quantized weights.  The encoder must equal ``forward_w8a8`` on the backend
its config puts attention on, and each kernel, on layer 0's activations, the
pure-XLA arithmetic it stands for.  Both ``ita`` phases check
that the compiled step holds the Pallas kernels (``tpu_custom_call``), so
they ran compiled and not interpreted.

Each phase prints its wall time and the time JAX spent tracing, lowering and
compiling in it.  Any failure exits non-zero.  Only when every phase passed
is the last line of standard output the result, one JSON object naming the
device.  Weights and inputs are random, drawn from fixed seeds.

  python3 chip_smoke.py             # published widths, on a machine with a TPU
  python3 chip_smoke.py --reduced   # tiny configs: a quick bring-up check

The JAX compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``.jax_cache/`` in the checkout, so a second run compiles far less.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import threading
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

#: JAX's duration events for tracing, lowering and backend compilation (the
#: last includes reading the persistent compile cache).
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
#: Per-read socket timeout of the HTTP clients; the first token waits for
#: the engine's compiles.
HTTP_TIMEOUT_S = 900.0


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@dataclasses.dataclass(frozen=True)
class Sizes:
    decoder: object  # ArchConfig
    encoder: object  # ArchConfig
    seq_len: int  # prefill length the decoder plans are lowered for
    new_tokens: int
    requests: int
    slots: int
    long_prompt: int  # paged phase prompt length (> seq_len: chunked prefill)
    kv_block_size: int
    kv_blocks: int
    encoder_batch: int

    @property
    def max_len(self) -> int:
        return self.long_prompt + self.new_tokens


def make_sizes(reduced_cfgs: bool) -> Sizes:
    from repro.configs import get_config, reduced

    dec, enc = get_config("olmo-1b"), get_config("mobilebert")
    if reduced_cfgs:
        return Sizes(reduced(dec), reduced(enc), seq_len=16, new_tokens=4,
                     requests=8, slots=4, long_prompt=24, kv_block_size=4,
                     kv_blocks=96, encoder_batch=2)
    return Sizes(dec, enc, seq_len=128, new_tokens=16, requests=8, slots=4,
                 long_prompt=160, kv_block_size=16, kv_blocks=96,
                 encoder_batch=8)


def make_prompts(vocab: int, n: int, length: int, seed: int) -> list[list[int]]:
    import numpy as np

    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, size=(n, length)).tolist()


class GreedyReference:
    """Independent greedy trajectories on the model path: ``prefill_w8a8``
    of the first ``seq_len`` prompt tokens, the rest of the prompt
    teacher-forced through ``decode_step_w8a8``, then greedy decoding.

    One batch row per request: every operation is row-local, so a row is
    the single-request trajectory.  Compiled once for all decoder phases.
    """

    def __init__(self, cfg, seq_len: int, max_len: int):
        import jax

        from repro.models import transformer as T

        self.cfg, self.seq_len = cfg, seq_len
        self._prefill = jax.jit(
            lambda qp, toks: T.prefill_w8a8(cfg, qp, {"tokens": toks}, max_len))
        self._decode = jax.jit(
            lambda qp, cache, tok: T.decode_step_w8a8(cfg, qp, cache, tok))

    def run(self, qp, prompts: list[list[int]], new_tokens: int) -> list[list[int]]:
        import numpy as np

        toks = np.asarray(prompts, np.int32)  # equal lengths: one shared depth
        logits, cache = self._prefill(qp, toks[:, : self.seq_len])
        for t in range(self.seq_len, toks.shape[1]):
            logits, cache = self._decode(qp, cache, toks[:, t : t + 1])
        out = []
        while True:
            # the engine's Greedy policy: argmax over the real vocabulary
            nxt = np.argmax(np.asarray(logits[:, -1, : self.cfg.vocab]), axis=-1)
            out.append(nxt.astype(np.int32))
            if len(out) == new_tokens:
                return np.stack(out, axis=1).tolist()
            logits, cache = self._decode(qp, cache, out[-1][:, None])


def check_streams(got: list[list[int]], reasons: list[str],
                  want: list[list[int]]) -> str:
    bad = [i for i, r in enumerate(reasons) if r not in ("eos", "length")]
    check(not bad, f"requests {bad} ended {[reasons[i] for i in bad]}")
    diff = [i for i, (g, w) in enumerate(zip(got, want)) if g != w]
    check(not diff, f"requests {diff} differ from their reference trajectory: "
          f"{[(got[i], want[i]) for i in diff[:2]]}")
    return f"{len(got)} streams bit-exact, finish {sorted(set(reasons))}"


def phase_device() -> tuple[str, dict]:
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    check(dev["platform"] == "tpu",
          f"JAX found no TPU (first device: {devs[0]}); this smoke run "
          f"measures the chip and has no CPU fallback")
    return (f"{dev['kind']} x{dev['count']}, jax {jax.__version__}, compile "
            f"cache {jax.config.jax_compilation_cache_dir}"), dev


def phase_decoder_http(sizes: Sizes, ref: GreedyReference) -> str:
    from repro.deploy import api
    from repro.deploy.serving import AsyncEngine, ServingFrontend
    from repro.launch.cli import http_generate

    cfg, n = sizes.decoder, sizes.requests
    model = api.compile(cfg, backend="w8a8", seq_len=sizes.seq_len,
                        max_len=sizes.max_len, use_cache=False)
    prompts = make_prompts(cfg.vocab, n, sizes.seq_len, seed=1)
    engine = AsyncEngine(model, sizes.slots)
    frontend = ServingFrontend(engine, port=0)
    host, port = frontend.start()
    finals: list[dict | None] = [None] * n
    errors: list[str] = []

    def client(i: int) -> None:
        try:
            events = list(http_generate(host, port, prompts[i], sizes.new_tokens,
                                        timeout=HTTP_TIMEOUT_S))
            finals[i] = events[-1]
        except Exception as e:  # noqa: BLE001 — reported as the phase's failure
            errors.append(f"request {i}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(HTTP_TIMEOUT_S)
    hung = [i for i, t in enumerate(threads) if t.is_alive()]
    frontend.shutdown(drain=not hung, timeout=60)
    check(not hung, f"requests {hung} did not finish")
    check(not errors, "; ".join(errors))
    check(all(f is not None and f.get("done") for f in finals),
          f"a stream ended without its final event: {finals}")
    want = ref.run(engine.engine.session.qp, prompts, sizes.new_tokens)
    detail = check_streams([f["tokens"] for f in finals],
                           [f["finish_reason"] for f in finals], want)
    return f"{detail}; HTTP on {sizes.slots} slots"


def phase_decoder_ita(sizes: Sizes, ref: GreedyReference) -> str:
    import jax.numpy as jnp

    from repro.deploy import api
    from repro.deploy.engine import Engine

    cfg = sizes.decoder
    model = api.compile(cfg, backend="ita", seq_len=sizes.seq_len,
                        max_len=sizes.max_len, use_cache=False)
    engine = Engine(model, sizes.slots)
    prompts = make_prompts(cfg.vocab, sizes.requests, sizes.seq_len, seed=2)
    handles = [engine.submit(p, sizes.new_tokens) for p in prompts]
    engine.run_until_idle()
    session = engine.session
    detail = check_streams([h.tokens for h in handles],
                           [h.finish_reason for h in handles],
                           ref.run(session.qp, prompts, sizes.new_tokens))
    # the session's own jitted prefill step, as the engine dispatched it
    # (batch 1 per admission): already compiled, so this costs no compile
    hlo = session._prefill_fn.lower(
        session.weights, {"tokens": jnp.zeros((1, sizes.seq_len), jnp.int32)}
    ).compile().as_text()
    check("tpu_custom_call" in hlo,
          "the compiled ita prefill step holds no tpu_custom_call: the Pallas "
          "GEMMs did not run as compiled kernels")
    return f"{detail}; prefill step holds tpu_custom_call"


def phase_decoder_paged(sizes: Sizes, ref: GreedyReference) -> str:
    from repro.deploy import api
    from repro.deploy.engine import Engine

    cfg = sizes.decoder
    model = api.compile(cfg, backend="w8a8", seq_len=sizes.seq_len,
                        max_len=sizes.max_len, kv_block_size=sizes.kv_block_size,
                        kv_blocks=sizes.kv_blocks, prefix_cache=True,
                        use_cache=False)
    engine = Engine(model, sizes.slots)
    prompts = make_prompts(cfg.vocab, sizes.requests, sizes.long_prompt, seed=3)
    # the first request after the first wave repeats request 0's prompt,
    # whose prefill the prefix index holds by then
    prompts[sizes.slots] = list(prompts[0])
    handles = [engine.submit(p, sizes.new_tokens) for p in prompts]
    stats = engine.run_until_idle()
    detail = check_streams([h.tokens for h in handles],
                           [h.finish_reason for h in handles],
                           ref.run(engine.session.qp, prompts, sizes.new_tokens))
    check(stats.prefix_hits >= 1, f"no prefix-cache hit: {stats.summary()}")
    check(stats.prefill_dispatches >= 2,
          f"prompts of {sizes.long_prompt} tokens did not prefill in chunks")
    engine.audit_sharing(strict=True)
    return (f"{detail}; {stats.prefix_hits} prefix hits "
            f"({stats.full_prefix_hits} full), {stats.prefill_dispatches} "
            f"chunked-prefill dispatches, sharing audit clean")


def encoder_layer0_kernels(cfg, q, qp, tokens):
    """Layer 0 of the encoder's integer forward, with each Pallas kernel the
    ``ita`` path calls beside the pure-XLA arithmetic it stands for, on the
    activations the model produces: the attention kernel against the XLA
    flash-ITAMax scan, the MLP GEMMs against ``qlinear``.  Returns
    ``{name: (kernel, xla)}``."""
    import jax
    import jax.numpy as jnp

    from repro.core.attention import MhaQParams, attention_flash_i8
    from repro.core.quant_linear import ACT_GELU
    from repro.kernels import int8_gemm, ita_attention
    from repro.models import encoder as EN
    from repro.models import layers as L
    from repro.models.transformer import _merge_heads, _split_heads

    lp = jax.tree.map(lambda a: a[0], qp["layers"])
    s, d, blk = tokens.shape[1], cfg.d_model, min(128, tokens.shape[1])
    site = L.QLinearSite(q.s_act, q.s_w, q.s_act)
    res = L.make_iadd_params(q.s_res, q.s_act, q.s_res)
    x_q = L.iadd_i8(qp["embed"]["table_q"][tokens], qp["pos_q"][None, :s],
                    *L.make_iadd_params(q.s_res, q.s_res, q.s_res))
    h_q = L.norm_apply_i8(cfg.norm, lp["norm1"], x_q, EN._S_GAMMA, q.s_act)
    qh, kh, vh = _split_heads(L.qlinear(lp["attn"]["wqkv"], h_q, site), cfg)
    p = MhaQParams.make_flash(q.s_act, q.s_act, q.s_act, q.s_act, cfg.head_dim)
    attn = (ita_attention(qh, kh, vh, s_q=q.s_act, s_k=q.s_act, s_v=q.s_act,
                          s_out=q.s_act, block_q=blk, block_k=blk),
            attention_flash_i8(qh, kh, vh, p, block_k=blk))
    x_q = L.iadd_i8(x_q, L.qlinear(lp["attn"]["wo"], _merge_heads(attn[1]), site), *res)
    h_q = L.norm_apply_i8(cfg.norm, lp["norm2"], x_q, EN._S_GAMMA, q.s_act).reshape(-1, d)
    up, down = lp["mlp"]["up"], lp["mlp"]["down"]
    gelu = L.QLinearSite(q.s_act, q.s_w, q.s_act, act=ACT_GELU, s_preact=q.s_act)
    kw = dict(s_in=q.s_act, s_w=q.s_w, s_out=q.s_act, block_m=128, block_n=128,
              block_k=128)
    pre = (int8_gemm(h_q, up["w_q"], up.get("b_q"), act=ACT_GELU, s_preact=q.s_act, **kw),
           L.qlinear(up, h_q, gelu))
    out = (int8_gemm(pre[1], down["w_q"], down.get("b_q"), **kw),
           L.qlinear(down, pre[1], site))
    return {"attention": attn, "mlp-up+gelu": pre, "mlp-down": out}


def phase_encoder_ita(sizes: Sizes) -> str:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.heterogeneous import PALLAS_GRANULE, OpDesc, ita_supports
    from repro.deploy import api
    from repro.models import encoder as EN
    from repro.models import layers as L

    cfg, b = sizes.encoder, sizes.encoder_batch
    model = api.compile(cfg, backend="ita", use_cache=False)
    session = model.session(b)
    plan = model.artifact
    s, q = plan.seq_len, L.QuantConfig(**plan.quant)
    x = jnp.asarray(make_prompts(cfg.vocab, b, s, seed=4), jnp.int32)
    got = np.asarray(session.forward(x))

    # the config decides where attention runs (head_dim at the accelerator's
    # granule: 64 at published widths), and the plan must agree.  On the
    # accelerator it is flash ITAMax, whose integer softmax is not the
    # rowwise one of the w8a8 model path: the model's ita branch is then
    # the reference, and the kernels are held to XLA below
    mha_on_ita = ita_supports(OpDesc("mha", ((s, cfg.head_dim),)), PALLAS_GRANULE)
    engines = {n.engine for n in plan.nodes if n.op == "MHA"}
    check(engines == {"ita" if mha_on_ita else "cluster"},
          f"the plan puts MHA on {engines}; head_dim {cfg.head_dim} says "
          f"{'ita' if mha_on_ita else 'cluster'}")
    ref_backend = "ita" if mha_on_ita else "w8a8"
    want = np.asarray(jax.jit(
        lambda qp, t: EN.forward_w8a8(cfg, qp, {"tokens": t}, q, backend=ref_backend)
    )(session.qp, x))
    check(np.array_equal(got, want), f"InferenceSession.forward [ita] differs "
          f"from forward_w8a8 [{ref_backend}] (max |diff| {np.abs(got - want).max()})")
    hlo = session._forward_fn.lower(session.weights, {"tokens": x}).compile().as_text()
    check("tpu_custom_call" in hlo, "the compiled ita encoder step holds no "
          "tpu_custom_call: the Pallas kernels did not run compiled")

    pairs = jax.jit(lambda qp, t: encoder_layer0_kernels(cfg, q, qp, t))(session.qp, x)
    bad = {name: int(np.sum(np.asarray(k) != np.asarray(r)))
           for name, (k, r) in pairs.items()}
    check(not any(bad.values()), f"kernels differ from XLA on layer 0 "
          f"(mismatched elements): {bad}")
    return (f"batch {b} x seq {s}: plan == forward_w8a8 [{ref_backend}], encoder "
            f"step holds tpu_custom_call, layer-0 attention and MLP kernels == "
            f"XLA on the model's activations")


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (any thread)."""

    def __init__(self):
        import jax

        self.total = 0.0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event in COMPILE_EVENTS:
            with self._lock:
                self.total += duration


def run_phase(name: str, clock: CompileClock, fn, *args):
    """Run one phase; print its outcome, wall time and compile time."""
    c0, t0 = clock.total, time.perf_counter()
    try:
        result = fn(*args)
        ok = True
    except Exception as e:  # noqa: BLE001 — every phase reports; main() exits 1
        traceback.print_exc()
        result, ok = f"{type(e).__name__}: {e}", False
    detail = result[0] if isinstance(result, tuple) else result
    print(f"[{name}] {'ok' if ok else 'FAILED'} wall {time.perf_counter() - t0:.1f}s "
          f"compile {clock.total - c0:.1f}s: {detail}", flush=True)
    gc.collect()
    return ok, result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family configs instead of published widths")
    args = ap.parse_args(argv)

    from repro.launch.cli import enable_compile_cache

    enable_compile_cache()
    clock = CompileClock()
    t_start = time.perf_counter()
    ok, result = run_phase("device", clock, phase_device)
    if not ok:
        return 1
    device = result[1]
    sizes = make_sizes(args.reduced)
    ref = GreedyReference(sizes.decoder, sizes.seq_len, sizes.max_len)
    phases = [
        ("decoder-http", phase_decoder_http, sizes, ref),
        ("decoder-ita", phase_decoder_ita, sizes, ref),
        ("decoder-paged", phase_decoder_paged, sizes, ref),
        ("encoder-ita", phase_encoder_ita, sizes),
    ]
    failed = [name for name, fn, *a in phases if not run_phase(name, clock, fn, *a)[0]]
    print(f"[total] wall {time.perf_counter() - t_start:.1f}s compile "
          f"{clock.total:.1f}s; failed: {failed or 'none'}", flush=True)
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
