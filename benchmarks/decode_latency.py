"""Per-step decode latency: the dense KV region vs the paged one.

The decode hot path executes one static plan per generated token, jitted
whole as the session's ``decode_step``.  This benchmark times that step
on the dense per-slot KV region and on the paged block pool, same
weights and same token trace, and reports per-step wall latency (p50 /
mean over ``--steps`` timed steps after warmup) for each.

Run:  PYTHONPATH=src python benchmarks/decode_latency.py
      PYTHONPATH=src python benchmarks/decode_latency.py --smoke \
          --csv out.csv --json BENCH_decode_latency.json
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.configs import get_config, reduced

CSV_HEADER = "mode,us_per_step_p50,us_per_step_mean,steps"


def _percentile(xs, pct: float) -> float:
    xs = sorted(xs)
    rank = max(1, -(-int(pct * len(xs)) // 100))
    return xs[rank - 1]


def measure_variant(cfg, *, backend, mode, batch, seq, max_len,
                    kv_block_size, kv_blocks, steps, warmup=2):
    import jax
    import jax.numpy as jnp

    from repro.deploy import api

    kw = dict(backend=backend, seq_len=seq, max_len=max_len, use_cache=False)
    if mode == "paged":
        kw.update(kv_block_size=kv_block_size, kv_blocks=kv_blocks)
    model = api.compile(cfg, **kw)
    session = model.session(batch)
    key = jax.random.PRNGKey(0)
    for b in range(batch):
        prompt = jax.random.randint(jax.random.fold_in(key, b), (1, seq),
                                    0, cfg.vocab, jnp.int32)
        session.prefill_slot(b, prompt)
    tokens = jnp.zeros((batch,), jnp.int32)
    active = np.ones((batch,), bool)
    times = []
    for i in range(warmup + steps):
        pos = np.full((batch,), seq + i, np.int32)
        t0 = time.perf_counter()
        if mode == "paged":
            logits = session.decode(tokens, pos, active=active)
        else:
            logits = session.decode(tokens, pos)
        jax.block_until_ready(logits)
        dt = time.perf_counter() - t0
        if i >= warmup:
            times.append(dt)
        tokens = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    return {
        "mode": mode,
        "us_per_step_p50": _percentile(times, 50) * 1e6,
        "us_per_step_mean": sum(times) / len(times) * 1e6,
        "steps": len(times),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--backend", default="w8a8")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=8)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--kv-block-size", type=int, default=4)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fixed shape for CI (reduced config, few steps)")
    ap.add_argument("--csv", default=None, metavar="FILE",
                    help="also write the CSV rows to FILE")
    ap.add_argument("--json", default=None, metavar="FILE",
                    help="write the rows as BENCH_decode_latency.json")
    args = ap.parse_args(argv)
    if args.smoke:
        args.steps = 6

    cfg = reduced(get_config(args.arch))
    seq = args.seq_len
    max_len = seq + args.steps + 4
    from repro.deploy.paging import blocks_for_rows

    kv_blocks = args.batch * blocks_for_rows(max_len, args.kv_block_size) + 1

    rows = [
        measure_variant(
            cfg, backend=args.backend, mode=mode,
            batch=args.batch, seq=seq, max_len=max_len,
            kv_block_size=args.kv_block_size, kv_blocks=kv_blocks,
            steps=args.steps,
        )
        for mode in ("dense", "paged")
    ]

    print(CSV_HEADER)
    lines = [CSV_HEADER]
    for r in rows:
        line = (f"{r['mode']},{r['us_per_step_p50']:.1f},"
                f"{r['us_per_step_mean']:.1f},{r['steps']}")
        print(line)
        lines.append(line)

    if args.csv:
        with open(args.csv, "w") as f:
            f.write("\n".join(lines) + "\n")
        print(f"# csv written to {args.csv}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=2)
        print(f"# json written to {args.json}")
    return rows


if __name__ == "__main__":
    main()
