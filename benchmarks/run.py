"""Benchmark entry point — one section per paper table/figure.

  micro_gemm_attention  paper §V-A microbenchmarks (+ functional Pallas
                        kernel timings, interpret mode)
  table1_e2e            paper Table I (E2E networks, Multi-Core vs +ITA)
  comparison_sota       paper §V-C commercial-device comparison
  roofline              §Roofline terms from the dry-run artifacts
  decode_latency        per-step decode latency, dense vs paged KV
  engine_throughput     request-level serving engine: continuous
                        batching vs serial on the compiled artifact
                        (``--open-loop`` adds Poisson arrivals +
                        goodput-under-SLO, fifo vs priority-deadline)
  serving_frontend      async serving stack overhead: engine-direct vs
                        streaming JSON-lines HTTP over loopback
  long_context          paged KV block pool + chunked prefill vs the
                        dense per-slot region at 4-16x seq_len prompts

Prints ``name,us_per_call,derived``-style CSV per section.
"""

from __future__ import annotations

import time


def _section(title: str) -> None:
    print(f"\n##### {title} " + "#" * max(1, 60 - len(title)), flush=True)


def main() -> None:
    t0 = time.time()

    _section("micro_gemm_attention (paper §V-A)")
    from benchmarks import micro_gemm_attention

    micro_gemm_attention.main()

    _section("table1_e2e (paper Table I)")
    from benchmarks import table1_e2e

    table1_e2e.main()

    _section("comparison_sota (paper §V-C)")
    from benchmarks import comparison_sota

    comparison_sota.main()

    _section("roofline (dry-run artifacts)")
    from benchmarks import roofline

    roofline.main([])

    _section("decode_latency (dense vs paged decode step)")
    from benchmarks import decode_latency

    decode_latency.main(["--smoke"])

    _section("engine_throughput (continuous batching vs serial)")
    from benchmarks import engine_throughput

    engine_throughput.main(["--batch", "2", "--requests", "4",
                            "--prompt-len", "8", "--gen", "4"])

    _section("serving_frontend (async stack overhead over loopback)")
    from benchmarks import serving_frontend

    serving_frontend.main(["--batch", "2", "--requests", "4", "--clients", "2",
                           "--prompt-len", "8", "--gen", "4"])

    _section("long_context (paged KV pool vs dense region)")
    from benchmarks import long_context

    long_context.main(["--smoke"])

    print(f"\n# benchmarks completed in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
