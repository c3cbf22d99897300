"""§Roofline: three-term analysis per (arch x shape) from dry-run artifacts.

Reads experiments/dryrun/*.json (written by repro.launch.dryrun), computes

  compute term    = HLO_FLOPs_per_device / peak_FLOPs_per_chip
  memory term     = HLO_bytes_per_device / HBM_bw
  collective term = collective_bytes_per_device / ICI link bw

(the dry-run analyzer reports per-device values from the partitioned HLO,
so the chip count cancels), plus MODEL_FLOPS / HLO_FLOPs (useful-compute
ratio: catches remat and dispatch redundancy).  Emits CSV + a markdown
table for EXPERIMENTS.md.

Hardware corners come from :mod:`repro.deploy.costmodel` (``HwTarget``) —
ONE source of truth shared with the calibrated analytical model, so
``table1_e2e`` predicted-vs-measured and this roofline can never use
drifting constants.  ``--hw tpu`` (default) is the TPU v5e corner
(197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI); ``--hw ita`` is the
Snitch+ITA corner derived from the calibrated HwConfig (870.4 GOp/s
int8, DMA-sustained L2 bandwidth, no interconnect).
"""

from __future__ import annotations

import argparse
import glob
import json
import os

from repro.configs import ALL_SHAPES, get_config
from repro.deploy.costmodel import TPU_V5E, HwTarget, hw_target

# module-level back-compat aliases (the TPU corner); prefer hw_target()
PEAK_FLOPS = TPU_V5E.peak_flops
HBM_BW = TPU_V5E.hbm_bw
ICI_BW = TPU_V5E.ici_bw
CHIPS = 256  # single-pod roofline table


def param_count(cfg) -> tuple[float, float]:
    """(total params, active params) — analytic, from the config."""
    e = cfg.d_model
    if cfg.family == "encdec":
        attn = (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.head_dim * e + cfg.n_heads * cfg.head_dim * e
        cross = attn  # wq + wkv + wo ~ same order
        mlp = 2 * e * cfg.d_ff
        n = cfg.enc_layers * (attn + mlp) + cfg.dec_layers * (attn + cross + mlp)
        n += 2 * cfg.vocab_padded * e
        return n, n
    if cfg.family == "ssm":
        d_inner = cfg.ssm_expand * e
        nh = d_inner // cfg.ssm_head_dim
        per = e * (2 * d_inner + 2 * cfg.ssm_state + nh) + d_inner * e
        n = cfg.n_layers * per + 2 * cfg.vocab_padded * e
        return n, n
    attn = (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.head_dim * e + cfg.n_heads * cfg.head_dim * e
    if cfg.family == "hybrid":
        d_inner = cfg.ssm_expand * e
        nh = d_inner // cfg.ssm_head_dim
        per = e * (2 * d_inner + 2 * cfg.ssm_state + nh) + d_inner * e
        n = cfg.n_layers * per + (attn + 2 * e * cfg.d_ff) + 2 * cfg.vocab_padded * e
        return n, n
    if cfg.n_experts:
        expert = 3 * e * cfg.d_ff_expert
        moe_total = cfg.n_experts * expert + 3 * e * cfg.shared_width
        moe_active = cfg.top_k * expert + 3 * e * cfg.shared_width
        per_shared = attn
        total = cfg.n_layers * (per_shared + moe_total) + 2 * cfg.vocab_padded * e
        active = cfg.n_layers * (per_shared + moe_active) + 2 * cfg.vocab_padded * e
        return total, active
    mlp = (3 if cfg.mlp == "swiglu" else 2) * e * cfg.d_ff
    n = cfg.n_layers * (attn + mlp) + (1 if cfg.tie_embeddings else 2) * cfg.vocab_padded * e
    return n, n


def model_flops(cfg, cell) -> float:
    """Reference useful FLOPs per device (6ND train / 2ND inference)."""
    total, active = param_count(cfg)
    if cell.kind == "train":
        tokens = cell.global_batch * cell.seq_len
        return 6.0 * active * tokens / CHIPS
    if cell.kind == "prefill":
        tokens = cell.global_batch * cell.seq_len
        return 2.0 * active * tokens / CHIPS
    # decode: one token per sequence
    return 2.0 * active * cell.global_batch / CHIPS


def load_records(dry_dir: str = "experiments/dryrun", mesh: str = "16x16") -> list[dict]:
    recs = []
    for path in sorted(glob.glob(os.path.join(dry_dir, f"*__{mesh}.json"))):
        with open(path) as f:
            recs.append(json.load(f))
    return recs


def roofline_row(rec: dict, hw: HwTarget = TPU_V5E) -> dict | None:
    if rec.get("status") != "ok":
        return None
    cfg = get_config(rec["arch"])
    cell = next(c for c in ALL_SHAPES if c.name == rec["shape"])
    t_comp = rec["flops"] / hw.peak_flops
    t_mem = rec["mem_bytes"] / hw.hbm_bw
    # a single-device target (ici_bw == 0) has no collective term
    coll_bytes = rec["collectives"]["total_bytes"]
    t_coll = coll_bytes / hw.ici_bw if hw.ici_bw else 0.0
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    dom = max(terms, key=terms.get)
    mf = model_flops(cfg, cell)
    return {
        "arch": rec["arch"],
        "shape": rec["shape"],
        "kind": rec.get("kind", ""),
        "hw": hw.name,
        "t_compute_s": t_comp,
        "t_memory_s": t_mem,
        "t_collective_s": t_coll,
        "bottleneck": dom,
        "model_flops_per_dev": mf,
        "useful_ratio": mf / rec["flops"] if rec["flops"] else 0.0,
        "roofline_fraction": t_comp / max(max(terms.values()), 1e-30),
    }


def summarize(dry_dir: str = "experiments/dryrun", mesh: str = "16x16",
              hw: HwTarget = TPU_V5E):
    rows = []
    for rec in load_records(dry_dir, mesh):
        r = roofline_row(rec, hw)
        if r:
            rows.append(r)
    return rows


def to_markdown(rows) -> str:
    hdr = (
        "| arch | shape | compute s | memory s | collective s | bottleneck "
        "| useful ratio | roofline frac |\n|---|---|---|---|---|---|---|---|\n"
    )
    lines = []
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"])):
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['t_compute_s']:.3e} | "
            f"{r['t_memory_s']:.3e} | {r['t_collective_s']:.3e} | {r['bottleneck']} | "
            f"{r['useful_ratio']:.2f} | {r['roofline_fraction']:.2f} |"
        )
    return hdr + "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--hw", choices=("tpu", "ita"), default="tpu",
                    help="roofline corner (from repro.deploy.costmodel)")
    args = ap.parse_args(argv)
    hw = hw_target(args.hw)
    rows = summarize(hw=hw)
    if not rows:
        print("no dry-run records found — run repro.launch.dryrun first")
        return []
    print("arch,shape,hw,t_compute,t_memory,t_collective,bottleneck,useful_ratio,roofline_frac")
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"])):
        print(
            f"{r['arch']},{r['shape']},{r['hw']},{r['t_compute_s']:.4e},{r['t_memory_s']:.4e},"
            f"{r['t_collective_s']:.4e},{r['bottleneck']},{r['useful_ratio']:.3f},"
            f"{r['roofline_fraction']:.3f}"
        )
    os.makedirs("experiments", exist_ok=True)
    suffix = "" if hw.name == "tpu" else f"_{hw.name}"
    with open(f"experiments/roofline{suffix}.md", "w") as f:
        f.write(to_markdown(rows) + "\n")
    return rows


if __name__ == "__main__":
    main()
