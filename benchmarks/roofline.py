"""Roofline analysis from the dry-run artifacts (§Roofline deliverable).

Per (arch x shape) cell on the single-pod mesh, derive:

  compute term    = HLO_FLOPs / (chips * peak bf16 FLOP/s)
  memory term     = HLO_bytes / (chips * peak HBM B/s)
  collective term = collective_bytes / (chips * ICI B/s per link)

with the peaks of the dry-run's target chip read from ``CHIP_PEAKS``.

``cost_analysis`` counts lax.scan bodies once, so raw numbers from the full
compile undercount by the trip count. The dry-run therefore lowers reduced
(microbatch x layer) variants and we solve the affine cost model

  train:   f(M, L) = A + M*(B + L*C)      (M grad-accum microbatches,
                                           L scan'd layer periods)
  serve:   f(L)    = A + L*C

from {(2,1),(2,2),(4,1)} / {1,2} and extrapolate to the full configuration.
The same extrapolation applies to the per-type collective bytes parsed from
the post-SPMD HLO.

Also reported: MODEL_FLOPS (6*N_active*D for training, 2*N_active*D for
inference) and the MODEL/HLO ratio (how much compiled compute is useful),
plus the dominant term and what would move it.
"""
from __future__ import annotations

import glob
import json
import math
import os
from typing import Dict, List, Optional

# Published peak rates of one chip, keyed by ``jax.Device.device_kind``.
# TPU v5e: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
# 819 GB/s HBM, 1,600 Gbit/s of chip-to-chip interconnect over 4 ICI links
# (50 GB/s per link).
CHIP_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bw": 819e9,
                    "ici_link_bw": 50e9},
}

# the chip the dry-run meshes model (launch/mesh.py: v5e pods)
TARGET_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> Dict[str, float]:
    """Peak rates of ``device_kind``; a chip missing from the table is an
    error, never a default."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add a sourced row to CHIP_PEAKS") from None

COLL_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
              "collective-permute")


# ---------------------------------------------------------------------------
# Analytic model FLOPs
# ---------------------------------------------------------------------------

def active_params(cfg) -> int:
    """Parameters touched per token (MoE: only routed top-k experts)."""
    from repro.models import model_defs, param_count
    from repro.models.moe import padded_experts
    total = param_count(model_defs(cfg))
    if cfg.moe is None:
        return total
    moe = cfg.moe
    n_moe_layers = sum(1 for s in cfg.period if s.ffn == "moe") * cfg.n_periods
    n_moe_layers += sum(1 for s in cfg.prelayers if s.ffn == "moe")
    per_expert = 3 * cfg.d_model * moe.d_ff_expert
    routed = n_moe_layers * padded_experts(moe) * per_expert
    active_routed = n_moe_layers * moe.top_k * per_expert
    return total - routed + active_routed


def nonembedding_params(cfg) -> int:
    from repro.models import model_defs, param_count
    total = param_count(model_defs(cfg))
    emb = cfg.vocab_size * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    return total - emb


def model_flops(cfg, shape) -> float:
    """6*N_active*tokens (train) / 2*N_active*tokens (prefill) /
    2*N_active*batch per decode step. Unembedding counted once."""
    n_act = active_params(cfg) - cfg.vocab_size * cfg.d_model * (
        1 if cfg.tie_embeddings else 2)
    unemb = 2 * cfg.vocab_size * cfg.d_model
    if shape.kind == "train":
        return (6 * n_act + 3 * unemb) * shape.tokens
    if shape.kind == "prefill":
        return 2 * n_act * shape.tokens + unemb * shape.global_batch
    return (2 * n_act + unemb) * shape.global_batch


# ---------------------------------------------------------------------------
# Extrapolation
# ---------------------------------------------------------------------------

def _coll_bytes(rec: Dict, key: str = "bytes") -> float:
    return sum(v.get(key, 0.0) for v in rec.get("collectives", {}).values())


def _metric(rec: Dict, metric: str) -> float:
    if metric == "flops":
        return rec["flops"]
    if metric == "bytes":
        return rec["bytes_accessed"]
    if metric == "coll":
        return _coll_bytes(rec)
    if metric.startswith("coll:"):
        k = metric.split(":", 1)[1]
        return rec.get("collectives", {}).get(k, {}).get("bytes", 0.0)
    raise KeyError(metric)


def extrapolate(cell: Dict, metric: str) -> Optional[float]:
    """Corrected full-model value of ``metric`` from the UNROLLED variant
    lowers (f(1,1), f(1,2), f(2,1) for training; f(1), f(2) for serving)."""
    vm = cell.get("variant_model")
    vs = cell.get("variants")
    if not vm or not vs:
        return None
    if vm["kind"] == "train":
        if "m1_l0" in vs:                 # scheme B: zero-period lowers
            f10 = _metric(vs["m1_l0"], metric)
            f11 = _metric(vs["m1_l1"], metric)
            f20 = _metric(vs["m2_l0"], metric)
            C = f11 - f10
            B = f20 - f10
            A = f10 - B
        elif "m1_l1" in vs:               # scheme A
            f11 = _metric(vs["m1_l1"], metric)
            f12 = _metric(vs["m1_l2"], metric)
            f21 = _metric(vs["m2_l1"], metric)
            C = f12 - f11
            B = f21 - f11 - C
            A = f11 - B - C
        else:
            return None
        M, L = vm["m_full"], vm["l_full"]
        return max(A + M * (B + L * C), 0.0)
    if "l0" in vs:
        f0 = _metric(vs["l0"], metric)
        f1 = _metric(vs["l1"], metric)
        C = f1 - f0
        A = f0
    else:
        f1 = _metric(vs["l1"], metric)
        f2 = _metric(vs["l2"], metric)
        C = f2 - f1
        A = f1 - C
    return max(A + vm["l_full"] * C, 0.0)


# ---------------------------------------------------------------------------
# Report
# ---------------------------------------------------------------------------

def analyse_cell(cell: Dict, chips: int = 256) -> Optional[Dict]:
    if cell.get("status") != "ok":
        return None
    from repro.configs import get_config
    from repro.configs.base import SHAPES
    cfg = get_config(cell["arch"])
    shape = SHAPES[cell["shape"]]

    flops = extrapolate(cell, "flops") or cell["full"]["flops"]
    hbytes = extrapolate(cell, "bytes") or cell["full"]["bytes_accessed"]
    cbytes = extrapolate(cell, "coll")
    if cbytes is None:
        cbytes = _coll_bytes(cell["full"])
    corrected = cell.get("variants") is not None

    # cost_analysis reports PER-DEVICE numbers on the post-SPMD module
    # (verified: sharded fwd == global/nshards), so the per-chip roofline
    # terms divide only by per-chip peak rates:
    peak = chip_peaks(TARGET_KIND)
    t_compute = flops / peak["bf16_flops"]
    t_memory = hbytes / peak["hbm_bw"]
    t_coll = cbytes / peak["ici_link_bw"]
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    mf = model_flops(cfg, shape)                  # global useful FLOPs
    mf_chip = mf / chips                          # per-chip useful FLOPs
    ideal = mf_chip / peak["bf16_flops"]
    roofline_frac = ideal / bound if bound > 0 else 0.0

    suggestions = {
        "compute": "cut non-useful FLOPs (remat recompute, causal-masked "
                   "tiles, padded experts) or raise arithmetic intensity",
        "memory": "reduce HBM traffic: fuse norms/elementwise (Pallas), "
                  "bf16 optimizer moments, sequence-sharded saved carries",
        "collective": "re-shard to cut all-gather/all-to-all volume or "
                      "overlap collectives behind the MXU (async schedule)",
    }
    mem = cell["full"].get("memory", {})
    return {
        "arch": cell["arch"], "shape": cell["shape"], "mesh": cell["mesh"],
        "chips": chips,
        "hlo_flops_per_chip": flops, "hlo_bytes_per_chip": hbytes,
        "collective_bytes_per_chip": cbytes,
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "model_flops": mf,
        "useful_ratio": mf_chip / flops if flops else 0.0,
        "roofline_fraction": roofline_frac,
        "corrected": corrected,
        "hbm_per_chip_gib": (mem.get("argument_bytes", 0)
                             + mem.get("temp_bytes", 0)) / 2**30,
        "note": suggestions[dominant],
    }


def run(art_dir: str = "artifacts/dryrun",
        out_dir: str = "artifacts/roofline") -> List[Dict]:
    os.makedirs(out_dir, exist_ok=True)
    rows = []
    for path in sorted(glob.glob(os.path.join(art_dir, "*__single.json"))):
        with open(path) as f:
            cell = json.load(f)
        r = analyse_cell(cell, chips=256)
        if r:
            rows.append(r)
    rows.sort(key=lambda r: (r["shape"], r["arch"]))
    # CSV
    if rows:
        keys = list(rows[0].keys())
        with open(os.path.join(out_dir, "roofline.csv"), "w") as f:
            f.write(",".join(keys) + "\n")
            for r in rows:
                f.write(",".join(str(r[k]) for k in keys) + "\n")
        with open(os.path.join(out_dir, "roofline.md"), "w") as f:
            f.write("| arch | shape | compute s | memory s | collective s | "
                    "dominant | MODEL/HLO | roofline frac | HBM GiB/chip |\n")
            f.write("|---|---|---|---|---|---|---|---|---|\n")
            for r in rows:
                f.write(f"| {r['arch']} | {r['shape']} "
                        f"| {r['t_compute_s']:.3e} | {r['t_memory_s']:.3e} "
                        f"| {r['t_collective_s']:.3e} | {r['dominant']} "
                        f"| {r['useful_ratio']:.2f} "
                        f"| {r['roofline_fraction']:.2f} "
                        f"| {r['hbm_per_chip_gib']:.1f} |\n")
    with open(os.path.join(out_dir, "roofline.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return rows


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--art-dir", default="artifacts/dryrun")
    ap.add_argument("--out-dir", default="artifacts/roofline")
    args = ap.parse_args()
    rows = run(args.art_dir, args.out_dir)
    for r in rows:
        print(f"{r['arch']:24s} {r['shape']:12s} dom={r['dominant']:10s} "
              f"comp={r['t_compute_s']:.2e}s mem={r['t_memory_s']:.2e}s "
              f"coll={r['t_collective_s']:.2e}s useful={r['useful_ratio']:.2f} "
              f"roofline={r['roofline_fraction']:.2f}")


if __name__ == "__main__":
    main()
