"""Roofline analysis over dry-run artifacts (§Roofline deliverable).

Per (arch × shape), from the single-pod compiled dry-run:

  compute    = HLO_FLOPs / (chips · 197e12 FLOP/s)          [bf16 MXU]
  memory     = HLO_bytes / (chips · 819e9 B/s)              [HBM]
  collective = collective_bytes / (chips · 4 · 50e9 B/s)    [ICI, 4 links]

MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE) per training step
(3·N·D fwd-only for prefill; 2·N_active per token for decode), and the
useful-compute ratio MODEL_FLOPS / HLO_FLOPs flags remat/redundancy waste.

    PYTHONPATH=src python -m repro.launch.roofline [--dir artifacts/dryrun]
"""
from __future__ import annotations

import argparse
import glob
import json
import os

from repro.configs import get_config
from repro.configs.base import SHAPES

CHIPS = 256              # single-pod roofline (16×16)
PEAK_FLOPS = 197e12      # TPU v5e bf16
HBM_BW = 819e9
ICI_BW_LINK = 50e9
ICI_LINKS = 4            # links/chip on a 2-D torus axis pair


def model_flops(arch: str, shape: str) -> float:
    cfg = get_config(arch)
    sc = SHAPES[shape]
    n_act = cfg.n_active_params()
    tokens = sc.global_batch * sc.seq_len
    if sc.kind == "train":
        return 6.0 * n_act * tokens
    if sc.kind == "prefill":
        return 2.0 * n_act * tokens  # fwd only
    # decode: one token per sequence + attention over the cache
    flops = 2.0 * n_act * sc.global_batch
    if cfg.family not in ("ssm",):
        hd = cfg.hd
        S = min(sc.seq_len, cfg.window) if cfg.window else sc.seq_len
        flops += (4.0 * cfg.n_heads * hd * S * cfg.n_layers
                  * sc.global_batch)
    return flops


def loop_scale(arch: str, shape: str) -> float:
    """XLA cost_analysis counts while-loop (scan-over-layers) bodies ONCE.
    Reconstruct full-step totals via the analytic ratio

        scale = model_flops(all L layers) / model_flops(one layer + out)

    where `out` (embedding/logits/optimizer) is outside the loop.  The
    measured HLO value then carries the real remat/redundancy overhead and
    the analytic ratio carries the trip count."""
    cfg = get_config(arch)
    sc = SHAPES[shape]
    tokens = sc.global_batch * sc.seq_len
    k = 6.0 if sc.kind == "train" else 2.0
    t_eff = tokens if sc.kind != "decode" else sc.global_batch
    emb = cfg.padded_vocab * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    layer_par = max(cfg.n_active_params() - emb, 1)
    L = cfg.n_layers + cfg.n_enc_layers
    # logits are computed on every token in training but only the last
    # position for prefill / the single new token for decode
    t_logits = tokens if sc.kind == "train" else sc.global_batch
    out_flops = k * emb * t_logits
    full = k * layer_par * t_eff + out_flops
    once = k * (layer_par / max(L, 1)) * t_eff + out_flops
    return full / max(once, 1.0)


def analyze(rec: dict) -> dict:
    """cost_analysis() on SPMD modules is PER-DEVICE with loop bodies
    counted once; scale by the analytic trip-count ratio (see loop_scale)
    to get full-step per-device totals."""
    arch, shape = rec["arch"], rec["shape"]
    scale = loop_scale(arch, shape)
    flops_dev = rec["flops"] * scale
    bytes_dev = rec["bytes_accessed"] * scale
    coll_dev = rec["collective_bytes"]["total"] * scale
    t_comp = flops_dev / PEAK_FLOPS
    t_mem = bytes_dev / HBM_BW
    t_coll = coll_dev / (ICI_LINKS * ICI_BW_LINK)
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    dom = max(terms, key=terms.get)
    mf = model_flops(arch, shape)
    useful = (mf / CHIPS) / max(flops_dev, 1.0)
    bound = max(terms.values())
    return {
        "arch": arch, "shape": shape, "loop_scale": scale,
        "t_compute_s": t_comp, "t_memory_s": t_mem, "t_collective_s": t_coll,
        "dominant": dom,
        "model_flops": mf,
        "useful_ratio": useful,
        "roofline_fraction": t_comp / max(bound, 1e-30),
        "per_device_bytes": (rec["memory"]["argument_size_bytes"]
                             + rec["memory"]["temp_size_bytes"]),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="artifacts/dryrun")
    ap.add_argument("--mesh", default="16x16")
    ap.add_argument("--out", default="artifacts/roofline.json")
    args = ap.parse_args()

    rows = []
    for path in sorted(glob.glob(os.path.join(args.dir, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        if rec.get("mesh") != args.mesh:
            continue
        if rec.get("status") != "ok":
            rows.append({"arch": rec["arch"], "shape": rec["shape"],
                         "status": rec.get("status")})
            continue
        rows.append(analyze(rec))

    hdr = (f"{'arch':<22s}{'shape':<13s}{'compute(s)':>11s}{'memory(s)':>11s}"
           f"{'coll(s)':>10s} {'dominant':<11s}{'useful':>7s}{'roofl%':>7s}")
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        if "dominant" not in r:
            print(f"{r['arch']:<22s}{r['shape']:<13s}  {r['status']}")
            continue
        print(f"{r['arch']:<22s}{r['shape']:<13s}"
              f"{r['t_compute_s']:>11.3e}{r['t_memory_s']:>11.3e}"
              f"{r['t_collective_s']:>10.2e} {r['dominant']:<11s}"
              f"{r['useful_ratio']:>7.2f}{r['roofline_fraction']*100:>6.0f}%")
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"\nwritten {args.out}")


if __name__ == "__main__":
    main()
