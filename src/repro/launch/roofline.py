"""Roofline analysis over the dry-run artifacts.

Per (arch x shape x mesh) cell (constants: v5e):

    compute_s    = FLOPs_per_chip / 197e12
    memory_s     = HLO_bytes_per_chip / 819e9
    collective_s = wire_bytes_per_chip / 50e9       (1 ICI link budget)

FLOPs/bytes come from the probe-extrapolated cost analysis (scan bodies
counted exactly L times — see hlo_analysis.py); wire bytes from the HLO
collective parse with ring-algorithm per-chip traffic factors.

``MODEL_FLOPS`` is the useful-work floor: 6·N_active·tokens for training,
2·N_active·tokens for inference; the ratio against compiled FLOPs x chips
flags remat/dispatch waste.  The dominant term is the bottleneck §Perf
iterates on.

Usage:  python -m repro.launch.roofline [--write-md]
"""
from __future__ import annotations

import argparse
import glob
import json
import os

from repro import configs
from repro.models.common import SHAPES

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.  Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM).
DEVICE_PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}

# The dry-run analysis below projects onto v5e pods.
PEAK_FLOPS = DEVICE_PEAKS["TPU v5 lite"]["flops_per_s"]
HBM_BW = DEVICE_PEAKS["TPU v5 lite"]["hbm_bytes_per_s"]
LINK_BW = 50e9

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                          "experiments", "dryrun")


# ---------------------------------------------------------------------------
# Analytic post-fusion HBM model.
#
# XLA's ``bytes accessed`` sums every HLO op's operand+result bytes with no
# fusion model (on the CPU backend), so elementwise chains that a TPU would
# fuse into one VMEM-resident pass are each charged a full HBM round trip —
# a 5-20x overestimate.  The analytic model below charges only the traffic
# that MUST cross HBM on a TPU: parameter reads (per microbatch pass),
# gradient/optimizer state traffic, scan-carry activations (written fwd,
# read bwd under full remat), and KV-cache reads.  Both numbers are
# reported; the bottleneck decision uses the analytic one.
# ---------------------------------------------------------------------------

class _MeshLike:
    def __init__(self, multi_pod: bool):
        self.axis_names = (("pod", "data", "model") if multi_pod
                           else ("data", "model"))
        self.shape = ({"pod": 2, "data": 16, "model": 16} if multi_pod
                      else {"data": 16, "model": 16})


def analytic_hbm_bytes(rec: dict) -> float:
    import dataclasses

    import jax.numpy as jnp

    from repro.launch.sharding import auto_rules
    from repro.models.api import model_defs
    from repro.models.common import input_specs
    from repro.models.params import sharded_size_bytes, tree_map_defs

    cfg = configs.get(rec["arch"])
    sc = SHAPES[rec["shape"]]
    pol = rec["policy"]
    multi = rec["mesh"] == "pod2x16x16"
    mesh = _MeshLike(multi)
    rules = auto_rules(cfg, mesh, zero_stage=int(pol["zero_stage"]))
    pdt = jnp.dtype(pol["param_dtype"])
    defs = tree_map_defs(
        lambda d: dataclasses.replace(
            d, dtype=pdt if jnp.issubdtype(d.dtype, jnp.floating)
            else d.dtype), model_defs(cfg))
    p_chip = sharded_size_bytes(defs, rules, mesh.shape)

    data = mesh.shape["data"] * mesh.shape.get("pod", 1)
    b_loc = max(sc.batch // data, 1)
    micro = int(pol["microbatches"])
    layers = cfg.n_layers + cfg.n_encoder_layers

    # Per-chip batch/cache bytes (input specs sharded over batch axes and,
    # for caches, kv-heads over model when divisible).
    kv_seq = pol.get("kv_seq_shard") in (True, "True")
    cache_chip = 0.0
    for k, s in input_specs(cfg, rec["shape"]).items():
        n = 1
        for d in s.shape:
            n *= d
        bytes_ = n * jnp.dtype(s.dtype).itemsize
        if s.shape and s.shape[0] == sc.batch:
            bytes_ /= data
        elif len(s.shape) > 1 and s.shape[1] == sc.batch:   # [L, B, ...]
            bytes_ /= data
            if len(s.shape) > 3 and s.shape[3] == cfg.n_kv_heads and \
                    cfg.n_kv_heads % 16 == 0:
                bytes_ /= 16
            elif kv_seq and k in ("k_cache", "v_cache") and \
                    s.shape[2] % 16 == 0:   # window sharded over "model"
                bytes_ /= 16
        cache_chip += bytes_

    if sc.kind == "train":
        mdt = jnp.dtype(pol["moment_dtype"]).itemsize
        o_base = p_chip
        if int(pol["zero_stage"]) == 1:      # moments sharded over data
            o_base = sharded_size_bytes(
                defs, auto_rules(cfg, mesh, zero_stage=3), mesh.shape)
        o_chip = 2 * o_base / jnp.dtype(pdt).itemsize * mdt
        carry = layers * (b_loc / micro) * sc.seq * cfg.d_model * 2.0
        return (3.0 * micro * p_chip          # fwd+bwd+remat weight reads
                + 2.0 * micro * p_chip        # grad accum write+read (fp32)
                + 2.0 * (p_chip + o_chip)     # optimizer read+write
                + 2.0 * micro * carry         # scan carries (fwd w, bwd r)
                + cache_chip)
    if sc.kind == "prefill":
        act = layers * b_loc * sc.seq * cfg.d_model * 2.0
        return p_chip + act + cache_chip      # weights + stream + kv write
    # decode: weights once + cache read/write
    return p_chip + 2.0 * cache_chip


def model_flops(arch: str, shape: str) -> float:
    cfg = configs.get(arch)
    sc = SHAPES[shape]
    n = cfg.active_param_count()
    if sc.kind == "train":
        return 6.0 * n * sc.batch * sc.seq
    tokens = sc.batch * (sc.seq if sc.kind == "prefill" else 1)
    return 2.0 * n * tokens


def analyze(rec: dict) -> dict | None:
    if rec.get("status") != "ok" or "flops" not in rec:
        return None
    chips = 512 if rec["mesh"] == "pod2x16x16" else 256
    compute_s = rec["flops"] / PEAK_FLOPS
    memory_hlo_s = rec["bytes"] / HBM_BW
    memory_s = analytic_hbm_bytes(rec) / HBM_BW
    coll_s = rec["wire_bytes"] / LINK_BW
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": coll_s}
    dominant = max(terms, key=terms.get)
    bound_s = max(terms.values())
    mf = model_flops(rec["arch"], rec["shape"])
    useful = mf / max(rec["flops"] * chips, 1.0)
    # Roofline fraction: useful-model-work time at peak vs. bound time.
    ideal_s = mf / chips / PEAK_FLOPS
    frac = ideal_s / max(bound_s, 1e-30)
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "tag": rec.get("tag", "baseline"),
        "compute_s": compute_s, "memory_s": memory_s,
        "memory_hlo_s": memory_hlo_s,
        "collective_s": coll_s, "dominant": dominant,
        "step_s_bound": bound_s,
        "model_flops": mf, "hlo_flops_chip": rec["flops"],
        "useful_ratio": useful, "roofline_frac": frac,
        "mem_per_chip_gb": rec.get("memory", {}).get("argument_bytes", 0)
        / 1e9 + rec.get("memory", {}).get("temp_bytes", 0) / 1e9,
        "arg_gb": rec.get("memory", {}).get("argument_bytes", 0) / 1e9,
        "temp_gb": rec.get("memory", {}).get("temp_bytes", 0) / 1e9,
        "coll_mix": rec.get("coll_mix", {}),
        "compile_s": rec.get("compile_s", 0),
    }


def load_all(tag: str | None = None) -> list[dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(DRYRUN_DIR, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        if tag is not None and rec.get("tag", "baseline") != tag:
            continue
        a = analyze(rec)
        if a is not None:
            out.append(a)
    return out


def hint(row: dict) -> str:
    d = row["dominant"]
    if d == "compute":
        if row["useful_ratio"] < 0.5:
            return ("compute-bound with low useful ratio: cut remat "
                    "recompute or dead attention FLOPs")
        return "compute-bound near the useful floor: good place to be"
    if d == "memory":
        return ("HBM-bound: raise arithmetic intensity (bigger batch/"
                "fusion) or shrink weight traffic (quantize, cache-resident"
                " tiles)")
    return ("collective-bound: reshard to cut gather/reduce volume or "
            "overlap collectives with compute")


def to_markdown(rows: list[dict]) -> str:
    hdr = ("| arch | shape | mesh | compute_s | memory_s | hlo_mem_s | "
           "collective_s | bound | MODEL_FLOPS | useful | roofline | "
           "mem/chip GB | next lever |\n"
           "|---|---|---|---|---|---|---|---|---|---|---|---|---|\n")
    lines = []
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"], r["mesh"])):
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {r['compute_s']:.3e} | {r['memory_s']:.3e} "
            f"| {r['memory_hlo_s']:.3e} "
            f"| {r['collective_s']:.3e} | {r['dominant']} "
            f"| {r['model_flops']:.2e} "
            f"| {r['useful_ratio']:.2f} | {r['roofline_frac']:.2f} "
            f"| {r['mem_per_chip_gb']:.1f} | {hint(r)} |")
    return hdr + "\n".join(lines) + "\n"


def pick_hillclimb_cells(rows: list[dict]) -> dict[str, dict]:
    """worst roofline fraction / most collective-bound / paper-representative
    (the biggest train cell — carbon pricing of training jobs is the
    paper-bridge workload)."""
    pod = [r for r in rows if r["mesh"] == "pod16x16"
           and r["shape"] != "long_500k"]
    worst = min(pod, key=lambda r: r["roofline_frac"])
    coll = max(pod, key=lambda r: r["collective_s"]
               / max(r["step_s_bound"], 1e-30))
    train = [r for r in pod if r["shape"] == "train_4k"]
    rep = max(train, key=lambda r: r["model_flops"])
    return {"worst_roofline": worst, "most_collective": coll,
            "paper_representative": rep}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--write-md", action="store_true")
    ap.add_argument("--tag", default="baseline")
    args = ap.parse_args()
    rows = load_all(args.tag)
    print(to_markdown(rows))
    picks = pick_hillclimb_cells(rows)
    for name, r in picks.items():
        print(f"{name}: {r['arch']} x {r['shape']} (dominant="
              f"{r['dominant']}, roofline={r['roofline_frac']:.2f}) — "
              f"{hint(r)}")
    if args.write_md:
        out = os.path.join(DRYRUN_DIR, "..", "roofline.md")
        with open(out, "w") as f:
            f.write(to_markdown(rows))
        print("wrote", os.path.abspath(out))


if __name__ == "__main__":
    main()
