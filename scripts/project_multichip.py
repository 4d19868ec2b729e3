"""Multi-chip time-to-solution projection: oktopk vs dense vs topkA.

The single benchmark chip cannot show the paper's headline — comm-bound
scaling wins — so this combines every measured input the repo has into the
same kind of alpha-beta projection the reference uses to reason about
density selection (VGG/utils.py:86-134):

  T_step(P) = T_compute(measured, single chip)
            + T_comm(analytic wire bytes, fabric alpha-beta)

Measured inputs (each cited in the output record):
  * single-chip VGG-16 step times from the newest BENCH_r*.json that
    carries them (dense_ms, oktopk_ms, and their bs-256 variants when
    present);
  * the oktopk steady-state volume calibration from the same records:
    volume_elems / k at the probe's (n=2^20, d=0.01) operating point —
    the paper's "<6k" property measured on the repo's own collective;
  * the topkA allgather volume law kP pairs/worker (2kP transmitted
    scalars in the repo's last_volume convention), which the 12-step EPS
    sweep reproduces exactly (logs/algo_sweep.json: 41936 elems =
    2 x 2621 x 8 at k=2621, P=8).

Analytic comm model (per-worker wire bytes; ring collectives):
  dense    2 n (P-1)/P f32 values          (reduce-scatter + allgather)
  oktopk   (volume_elems/2) pairs of int32 index + bf16 value —
           volume_elems = calib * k, P-independent (the paper's claim;
           phase A all_to_all splits 2k across P, phase B gathers the
           balanced winners)
  topkA    k P pairs per worker (allgather of every worker's local
           top-k; measured convention of logs/algo_sweep.json)

Compute-side deltas: oktopk_ms - dense_ms measured single-chip covers
selection + compaction + residual bookkeeping; topkA's selection cost is
taken as the measured threshold-selection share of that same delta (it
runs one local top-k but no two-phase repartition), bounded below by 0.

Fabrics (overridable): ICI ring (TPU pod slice), DCN (multi-host), and the
GbE-class fabric the reference's cluster numbers come from.  For each
(P, fabric) the table states who wins and by how much; the record also
solves the bandwidth crossover at which oktopk overtakes dense.

Usage:  python scripts/project_multichip.py [--json logs/projection.json]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from oktopk_tpu.comm.fabric import alpha_beta_table  # noqa: E402

# ---- constants (every one surfaced in the output record) -------------------

# VGG-16/CIFAR-10 flat parameter count (oktopk_tpu.models.vgg, measured by
# flat_size at Trainer init; logged in logs/convergence* headers).
N_VGG16_DEFAULT = 14_728_266

DENSITY = 0.02            # the reference's VGG operating point
                          # (/root/reference/VGG/exp_configs/vgg16.conf)
WIRE_PAIR_BYTES = 6       # int32 index + bf16 value (config.wire_pair_bytes)
DENSE_ELEM_BYTES = 4      # f32 ring allreduce

# Fabric presets: (alpha seconds/message-round, bandwidth GB/s per worker).
# Single source of truth is oktopk_tpu/comm/fabric.py (ICI / DCN / GBE
# rationale documented there); this module keeps a fresh mutable copy so
# scenario runs (and tests) may add entries without touching the presets.
FABRICS = alpha_beta_table()


def load_bench_records():
    """Newest-first list of bench records that parsed."""
    paths = sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json")))
    recs = []
    for p in paths:
        try:
            with open(p) as f:
                d = json.load(f)
            r = d.get("parsed") or {}
            if r:
                recs.append((os.path.basename(p), r))
        except (ValueError, OSError):
            continue
    recs = list(reversed(recs))
    # oldest fallback: the round-3 on-chip session measurements (PERF.md
    # prose, recorded machine-readably with provenance)
    chip = os.path.join(REPO, "logs", "chip_measurements.json")
    if os.path.exists(chip):
        try:
            with open(chip) as f:
                recs.append(("logs/chip_measurements.json", json.load(f)))
        except (ValueError, OSError):
            pass
    return recs


def pick(recs, key):
    """(value, source, record) for the newest record carrying ``key``."""
    for name, r in recs:
        if key in r:
            return float(r[key]), name, r
    return None, None, {}


def pick_compute(recs):
    """(dense_ms, oktopk_ms, source, record) from the newest record that
    carries BOTH step times on accelerator hardware. The overhead
    subtraction is only meaningful within one session on one device, and
    a CPU-fallback bench record must never pose as chip compute."""
    for name, r in recs:
        if ("dense_ms" in r and "oktopk_ms" in r
                and str(r.get("device", "cpu")).lower() != "cpu"):
            return float(r["dense_ms"]), float(r["oktopk_ms"]), name, r
    return None, None, None, {}


def comm_time(bytes_per_worker, rounds, alpha, gbps):
    return rounds * alpha + bytes_per_worker / (gbps * 1e9)


def project(n, k, P, fabric, dense_compute_ms, oktopk_overhead_ms,
            topka_overhead_ms, oktopk_volume_elems):
    """Per-algorithm projected step time (ms) at P workers on a fabric."""
    alpha, gbps = FABRICS[fabric]
    dense_bytes = 2.0 * n * (P - 1) / P * DENSE_ELEM_BYTES
    okt_bytes = (oktopk_volume_elems / 2.0) * WIRE_PAIR_BYTES
    topka_bytes = float(k) * P * WIRE_PAIR_BYTES
    # rounds: ring allreduce 2(P-1); oktopk O(1) + (P-1) balanced gather;
    # topkA ring allgather (P-1)
    t_dense = dense_compute_ms + 1e3 * comm_time(
        dense_bytes, 2 * (P - 1), alpha, gbps)
    t_okt = dense_compute_ms + oktopk_overhead_ms + 1e3 * comm_time(
        okt_bytes, P + 1, alpha, gbps)
    t_topka = dense_compute_ms + topka_overhead_ms + 1e3 * comm_time(
        topka_bytes, P - 1, alpha, gbps)
    return {"dense_ms": t_dense, "oktopk_ms": t_okt, "topkA_ms": t_topka,
            "dense_comm_mb": dense_bytes / 1e6,
            "oktopk_comm_mb": okt_bytes / 1e6,
            "topkA_comm_mb": topka_bytes / 1e6}


def crossover_gbps(n, k, P, dense_compute_ms, oktopk_overhead_ms,
                   oktopk_volume_elems):
    """Bandwidth (GB/s) below which projected oktopk beats dense at P,
    ignoring alpha terms (they favor oktopk, whose round count is lower
    for P >= 4, so this is conservative)."""
    dense_bytes = 2.0 * n * (P - 1) / P * DENSE_ELEM_BYTES
    okt_bytes = (oktopk_volume_elems / 2.0) * WIRE_PAIR_BYTES
    saved_bytes = dense_bytes - okt_bytes
    if saved_bytes <= 0 or oktopk_overhead_ms <= 0:
        return float("inf")
    return saved_bytes / (oktopk_overhead_ms / 1e3) / 1e9


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", default=os.path.join(REPO, "logs",
                                                   "projection.json"))
    ap.add_argument("--n", type=int, default=None,
                    help="model size (default: vgg16 header or constant)")
    args = ap.parse_args(argv)

    recs = load_bench_records()

    # model size: prefer the committed convergence header's measured count
    n, n_src = args.n, "--n"
    if n is None:
        for path in sorted(glob.glob(os.path.join(
                REPO, "logs", "convergence*", "vgg16_*.jsonl"))):
            try:
                with open(path) as f:
                    hdr = json.loads(f.readline())
                n = int(hdr["n_params"])
                n_src = os.path.relpath(path, REPO)
                break
            except (ValueError, OSError, KeyError):
                continue
    if n is None:
        n, n_src = N_VGG16_DEFAULT, "models/vgg.py flat_size (PERF.md)"
    k = int(DENSITY * n)

    # measured single-chip compute, from the newest record carrying each
    # key (BENCH_r05+ once the kernel path lands on chip; until then the
    # round-3 session in logs/chip_measurements.json)
    dense_ms, okt_ms, compute_src, okt_rec = pick_compute(recs)
    dense_src = okt_src = compute_src
    vol_elems, vol_src, _ = pick(recs, "volume_elems")
    vol_k = None
    if vol_elems is not None:
        # the volume probe runs at n=2^20, d=0.01 (bench.py): calibrate
        # transmitted elems per k
        vol_k = vol_elems / (0.01 * (1 << 20))
    if dense_ms is None or okt_ms is None or vol_k is None:
        print("[project] missing measured inputs "
              f"(dense_ms={dense_ms}, oktopk_ms={okt_ms}, "
              f"volume={vol_elems}); refusing to project from nothing",
              file=sys.stderr)
        return 1

    # single-chip oktopk overhead (selection + compaction + residuals).
    # When the record that supplied oktopk_ms carries the portable-path
    # flag, a second kernel-path scenario is projected from the cost
    # model's predicted step time (docs/PERF.md "Where the time goes"),
    # labeled predicted — measured and predicted are never mixed silently.
    portable = bool(okt_rec.get("oktopk_pallas_failed"))
    overhead_ms = okt_ms - dense_ms
    kernel_overhead_ms = None
    if portable and "oktopk_kernel_path_predicted_ms" in okt_rec:
        kernel_overhead_ms = (
            float(okt_rec["oktopk_kernel_path_predicted_ms"]) - dense_ms)
    topka_overhead_ms = max(0.0, 0.35 * overhead_ms)
    # topkA runs one local selection but no repartition/compaction: the
    # measured phase split (scripts/profile_step.py; PERF.md step-phase
    # breakdown — selection ~= 1/3 of the sparse-path overhead) gives the
    # 0.35 share; bounded at 0.

    okt_volume = vol_k * k

    out = {
        "inputs": {
            "n": n, "n_source": n_src, "density": DENSITY, "k": k,
            "dense_compute_ms": dense_ms, "dense_compute_src": dense_src,
            "oktopk_ms": okt_ms, "oktopk_src": okt_src,
            "oktopk_overhead_ms": overhead_ms,
            "oktopk_portable_path": portable,
            "oktopk_kernel_overhead_ms_predicted": kernel_overhead_ms,
            "topka_overhead_ms": topka_overhead_ms,
            "volume_elems_per_k": vol_k, "volume_src": vol_src,
            "oktopk_volume_elems": okt_volume,
            "wire_pair_bytes": WIRE_PAIR_BYTES,
            "fabrics": {f: {"alpha_s": a, "gbps": b}
                        for f, (a, b) in FABRICS.items()},
        },
        "projections": {},
        "crossover_gbps": {},
    }
    for P in (8, 32, 128):
        for fab in FABRICS:
            p = {kk: round(v, 2) for kk, v in project(
                n, k, P, fab, dense_ms, overhead_ms,
                topka_overhead_ms, okt_volume).items()}
            if kernel_overhead_ms is not None:
                p["oktopk_kernel_ms"] = round(project(
                    n, k, P, fab, dense_ms, kernel_overhead_ms,
                    topka_overhead_ms, okt_volume)["oktopk_ms"], 2)
            out["projections"][f"P{P}_{fab}"] = p
        out["crossover_gbps"][f"P{P}"] = round(
            crossover_gbps(n, k, P, dense_ms, overhead_ms, okt_volume), 2)
        if kernel_overhead_ms is not None:
            out["crossover_gbps"][f"P{P}_kernel"] = round(
                crossover_gbps(n, k, P, dense_ms, kernel_overhead_ms,
                               okt_volume), 2)

    os.makedirs(os.path.dirname(args.json), exist_ok=True)
    with open(args.json, "w") as f:
        json.dump(out, f, indent=1)

    # markdown table for PERF.md
    print(f"VGG-16 n={n} d={DENSITY} k={k}; compute {dense_ms:.1f} ms "
          f"(src {dense_src}), oktopk overhead {overhead_ms:.1f} ms "
          f"({'portable path' if portable else 'kernel path'}), oktopk "
          f"volume {okt_volume/1e6:.2f}M elems "
          f"({vol_k:.2f}/k, src {vol_src})")
    print()
    kcol = kernel_overhead_ms is not None
    print("| P | fabric | dense ms (comm MB) | oktopk ms (comm MB) | "
          + ("oktopk-kernel ms (pred) | " if kcol else "")
          + "topkA ms (comm MB) | winner |")
    print("|---|---|---|---|---|" + ("---|---|" if kcol else "---|"))
    for key, p in out["projections"].items():
        P, fab = key.split("_", 1)
        cands = {"dense": p["dense_ms"], "oktopk": p["oktopk_ms"],
                 "topkA": p["topkA_ms"]}
        if kcol:
            cands["oktopk-kernel"] = p["oktopk_kernel_ms"]
        win = min(cands, key=cands.get)
        row = (f"| {P[1:]} | {fab} | {p['dense_ms']} "
               f"({p['dense_comm_mb']}) | {p['oktopk_ms']} "
               f"({p['oktopk_comm_mb']}) | ")
        if kcol:
            row += f"{p['oktopk_kernel_ms']} | "
        row += (f"{p['topkA_ms']} ({p['topkA_comm_mb']}) | {win} |")
        print(row)
    print()
    for P, g in out["crossover_gbps"].items():
        print(f"crossover {P}: oktopk beats dense below ~{g} GB/s "
              "effective per-worker bandwidth")
    print(f"\n[project] record -> {args.json}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
