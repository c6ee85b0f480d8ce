"""Roofline report generator: merges the dry-run sweep JSON with
registry-derived MODEL_FLOPS into the EXPERIMENTS.md tables, plus the
*measured* device-path benchmark (``bench_device`` → BENCH_device.json).

Terms per (arch × shape × mesh), all per-chip of the dry run's TPU v5e
target:
  compute_s    = HLO_FLOPs / bf16 peak
  memory_s     = HLO_bytes / HBM bandwidth
  collective_s = collective_bytes / ICI link bandwidth
MODEL_FLOPS = 6·N_active·D + 3·attn (train), 2·N_active·D + attn
(prefill/decode); roofline_fraction = ideal_compute_time / bound.

``bench_device`` measures the fused delta-apply + analytics retrieval
against the pre-fusion pipeline (XLA-scan chain, host round-trip, separate
unpack/popcount/degree-feed/weighted passes) producing the *same outputs*,
reports achieved logical bytes/s for both, and asserts the fused path's
analytics stay bit-identical to the ``ref.py`` oracle.  Its share of the
HBM roofline uses the peaks of the device it ran on
(``launch/hlo_analysis.DEVICE_PEAKS``); a device without published peaks
there — the CPU among them — is an error.
Run on the chip::

    PYTHONPATH=src python -m benchmarks.roofline_report --device --quick
"""
from __future__ import annotations

import json
import os
import time

from repro.launch.hlo_analysis import (DRYRUN_TARGET, PEAKS_SOURCE,
                                      device_peaks)

DEVICE_JSON = "BENCH_device.json"


def load_results(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def refresh_model_flops(results: dict) -> None:
    """Recompute MODEL_FLOPS from the (possibly newer) registry."""
    import numpy as np
    import jax
    from jax.sharding import Mesh
    from repro.configs.registry import get_cell
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    cache: dict = {}
    for key, rec in results.items():
        if rec.get("status") != "ok":
            continue
        ck = (rec["arch"], rec["shape"])
        if ck not in cache:
            cell = get_cell(rec["arch"], rec["shape"], mesh, False)
            cache[ck] = cell.flops_model
        rec["model_flops"] = cache[ck]
        h = rec.get("hlo")
        if not h:
            continue
        chips = rec["chips"]
        r = rec["roofline"]
        bound = max(r["compute_s"], r["memory_s"], r["collective_s"])
        peak = device_peaks(DRYRUN_TARGET)["bf16_flops"]
        ideal = rec["model_flops"] / (chips * peak)
        r["roofline_fraction"] = ideal / bound if bound else 0.0
        total = h["flops"] * chips
        r["useful_flops_ratio"] = rec["model_flops"] / total if total else 0.0


def table(results: dict, multi_pod: bool | None = False) -> str:
    hdr = ("| cell | chips | mem/dev GiB | fits | compute_s | memory_s | "
           "collective_s | bottleneck | useful | roofline |\n"
           "|---|---|---|---|---|---|---|---|---|---|\n")
    rows = []
    for key, r in sorted(results.items()):
        if r.get("status") == "skipped":
            if (multi_pod is None) or (r["multi_pod"] == multi_pod):
                reason = r.get("skip_reason", "")[:48]
                rows.append(f"| {r['arch']}×{r['shape']} | — | — | — | — | — "
                            f"| — | skipped: {reason} | — | — |")
            continue
        if r.get("status") != "ok":
            continue
        if multi_pod is not None and r["multi_pod"] != multi_pod:
            continue
        m = r.get("memory", {}).get("live_bytes_per_device", 0) / 2 ** 30
        rl = r.get("roofline", {})
        rows.append(
            f"| {r['arch']}×{r['shape']} | {r['chips']} | {m:.2f} | "
            f"{'✓' if r.get('fits_16gb') else '✗'} | "
            f"{rl.get('compute_s', 0):.2e} | {rl.get('memory_s', 0):.2e} | "
            f"{rl.get('collective_s', 0):.2e} | "
            f"{rl.get('bottleneck', '-').replace('_s', '')} | "
            f"{rl.get('useful_flops_ratio', 0):.3f} | "
            f"{rl.get('roofline_fraction', 0):.4f} |")
    return hdr + "\n".join(rows) + "\n"


def summary(results: dict) -> dict:
    ok = [r for r in results.values() if r.get("status") == "ok"]
    sk = [r for r in results.values() if r.get("status") == "skipped"]
    er = [r for r in results.values() if r.get("status") == "error"]
    fits = [r for r in ok if r.get("fits_16gb")]
    return {"ok": len(ok), "skipped": len(sk), "errors": len(er),
            "fits_16gb": len(fits),
            "over_budget": [k for k, r in results.items()
                            if r.get("status") == "ok"
                            and not r.get("fits_16gb")]}


# ---------------------------------------------------------------------------
# measured device path: fused retrieval+analytics vs the pre-fusion pipeline
# ---------------------------------------------------------------------------


def _bench_loop(fn, reps: int) -> float:
    fn()                       # warmup / compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_device(quick: bool = False):
    """Fused kernel family vs the separate-pass baseline, same outputs.

    Both paths produce (landed mask, per-block popcounts, per-word weighted
    partials, unpacked live f32 feed).  The baseline is the pre-fusion
    pipeline: XLA-scan chain call, device→host round-trip, then host-side
    unpack + popcount + weighted reductions (exactly what the training
    example's ``snapshot_batch`` and the analytics ops used to do).  The
    fused path is one compiled call.
    Achieved bytes/s counts the logical chain traffic (K+2 planes of W
    words), the quantity the roofline's HBM term bounds.
    """
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core import bitmaps as bmod
    from repro.kernels import delta_apply_fused
    from repro.kernels.delta_apply.ops import _fused_pad
    from repro.kernels.delta_apply import (delta_apply_chain,
                                           delta_apply_fused_ref)

    dev = jax.devices()[0]
    peaks = device_peaks(dev.device_kind)
    W = 1 << 14 if quick else 1 << 16     # words: 2^19 / 2^21 slots
    K = 8 if quick else 16
    reps = 5 if quick else 10
    U = W * 32
    rng = np.random.default_rng(0)
    base = jnp.asarray(rng.integers(0, 2 ** 32, W, dtype=np.uint32))
    adds = jnp.asarray(rng.integers(0, 2 ** 32, (K, W), dtype=np.uint32))
    dels = jnp.asarray(rng.integers(0, 2 ** 32, (K, W), dtype=np.uint32))
    weights = jnp.asarray(rng.random(U, dtype=np.float32))
    logical_bytes = (K + 2) * W * 4

    def fused():
        out = delta_apply_fused(base, adds, dels, weights)
        return (np.asarray(out.mask), np.asarray(out.pop),
                np.asarray(out.accw), np.asarray(out.live))

    G = W // 1024

    def baseline():
        m = np.asarray(delta_apply_chain(base, adds, dels, impl="xla"))
        live = bmod.np_unpack(m, U).astype(np.float32)
        pop = (np.unpackbits(m.view(np.uint8)).astype(np.int32)
               .reshape(G, -1).sum(axis=1))
        accw = (live * np.asarray(weights)).reshape(W, 32).sum(axis=1)
        return m, pop, accw, live

    t_fused = _bench_loop(fused, reps)
    t_base = _bench_loop(baseline, reps)

    # bit-identity vs the oracle (the acceptance gate for fused analytics)
    pb, pa, pd, pw, _ = _fused_pad(base, adds, dels, weights, 1024)
    rm, rp, ra, rl = delta_apply_fused_ref(pb, pa, pd, pw, block_w=1024)
    fo = delta_apply_fused(base, adds, dels, weights)
    parity = bool(
        np.array_equal(np.asarray(fo.mask), np.asarray(rm[:W]))
        and np.array_equal(np.asarray(fo.pop), np.asarray(rp))
        and np.array_equal(np.asarray(fo.accw), np.asarray(ra[:W]))
        and np.array_equal(np.asarray(fo.live), np.asarray(rl[:U])))

    fused_gbps = logical_bytes / t_fused / 1e9
    base_gbps = logical_bytes / t_base / 1e9
    report = {
        "platform": dev.platform, "device_kind": dev.device_kind,
        "W_words": W, "K": K, "slots": U,
        "logical_bytes_per_apply": logical_bytes,
        "fused_s": t_fused, "baseline_s": t_base,
        "fused_gbps": round(fused_gbps, 3),
        "baseline_gbps": round(base_gbps, 3),
        "speedup_fused_vs_baseline": round(t_base / t_fused, 3),
        "hbm_fraction_of_peak": fused_gbps * 1e9 / peaks["hbm_bytes_per_s"],
        "peaks_source": PEAKS_SOURCE,
        "analytics_bit_identical_to_ref": parity,
    }
    with open(DEVICE_JSON, "w") as f:
        json.dump(report, f, indent=2)
    return [
        ("device/fused_apply", t_fused * 1e6,
         {"gbps": report["fused_gbps"], "parity": parity}),
        ("device/baseline_separate_passes", t_base * 1e6,
         {"gbps": report["baseline_gbps"]}),
        ("device/report", 0.0,
         {"json": DEVICE_JSON,
          "speedup": report["speedup_fused_vs_baseline"]}),
    ]


def run(path: str = "dryrun_results.json", quick: bool = False):
    if not os.path.exists(path):
        return [("roofline/report", 0.0, {"error": f"{path} missing — run "
                 "PYTHONPATH=src python -m repro.launch.dryrun first"})]
    results = load_results(path)
    refresh_model_flops(results)
    with open(path, "w") as f:
        json.dump(results, f, indent=1)
    s = summary(results)
    rows = [("roofline/summary", 0.0, s)]
    for key, r in results.items():
        if r.get("status") != "ok":
            continue
        rl = r.get("roofline", {})
        rows.append((f"roofline/{key}", 0.0,
                     {"bottleneck": rl.get("bottleneck"),
                      "fraction": round(rl.get("roofline_fraction", 0), 4),
                      "mem_gib": round(r.get("memory", {}).get(
                          "live_bytes_per_device", 0) / 2 ** 30, 2)}))
    return rows


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--device", action="store_true",
                    help="run the measured device-path benchmark only")
    ap.add_argument("--dryrun-json", default="dryrun_results.json")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    rows = (bench_device(quick=args.quick) if args.device
            else run(args.dryrun_json, quick=args.quick))
    for name, us, derived in rows:
        print(f"{name},{us:.1f},\"{json.dumps(derived)}\"")


if __name__ == "__main__":
    main()
