import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init) — this is the only entry point that fakes 512 devices.

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from ..configs.registry import get_cell, list_cells  # noqa: E402
from . import hlo_analysis  # noqa: E402
from .mesh import make_production_mesh  # noqa: E402

PEAKS = hlo_analysis.device_peaks(hlo_analysis.DRYRUN_TARGET)


def _shardings(mesh, pspec_tree):
    return jax.tree.map(
        lambda sp: NamedSharding(mesh, sp if isinstance(sp, P) else P()),
        pspec_tree, is_leaf=lambda x: isinstance(x, P) or x is None)


def input_specs(arch: str, shape: str, mesh, multi_pod: bool):
    """ShapeDtypeStruct stand-ins for every input of the cell's step
    function — weak-type-correct, shardable, no device allocation."""
    cell = get_cell(arch, shape, mesh, multi_pod)
    return cell.args


def run_cell(arch: str, shape: str, multi_pod: bool, *,
             want_hlo: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    cell = get_cell(arch, shape, mesh, multi_pod)
    rec: dict = {"arch": arch, "shape": shape, "multi_pod": multi_pod,
                 "chips": chips, "step_kind": cell.step_kind,
                 "model_flops": cell.flops_model,
                 "n_params": cell.n_params,
                 "n_params_active": cell.n_params_active}
    if cell.skip_reason:
        rec["status"] = "skipped"
        rec["skip_reason"] = cell.skip_reason
        return rec
    in_sh = _shardings(mesh, cell.pspecs)
    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = jax.jit(cell.fn, in_shardings=in_sh).lower(*cell.args)
        t1 = time.time()
        compiled = lowered.compile()
    t2 = time.time()
    rec["lower_s"] = round(t1 - t0, 2)
    rec["compile_s"] = round(t2 - t1, 2)

    ma = compiled.memory_analysis()
    if ma is not None:
        rec["memory"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "code_bytes": ma.generated_code_size_in_bytes,
        }
        live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        rec["memory"]["live_bytes_per_device"] = live
        rec["fits_16gb"] = bool(live <= 16 * 1024 ** 3)
    ca = compiled.cost_analysis() or {}
    rec["cost_analysis"] = {k: float(v) for k, v in ca.items()
                            if k in ("flops", "bytes accessed",
                                     "transcendentals")}
    if want_hlo:
        text = compiled.as_text()
        h = hlo_analysis.analyze(text)
        rec["hlo"] = {k: h[k] for k in ("flops", "hbm_bytes",
                                        "collective_bytes", "collectives")}
        # roofline terms (per device; HLO is the per-device SPMD program)
        rec["roofline"] = {
            "compute_s": h["flops"] / PEAKS["bf16_flops"],
            "memory_s": h["hbm_bytes"] / PEAKS["hbm_bytes_per_s"],
            "collective_s": (h["collective_bytes"]
                             / PEAKS["ici_link_bytes_per_s"]),
        }
        dom = max(rec["roofline"], key=rec["roofline"].get)
        rec["roofline"]["bottleneck"] = dom
        total_hlo_flops = h["flops"] * chips
        rec["roofline"]["useful_flops_ratio"] = (
            cell.flops_model / total_hlo_flops if total_hlo_flops else 0.0)
        bound = max(rec["roofline"]["compute_s"], rec["roofline"]["memory_s"],
                    rec["roofline"]["collective_s"])
        ideal = cell.flops_model / (chips * PEAKS["bf16_flops"])
        rec["roofline"]["roofline_fraction"] = (ideal / bound) if bound else 0.0
    rec["status"] = "ok"
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--no-resume", action="store_true")
    args = ap.parse_args()

    results: dict[str, dict] = {}
    if os.path.exists(args.out) and not args.no_resume:
        with open(args.out) as f:
            results = json.load(f)

    cells = list_cells()
    if args.arch:
        cells = [(a, s) for a, s in cells if a == args.arch]
    if args.shape:
        cells = [(a, s) for a, s in cells if s == args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    for arch, shape in cells:
        for multi_pod in meshes:
            key = f"{arch}|{shape}|{'multi' if multi_pod else 'single'}"
            if key in results and results[key].get("status") in ("ok", "skipped"):
                continue
            print(f"=== {key} ===", flush=True)
            try:
                rec = run_cell(arch, shape, multi_pod)
            except Exception as e:  # record the failure, keep sweeping
                rec = {"arch": arch, "shape": shape, "multi_pod": multi_pod,
                       "status": "error", "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]}
                print(rec["error"], flush=True)
            results[key] = rec
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
            if rec.get("status") == "ok":
                r = rec.get("roofline", {})
                print(f"  compile={rec.get('compile_s')}s "
                      f"mem/dev={rec.get('memory', {}).get('live_bytes_per_device', 0)/2**30:.2f}GiB "
                      f"bottleneck={r.get('bottleneck')} "
                      f"roofline={r.get('roofline_fraction', 0):.3f}",
                      flush=True)

    ok = sum(1 for r in results.values() if r.get("status") == "ok")
    sk = sum(1 for r in results.values() if r.get("status") == "skipped")
    er = sum(1 for r in results.values() if r.get("status") == "error")
    print(f"done: {ok} ok, {sk} skipped, {er} errors -> {args.out}")


if __name__ == "__main__":
    main()
