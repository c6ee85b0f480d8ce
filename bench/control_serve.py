#!/usr/bin/env python3
"""The served cells' control: the plain reference put in the program's
place with one stated guarantee broken, read by the comparison that
decides a run's ``correct`` (``compare`` in ``bench/paths/serve.py``).
Each break has to come out not correct.

    python bench/control_serve.py --workload <name> --seed <n> [<n> ...]

The documents are the first ``--per-kind`` of each kind in the cell's
pool for the seed, answered as the program's envelopes carry them.  The
breaks:

* ``stale``: every later point of an evolve document answered with its
  first point's value, as an engine that never advanced would answer
  (the configuration promises each point exact, or PageRank within
  ``pagerank_l1_max``);
* ``bf16``: PageRank iterated in bfloat16 (ranks and contributions
  rounded to bfloat16 at every step, the program's stopping rule: an L1
  step of 1e-6 or 200 iterations), where the cell holds PageRank to
  float32's accuracy.

The benchmark's own runs never run it.  ``bench/tests/test_serve_cell.py``
runs it at the cell's rehearsal size; on the chip it runs at full size.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import ml_dtypes
import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)

from bench import history, parts, reference as ref  # noqa: E402
from bench import reference_analytics as ana  # noqa: E402

BREAKS = ("stale", "bf16")


def bf16(x: np.ndarray) -> np.ndarray:
    return x.astype(ml_dtypes.bfloat16).astype(np.float64)


def envelope(h, snaps, doc: dict, brk: str | None) -> dict:
    """The reference's answer to ``doc`` as its envelope carries it, with
    the break ``brk`` (or none)."""
    kind = parts.find("paths", "serve").kind_of(doc)
    times = doc["times"]
    if kind == "multipoint":
        pts = []
        for t in times:
            nm, em = snaps.node_mask(t), snaps.edge_mask(t)
            pts.append({"t": t, "nodes": int(nm.sum()),
                        "edges": int(em.sum()),
                        "node_crc": ana.crc(np.packbits(nm)),
                        "edge_crc": ana.crc(np.packbits(em))})
        return {"ok": True, "result": {"points": pts}}
    values = []
    for t in times[:1] if brk == "stale" else times:
        nm, em = snaps.node_mask(t), snaps.edge_mask(t)
        if kind == "components":
            lab = ana.components(h, nm, em)
            values.append({"size": lab.size, "dtype": "int32",
                           "crc": ana.crc(lab)})
        elif brk == "bf16":
            values.append(ana.pagerank(h, nm, em, tol=1e-6, max_iters=200,
                                       cast=bf16).tolist())
        else:
            values.append(ana.pagerank(h, nm, em).tolist())
    if brk == "stale":
        values *= len(times)
    return {"ok": True, "result": {"times": list(times), "values": values}}


def readings(workload: str, seed: int, *, rehearse: bool = False,
             per_kind: int = 3) -> dict:
    """The control's numbers for one seed, break by break."""
    from bench.run import load_cell
    _, _, config, traffic = load_cell(workload, rehearse)
    serve = parts.find("paths", "serve")
    h = history.build(config, seed)
    pool = serve.documents(np.random.default_rng([seed, 4]), h.tmax,
                           traffic, traffic["pool"])
    docs = [d for k in serve.KINDS
            for d in [d for d in pool if serve.kind_of(d) == k][:per_kind]]
    snaps = ref.Snapshots(h, {t for d in docs for t in d["times"]})
    return {brk: dict(serve.compare(h, [(d, envelope(h, snaps, d, brk))
                                        for d in docs]), failed_docs=0)
            for brk in BREAKS}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    ap.add_argument("--per-kind", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    for seed in args.seed:
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": readings(args.workload, seed,
                                              rehearse=args.rehearse,
                                              per_kind=args.per_kind)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
