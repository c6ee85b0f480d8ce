#!/usr/bin/env python3
"""The control: the plain reference put in the program's place with one
stated guarantee broken, read by the same comparison that decides a
run's ``correct``.  It has to come out not correct.

    python bench/control.py --workload <name> --seed <n> [<n> ...]

Loader batches (``wrong_snapshots``): each batch's first snapshot
standing for all of its times, as a loader that skipped the prefix
chain would give, where the configuration promises every snapshot
exact.

The benchmark's own runs never run it.  ``bench/tests/test_control.py``
keeps it at a size a test run holds; on the chip it runs at the cell's
own size.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)

from bench import history, load, reference as ref  # noqa: E402


def loader_readings(h, traffic: dict, seed: int, batches: int = 16) -> dict:
    """Windows as the loader cell draws them; each batch answered with
    its first snapshot for all of its times."""
    rng = np.random.default_rng([seed, 2])
    horizon = int(h.tmax * traffic["label_horizon"])
    bs = traffic["batch_size"]
    groups = []
    while len(groups) < batches:
        w = load.interval(rng, h.tmax, traffic["span"], traffic["points"],
                          tail=horizon)
        groups += [w[i:i + bs] for i in range(0, len(w) - bs + 1, bs)]
    groups = groups[:batches]
    snaps = ref.Snapshots(h, {t for g in groups for t in g})
    wrong = 0
    for g in groups:
        first = snaps.node[g[0]], snaps.edge[g[0]]
        wrong += sum(not (np.array_equal(first[0], snaps.node[t])
                          and np.array_equal(first[1], snaps.edge[t]))
                     for t in g)
    return {"wrong_snapshots": wrong}


def readings(workload: str, seed: int, *, rehearse: bool = False) -> dict:
    """The control's numbers for one seed."""
    from bench.run import load_cell
    _, _, config, traffic = load_cell(workload, rehearse)
    return loader_readings(history.build(config, seed), traffic, seed)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    args = ap.parse_args()
    for seed in args.seed:
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": readings(args.workload, seed)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
