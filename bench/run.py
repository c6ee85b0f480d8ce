#!/usr/bin/env python3
"""Runs one benchmark cell once, in this one process, on the chip.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The process loads the cell, warms up, measures for ``--seconds``, checks
what the timed path produced against the plain reference, and prints
one JSON line: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` when traced), then ``checks``, the numbers
compared, each with its limit.  With ``--trace 0`` the metrics are the
cell's end-to-end metrics; with ``--trace 1`` its per-layer metrics,
read from a profiler trace of the window and from the program's
counters.  Without a TPU it exits 3 and prints no result;
``--rehearse`` runs the cell's small sizes on whatever JAX finds, for
trying the harness out, and its numbers are never device numbers.

Everything is found by name, so a later change adds a cell, a
configuration or a metric by adding files and entries only:

* ``BENCHMARK.json`` lists the cells (``workloads``: a configuration and a
  traffic mix each) and the metrics;
* a configuration is the file its ``configs`` entry names (history
  generator and parameters, store capacity, index, store, caches,
  guarantees);
* a traffic mix is ``bench/traffic/<traffic>.json``, which names its
  ``path`` and the parameters of its load (``bench/load.py``);
* the generator, the store kind, the path and each metric are files of
  their own, ``bench/{generators,stores,paths,metrics}/<name>.py``,
  found by ``bench/parts.py``; a name with no file fails before anything
  is generated or compiled;
* limits of the numbers compared live in the traffic file's ``limits``.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
RUN_DIR = BENCH / ".run"
if sys.path and Path(sys.path[0]).resolve() == BENCH:
    sys.path[0] = str(ROOT)     # keep bench/trace.py from shadowing stdlib
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import parts  # noqa: E402

EXIT_NO_DEVICE = 3


def log(*a) -> None:
    print(*a, flush=True)


def merged(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def load_cell(workload: str, rehearse: bool):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise SystemExit(f"unknown workload {workload!r}")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{wl['traffic']}.json")
                         .read_text())
    if rehearse:
        config = merged(config, config.get("rehearse", {}))
        traffic = merged(traffic, traffic.get("rehearse", {}))
    return bench, wl, config, traffic


class Compiles:
    """Backend compiles and persistent-cache hits, with when they ended,
    from ``jax.monitoring`` (as ``chip_smoke.Phases`` counts them)."""

    def __init__(self) -> None:
        from jax import monitoring
        self.events: list[tuple[float, float]] = []
        self.cache = {"hits": 0, "misses": 0}
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.monotonic(), secs))

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache["misses"] += 1

    def between(self, a: float, b: float) -> list[float]:
        return [s for t, s in self.events if a <= t <= b]


class Tracer:
    """A profiler trace of the window alone (``--trace 1``), with the
    Python tracer off; the harness spans that the cell's path opens
    (``spans``, the window's first) mark the window."""

    def __init__(self, enabled: bool, directory: Path,
                 spans: tuple[str, ...]) -> None:
        self.enabled, self.dir, self.spans = enabled, directory, spans

    @contextlib.contextmanager
    def window(self):
        import jax
        from jax.profiler import TraceAnnotation
        if not self.enabled:
            yield
            return
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        try:
            with TraceAnnotation(self.spans[0]):
                yield
        finally:
            jax.profiler.stop_trace()

    def summary(self) -> dict | None:
        if not self.enabled:
            return None
        from bench.trace import reduce_file
        files = glob.glob(str(self.dir / "plugins/profile/*/*.xplane.pb"))
        if not files:
            return None
        return reduce_file(max(files, key=os.path.getmtime), self.spans)


class Cell:
    """One configuration's history, index and store, for one seed.  A
    store that keeps files keeps them in ``store_dir``, under
    ``bench/.run/``, one for each cell and seed; ``close`` removes it."""

    def __init__(self, name: str, config: dict, seed: int,
                 seconds: float) -> None:
        from bench import history
        from repro.core import GraphManager
        self.name, self.config, self.seed = name, config, seed
        self.seconds = float(seconds)
        generate = history.generator(config["history"])
        self.store_dir = RUN_DIR / "stores" / f"{name}.{seed}"
        shutil.rmtree(self.store_dir, ignore_errors=True)
        self.store = parts.store(config["store"], self.store_dir)
        t = time.monotonic()
        own = generate(seed)
        self.sizes = {"nodes": own.num_nodes, "edges": own.num_edges}
        self.hist = history.with_capacity(own, config["universe"])
        uni, ev = history.to_program(self.hist)
        self.timings = {"generate_s": time.monotonic() - t}
        t = time.monotonic()
        idx, caches = config["index"], config["caches"]
        self.gm = GraphManager(uni, ev, store=self.store, L=idx["L"],
                               k=idx["k"], diff_fn=idx["diff_fn"], **caches)
        self.timings["index_s"] = time.monotonic() - t
        self.store_bytes = self.store.total_bytes()
        self.t0 = self.t_end = None     # the window, set by the driver

    def counters(self) -> dict:
        """The program's own counters, read as they stand: the store's
        gets, and those that missed a hot tier (0 for a store without
        one), each a read of the cold store."""
        stats = self.gm.store.stats
        return {"kv_gets": stats.gets, "kv_hot_misses": stats.hot_misses}

    def close(self) -> None:
        self.gm.close()
        self.store.close()
        shutil.rmtree(self.store_dir, ignore_errors=True)


class Run:
    """What a metric reader sees."""

    def __init__(self, **kw) -> None:
        self.__dict__.update(kw)

    def delta(self, name: str) -> float | None:
        a, b = self.counters[0].get(name), self.counters[1].get(name)
        return None if a is None or b is None else b - a


def read_metric(name: str, run: Run):
    return parts.find("metrics", name).read(run)


def cell_metrics(bench: dict, workload: str, traced: bool) -> list[dict]:
    kind = "per_layer" if traced else "end_to_end"
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def main(argv=None, *, allow_cpu: bool = False) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the cell's small sizes on any platform; not a "
                         "measurement")
    args = ap.parse_args(argv)
    bench, wl, config, traffic = load_cell(args.workload, args.rehearse)
    path = parts.find("paths", traffic["path"])

    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not (args.rehearse or allow_cpu):
        print(f"no TPU: JAX found {dev.platform} ({dev.device_kind}); "
              f"this benchmark measures only on the chip", file=sys.stderr)
        return EXIT_NO_DEVICE
    if len(devices) < wl["chips"]:
        print(f"{wl['chips']} chips asked for, {len(devices)} found",
              file=sys.stderr)
        return EXIT_NO_DEVICE
    log(f"platform {dev.platform} ({dev.device_kind}) x{len(devices)}"
        + ("  REHEARSAL: not a measurement" if dev.platform != "tpu" else ""))
    compiles = Compiles()

    cell = Cell(args.workload, config, args.seed, args.seconds)
    driver = path.Driver(cell, traffic)
    t = time.monotonic()
    driver.build()
    driver.warm()
    cell.timings["build_and_warm_s"] = time.monotonic() - t
    tracer = Tracer(bool(args.trace), RUN_DIR / "trace", path.SPANS)
    before = cell.counters()
    log(f"window: opens after {time.monotonic() - T_START:.3f}s of set-up")
    window = driver.drive(tracer)
    after = cell.counters()
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    in_window = compiles.between(cell.t0, cell.t_end)
    setup_compile = sum(s for t, s in compiles.events if t < cell.t0)
    log(f"setup: {json.dumps({k: round(v, 3) for k, v in cell.timings.items()})}"
        f" compile_s={setup_compile:.3f} cache={compiles.cache}"
        f" store_bytes={cell.store_bytes}"
        f" events={len(cell.hist.time)} nodes={cell.sizes['nodes']}"
        f" edges={cell.sizes['edges']} edge_slots={cell.hist.num_edges}")
    log(f"window: compiles={len(in_window)} ({sum(in_window):.3f}s) "
        + json.dumps(window))
    driver.close()
    trace = tracer.summary()
    cell.close()
    del cell.gm
    gc.collect()

    t = time.monotonic()
    checks = driver.verify(window)
    log(f"verify: {time.monotonic() - t:.3f}s")
    limits = traffic["limits"]
    compared = {k: {"value": v, "limit": limits[k]} for k, v in checks.items()}
    correct = all(c["value"] <= c["limit"] for c in compared.values())

    run = Run(window=window, seconds=cell.seconds, sizes=cell.sizes,
              setup_s=cell.t0 - T_START, counters=(before, after),
              trace=trace, device_kind=dev.device_kind)
    metrics = {}
    for m in cell_metrics(bench, args.workload, bool(args.trace)):
        v = read_metric(m["name"], run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": window["snapshots"],
              "failed": 0, "metrics": metrics, "device": device}
    if trace is not None:
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        log("kernels: " + json.dumps(
            {n: {k: v for k, v in kern.items() if k != "shapes"}
             for n, kern in trace["kernels"].items()}))
        result["breakdown"] = trace["breakdown"]
    result["checks"] = compared
    print(json.dumps(result), flush=True)
    for k, c in compared.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
