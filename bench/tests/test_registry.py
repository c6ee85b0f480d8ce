"""A cell's parts found by name from files: a whole rehearsal-size cell on
the CPU, built from a configuration that names the growing generator, a
hot tier over a log file and the loader path, and the errors of names
with no file."""
import pytest

from bench import parts
from bench import run as harness

SEED = 2**31 + 21
CONFIG = {
    "history": {"generator": "growing_network", "n_events": 6000,
                "attrs_on_add": False},
    "universe": {"node_slots": 2000, "edge_slots": 4500},
    "index": {"L": 1000, "k": 2, "diff_fn": "intersection"},
    "store": {"kind": "tiered", "hot_bytes": 4 << 10,
              "cold": {"kind": "logfile"}},
    "caches": {"cache_bytes": 1 << 20, "cache_entries": 16,
               "prefetch_workers": 2},
}


def traffic() -> dict:
    _, _, _, tr = harness.load_cell("churn-1m.interval-loader", True)
    return tr


def test_growing_history_on_a_tiered_log_file_through_the_loader():
    path = parts.find("paths", traffic()["path"])
    cell = harness.Cell("registry-test", CONFIG, SEED, 3)
    try:
        assert cell.sizes["nodes"] < CONFIG["universe"]["node_slots"]
        assert cell.sizes["edges"] < CONFIG["universe"]["edge_slots"]
        assert (cell.store_dir / "kv.log").is_file()
        assert cell.store.total_bytes() > CONFIG["store"]["hot_bytes"]
        driver = path.Driver(cell, traffic())
        driver.build()
        driver.warm()
        cold_before = cell.store.cold.stats.gets
        before = cell.counters()
        window = driver.drive(harness.Tracer(False, harness.RUN_DIR / "trace",
                                             path.SPANS))
        after = cell.counters()
        cold_after = cell.store.cold.stats.gets
        driver.close()
    finally:
        cell.close()
    assert not cell.store_dir.exists()
    assert window["snapshots"] > 0
    assert cold_after > cold_before
    assert after["kv_hot_misses"] > before["kv_hot_misses"]
    assert driver.verify(window) == {"wrong_snapshots": 0}


@pytest.fixture
def no_generation(monkeypatch):
    """A generator that fails the test if anything is generated."""
    def boom(*a, **kw):
        raise AssertionError("generated before the lookup failed")
    monkeypatch.setattr(parts.find("generators", "growing_network"),
                        "generate", boom)


@pytest.mark.parametrize("where,spec,looked_for", [
    ("history", {"generator": "no_such_generator"},
     "bench/generators/no_such_generator.py"),
    ("store", {"kind": "no_such_store"}, "bench/stores/no_such_store.py"),
    ("store", {"kind": "tiered", "hot_bytes": 1024,
               "cold": {"kind": "no_such_cold"}},
     "bench/stores/no_such_cold.py"),
])
def test_unknown_name_fails_before_generation(no_generation, where, spec,
                                              looked_for):
    config = dict(CONFIG, **{where: spec})
    with pytest.raises(LookupError, match=looked_for):
        harness.Cell("registry-test", config, SEED, 3)


def test_unknown_path_fails_before_generation(no_generation, monkeypatch):
    real = harness.load_cell

    def load_cell(workload, rehearse):
        bench, wl, config, tr = real(workload, rehearse)
        return bench, wl, CONFIG, dict(tr, path="no_such_path")

    monkeypatch.setattr(harness, "load_cell", load_cell)
    with pytest.raises(LookupError, match="bench/paths/no_such_path.py"):
        harness.main(["--workload", "churn-1m.interval-loader", "--seed",
                      str(SEED), "--seconds", "3", "--rehearse"],
                     allow_cpu=True)
