"""The served evolve cell at its rehearsal size on the CPU: a whole run
ends correct, each of the control's breaks ends not correct, and the
benchmark's float64 reference agrees with the tier-1 tests' own."""
import json
import sys

import numpy as np
import pytest

from bench import control_serve, history, reference as ref
from bench import reference_analytics as ana
from bench import run as harness
from bench.tests.conftest import ROOT

WORKLOAD = "churn-1m.serve-evolve"


def test_rehearsal_is_correct(capsys):
    rc = harness.main(["--workload", WORKLOAD, "--seed", str(2**31 + 41),
                       "--seconds", "3", "--trace", "0", "--rehearse"],
                      allow_cpu=True)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["wrong_points"]["value"] == 0
    assert out["checks"]["failed_docs"]["value"] == 0
    assert out["metrics"]["snapshots_per_s"]["value"] > 0


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_each_break_of_the_control_fails_a_limit(seed):
    limits = harness.load_cell(WORKLOAD, True)[3]["limits"]
    got = control_serve.readings(WORKLOAD, seed, rehearse=True)
    assert set(got) == set(control_serve.BREAKS)
    for brk, checks in got.items():
        assert any(v > limits[k] for k, v in checks.items()), (brk, checks)


@pytest.mark.parametrize("seed", [7, 2**31 + 9])
def test_reference_agrees_with_the_tests_reference(seed):
    sys.path.insert(0, str(ROOT / "tests"))
    import analytics_reference as tests_ref
    config = harness.load_cell(WORKLOAD, True)[2]
    h = history.build(config, seed)
    times = np.linspace(0, h.tmax, 5).astype(int)[1:].tolist()
    snaps = ref.Snapshots(h, times)
    uni, ev = history.to_program(h)
    for t in times:
        nm, em = snaps.node_mask(t), snaps.edge_mask(t)
        tnm, tem = tests_ref.masks(uni, ev, t)
        assert np.array_equal(nm, tnm) and np.array_equal(em, tem)
        assert np.array_equal(
            ana.components(h, nm, em),
            tests_ref.components(h.edge_src, h.edge_dst, nm, em))
        a = ana.pagerank(h, nm, em)
        b = tests_ref.pagerank(h.edge_src, h.edge_dst, nm, em)
        assert np.abs(a - b).sum() < 1e-10


def test_served_span_busy_time():
    """Per thread, the union of the named spans inside the window; lines
    without them add nothing; no window or no such span reads None."""
    from bench import serve_spans as ss
    win = ("serve.window", 100, 200)
    lines = [[win],
             [("retrieve.plan", 90, 120), ("slice.quads", 110, 130),
              ("kv.wait", 140, 150), ("slice.quads", 190, 260)],
             [("retrieve.plan", 150, 160), ("retrieve.plan", 300, 310)]]
    red = ss.reduce_lines(lines)
    assert red["window_s"] == 100 / 1e9
    assert ss.busy_s(red, ["retrieve.plan", "slice.quads"]) * 1e9 == (
        pytest.approx(30 + 10 + 10))
    assert ss.busy_s(red, ["kv.wait"]) * 1e9 == pytest.approx(10)
    assert ss.busy_s(red, ["codec.decode"]) is None
    assert ss.reduce_lines(lines[1:]) is None
    assert ss.busy_s(None, ["kv.wait"]) is None


def test_traced_rehearsal_reads_the_served_metrics(capsys, monkeypatch,
                                                   tmp_path):
    """A traced run at rehearsal size reads every per-layer metric of the
    cell that the program's spans and counters feed (the device's come
    only from a chip).  Its trace and store go under ``tmp_path``, apart
    from other traced runs of the harness tests."""
    from bench import program_spans, serve_spans
    monkeypatch.setattr(harness, "RUN_DIR", tmp_path)
    monkeypatch.setattr(serve_spans, "newest_trace",
                        lambda: program_spans.newest_trace(tmp_path / "trace"))
    rc = harness.main(["--workload", WORKLOAD, "--seed", str(2**31 + 43),
                       "--seconds", "3", "--trace", "1", "--rehearse"],
                      allow_cpu=True)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True, out["checks"]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    mine = [m["name"] for m in bench["per_layer"]
            if WORKLOAD in m.get("workloads", [])
            and m["source"] in ("program_counter", "program_span")]
    assert len(mine) == 8
    for name in mine:
        assert out["metrics"][name]["value"] > 0, name
