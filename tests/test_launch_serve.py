"""The serving launcher's command line (``launch/serve.py``), called in
process at tiny sizes: with no ``--mode`` it answers NDJSON documents as
``--mode query`` does, and the snapshot, evolve and ingest modes run to
their reports."""
import json

import numpy as np
import pytest

from repro.core import replay
from repro.data.generators import churn_network
from repro.launch import serve

EVENTS = 1500


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    # main() turns JAX's persistent compile cache on; a test leaves the
    # process's cache settings as they were
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: "")


def _envelopes(out: str) -> list[dict]:
    return [json.loads(line) for line in out.splitlines() if line.strip()]


def _answers(env):
    """An envelope without its timings."""
    if isinstance(env, dict):
        return {k: _answers(v) for k, v in env.items()
                if k not in ("stats", "wall_s")}
    return env


def test_default_mode_answers_documents(tmp_path, capsys):
    # the launcher's own history for --events EVENTS
    uni, ev = churn_network(n_initial_edges=max(EVENTS // 12, 50),
                            n_events=EVENTS, seed=0)
    t1, t2 = int(ev.time[300]), int(ev.time[1200])
    docs = tmp_path / "docs.ndjson"
    docs.write_text("\n".join([
        json.dumps({"kind": "snapshot", "t": t1, "attrs": ""}),
        json.dumps({"kind": "multipoint", "times": [t1, t2]}),
        "not json",
        json.dumps({"kind": "evolve", "times": [t1, t2], "op": "density"}),
    ]) + "\n")
    argv = ["--events", str(EVENTS), "--input", str(docs)]
    serve.main(argv)
    default = capsys.readouterr()
    serve.main(["--mode", "query"] + argv)
    query = capsys.readouterr()

    envs = _envelopes(default.out)
    assert [e["ok"] for e in envs] == [True, True, False, True]
    assert "served 4 documents (3 ok)" in default.err
    truth = replay(uni, ev, t1)
    assert envs[0]["result"]["nodes"] == int(truth.node_mask.sum())
    assert envs[0]["result"]["edges"] == int(truth.edge_mask.sum())
    assert [p["t"] for p in envs[1]["result"]["points"]] == [t1, t2]
    # the same answers as --mode query; only the timings differ
    assert ([_answers(e) for e in envs]
            == [_answers(e) for e in _envelopes(query.out)])


def test_snapshots_mode(capsys):
    serve.main(["--mode", "snapshots", "--events", str(EVENTS),
                "--queries", "40", "--budget-mb", "1"])
    out = capsys.readouterr().out
    for head in ("cold    :", "advised :", "store   : codec=v2 kv=mem",
                 "kv      :"):
        assert head in out


def test_evolve_mode(capsys):
    serve.main(["--mode", "evolve", "--events", str(EVENTS),
                "--intervals", "2", "--points", "4", "--op", "density"])
    out = capsys.readouterr().out
    assert "recompute" in out and "incremental" in out
    assert "(2 intervals x 4 points, op=density)" in out


def test_ingest_mode_summary(capsys):
    events = EVENTS
    serve.main(["--mode", "ingest", "--events", str(events),
                "--duration", "0.25", "--readers", "1", "--group", "128"])
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert last.startswith("INGEST_SUMMARY ")
    s = json.loads(last.split(" ", 1)[1])
    built = max(events // 5, 200)
    assert s["committed_events"] == events - built
    assert s["docs_served"] > 0 and s["docs_failed"] == 0
    assert np.isfinite(s["events_per_s"]) and s["events_per_s"] > 0
