"""The temporal GNN example (``examples/temporal_gnn_train.py``): its own
GCN trains on the loader's snapshots of its small history, and a second
run resumes from the first run's checkpoint."""
import importlib.util
from pathlib import Path

import numpy as np
import pytest

from repro.storage.checkpoint import latest_step, restore_checkpoint
from repro.storage.kv import LogFileKV

EXAMPLE = Path(__file__).resolve().parents[1] / "examples" / \
    "temporal_gnn_train.py"


@pytest.fixture(scope="module")
def example():
    spec = importlib.util.spec_from_file_location("temporal_gnn_train",
                                                  EXAMPLE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _no_persistent_cache(example, monkeypatch):
    # main() turns JAX's persistent compile cache on; a test leaves the
    # process's cache settings as they were
    monkeypatch.setattr(example, "enable_compile_cache", lambda: "")


def test_example_loss_is_finite_and_falls(example, tmp_path):
    losses = example.main(["--steps", "12", "--ckpt-every", "6",
                           "--ckpt-dir", str(tmp_path)])
    assert len(losses) == 12 and np.all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    assert np.mean(losses[6:]) < np.mean(losses[:6])
    store = LogFileKV(str(tmp_path))
    assert latest_step(store) == 12
    store.close()


def test_example_resumes_at_saved_step(example, tmp_path, capsys):
    example.main(["--steps", "6", "--ckpt-dir", str(tmp_path)])
    capsys.readouterr()
    losses = example.main(["--steps", "10", "--ckpt-dir", str(tmp_path)])
    assert "resumed from step 6" in capsys.readouterr().out
    assert len(losses) == 4 and np.all(np.isfinite(losses))
    # Adam's step count went on from the checkpoint: 6 restored + 4 run
    store = LogFileKV(str(tmp_path))
    like = example.init_params(np.zeros(2, np.uint32))
    (params, (m, v, t)), _, step = restore_checkpoint(store, like=like)
    store.close()
    assert step == 10 and float(t) == 10.0
    assert set(params) == {"w0", "b0", "w1", "b1"}
