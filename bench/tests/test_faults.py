"""A run with the timed path broken underneath comes out not correct.

Each test drives a whole run of a cell at its rehearsal size on the CPU
(skipping only the harness's look for a chip), with one fault planted
in the program where the answer is produced."""
import json

import numpy as np
import pytest

from bench import run as harness


def result(capsys, workload: str, seed: int = 11) -> dict:
    rc = harness.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", "3", "--trace", "0", "--rehearse"],
                      allow_cpu=True)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(capsys):
    out = result(capsys, "churn-1m.interval-loader")
    assert out["correct"] is True
    assert out["checks"]["wrong_snapshots"] == {"value": 0, "limit": 0}


def test_altered_loader_answer(capsys, monkeypatch):
    from repro.core import temporal
    good = temporal.SnapshotBatchLoader._degrees

    def bad(self, edge_masks):
        deg, num_edges = good(self, edge_masks)
        return deg, num_edges + 1

    monkeypatch.setattr(temporal.SnapshotBatchLoader, "_degrees", bad)
    out = result(capsys, "churn-1m.interval-loader")
    assert out["correct"] is False
    assert out["checks"]["wrong_snapshots"]["value"] > 0


def test_half_the_batch_left_out(capsys, monkeypatch):
    """The second half of each batch repeats the first half."""
    import jax.numpy as jnp
    from repro.core import temporal
    good = temporal.SnapshotBatchLoader.__iter__

    def bad(self):
        for b in good(self):
            h = len(b["times"]) // 2
            for k in ("x", "edge_mask", "label_mask", "num_edges", "labels"):
                a = np.asarray(b[k])
                b[k] = jnp.asarray(np.concatenate([a[:h], a[:h]]))
            yield b

    monkeypatch.setattr(temporal.SnapshotBatchLoader, "__iter__", bad)
    out = result(capsys, "churn-1m.interval-loader")
    assert out["correct"] is False


def test_loader_step_returns_its_state_unchanged(capsys, monkeypatch):
    """Every prefix of the chain sweep is the window's start snapshot."""
    import jax.numpy as jnp
    from repro.runtime import jax_exec

    def stuck(bases, adds, dels):
        return jnp.repeat(bases[:, None], adds.shape[1], axis=1)

    monkeypatch.setattr(jax_exec, "delta_apply_chain_prefix_batched", stuck)
    out = result(capsys, "churn-1m.interval-loader")
    assert out["correct"] is False
    assert out["checks"]["wrong_snapshots"]["value"] > 0
