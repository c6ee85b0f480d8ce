"""Process hygiene around the accelerator: who may take the device, and
where compiled programs are cached."""
import os
from pathlib import Path

import jax
import pytest

from repro.launch import shardd
from repro.runtime import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_shardd_children_stay_off_the_device(monkeypatch):
    """shardd only fetches blobs: its children are pinned to the CPU even
    when the coordinator's own environment names an accelerator."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    seen = {}

    class Spawned(Exception):
        pass

    def fake_popen(cmd, **kw):
        seen.update(kw["env"])
        raise Spawned

    monkeypatch.setattr(shardd.subprocess, "Popen", fake_popen)
    with pytest.raises(Spawned):
        shardd.spawn_shard_procs(1)
    assert seen["JAX_PLATFORMS"] == "cpu"
    assert str(ROOT / "src") in seen["PYTHONPATH"].split(os.pathsep)
    assert os.environ["JAX_PLATFORMS"] == "tpu"   # the parent's is untouched


@pytest.mark.parametrize("preset", [None, "/elsewhere/jax-cache"])
def test_compile_cache_location(monkeypatch, preset):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it and the helper
    sets no directory; without it the cache sits at one fixed path in the
    checkout."""
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.__setitem__(name, value))
    if preset is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", preset)
    got = compile_cache.enable_compile_cache()
    assert calls["jax_persistent_cache_min_compile_time_secs"] == 0.0
    if preset is None:
        assert got == str(ROOT / ".jax_cache")
        assert calls["jax_compilation_cache_dir"] == got
    else:
        assert got == preset
        assert "jax_compilation_cache_dir" not in calls
