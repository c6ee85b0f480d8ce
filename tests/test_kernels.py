"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref oracle."""
import numpy as np
import pytest
from numpy.testing import assert_allclose

import jax.numpy as jnp

from repro.kernels import bucket_edges, delta_apply_chain, segment_sum
from repro.kernels.delta_apply.delta_apply import delta_apply_chain_pallas

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("W", [1, 7, 128, 1000])
@pytest.mark.parametrize("K", [0, 1, 3, 6])
def test_delta_apply_sweep(W, K):
    base = RNG.integers(0, 2 ** 32, W, dtype=np.uint32)
    adds = RNG.integers(0, 2 ** 32, (K, W), dtype=np.uint32)
    dels = RNG.integers(0, 2 ** 32, (K, W), dtype=np.uint32)
    ref = delta_apply_chain(jnp.array(base), jnp.array(adds), jnp.array(dels))
    got = delta_apply_chain_pallas(jnp.array(base), jnp.array(adds),
                                   jnp.array(dels), block_w=256,
                                   interpret=True)
    assert np.array_equal(np.asarray(ref), np.asarray(got))


@pytest.mark.parametrize("E,N,D,bn", [(100, 37, 8, 16), (1000, 200, 16, 128),
                                      (5, 3, 4, 8), (64, 64, 1, 8)])
@pytest.mark.parametrize("dtype", [np.float32])
def test_segment_sum_sweep(E, N, D, bn, dtype):
    ids = RNG.integers(0, N, E)
    data = jnp.asarray(RNG.standard_normal((E, D)), dtype)
    ref = segment_sum(data, ids, N, impl="xla")
    got = segment_sum(data, ids, N, impl="pallas", block_n=bn)
    assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)


def _loader_edges():
    """The target row of ``SnapshotBatchLoader``'s ``[2, 2E]``
    ``edge_index`` over a churn universe (every edge in both directions),
    the node count, and a live indicator per entry as the loader's
    ``edge_mask`` holds it."""
    from repro.core import GraphManager, SnapshotBatchLoader
    from repro.data.generators import churn_network
    uni, ev = churn_network(n_initial_edges=120, n_events=400, seed=3)
    with GraphManager(uni, ev, L=64, k=2) as gm:
        ids = SnapshotBatchLoader(gm, [0])._edge_index[1]
    live = RNG.random(uni.num_edges) < 0.6
    return ids, uni.num_nodes, np.concatenate([live, live])[:, None]


@pytest.mark.parametrize("case", ["loader_2e_d1", "d4", "d8", "d16",
                                  "n_not_lane_multiple", "empty",
                                  "one_target"])
def test_segment_sum_precomputed_buckets(case):
    E, N, D, bn = 200, 50, 8, 16
    ids = data = None
    if case == "loader_2e_d1":
        ids, N, live = _loader_edges()
        E, D, bn = ids.size, 1, 128
        data = jnp.asarray(live, np.float32)
    elif case in ("d4", "d16"):
        D = int(case[1:])
    elif case == "n_not_lane_multiple":
        E, N, bn = 900, 300, 128
    elif case == "empty":
        E = 0
    elif case == "one_target":
        ids = np.full(E, 37)
    if ids is None:
        ids = RNG.integers(0, N, E)
    buckets = bucket_edges(ids, N, bn)
    if data is None:
        data = jnp.asarray(RNG.standard_normal((E, D)), np.float32)
    ref = segment_sum(data, ids, N, impl="xla")
    got = segment_sum(data, ids, N, impl="pallas", block_n=bn,
                      buckets=buckets)
    assert got.shape == (N, D)
    # float32 sums in another order: sums near zero need an absolute
    # tolerance (d8 is the original case, held to its original check)
    atol = 0.0 if case == "d8" else 1e-6
    assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=atol)


# ---------------------------------------------------------------------------
# fused chain + analytics
# ---------------------------------------------------------------------------


def _rand_chain(W, K, with_weights=True, rng=RNG):
    base = rng.integers(0, 2 ** 32, W, dtype=np.uint32)
    adds = rng.integers(0, 2 ** 32, (K, W), dtype=np.uint32)
    dels = rng.integers(0, 2 ** 32, (K, W), dtype=np.uint32)
    w = rng.random(W * 32, dtype=np.float32) if with_weights else None
    return (jnp.asarray(base), jnp.asarray(adds), jnp.asarray(dels),
            None if w is None else jnp.asarray(w))


def _fused_oracle(base, adds, dels, w, block_w, emit_live=True):
    from repro.kernels.delta_apply.ops import _fused_pad
    from repro.kernels.delta_apply.ref import delta_apply_fused_ref
    pb, pa, pd, pw, W = _fused_pad(base, adds, dels, w, block_w)
    m, pop, accw, live = delta_apply_fused_ref(pb, pa, pd, pw,
                                               block_w=block_w,
                                               emit_live=emit_live)
    return (m[:W], pop, accw[:W],
            None if live is None else live[:W * 32])


@pytest.mark.parametrize("W,K,block_w", [
    (100, 0, 128),        # K=0: identity chain, analytics over the base
    (300, 3, 128),        # W not a multiple of block_w
    (1024, 5, 256),       # exact block multiple
    (128, 1, 128),        # single block, single delta
    (129, 2, 128),        # one word past a bucket boundary
])
@pytest.mark.parametrize("weighted", [True, False])
def test_fused_parity_bitwise(W, K, block_w, weighted):
    """Fused analytics must be *bit-identical* across pallas-interpret,
    XLA, and the padded ref oracle — including the f32 partials (fixed
    per-word reduction groups)."""
    from repro.kernels import delta_apply_fused
    base, adds, dels, w = _rand_chain(W, K, weighted)
    rm, rp, ra, rl = _fused_oracle(base, adds, dels, w, block_w)
    for impl, interp in (("pallas", True), ("xla", None)):
        out = delta_apply_fused(base, adds, dels, w, impl=impl,
                                block_w=block_w, interpret=interp)
        assert np.array_equal(np.asarray(out.mask), np.asarray(rm)), impl
        assert np.array_equal(np.asarray(out.pop), np.asarray(rp)), impl
        assert np.array_equal(np.asarray(out.accw), np.asarray(ra)), impl
        assert np.array_equal(np.asarray(out.live), np.asarray(rl)), impl


def test_fused_emit_live_off():
    from repro.kernels import delta_apply_fused
    base, adds, dels, w = _rand_chain(256, 2)
    for impl, interp in (("pallas", True), ("xla", None)):
        out = delta_apply_fused(base, adds, dels, w, impl=impl,
                                block_w=128, interpret=interp,
                                emit_live=False)
        assert out.live is None
        rm, rp, ra, _ = _fused_oracle(base, adds, dels, w, 128,
                                      emit_live=False)
        assert np.array_equal(np.asarray(out.mask), np.asarray(rm))
        assert np.array_equal(np.asarray(out.pop), np.asarray(rp))


def test_fused_matches_plain_chain_mask():
    from repro.kernels import delta_apply_chain, delta_apply_fused
    base, adds, dels, _ = _rand_chain(777, 4, False)
    plain = delta_apply_chain(base, adds, dels, impl="xla")
    out = delta_apply_fused(base, adds, dels, impl="xla")
    assert np.array_equal(np.asarray(plain), np.asarray(out.mask))
    assert int(out.live_count()) == int(
        np.unpackbits(np.asarray(plain).view(np.uint8)).sum())


def test_fused_batched_parity():
    from repro.kernels import delta_apply_fused, delta_apply_fused_batched
    rng = np.random.default_rng(5)
    B, K, W = 3, 4, 200
    bases = jnp.asarray(rng.integers(0, 2 ** 32, (B, W), dtype=np.uint32))
    adds = jnp.asarray(rng.integers(0, 2 ** 32, (B, K, W), dtype=np.uint32))
    dels = jnp.asarray(rng.integers(0, 2 ** 32, (B, K, W), dtype=np.uint32))
    w = jnp.asarray(rng.random(W * 32, dtype=np.float32))
    for impl, interp in (("pallas", True), ("xla", None)):
        out = delta_apply_fused_batched(bases, adds, dels, w, impl=impl,
                                        block_w=128, interpret=interp)
        for i in range(B):
            one = delta_apply_fused(bases[i], adds[i], dels[i], w,
                                    impl="xla", block_w=128)
            assert np.array_equal(np.asarray(out.mask[i]),
                                  np.asarray(one.mask))
            assert np.array_equal(np.asarray(out.pop[i]),
                                  np.asarray(one.pop))
            assert np.array_equal(np.asarray(out.accw[i]),
                                  np.asarray(one.accw))


try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=25, deadline=None)
    @given(st.integers(1, 400), st.integers(0, 6), st.integers(0, 2 ** 31))
    def test_fused_parity_hypothesis(W, K, seed):
        from repro.kernels import delta_apply_fused
        rng = np.random.default_rng(seed)
        base, adds, dels, w = _rand_chain(W, K, True, rng)
        rm, rp, ra, rl = _fused_oracle(base, adds, dels, w, 128)
        out = delta_apply_fused(base, adds, dels, w, impl="xla",
                                block_w=128)
        assert np.array_equal(np.asarray(out.mask), np.asarray(rm))
        assert np.array_equal(np.asarray(out.pop), np.asarray(rp))
        assert np.array_equal(np.asarray(out.accw), np.asarray(ra))
        assert np.array_equal(np.asarray(out.live), np.asarray(rl))
except ImportError:  # pragma: no cover
    pass


# ---------------------------------------------------------------------------
# impl/interpret policy
# ---------------------------------------------------------------------------


def test_policy_defaults_off_tpu(monkeypatch):
    from repro.kernels import policy
    monkeypatch.delenv("REPRO_KERNEL", raising=False)
    monkeypatch.delenv("REPRO_KERNEL_INTERPRET", raising=False)
    monkeypatch.setattr(policy, "backend", lambda: "cpu")
    assert policy.resolve() == ("xla", True)
    # explicit call-site values always win
    assert policy.resolve("pallas", False) == ("pallas", False)


def test_policy_defaults_on_tpu(monkeypatch):
    from repro.kernels import policy
    monkeypatch.delenv("REPRO_KERNEL", raising=False)
    monkeypatch.delenv("REPRO_KERNEL_INTERPRET", raising=False)
    monkeypatch.setattr(policy, "backend", lambda: "tpu")
    assert policy.resolve() == ("pallas", False)


@pytest.mark.parametrize("var,value", [("REPRO_KERNEL_INTERPRET", "1"),
                                       ("REPRO_KERNEL", "xla")])
def test_policy_tpu_refuses_env_fallback(monkeypatch, var, value):
    """On a TPU no environment setting may route around the compiled
    kernel; a call site that asks explicitly still wins."""
    from repro.kernels import policy
    monkeypatch.delenv("REPRO_KERNEL", raising=False)
    monkeypatch.delenv("REPRO_KERNEL_INTERPRET", raising=False)
    monkeypatch.setattr(policy, "backend", lambda: "tpu")
    monkeypatch.setenv(var, value)
    with pytest.raises(ValueError, match="TPU"):
        policy.resolve()
    assert policy.resolve("xla", True) == ("xla", True)
    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "0")
    monkeypatch.setenv("REPRO_KERNEL", "pallas")
    assert policy.resolve() == ("pallas", False)


def test_policy_env_override(monkeypatch):
    from repro.kernels import policy
    monkeypatch.setattr(policy, "backend", lambda: "cpu")
    monkeypatch.setenv("REPRO_KERNEL", "pallas")
    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "1")
    assert policy.resolve() == ("pallas", True)
    monkeypatch.setenv("REPRO_KERNEL", "bogus")
    with pytest.raises(ValueError):
        policy.resolve()
    with pytest.raises(ValueError):
        policy.resolve("mosaic")


def test_policy_drives_kernel_entry(monkeypatch):
    """REPRO_KERNEL steers un-annotated calls through both impls — same
    bits either way."""
    from repro.kernels import delta_apply_chain
    base, adds, dels, _ = _rand_chain(200, 3, False)
    outs = {}
    for impl in ("xla", "pallas"):
        monkeypatch.setenv("REPRO_KERNEL", impl)
        monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "1")
        outs[impl] = np.asarray(delta_apply_chain(base, adds, dels))
    assert np.array_equal(outs["xla"], outs["pallas"])


def test_recompile_counts_bounded():
    """Shape bucketing bounds retraces: many distinct (B, K) shapes inside
    one bucket compile once per entry point."""
    from repro.kernels import delta_apply_chain, delta_apply_chain_batched
    from repro.kernels.delta_apply import ops
    rng = np.random.default_rng(9)
    W = 128          # aligned: isolates the K/B bucketing
    ops.reset_trace_counts()
    for K in (5, 6, 7, 8):          # all bucket to Kp=8
        adds = jnp.asarray(rng.integers(0, 2 ** 32, (K, W), np.uint32))
        dels = jnp.asarray(rng.integers(0, 2 ** 32, (K, W), np.uint32))
        base = jnp.asarray(rng.integers(0, 2 ** 32, W, np.uint32))
        delta_apply_chain(base, adds, dels, impl="xla")
    assert ops.trace_counts["chain"] <= 1   # may be cached from earlier runs
    ops.reset_trace_counts()
    for B, K, Wb in ((2, 3, 128), (2, 4, 128), (2, 3, 100)):  # Bp=2 Kp=4 Wp=128
        bases = jnp.asarray(rng.integers(0, 2 ** 32, (B, Wb), np.uint32))
        adds = jnp.asarray(rng.integers(0, 2 ** 32, (B, K, Wb), np.uint32))
        dels = jnp.asarray(rng.integers(0, 2 ** 32, (B, K, Wb), np.uint32))
        delta_apply_chain_batched(bases, adds, dels, impl="xla")
    assert ops.trace_counts["chain_batched"] <= 1

