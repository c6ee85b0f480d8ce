"""The frozen generator copies give the program's traces, byte for byte."""
import ast
import hashlib

import numpy as np
import pytest

from bench import history, parts

TRACE_ARRAYS = ("time", "etype", "slot", "attr_col", "value", "old_value")


def generate(name: str, **kwargs) -> history.History:
    return parts.find("generators", name).generate(**kwargs)


def churn_network(**kwargs) -> history.History:
    return generate("churn_network", **kwargs)


def digest(uni, ev) -> str:
    """The digest ``tests/test_generators.py`` pins, over the program's
    universe and event list."""
    h = hashlib.sha256()
    for a in (ev.time, ev.etype, ev.slot, ev.attr_col, ev.value, ev.old_value,
              uni.edge_src, uni.edge_dst, uni.edge_directed,
              uni.edge_transient, uni.node_transient):
        h.update(np.ascontiguousarray(a).tobytes())
    h.update(repr((uni.node_ids, uni.edge_ids, uni.node_attr_cols,
                   uni.edge_attr_cols)).encode())
    return h.hexdigest()[:16]


# growing_network's digests were computed from the program's
# repro.data.generators.growing_network; churn_network's are those
# tests/test_generators.py pins.
@pytest.mark.parametrize("name,kwargs,pinned", [
    ("churn_network", dict(seed=0, n_events=2000), "6dd31eff4e1f905e"),
    ("churn_network", dict(seed=1, n_events=5000), "255bbc80a2f1a92a"),
    ("churn_network", dict(seed=7, n_events=3000, n_initial_edges=100,
                           p_delete=0.6), "2101a78835b47bc0"),
    ("churn_network", dict(seed=3, n_events=4000, n_initial_edges=2000,
                           superlinear=True), "1df0db1101521a13"),
    ("growing_network", dict(seed=0, n_events=2000), "44292cc786fc8b45"),
    ("growing_network", dict(seed=1, n_events=5000, attrs_on_add=False),
     "f2b6d859eb73387a"),
    ("growing_network", dict(seed=7, n_events=3000, n_attrs=2,
                             superlinear=True), "721a80024e4bd34d"),
    ("growing_network", dict(seed=2**31 + 5, n_events=4000,
                             attrs_on_add=False, superlinear=True),
     "8cfbf420f69f23f9"),
])
def test_churn_copy_matches_pinned_digest(name, kwargs, pinned):
    assert digest(*history.to_program(generate(name, **kwargs))) == pinned


@pytest.mark.parametrize("name,kwargs", [
    ("churn_network", dict(seed=2**31 + 11, n_events=3000,
                           n_initial_edges=900)),
    ("churn_network", dict(seed=5, n_events=2000, n_initial_edges=300,
                           p_delete=0.6)),
    ("growing_network", dict(seed=2**31 + 13, n_events=6000,
                             attrs_on_add=False)),
    ("growing_network", dict(seed=9, n_events=3000, n_attrs=4)),
    ("growing_network", dict(seed=2**32 + 17, n_events=5000,
                             attrs_on_add=False, superlinear=True)),
])
def test_copy_is_byte_identical_to_program(name, kwargs):
    from repro.data import generators
    uni, ev = getattr(generators, name)(**kwargs)
    h = generate(name, **kwargs)
    for f in TRACE_ARRAYS:
        a, b = getattr(ev, f), getattr(h, f)
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), f
    assert digest(uni, ev) == digest(*history.to_program(h))


def test_copies_import_nothing_of_the_program():
    for path in sorted((parts.BENCH / "generators").glob("*.py")):
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""]
            else:
                continue
            assert not any(n.split(".")[0] == "repro" for n in names), \
                (path.name, names)


@pytest.mark.parametrize("seed", [0, 2**31 + 7])
def test_capacity_slots_are_never_born_and_spread_evenly(seed):
    from bench import reference as ref
    h = churn_network(n_initial_edges=600, n_events=4000, seed=seed)
    spec = {"node_slots": h.num_nodes + 5, "edge_slots": h.num_edges + 1000}
    p = history.with_capacity(h, spec)
    assert (p.num_nodes, p.num_edges) == (spec["node_slots"],
                                          spec["edge_slots"])
    for ends in (p.edge_src[h.num_edges:], p.edge_dst[h.num_edges:]):
        counts = np.bincount(ends, minlength=p.num_nodes)
        assert counts.max() - counts.min() <= 1
    assert np.array_equal(p.edge_src[:h.num_edges], h.edge_src)
    assert np.array_equal(p.edge_dst[:h.num_edges], h.edge_dst)
    for f in ("time", "etype", "slot"):
        assert getattr(p, f) is getattr(h, f)
    snaps = ref.Snapshots(p, [p.tmax // 2, p.tmax])
    for t in snaps.node:
        assert not snaps.node_mask(t)[h.num_nodes:].any()
        assert not snaps.edge_mask(t)[h.num_edges:].any()


def test_capacity_below_the_history_is_refused():
    h = churn_network(n_initial_edges=300, n_events=2000, seed=1)
    with pytest.raises(ValueError, match="exceeds the capacity"):
        history.with_capacity(h, {"node_slots": h.num_nodes,
                                  "edge_slots": h.num_edges - 1})
