"""Generator traces are pinned: the same seed gives the same bytes."""
import hashlib

import numpy as np
import pytest

from repro.data.generators import _LiveOrder, churn_network


def trace_digest(uni, ev) -> str:
    h = hashlib.sha256()
    for a in (ev.time, ev.etype, ev.slot, ev.attr_col, ev.value, ev.old_value,
              uni.edge_src, uni.edge_dst, uni.edge_directed,
              uni.edge_transient, uni.node_transient):
        h.update(np.ascontiguousarray(a).tobytes())
    h.update(repr((uni.node_ids, uni.edge_ids, uni.node_attr_cols,
                   uni.edge_attr_cols)).encode())
    return h.hexdigest()[:16]


# Digests recorded from the list-scan delete pick that the Fenwick-tree
# pick replaced: both take the i-th live edge in insertion order.
@pytest.mark.parametrize("kwargs,digest", [
    (dict(seed=0, n_events=2000), "6dd31eff4e1f905e"),
    (dict(seed=1, n_events=5000), "255bbc80a2f1a92a"),
    (dict(seed=7, n_events=3000, n_initial_edges=100, p_delete=0.6),
     "2101a78835b47bc0"),
    (dict(seed=3, n_events=4000, n_initial_edges=2000, superlinear=True),
     "1df0db1101521a13"),
])
def test_churn_trace_pinned(kwargs, digest):
    assert trace_digest(*churn_network(**kwargs)) == digest


def test_live_order_matches_dict_order():
    rng = np.random.default_rng(4)
    order, live = _LiveOrder(600), {}
    for k in range(600):
        if live and rng.random() < 0.45:
            key = list(live)[int(rng.integers(0, len(live)))]
            assert order.nth(list(live).index(key)) == key
            order.remove(live.pop(key))
        else:
            live[k] = order.append(k)
    assert [order.nth(i) for i in range(len(live))] == list(live)
