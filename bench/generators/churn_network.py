"""The paper's Dataset 2/3 analogue (Khurana & Deshpande, §7): a starting
graph, then edge additions and deletions, attribute updates and
transient edges.

A frozen copy of ``repro.data.generators.churn_network``: it draws the
same random numbers in the same order and records the same events, so
the same seed gives a byte-identical trace (``bench/tests/test_history.py``).
"""
from __future__ import annotations

import numpy as np

from bench.history import ATTR_NAMES, History, _LiveOrder, _Recorder, _times


def generate(seed: int, n_initial_edges: int = 500, n_events: int = 4000,
             p_delete: float = 0.4, p_attr_update: float = 0.1,
             p_transient: float = 0.02, n_attrs: int = 2,
             superlinear: bool = False) -> History:
    rng = np.random.default_rng(seed)
    b = _Recorder()
    n_nodes = max(8, n_initial_edges // 3)
    for n in range(n_nodes):
        b.add_node(n, 0, attrs={ATTR_NAMES[j]: float(rng.random())
                                for j in range(n_attrs)})
    live: dict[tuple[int, int], tuple[int, int]] = {}
    order = _LiveOrder(n_initial_edges + n_events)
    eid = 0
    for _ in range(n_initial_edges):
        u, v = rng.integers(0, n_nodes, 2)
        if u == v or (int(u), int(v)) in live or (int(v), int(u)) in live:
            continue
        key = (int(u), int(v))
        live[key] = (b.add_edge(*key, 1, edge_id=("e", eid)),
                     order.append(key))
        eid += 1
    times = _times(rng, n_events, superlinear) + 2
    i = 0
    emitted = 0
    while emitted < n_events:
        t = int(times[min(i, len(times) - 1)])
        i += 1
        r = rng.random()
        if r < p_transient:
            u, v = rng.integers(0, n_nodes, 2)
            b.transient_edge(int(u), int(v), t)
            emitted += 1
        elif r < p_transient + p_attr_update:
            n = int(rng.integers(0, n_nodes))
            b.set_node_attr(n, ATTR_NAMES[int(rng.integers(0, n_attrs))],
                            float(rng.random()), t)
            emitted += 1
        elif live and r < p_transient + p_attr_update + p_delete:
            slot, pos = live.pop(order.nth(int(rng.integers(0, len(live)))))
            order.remove(pos)
            b.delete_edge_slot(slot, t)
            emitted += 1
        else:
            u, v = rng.integers(0, n_nodes, 2)
            if u == v or (int(u), int(v)) in live or (int(v), int(u)) in live:
                continue
            key = (int(u), int(v))
            live[key] = (b.add_edge(*key, t, edge_id=("e", eid)),
                         order.append(key))
            eid += 1
            emitted += 1
    return b.finalize()
