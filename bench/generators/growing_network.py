"""The paper's Dataset 1 analogue (Khurana & Deshpande, §7): a growing
co-authorship network.  Node births are 30 % of the draws, each edge
joins a node biased towards the recent ones to a uniform one, and
nothing is ever deleted.

A frozen copy of ``repro.data.generators.growing_network``: it draws
the same random numbers in the same order and records the same events,
so the same seed gives a byte-identical trace
(``bench/tests/test_history.py``).  Node ids are born in order, so a
node's id is its slot.
"""
from __future__ import annotations

import numpy as np

from bench.history import ATTR_NAMES, History, _Recorder, _times


def generate(seed: int, n_events: int = 4000, n_attrs: int = 3,
             attrs_on_add: bool = True, superlinear: bool = False) -> History:
    rng = np.random.default_rng(seed)
    b = _Recorder()
    times = _times(rng, n_events, superlinear)
    nodes: list[int] = []
    budget = n_events
    i = 0
    nid = 0
    while budget > 0:
        t = int(times[min(i, len(times) - 1)])
        if len(nodes) < 2 or rng.random() < 0.3:
            attrs = ({ATTR_NAMES[j]: float(rng.random())
                      for j in range(n_attrs)} if attrs_on_add else None)
            b.add_node(nid, t, attrs=attrs)
            nodes.append(nid)
            nid += 1
            budget -= 1 + (n_attrs if attrs_on_add else 0)
        else:
            u = nodes[int(len(nodes) * rng.beta(2, 1)) - 1]
            v = nodes[rng.integers(0, len(nodes))]
            if u != v:
                b.add_edge(u, v, t, edge_id=("e", u, v, i))
                budget -= 1
        i += 1
    return b.finalize()
