"""Seeded historical graphs.

Each generator is a file of its own under ``bench/generators/``, a
frozen copy of the generator of that name in ``repro.data.generators``:
it draws exactly the random numbers the original draws, in the same
order, and records the same events through :class:`_Recorder`, so the
same seed gives a byte-identical trace (``bench/tests/test_history.py``).
They are copied so that a change to the program cannot change the data
a cell runs on.

A history is kept as plain arrays (:class:`History`), built without the
program.  :func:`to_program` hands it to the program's own universe and
event list, the form ``GraphManager`` indexes; the plain reference
(``bench/reference.py``) reads the arrays directly.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np

from . import parts

ATTR_NAMES = [f"attr{i}" for i in range(10)]

# event codes, as the paper's event model numbers them (§3.1)
NEW_NODE, DEL_NODE, NEW_EDGE, DEL_EDGE = 0, 1, 2, 3
UPD_NODE_ATTR, TRANS_EDGE = 4, 6


@dataclasses.dataclass
class History:
    """A chronologically sorted event log over dense node and edge slots."""

    time: np.ndarray        # int64[M]
    etype: np.ndarray       # int8[M]
    slot: np.ndarray        # int32[M]
    attr_col: np.ndarray    # int16[M]
    value: np.ndarray       # float32[M]
    old_value: np.ndarray   # float32[M]
    node_ids: list
    edge_ids: list
    edge_src: np.ndarray    # int32[E]
    edge_dst: np.ndarray    # int32[E]
    edge_directed: np.ndarray   # bool[E]
    edge_transient: np.ndarray  # bool[E]
    node_attr_cols: dict

    @property
    def num_nodes(self) -> int:
        return len(self.node_ids)

    @property
    def num_edges(self) -> int:
        return len(self.edge_ids)

    @property
    def tmax(self) -> int:
        return int(self.time[-1])


class _Recorder:
    """The builder's bookkeeping, reduced to what the generators use."""

    def __init__(self) -> None:
        self.rows: list[tuple] = []
        self.node_ids: list = []
        self.edge_ids: list = []
        self.src: list[int] = []
        self.dst: list[int] = []
        self.directed: list[bool] = []
        self.transient: list[bool] = []
        self.cols: dict[str, int] = {}
        self.attr_state: dict[tuple[int, int], float] = {}

    def add_node(self, nid: int, t: int, attrs=None) -> int:
        s = len(self.node_ids)        # every generator id is new
        self.node_ids.append(nid)
        self.rows.append((t, NEW_NODE, s, -1, np.nan, np.nan))
        for k, v in (attrs or {}).items():
            self.set_node_attr(s, k, v, t)
        return s

    def _edge(self, key, u: int, v: int, directed: bool,
              transient: bool) -> int:
        s = len(self.edge_ids)
        self.edge_ids.append(key)
        self.src.append(u)
        self.dst.append(v)
        self.directed.append(directed)
        self.transient.append(transient)
        return s

    def add_edge(self, u: int, v: int, t: int, edge_id) -> int:
        s = self._edge(edge_id, u, v, False, False)
        self.rows.append((t, NEW_EDGE, s, -1, np.nan, np.nan))
        return s

    def delete_edge_slot(self, slot: int, t: int) -> None:
        self.rows.append((t, DEL_EDGE, slot, -1, np.nan, np.nan))

    def set_node_attr(self, s: int, name: str, value: float, t: int) -> None:
        c = self.cols.setdefault(name, len(self.cols))
        val = float(value)
        old = self.attr_state.get((s, c), np.nan)
        self.attr_state[(s, c)] = val
        self.rows.append((t, UPD_NODE_ATTR, s, c, val, old))

    def transient_edge(self, u: int, v: int, t: int) -> int:
        s = self._edge(("__te", u, v, t, len(self.rows)), u, v, True, True)
        self.rows.append((t, TRANS_EDGE, s, -1, np.nan, np.nan))
        return s

    def finalize(self) -> History:
        rows = self.rows
        t = np.fromiter((r[0] for r in rows), np.int64, len(rows))
        order = np.argsort(t, kind="stable")
        cols = [np.fromiter((r[i] for r in rows), dt, len(rows))[order]
                for i, dt in ((1, np.int8), (2, np.int32), (3, np.int16),
                              (4, np.float32), (5, np.float32))]
        return History(t[order], *cols, self.node_ids, self.edge_ids,
                       np.asarray(self.src, np.int32),
                       np.asarray(self.dst, np.int32),
                       np.asarray(self.directed, bool),
                       np.asarray(self.transient, bool), dict(self.cols))


def _times(rng, n: int, superlinear: bool) -> np.ndarray:
    if superlinear:
        u = np.sort(rng.uniform(0, 1, n))
        t = (np.sqrt(u) * n * 10).astype(np.int64)
    else:
        t = np.sort(rng.integers(0, n * 10, n).astype(np.int64))
    return t


class _LiveOrder:
    """Live edges in insertion order; the i-th live one in O(log n)
    through a Fenwick tree over insertion positions."""

    def __init__(self, cap: int):
        self._tree = [0] * (cap + 1)
        self._step = 1 << cap.bit_length()
        self._keys: list = []

    def _add(self, pos: int, delta: int) -> None:
        i = pos + 1
        while i < len(self._tree):
            self._tree[i] += delta
            i += i & -i

    def append(self, key) -> int:
        pos = len(self._keys)
        self._keys.append(key)
        self._add(pos, 1)
        return pos

    def remove(self, pos: int) -> None:
        self._add(pos, -1)

    def nth(self, i: int):
        pos, step, tree = 0, self._step, self._tree
        while step:
            nxt = pos + step
            if nxt < len(tree) and tree[nxt] <= i:
                pos = nxt
                i -= tree[nxt]
            step >>= 1
        return self._keys[pos]


def generator(spec: dict) -> Callable[[int], History]:
    """The generator a configuration's ``history`` entry names,
    ``{"generator": name, **parameters}``: ``bench/generators/<name>.py``,
    found before anything is generated.  Call it with the seed."""
    mod = parts.find("generators", spec["generator"])
    params = {k: v for k, v in spec.items() if k != "generator"}
    return lambda seed: mod.generate(seed, **params)


def with_capacity(h: History, spec: dict) -> History:
    """``h`` in a universe of ``spec["node_slots"]`` node and
    ``spec["edge_slots"]`` edge slots, the store's fixed capacity: slots
    past the history's own are registered and never born, so no snapshot
    holds them, and every seed gives the program planes and edge arrays
    of one shape.  Padding edge ``i`` joins node slots ``i`` and ``i + 1``
    (modulo the node slots), so the padding spreads evenly over the nodes
    whatever layout the program gives them."""
    n_cap, e_cap = int(spec["node_slots"]), int(spec["edge_slots"])
    n_pad, e_pad = n_cap - h.num_nodes, e_cap - h.num_edges
    if n_pad < 0 or e_pad < 0:
        raise ValueError(f"history of {h.num_nodes} nodes and {h.num_edges} "
                         f"edges exceeds the capacity {n_cap}, {e_cap}")
    src = np.arange(e_pad, dtype=np.int32) % n_cap
    return dataclasses.replace(
        h,
        node_ids=h.node_ids + [("pad", i) for i in range(n_pad)],
        edge_ids=h.edge_ids + [("pad", i) for i in range(e_pad)],
        edge_src=np.concatenate([h.edge_src, src]),
        edge_dst=np.concatenate([h.edge_dst, (src + 1) % n_cap]),
        edge_directed=np.concatenate([h.edge_directed,
                                      np.zeros(e_pad, bool)]),
        edge_transient=np.concatenate([h.edge_transient,
                                       np.zeros(e_pad, bool)]))


def build(config: dict, seed: int) -> History:
    """The history of a configuration file, at its capacity."""
    return with_capacity(generator(config["history"])(seed),
                         config["universe"])


def to_program(h: History):
    """The program's ``(GraphUniverse, EventList)`` for ``h``, filled
    through the universe's public registration calls."""
    from repro.core.events import EventList, GraphUniverse
    uni = GraphUniverse()
    for nid in h.node_ids:
        uni.node_slot(nid, create=True)
    for key, u, v, d, tr in zip(h.edge_ids, h.edge_src.tolist(),
                                h.edge_dst.tolist(), h.edge_directed.tolist(),
                                h.edge_transient.tolist()):
        uni.new_edge_slot(key, u, v, d, transient=tr)
    for name in h.node_attr_cols:
        uni.attr_col("node", name, create=True)
    ev = EventList(h.time, h.etype, h.slot, h.attr_col, h.value,
                   h.old_value)
    return uni, ev
