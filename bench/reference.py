"""The plain reference: what each answer must be, from the event log alone.

Nothing here imports the program.  A snapshot at ``t`` is every event
with ``time <= t`` applied to the empty graph (membership by +1/-1
counts, which alternate along any element's life), with transient edges
left out: the paper's "Log" approach (§4.1).  Degrees are counted with
numpy over those masks.
"""
from __future__ import annotations

import numpy as np

from .history import DEL_EDGE, DEL_NODE, NEW_EDGE, NEW_NODE, History


class Snapshots:
    """Packed node and edge masks of ``h`` at each of ``times``, made by
    one sweep over the event log in time order."""

    def __init__(self, h: History, times) -> None:
        self.h = h
        self.node: dict[int, np.ndarray] = {}
        self.edge: dict[int, np.ndarray] = {}
        ncnt = np.zeros(h.num_nodes, np.int32)
        ecnt = np.zeros(h.num_edges, np.int32)
        keep = ~h.edge_transient
        pos = 0
        for t in sorted(set(int(x) for x in times)):
            hi = int(np.searchsorted(h.time, t, side="right"))
            et, sl = h.etype[pos:hi], h.slot[pos:hi]
            np.add.at(ncnt, sl[et == NEW_NODE], 1)
            np.add.at(ncnt, sl[et == DEL_NODE], -1)
            np.add.at(ecnt, sl[et == NEW_EDGE], 1)
            np.add.at(ecnt, sl[et == DEL_EDGE], -1)
            pos = hi
            self.node[t] = np.packbits(ncnt > 0)
            self.edge[t] = np.packbits((ecnt > 0) & keep)

    def node_mask(self, t: int) -> np.ndarray:
        return np.unpackbits(self.node[t], count=self.h.num_nodes).view(bool)

    def edge_mask(self, t: int) -> np.ndarray:
        return np.unpackbits(self.edge[t], count=self.h.num_edges).view(bool)


def degrees(h: History, edge_mask: np.ndarray) -> np.ndarray:
    """Live edges counted at both endpoints."""
    live = np.nonzero(edge_mask)[0]
    return (np.bincount(h.edge_src[live], minlength=h.num_nodes)
            + np.bincount(h.edge_dst[live], minlength=h.num_nodes))
