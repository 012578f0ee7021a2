"""The plain reference: link-state routing by textbook Dijkstra.

Independent of the program: it reads only `perf.deployment.Topology`
and runs scipy's Dijkstra over the links that are up.

- `routes`: the FIB an Open/R router computes for itself (SP_ECMP, one
  route per other node's prefix): metric = shortest distance, next hops
  = every neighbour n with metric(self, n) + dist(n, d) == dist(self, d).
- `what_if`: per failure scenario (a shared-risk link group that fails
  in both directions), the (source, destination) pairs that become
  unreachable and those that stay reachable at a higher metric.
"""

from __future__ import annotations

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import dijkstra


def _key(a: str, b: str) -> tuple[str, str]:
    return (a, b) if a <= b else (b, a)


class Graph:
    """Directed edge arrays of a topology, with links masked out."""

    def __init__(self, topo) -> None:
        self.topo = topo
        self.n = len(topo.nodes)
        idx = topo.index
        src, dst, w, link_of, fwd = [], [], [], [], []
        self.link_id: dict[tuple[str, str], int] = {}
        for li, (a, b, mab, mba) in enumerate(topo.links):
            self.link_id[_key(a, b)] = li
            src += [idx[a], idx[b]]
            dst += [idx[b], idx[a]]
            w += [mab, mba]
            link_of += [li, li]
            fwd += [a <= b, b < a]
        self.src = np.asarray(src, dtype=np.int64)
        self.dst = np.asarray(dst, dtype=np.int64)
        self.w = np.asarray(w, dtype=np.float64)
        self.link_of = np.asarray(link_of, dtype=np.int64)
        # True for the edge from the lower name to the higher one
        self.forward = np.asarray(fwd, dtype=bool)

    def edge_mask(self, down_links, one_direction: bool = False) -> np.ndarray:
        """True for edges that are up.  `one_direction` fails only the
        edge from the lower name to the higher one (a broken guarantee,
        used as the what-if control)."""
        keep = np.ones(len(self.src), dtype=bool)
        ids = [self.link_id[_key(a, b)] for a, b in down_links if _key(a, b) in self.link_id]
        if ids:
            hit = np.isin(self.link_of, ids)
            if one_direction:
                hit &= self.forward
            keep &= ~hit
        return keep

    def dist(self, sources: list[int], keep: np.ndarray) -> np.ndarray:
        m = csr_matrix(
            (self.w[keep], (self.src[keep], self.dst[keep])), shape=(self.n, self.n)
        )
        return dijkstra(m, directed=True, indices=sources)


def routes(graph: Graph, self_node: str, down_links=()) -> dict:
    """{prefix: frozenset((neighbour, if_name, next_hop_v6, metric))}."""
    topo = graph.topo
    keep = graph.edge_mask(down_links)
    down = {_key(a, b) for a, b in down_links}
    nbrs = [a for a in topo.adj[self_node] if _key(self_node, a.other) not in down]
    rows = [topo.index[self_node]] + [topo.index[a.other] for a in nbrs]
    d = graph.dist(rows, keep)
    d_self, d_nbr = d[0], d[1:]
    out = {}
    for node, prefixes in topo.prefixes.items():
        if node == self_node:
            continue
        j = topo.index[node]
        best = d_self[j]
        if not np.isfinite(best):
            continue
        hops = frozenset(
            (a.other, a.if_name, a.next_hop_v6, int(best))
            for k, a in enumerate(nbrs)
            if a.metric + d_nbr[k, j] == best
        )
        for p in prefixes:
            out[p] = hops
    return out


def what_if(
    graph: Graph, sources: list[str], scenarios: list, one_direction: bool = False
) -> list[tuple[int, int]]:
    """Per scenario: (newly unreachable pairs, degraded pairs), over
    every source and every node of the topology."""
    rows = [graph.topo.index[s] for s in sources]
    base = graph.dist(rows, graph.edge_mask(()))
    reach = np.isfinite(base)
    out = []
    for links in scenarios:
        d = graph.dist(rows, graph.edge_mask(links, one_direction))
        now = np.isfinite(d)
        out.append(
            (int((reach & ~now).sum()), int((reach & now & (d > base)).sum()))
        )
    return out
