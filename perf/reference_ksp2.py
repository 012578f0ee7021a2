"""The plain reference for KSP2_ED_ECMP over SR_MPLS (grid1k_ksp2).

Independent of the program: it reads only `perf.deployment.Topology`
and runs scipy's Dijkstra over the links that are up.  Node labels are
`index + 1`, as the harness advertises them.

Open/R's KSP2_ED_ECMP (Decision.cpp selectBestPathsKsp2, LinkState.cpp
getKthPaths): the route to a prefix goes over edge-disjoint shortest
paths from the router to the advertiser (k=1) and, once every k=1 link
is removed, over edge-disjoint shortest paths of what is left (k=2).
Each path is one next hop: its first hop's interface and address, the
path's metric, and an MPLS PUSH of the node labels of the later nodes,
the advertiser's at the bottom of the stack and the first hop's label
left off.

Which paths are taken among equals depends on the order in which they
are traced, so `n_wrong` holds a FIB to what every such choice has.  A
prefix is wrong unless:

- (a) every next hop's stack spells a walk over links that are up, from
  its first hop (matched by interface and address) to the advertiser,
  whose metric is the next hop's;
- (b) the walks of the shortest distance (k=1) are pairwise
  edge-disjoint, and with their links removed the distance is larger;
- (c) the other walks (k=2) have the distance with every k=1 link
  removed, are pairwise edge-disjoint and disjoint from the k=1 links,
  and with their links removed too the distance is larger; there are
  none exactly when that distance is infinite;
- (d) it is in the FIB exactly when its advertiser is reachable.

`routes` builds one route set with these properties (the control's).
"""

from __future__ import annotations

from collections import namedtuple

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import dijkstra

# a route in the FIB agent's form
Route = namedtuple("Route", "next_hops")
NextHop = namedtuple("NextHop", "neighbor_node_name if_name address metric mpls_action")
Push = namedtuple("Push", "push_labels")


class Graph:
    """Directed edges of a topology, by link, with links removable."""

    def __init__(self, topo) -> None:
        self.topo = topo
        self.n = len(topo.nodes)
        idx = topo.index
        src, dst, w, link_of = [], [], [], []
        self.link_id: dict[frozenset, int] = {}
        self.metric: dict[tuple[str, str], int] = {}
        for li, (a, b, mab, mba) in enumerate(topo.links):
            self.link_id[frozenset((a, b))] = li
            self.metric[(a, b)], self.metric[(b, a)] = mab, mba
            src += [idx[a], idx[b]]
            dst += [idx[b], idx[a]]
            w += [mab, mba]
            link_of += [li, li]
        self.src = np.asarray(src, dtype=np.int64)
        self.dst = np.asarray(dst, dtype=np.int64)
        self.w = np.asarray(w, dtype=np.float64)
        self.link_of = np.asarray(link_of, dtype=np.int64)
        self.node_of_label = {i + 1: n for n, i in idx.items()}

    def label(self, node: str) -> int:
        return self.topo.index[node] + 1

    def links(self, pairs) -> set[int]:
        """Link ids of (a, b) pairs; pairs that are no link are left out."""
        ids = (self.link_id.get(frozenset(p)) for p in pairs)
        return {i for i in ids if i is not None}

    def _matrix(self, removed) -> csr_matrix:
        keep = ~np.isin(self.link_of, list(removed)) if removed else slice(None)
        return csr_matrix(
            (self.w[keep], (self.src[keep], self.dst[keep])), shape=(self.n, self.n)
        )

    def dist(self, node: str, removed=()) -> np.ndarray:
        """Distances from `node` with the links `removed` taken out."""
        return dijkstra(self._matrix(removed), directed=True, indices=self.topo.index[node])

    def shortest_path(self, a: str, b: str, removed=()):
        """(distance, node list) of one shortest path, or (inf, None)."""
        d, pred = dijkstra(
            self._matrix(removed),
            directed=True,
            indices=self.topo.index[a],
            return_predecessors=True,
        )
        j = self.topo.index[b]
        if not np.isfinite(d[j]):
            return np.inf, None
        nodes = self.topo.nodes
        path = [j]
        while path[-1] != self.topo.index[a]:
            path.append(int(pred[path[-1]]))
        return d[j], [nodes[i] for i in reversed(path)]


def _path_links(graph: Graph, path: list[str]) -> set[int]:
    return graph.links(zip(path, path[1:]))


def _disjoint_shortest(graph: Graph, a: str, b: str, removed: set[int]) -> list[list[str]]:
    """Shortest a-b paths, each with the links of those before removed,
    while the distance stays the first's."""
    out: list[list[str]] = []
    removed = set(removed)
    best = None
    while True:
        d, path = graph.shortest_path(a, b, removed)
        if path is None or (best is not None and d > best):
            return out
        best = d
        out.append(path)
        removed |= _path_links(graph, path)


def routes(graph: Graph, self_node: str, down_links=()) -> dict:
    """{prefix: Route}: one KSP2_ED_ECMP route set of the state with
    `down_links` down."""
    topo = graph.topo
    down = graph.links(down_links)
    first_hop = {a.other: a for a in topo.adj[self_node]}
    out = {}
    for node, prefixes in topo.prefixes.items():
        if node == self_node:
            continue
        k1 = _disjoint_shortest(graph, self_node, node, down)
        if not k1:
            continue
        used = set(down).union(*(_path_links(graph, p) for p in k1))
        hops = []
        for path in k1 + _disjoint_shortest(graph, self_node, node, used):
            adj = first_hop[path[1]]
            labels = tuple(graph.label(n) for n in reversed(path[2:]))
            hops.append(
                NextHop(
                    adj.other,
                    adj.if_name,
                    adj.next_hop_v6,
                    sum(graph.metric[e] for e in zip(path, path[1:])),
                    Push(labels) if labels else None,
                )
            )
        for p in prefixes:
            out[p] = Route(hops)
    return out


class Checker:
    """`n_wrong` for one router over its topology (module docstring)."""

    def __init__(self, graph: Graph, self_node: str) -> None:
        self.graph = graph
        self.me = self_node
        self.first_hop = {(a.if_name, a.next_hop_v6): a.other for a in graph.topo.adj[self_node]}

    def _walk(self, nh, dest: str, down: set[int]):
        """(link ids, metric) of the walk a next hop spells, or None where
        it spells none that ends at `dest` over links that are up."""
        g = self.graph
        first = self.first_hop.get((nh.if_name, nh.address))
        if first is None or first != nh.neighbor_node_name:
            return None
        labels = getattr(nh.mpls_action, "push_labels", None) or ()
        later = [g.node_of_label.get(label) for label in reversed(labels)]
        if None in later:
            return None
        path = [self.me, first, *later]
        if path[-1] != dest:
            return None
        ids = [g.link_id.get(frozenset(e)) for e in zip(path, path[1:])]
        if None in ids or down.intersection(ids) or len(set(ids)) != len(ids):
            return None
        metric = sum(g.metric[e] for e in zip(path, path[1:]))
        return set(ids), metric

    def _route_ok(self, route, dest: str, best: float, down: set[int]) -> bool:
        walks = []
        for nh in route.next_hops:
            w = self._walk(nh, dest, down)
            if w is None or w[1] != int(nh.metric):
                return False
            walks.append(w)
        k1 = [ids for ids, m in walks if m == best]
        k2 = [(ids, m) for ids, m in walks if m != best]
        if not k1 or not _disjoint(k1):
            return False
        removed = down.union(*k1)
        j = self.graph.topo.index[dest]
        second = self.graph.dist(self.me, removed)[j]
        if not second > best:
            return False  # (b) not maximal
        if not k2:
            return not np.isfinite(second)
        if any(m != second for _ids, m in k2):
            return False
        k2_ids = [ids for ids, _m in k2]
        if not _disjoint(k2_ids) or any(ids & removed for ids in k2_ids):
            return False
        return self.graph.dist(self.me, removed.union(*k2_ids))[j] > second

    def n_wrong(self, fib: dict, down_links=()) -> int:
        """Prefixes of `fib` ({prefix: route}) that break (a)-(d) in the
        state with `down_links` down."""
        g = self.graph
        down = g.links(down_links)
        d_self = g.dist(self.me, down)
        wrong = 0
        want = set()
        for node, prefixes in g.topo.prefixes.items():
            best = d_self[g.topo.index[node]]
            if node == self.me or not np.isfinite(best):
                continue
            for p in prefixes:
                want.add(p)
                route = fib.get(p)
                if route is None or not self._route_ok(route, node, best, down):
                    wrong += 1
        return wrong + len(set(fib) - want)


def _disjoint(link_sets: list[set[int]]) -> bool:
    return sum(map(len, link_sets)) == len(set().union(*link_sets))
