"""Synthetic topology generators as flat numpy edge arrays.

At 10k-100k nodes, building AdjacencyDatabase/LinkState Python object graphs
is pure overhead; benchmark topologies go straight to the padded directed-
edge arrays the kernels consume.  Mirrors the reference benchmark topology
classes (grid: RoutingBenchmarkUtils.h createGrid; fat-tree: createFabric
:320) plus a WAN small-world mesh for the 100k configs.

`Topology.ell` is the bucketed-ELL mirror (ops.sssp.build_ell) over padded
arrays, exactly as CsrTopology builds for production graphs."""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


def _pad_cap(n: int, quantum: int = 512) -> int:
    return ((n + quantum) // quantum) * quantum


@dataclass
class Topology:
    name: str
    n_nodes: int
    n_edges: int  # directed
    node_capacity: int
    edge_capacity: int
    edge_src: np.ndarray  # [E_cap] int32
    edge_dst: np.ndarray  # [E_cap] int32
    edge_metric: np.ndarray  # [E_cap] int32
    edge_up: np.ndarray  # [E_cap] bool
    node_overloaded: np.ndarray  # [N_cap] bool
    ell: object = None
    banded: object = None  # ops.banded.BandedGraph | None
    _runner: object = None

    @property
    def runner(self):
        """Lazy ops.banded.SpfRunner — the production fixed-sweep
        execution path (band-aware kernel dispatch + adaptive hints)."""
        if self._runner is None:
            from openr_tpu.ops.banded import SpfRunner

            self._runner = SpfRunner(
                self.ell,
                self.banded,
                self.edge_src,
                self.edge_dst,
                self.edge_metric,
                self.edge_up,
                self.node_overloaded,
                self.n_edges,
            )
            # pin the runtime arrays device-resident, so no dispatch
            # re-uploads the ~11MB of edge state.  Callers that mutate the
            # arrays in place AFTER this point must call runner.stage()
            # again (tests mutate before first runner access)
            self._runner.stage()
        return self._runner

    @classmethod
    def from_links(
        cls, name: str, n_nodes: int, links: np.ndarray, metrics: np.ndarray
    ) -> "Topology":
        """links [L, 2] int32 undirected, metrics [L] (or [L, 2] for
        asymmetric per-direction metrics)."""
        from openr_tpu.ops.banded import build_banded
        from openr_tpu.ops.sssp import build_ell

        if metrics.ndim == 1:
            metrics = np.stack([metrics, metrics], axis=1)
        # two directed edges per link, sorted by (dst, src) like CsrTopology
        src = np.concatenate([links[:, 0], links[:, 1]])
        dst = np.concatenate([links[:, 1], links[:, 0]])
        met = np.concatenate([metrics[:, 0], metrics[:, 1]])
        order = np.lexsort((src, dst))
        src, dst, met = src[order], dst[order], met[order]

        e = len(src)
        n_cap = _pad_cap(n_nodes)
        e_cap = _pad_cap(e)
        pad_node = n_cap - 1
        edge_src = np.full(e_cap, pad_node, dtype=np.int32)
        edge_dst = np.full(e_cap, pad_node, dtype=np.int32)
        edge_metric = np.ones(e_cap, dtype=np.int32)
        edge_up = np.zeros(e_cap, dtype=bool)
        edge_src[:e] = src
        edge_dst[:e] = dst
        edge_metric[:e] = met
        edge_up[:e] = True
        node_overloaded = np.zeros(n_cap, dtype=bool)
        ell = build_ell(
            edge_src, edge_dst, edge_metric, edge_up, node_overloaded, e
        )
        banded = build_banded(edge_src, edge_dst, e, n_nodes)
        return cls(
            name=name,
            n_nodes=n_nodes,
            n_edges=e,
            node_capacity=n_cap,
            edge_capacity=e_cap,
            edge_src=edge_src,
            edge_dst=edge_dst,
            edge_metric=edge_metric,
            edge_up=edge_up,
            node_overloaded=node_overloaded,
            ell=ell,
            banded=banded,
        )


def grid(n_side: int) -> Topology:
    """n_side x n_side unit-metric grid (reference createGrid)."""
    ids = np.arange(n_side * n_side, dtype=np.int32).reshape(n_side, n_side)
    horiz = np.stack([ids[:, :-1].ravel(), ids[:, 1:].ravel()], axis=1)
    vert = np.stack([ids[:-1, :].ravel(), ids[1:, :].ravel()], axis=1)
    links = np.concatenate([horiz, vert]).astype(np.int32)
    return Topology.from_links(
        f"grid{n_side * n_side}",
        n_side * n_side,
        links,
        np.ones(len(links), dtype=np.int32),
    )


def fat_tree(
    pods: int = 96,
    planes: int = 4,
    ssw_per_plane: int = 24,
    rsw_per_pod: int = 100,
) -> Topology:
    """Three-tier fabric (reference createFabric, RoutingBenchmarkUtils.h:320):
    each pod has `planes` fabric switches; fsw f of a pod uplinks to every
    spine in plane f and downlinks to every rack switch in its pod.
    Defaults give ~10k nodes with 4-way ECMP between pods."""
    n_ssw = planes * ssw_per_plane
    n_fsw = pods * planes
    n_rsw = pods * rsw_per_pod
    n = n_ssw + n_fsw + n_rsw

    def ssw_id(plane, s):
        return plane * ssw_per_plane + s

    def fsw_id(pod, f):
        return n_ssw + pod * planes + f

    def rsw_id(pod, r):
        return n_ssw + n_fsw + pod * rsw_per_pod + r

    links = []
    for pod in range(pods):
        for f in range(planes):
            fsw = fsw_id(pod, f)
            for s in range(ssw_per_plane):
                links.append((fsw, ssw_id(f, s)))
            for r in range(rsw_per_pod):
                links.append((fsw, rsw_id(pod, r)))
    links = np.asarray(links, dtype=np.int32)
    return Topology.from_links(
        f"fattree{n}", n, links, np.ones(len(links), dtype=np.int32)
    )


def wan(n_nodes: int = 100_000, chords: int = 2, seed: int = 0) -> Topology:
    """Small-world WAN mesh: ring of n nodes (adjacent + skip-2 links) plus
    `chords` random long-haul links per node, metrics 1..10 asymmetric —
    the 100k-node dual-metric WAN config (BASELINE config #3 shape)."""
    rng = np.random.RandomState(seed)
    ids = np.arange(n_nodes, dtype=np.int32)
    ring1 = np.stack([ids, (ids + 1) % n_nodes], axis=1)
    ring2 = np.stack([ids, (ids + 2) % n_nodes], axis=1)
    chord_list = []
    for _ in range(chords):
        perm = rng.permutation(n_nodes).astype(np.int32)
        chord_list.append(np.stack([ids, perm], axis=1))
    links = np.concatenate([ring1, ring2] + chord_list)
    # drop self-links from chord permutation collisions
    links = links[links[:, 0] != links[:, 1]]
    # dedupe (a, b) vs (b, a)
    key = np.sort(links, axis=1)
    _, keep = np.unique(key[:, 0].astype(np.int64) * n_nodes + key[:, 1], return_index=True)
    links = links[keep]
    metrics = rng.randint(1, 11, size=(len(links), 2)).astype(np.int32)
    return Topology.from_links(f"wan{n_nodes}", n_nodes, links, metrics)


def reversed_topology(topo: Topology) -> Topology:
    """Same nodes, every directed edge reversed (per-direction metrics
    travel with their edge) — the graph on which P-source SSSP computes
    all-sources-to-P-destinations distances (ops.allsources)."""
    from openr_tpu.ops.banded import build_banded
    from openr_tpu.ops.sssp import build_ell

    e = topo.n_edges
    src = topo.edge_dst[:e].copy()
    dst = topo.edge_src[:e].copy()
    met = topo.edge_metric[:e].copy()
    order = np.lexsort((src, dst))
    pad_node = topo.node_capacity - 1
    edge_src = np.full(topo.edge_capacity, pad_node, dtype=np.int32)
    edge_dst = np.full(topo.edge_capacity, pad_node, dtype=np.int32)
    edge_metric = np.ones(topo.edge_capacity, dtype=np.int32)
    edge_up = np.zeros(topo.edge_capacity, dtype=bool)
    edge_src[:e] = src[order]
    edge_dst[:e] = dst[order]
    edge_metric[:e] = met[order]
    edge_up[:e] = topo.edge_up[:e][order]
    node_overloaded = topo.node_overloaded.copy()
    ell = build_ell(
        edge_src, edge_dst, edge_metric, edge_up, node_overloaded, e
    )
    banded = build_banded(edge_src, edge_dst, e, topo.n_nodes)
    return Topology(
        name=topo.name + "-rev",
        n_nodes=topo.n_nodes,
        n_edges=e,
        node_capacity=topo.node_capacity,
        edge_capacity=topo.edge_capacity,
        edge_src=edge_src,
        edge_dst=edge_dst,
        edge_metric=edge_metric,
        edge_up=edge_up,
        node_overloaded=node_overloaded,
        ell=ell,
        banded=banded,
    )


def neighbors_of(topo: Topology, node: int) -> np.ndarray:
    """Unique out-neighbors of `node` among up edges."""
    mask = (topo.edge_src[: topo.n_edges] == node) & topo.edge_up[: topo.n_edges]
    return np.unique(topo.edge_dst[: topo.n_edges][mask])
