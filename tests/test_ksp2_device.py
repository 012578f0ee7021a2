"""Device-batched KSP2 conformance: DeviceSpfBackend.get_kth_paths /
prefetch_kth_paths must reproduce LinkState.get_kth_paths (the reference's
sequential per-destination recursion, LinkState.cpp:763-793) exactly, and
the KSP2 route-selection path must produce identical RIBs on both
backends."""

from __future__ import annotations

import copy

import pytest

from openr_tpu.decision.link_state import LinkState
from openr_tpu.decision.prefix_state import PrefixState
from openr_tpu.decision.spf_solver import DeviceSpfBackend, SpfSolver
from openr_tpu.types import (
    Adjacency,
    PrefixEntry,
    PrefixForwardingAlgorithm,
    PrefixForwardingType,
)
from openr_tpu.utils.topo import grid_topology, random_topology


def build_ls(dbs) -> LinkState:
    ls = LinkState()
    for db in dbs:
        ls.update_adjacency_database(db)
    return ls


def canon(paths):
    """Order-insensitive canonical form of a path set (ECMP tie order may
    differ between host heap order and device DAG order)."""
    return sorted(
        tuple((link.n1, link.n2) for link in path) for path in paths
    )


class TestKthPathsConformance:
    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_random_topologies(self, seed):
        dbs = random_topology(n_nodes=80, n_extra_edges=120, seed=seed)
        ls_host = build_ls(dbs)
        ls_dev = build_ls(dbs)
        backend = DeviceSpfBackend(min_device_nodes=1, min_device_sources=1)

        nodes = sorted(ls_host.node_names)
        src = nodes[0]
        dests = nodes[1:25]
        backend.prefetch_kth_paths(ls_dev, src, dests)
        for dest in dests:
            for k in (1, 2):
                host = ls_host.get_kth_paths(src, dest, k)
                dev = backend.get_kth_paths(ls_dev, src, dest, k)
                assert canon(dev) == canon(host), (seed, src, dest, k)

    def test_grid(self):
        dbs = grid_topology(6)
        ls_host = build_ls(dbs)
        ls_dev = build_ls(dbs)
        backend = DeviceSpfBackend(min_device_nodes=1, min_device_sources=1)
        src = "node-0-0"
        dests = ["node-5-5", "node-0-5", "node-3-2", "node-1-0"]
        for dest in dests:
            for k in (1, 2):
                host = ls_host.get_kth_paths(src, dest, k)
                dev = backend.get_kth_paths(ls_dev, src, dest, k)
                assert canon(dev) == canon(host), (dest, k)

    def test_src_equals_dest_and_unknown(self):
        dbs = grid_topology(4)
        ls = build_ls(dbs)
        backend = DeviceSpfBackend(min_device_nodes=1, min_device_sources=1)
        assert backend.get_kth_paths(ls, "node-0-0", "node-0-0", 1) == []
        assert backend.get_kth_paths(ls, "node-0-0", "node-0-0", 2) == []

    def test_cache_invalidated_on_topology_change(self):
        dbs = grid_topology(4)
        ls = build_ls(dbs)
        backend = DeviceSpfBackend(min_device_nodes=1, min_device_sources=1)
        before = backend.get_kth_paths(ls, "node-0-0", "node-3-3", 1)
        assert before
        # fail a link on the first path: results must change
        link = before[0][0]
        db = next(
            d for d in dbs if d.this_node_name == link.n1
        )
        db.adjacencies = [
            a for a in db.adjacencies if a.other_node_name != link.n2
        ]
        ls.update_adjacency_database(db)
        after = backend.get_kth_paths(ls, "node-0-0", "node-3-3", 1)
        host = ls.get_kth_paths("node-0-0", "node-3-3", 1)
        assert canon(after) == canon(host)


def _topology(name):
    if name == "grid":
        return grid_topology(8), "node-3-3"
    seed = int(name.removeprefix("random-"))
    dbs = random_topology(n_nodes=80, n_extra_edges=120, seed=seed)
    return dbs, "n0"


def _assert_raw_lists_equal(backend, ls_dev, ls_host, src, label):
    """Every destination's k=1 and k=2 paths, list for list and in
    order: the device decode promises the host Dijkstra's walk, not only
    the same set of paths."""
    dests = [d for d in sorted(ls_host.node_names) if d != src]
    backend.prefetch_kth_paths(ls_dev, src, dests)
    for dest in dests:
        for k in (1, 2):
            host = ls_host.get_kth_paths(src, dest, k)
            dev = backend.get_kth_paths(ls_dev, src, dest, k)
            assert dev == host, (label, dest, k)


class TestKthPathsRawOrder:
    @pytest.mark.parametrize(
        "topology", ["random-0", "random-1", "random-2", "random-3", "grid"]
    )
    def test_raw_lists_equal_host(self, topology):
        dbs, src = _topology(topology)
        backend = DeviceSpfBackend(min_device_nodes=1, min_device_sources=1)
        _assert_raw_lists_equal(backend, build_ls(dbs), build_ls(dbs), src, topology)

    @pytest.mark.parametrize("topology", ["random-0", "grid"])
    def test_raw_lists_equal_host_after_rewires(self, topology):
        """In-place rewires on one mirror: a link removed, a link added
        into the freed slots, a metric changed.  The in-edge index is
        rebuilt when the edge arrays change and reused when they do
        not, and the paths stay the host's after each step."""
        dbs, src = _topology(topology)
        by_name = {db.this_node_name: db for db in dbs}
        ls_dev, ls_host = build_ls(dbs), build_ls(dbs)
        backend = DeviceSpfBackend(min_device_nodes=1, min_device_sources=1)
        _assert_raw_lists_equal(backend, ls_dev, ls_host, src, "start")
        csr = backend._mirror(ls_dev)
        index = csr._in_edges

        def publish(*names):
            for name in names:
                for ls in (ls_dev, ls_host):
                    ls.update_adjacency_database(copy.deepcopy(by_name[name]))

        def adjacent(a, b):
            return any(adj.other_node_name == b for adj in by_name[a].adjacencies)

        # remove a link of the first path to the farthest destination
        far = sorted(ls_host.node_names)[-1]
        gone = ls_host.get_kth_paths(src, far, 1)[0][0]
        a, b = gone.n1, gone.n2
        for x, y in ((a, b), (b, a)):
            by_name[x].adjacencies = [
                adj for adj in by_name[x].adjacencies if adj.other_node_name != y
            ]
        publish(a, b)
        _assert_raw_lists_equal(backend, ls_dev, ls_host, src, "removed")
        assert backend._mirror(ls_dev) is csr and csr.rewire_seq == 1
        assert csr._in_edges is not index
        n_edges, index = csr.n_edges, csr._in_edges

        # add a link from one end of the removed one to another node it
        # does not reach directly: the two freed slots take it
        c = next(
            n for n in sorted(by_name, reverse=True)
            if n not in (a, b) and not adjacent(a, n)
        )
        for x, y in ((a, c), (c, a)):
            by_name[x].adjacencies.append(
                Adjacency(
                    other_node_name=y,
                    if_name=f"if_{x}_{y}",
                    other_if_name=f"if_{y}_{x}",
                    metric=2,
                    next_hop_v6=f"fe80::{len(x)}:{len(y)}",
                )
            )
        publish(a, c)
        _assert_raw_lists_equal(backend, ls_dev, ls_host, src, "added")
        assert backend._mirror(ls_dev) is csr and csr.rewire_seq == 2
        assert csr.n_edges == n_edges and csr._free_slots == []
        assert csr._in_edges is not index
        index = csr._in_edges

        # a metric change moves no edge: the index is kept
        by_name[src].adjacencies[0].metric += 3
        publish(src)
        _assert_raw_lists_equal(backend, ls_dev, ls_host, src, "metric")
        assert backend._mirror(ls_dev) is csr and csr.rewire_seq == 2
        assert csr._in_edges is index


class TestKsp2RouteParity:
    def _route_db(self, backend, dbs, algo_nodes):
        ls = build_ls(dbs)
        ps = PrefixState()
        for node in algo_nodes:
            ps.update_prefix(
                node,
                "0",
                PrefixEntry(
                    prefix="fc00:dead::/64",
                    forwarding_type=PrefixForwardingType.SR_MPLS,
                    forwarding_algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP,
                ),
            )
        solver = SpfSolver("node-0-0", spf_backend=backend)
        return solver.build_route_db({"0": ls}, ps)

    def test_grid_rib_identical(self):
        dbs = grid_topology(5)
        algo_nodes = ["node-4-4", "node-2-3"]
        host_rdb = self._route_db(None, grid_topology(5), algo_nodes)
        dev_rdb = self._route_db(
            DeviceSpfBackend(min_device_nodes=1, min_device_sources=1), grid_topology(5), algo_nodes
        )
        assert host_rdb.unicast_routes == dev_rdb.unicast_routes
