"""Fleet route view (decision/fleet.py): the daemon consumer of the
reduced all-sources product (ops/allsources.py).

Golden parity contract (round-5 brief): for every node, the route DB
built from the fleet product equals the per-source build on BOTH
backends (host Dijkstra and device kernels) — the reference consumer
being buildRouteDb (openr/decision/Decision.cpp:615-793) and the
any-node ctrl query (Decision.cpp:1510-1530)."""

from __future__ import annotations

import pytest

from openr_tpu.decision.fleet import (
    INF32,
    FleetViewCache,
    fleet_destinations,
)
from openr_tpu.decision.link_state import LinkState
from openr_tpu.decision.spf_solver import DeviceSpfBackend, SpfSolver
from openr_tpu.types import (
    Adjacency,
    AdjacencyDatabase,
    PrefixEntry,
    PrefixForwardingAlgorithm,
    PrefixForwardingType,
)
from tests.test_spf_solver import (
    PFX,
    adj,
    build_link_state,
    prefix_state_with,
    square,
)


def test_inf_sentinel_matches_kernels():
    from openr_tpu.decision.fleet import INF16
    from openr_tpu.ops.banded import INF16 as KERNEL_INF16
    from openr_tpu.ops.sssp import INF32 as KERNEL_INF

    assert INF32 == int(KERNEL_INF)
    # the uint16 sentinel _row_i32 keys on must track the kernel's: a
    # retuned ops.banded.INF16 with a stale mirror here would classify
    # unreachable (sentinel) entries as finite distances
    assert INF16 == int(KERNEL_INF16)


def grid_link_state(side: int, metric=lambda a, b: 10) -> LinkState:
    """side x side grid as adjacency DBs (node names zero-padded so the
    sorted-name id order is the natural order)."""
    def name(r, c):
        return f"n{r * side + c:03d}"

    adj_map: dict[str, list] = {}
    labels: dict[str, int] = {}
    for r in range(side):
        for c in range(side):
            me = name(r, c)
            adjs = []
            for dr, dc in ((0, 1), (0, -1), (1, 0), (-1, 0)):
                rr, cc = r + dr, c + dc
                if 0 <= rr < side and 0 <= cc < side:
                    other = name(rr, cc)
                    adjs.append(adj(me, other, metric=metric(me, other)))
            adj_map[me] = adjs
            labels[me] = 1000 + r * side + c
    return build_link_state(adj_map, labels=labels)


def assert_fleet_parity(area_ls: dict, ps, nodes=None):
    """fleet_route_dbs == per-node build_route_db on host AND device."""
    host_solver = SpfSolver("__fleet__")
    fleet = host_solver.fleet_route_dbs(area_ls, ps, nodes=nodes)
    all_nodes = nodes or sorted(
        {n for ls in area_ls.values() for n in ls.node_names}
    )
    dev_backend = DeviceSpfBackend(min_device_nodes=1, min_device_sources=1)
    for node in all_nodes:
        host = SpfSolver(node).build_route_db(area_ls, ps)
        device = SpfSolver(node, spf_backend=dev_backend).build_route_db(
            area_ls, ps
        )
        got = fleet[node]
        if host is None:
            assert device is None
            assert not got.unicast_routes and not got.mpls_routes
            continue
        assert got.unicast_routes == host.unicast_routes, node
        assert got.mpls_routes == host.mpls_routes, node
        assert device.unicast_routes == host.unicast_routes, node
        assert device.mpls_routes == host.mpls_routes, node
    return fleet


class TestFleetParity:
    def test_square_every_node(self):
        ps = prefix_state_with(
            ("2", "0", PrefixEntry(prefix=PFX)),
            ("4", "0", PrefixEntry(prefix="::2:0/112")),
        )
        assert_fleet_parity({"0": square()}, ps)

    def test_square_anycast_two_advertisers(self):
        ps = prefix_state_with(
            ("2", "0", PrefixEntry(prefix=PFX)),
            ("3", "0", PrefixEntry(prefix=PFX)),
        )
        assert_fleet_parity({"0": square()}, ps)

    def test_overloaded_transit_drain(self):
        # 1-2-4 and 1-3-4: overload 2; routes to 4's prefix must avoid 2
        # as transit while 2 itself stays reachable (d==0 exception)
        ls = build_link_state(
            {
                "1": [adj("1", "2"), adj("1", "3")],
                "2": [adj("2", "1"), adj("2", "4")],
                "3": [adj("3", "1"), adj("3", "4")],
                "4": [adj("4", "2"), adj("4", "3")],
            },
            labels={"1": 101, "2": 102, "3": 103, "4": 104},
            overloaded={"2"},
        )
        ps = prefix_state_with(
            ("4", "0", PrefixEntry(prefix=PFX)),
            ("2", "0", PrefixEntry(prefix="::2:0/112")),
        )
        fleet = assert_fleet_parity({"0": ls}, ps)
        nhs = {
            nh.neighbor_node_name
            for nh in fleet["1"].unicast_routes[PFX].nexthops
        }
        assert nhs == {"3"}

    def test_overloaded_advertiser_filtering(self):
        # both advertisers overloaded -> kept (maybeFilterDrainedNodes
        # keeps the full set when filtering would empty it)
        ls = build_link_state(
            {
                "1": [adj("1", "2")],
                "2": [adj("2", "1"), adj("2", "3")],
                "3": [adj("3", "2")],
            },
            overloaded={"3"},
        )
        ps = prefix_state_with(("3", "0", PrefixEntry(prefix=PFX)))
        assert_fleet_parity({"0": ls}, ps)

    def test_parallel_links_share_slot(self):
        # two links 1<->2 with different metrics: only the cheaper is an
        # ECMP next hop; fleet per-link evaluation must keep per-link
        # metric semantics (slots are per unique neighbor)
        a1 = Adjacency(
            other_node_name="2",
            if_name="1/2-a",
            other_if_name="2/1-a",
            metric=10,
            next_hop_v6="fe80::2a",
        )
        a2 = Adjacency(
            other_node_name="2",
            if_name="1/2-b",
            other_if_name="2/1-b",
            metric=20,
            next_hop_v6="fe80::2b",
        )
        b1 = Adjacency(
            other_node_name="1",
            if_name="2/1-a",
            other_if_name="1/2-a",
            metric=10,
            next_hop_v6="fe80::1a",
        )
        b2 = Adjacency(
            other_node_name="1",
            if_name="2/1-b",
            other_if_name="1/2-b",
            metric=20,
            next_hop_v6="fe80::1b",
        )
        ls = build_link_state({"1": [a1, a2], "2": [b1, b2]})
        ps = prefix_state_with(("2", "0", PrefixEntry(prefix=PFX)))
        fleet = assert_fleet_parity({"0": ls}, ps)
        route = fleet["1"].unicast_routes[PFX]
        assert {nh.if_name for nh in route.nexthops} == {"1/2-a"}

    def test_equal_parallel_links_both_used(self):
        a1 = Adjacency(
            other_node_name="2",
            if_name="1/2-a",
            other_if_name="2/1-a",
            metric=10,
            next_hop_v6="fe80::2a",
        )
        a2 = Adjacency(
            other_node_name="2",
            if_name="1/2-b",
            other_if_name="2/1-b",
            metric=10,
            next_hop_v6="fe80::2b",
        )
        b1 = Adjacency(
            other_node_name="1",
            if_name="2/1-a",
            other_if_name="1/2-a",
            metric=10,
            next_hop_v6="fe80::1a",
        )
        b2 = Adjacency(
            other_node_name="1",
            if_name="2/1-b",
            other_if_name="1/2-b",
            metric=10,
            next_hop_v6="fe80::1b",
        )
        ls = build_link_state({"1": [a1, a2], "2": [b1, b2]})
        ps = prefix_state_with(("2", "0", PrefixEntry(prefix=PFX)))
        fleet = assert_fleet_parity({"0": ls}, ps)
        route = fleet["1"].unicast_routes[PFX]
        assert {nh.if_name for nh in route.nexthops} == {"1/2-a", "1/2-b"}

    def test_ksp2_prefix_falls_back_to_per_source(self):
        # KSP2 prefixes go through get_kth_paths (per-source machinery);
        # the fleet build must still produce identical routes
        ps = prefix_state_with(
            (
                "4",
                "0",
                PrefixEntry(
                    prefix=PFX,
                    forwarding_type=PrefixForwardingType.SR_MPLS,
                    forwarding_algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP,
                ),
            ),
            ("2", "0", PrefixEntry(prefix="::2:0/112")),
        )
        assert_fleet_parity({"0": square()}, ps)

    def test_grid64_every_node(self):
        # 64 nodes — above DeviceSpfBackend's default min_device_nodes;
        # asymmetric metrics break ECMP ties in interesting ways
        import random

        rnd = random.Random(5)
        weights = {}

        def metric(a, b):
            return weights.setdefault((a, b), rnd.randint(1, 5))

        ls = grid_link_state(8, metric=metric)
        names = sorted(ls.node_names)
        ps = prefix_state_with(
            (names[0], "0", PrefixEntry(prefix=PFX)),
            (names[-1], "0", PrefixEntry(prefix=PFX)),
            (names[27], "0", PrefixEntry(prefix="::2:0/112")),
            (names[13], "0", PrefixEntry(prefix="::3:0/112")),
        )
        assert_fleet_parity({"0": ls}, ps)

    def test_multi_area(self):
        # area 0: 1-2; area 1: 2-3 (2 spans both); prefix in each area
        ls0 = build_link_state(
            {"1": [adj("1", "2")], "2": [adj("2", "1")]}, area="0"
        )
        ls1 = LinkState("1")
        for node, adjs in (
            ("2", [adj("2", "3")]),
            ("3", [adj("3", "2")]),
        ):
            ls1.update_adjacency_database(
                AdjacencyDatabase(
                    this_node_name=node,
                    adjacencies=adjs,
                    area="1",
                )
            )
        ps = prefix_state_with(
            ("3", "1", PrefixEntry(prefix=PFX)),
            ("1", "0", PrefixEntry(prefix="::2:0/112")),
        )
        assert_fleet_parity({"0": ls0, "1": ls1}, ps)

    def test_disconnected_components(self):
        ls = build_link_state(
            {
                "1": [adj("1", "2")],
                "2": [adj("2", "1")],
                "3": [adj("3", "4")],
                "4": [adj("4", "3")],
            },
            labels={"1": 101, "2": 102, "3": 103, "4": 104},
        )
        ps = prefix_state_with(
            ("2", "0", PrefixEntry(prefix=PFX)),
            ("4", "0", PrefixEntry(prefix="::2:0/112")),
        )
        fleet = assert_fleet_parity({"0": ls}, ps)
        assert PFX in fleet["1"].unicast_routes
        assert "::2:0/112" not in fleet["1"].unicast_routes
        assert "::2:0/112" in fleet["3"].unicast_routes


class TestFleetBitmapCrossCheck:
    def test_bitmap_matches_route_nexthops(self):
        # device bitmap decode == the host-side per-link evaluation for a
        # single-advertiser non-SR prefix
        ls = square()
        ps = prefix_state_with(("4", "0", PrefixEntry(prefix=PFX)))
        solver = SpfSolver("__fleet__")
        fleet = solver.fleet_route_dbs({"0": ls}, ps)
        view = solver.fleet.view({"0": ls}["0"], fleet_destinations(ls, ps))
        for me in ("1", "2", "3"):
            route = fleet[me].unicast_routes.get(PFX)
            route_nhs = (
                {nh.neighbor_node_name for nh in route.nexthops}
                if route
                else set()
            )
            assert view.next_hop_neighbors(me, "4") == route_nhs, me


class TestEngineIntegration:
    def test_fused_product_parity_through_view(self):
        """The engine-routed fused product (odd-N ring: padding rows
        live) against the host Dijkstra oracle, every router: distances
        and the decoded ECMP next-hop neighbors."""
        from openr_tpu.device.engine import DeviceResidencyEngine
        from openr_tpu.utils.topo import ring_topology

        ls = LinkState()
        for db in ring_topology(65):
            ls.update_adjacency_database(db)
        dests = ["r0", "r7", "r64"]
        engine = DeviceResidencyEngine()
        view = FleetViewCache().view(ls, dests, engine=engine)
        assert view.converged and not view.node_sharded
        assert engine.get_counters()["device.engine.dispatches"] >= 1
        for node in sorted(ls.node_names):
            spf = ls.run_spf(node)
            for dest in dests:
                assert view.dist(node, dest) == spf[dest].metric
                want = spf[dest].next_hops if dest != node else set()
                assert view.next_hop_neighbors(node, dest) == want, (
                    node,
                    dest,
                )


class TestFleetCache:
    def test_warm_cache_reuses_view(self):
        ls = square()
        ps = prefix_state_with(("4", "0", PrefixEntry(prefix=PFX)))
        cache = FleetViewCache()
        dests = fleet_destinations(ls, ps)
        v1 = cache.view(ls, dests)
        assert cache.is_warm(ls, dests)
        assert cache.view(ls, dests) is v1

    def test_version_bump_invalidates(self):
        ls = square()
        ps = prefix_state_with(("4", "0", PrefixEntry(prefix=PFX)))
        cache = FleetViewCache()
        dests = fleet_destinations(ls, ps)
        v1 = cache.view(ls, dests)
        # metric change bumps the LinkState version
        ls.update_adjacency_database(
            AdjacencyDatabase(
                this_node_name="1",
                adjacencies=[adj("1", "2", metric=30), adj("1", "3")],
                node_label=101,
                area="0",
            )
        )
        assert not cache.is_warm(ls, dests)
        v2 = cache.view(ls, dests)
        assert v2 is not v1 and v2.version == ls.version

    def test_dest_change_invalidates(self):
        # unlabeled topology: dests = advertisers only, so a new
        # advertiser really changes the destination set
        ls = build_link_state(
            {
                "1": [adj("1", "2"), adj("1", "3")],
                "2": [adj("2", "1"), adj("2", "4")],
                "3": [adj("3", "1"), adj("3", "4")],
                "4": [adj("4", "2"), adj("4", "3")],
            }
        )
        ps = prefix_state_with(("4", "0", PrefixEntry(prefix=PFX)))
        cache = FleetViewCache()
        v1 = cache.view(ls, fleet_destinations(ls, ps))
        assert v1.dest_names == ["4"]
        ps.update_prefix("2", "0", PrefixEntry(prefix="::9:0/112"))
        dests2 = fleet_destinations(ls, ps)
        assert dests2 == ["2", "4"]
        v2 = cache.view(ls, dests2)
        assert v2 is not v1

    def test_reroute_after_metric_change(self):
        # end-to-end: fleet answers track topology changes
        ls = square()
        ps = prefix_state_with(("4", "0", PrefixEntry(prefix=PFX)))
        solver = SpfSolver("__fleet__")
        fleet1 = solver.fleet_route_dbs({"0": ls}, ps)
        assert {
            nh.neighbor_node_name
            for nh in fleet1["1"].unicast_routes[PFX].nexthops
        } == {"2", "3"}
        ls.update_adjacency_database(
            AdjacencyDatabase(
                this_node_name="1",
                adjacencies=[adj("1", "2", metric=30), adj("1", "3")],
                node_label=101,
                area="0",
            )
        )
        fleet2 = solver.fleet_route_dbs({"0": ls}, ps)
        assert {
            nh.neighbor_node_name
            for nh in fleet2["1"].unicast_routes[PFX].nexthops
        } == {"3"}
        assert_fleet_parity({"0": ls}, ps)


class TestAnyNodeQuery:
    def test_host_backend_no_fleet_compute(self):
        # host backend must not compute fleet views, but the answer is
        # still correct via the per-source path
        ls = square()
        ps = prefix_state_with(("4", "0", PrefixEntry(prefix=PFX)))
        solver = SpfSolver("1")
        db = solver.any_node_route_db({"0": ls}, ps, "2")
        ref = SpfSolver("2").build_route_db({"0": ls}, ps)
        assert db.unicast_routes == ref.unicast_routes
        assert not solver.fleet._views  # no view computed

    def test_device_backend_warm_fleet_serves_query(self):
        ls = square()
        ps = prefix_state_with(("4", "0", PrefixEntry(prefix=PFX)))
        solver = SpfSolver(
            "1",
            spf_backend=DeviceSpfBackend(
                min_device_nodes=1, min_device_sources=1
            ),
        )
        # warm the cache via a fleet dump, then query any node
        solver.fleet_route_dbs({"0": ls}, ps, nodes=["1"])
        dests = fleet_destinations(ls, ps)
        assert solver.fleet.is_warm(ls, dests)
        db = solver.any_node_route_db({"0": ls}, ps, "3")
        ref = SpfSolver("3").build_route_db({"0": ls}, ps)
        assert db.unicast_routes == ref.unicast_routes
        assert db.mpls_routes == ref.mpls_routes

    def test_unknown_node(self):
        ls = square()
        ps = prefix_state_with(("4", "0", PrefixEntry(prefix=PFX)))
        assert SpfSolver("1").any_node_route_db({"0": ls}, ps, "zz") is None


class TestWarmStart:
    """Warm-started fleet rebuilds, BOTH directions: improvement-only
    changes seed the relax with the previous distances (upper-bound
    init, ops.banded.spf_forward_banded); worsening changes (link DOWN,
    metric increase, drain) seed it with the previous distances minus
    the certified affected set (fleet._affected_init).  Either way the
    result must equal a fresh cold build bit-for-bit — _rebuild_pair
    asserts dist AND bitmap equality on every path.

    Fixtures are 64-node rings: the warm paths engage only where the
    BANDED kernel runs (build_banded needs >=64 nodes with circulant
    structure; the ELL fallback ignores dist0 and stays cold)."""

    @staticmethod
    def ring_ls(n=64, metric=lambda a, b: 20):
        def name(i):
            return f"r{i % 64:03d}" if n <= 1000 else f"r{i % n:06d}"

        adj_map = {}
        labels = {}
        for i in range(n):
            me = name(i)
            adj_map[me] = [
                adj(me, name(i + d), metric=metric(i, (i + d) % n))
                for d in (1, -1, 2, -2)
            ]
            labels[me] = 1000 + i
        return build_link_state(adj_map, labels=labels)

    @staticmethod
    def ring_adjs(i, metric=lambda a, b: 20, drop=None):
        def name(j):
            return f"r{j % 64:03d}"

        return [
            adj(name(i), name(i + d), metric=metric(i, (i + d) % 64))
            for d in (1, -1, 2, -2)
            if d != drop
        ]

    def _dists(self, view):
        import numpy as np

        return np.asarray(view._dist_dev)

    def _assert_banded(self, view):
        # the fixture must actually run the banded kernel or this class
        # tests nothing (the ELL fallback never warms)
        from openr_tpu.ops.banded import build_banded

        assert (
            build_banded(
                view.csr.edge_src,
                view.csr.edge_dst,
                view.csr.n_edges,
                view.csr.n_nodes,
            )
            is not None
        )

    def _rebuild_pair(self, mutate):
        """(warm-capable view, fresh cold view) after `mutate(ls)` on
        two identically-constructed LinkStates."""
        import numpy as np

        views = []
        for use_cache in (True, False):
            ls = self.ring_ls()
            ps = prefix_state_with(
                ("r063", "0", PrefixEntry(prefix=PFX)),
                ("r000", "0", PrefixEntry(prefix="::2:0/112")),
            )
            dests = fleet_destinations(ls, ps)
            cache = FleetViewCache()
            if use_cache:
                v1 = cache.view(ls, dests)
                assert not v1.warm
                self._assert_banded(v1)
            mutate(ls)
            views.append(cache.view(ls, fleet_destinations(ls, ps)))
        warm_view, cold_view = views
        assert not cold_view.warm
        np.testing.assert_array_equal(
            self._dists(warm_view), self._dists(cold_view)
        )
        np.testing.assert_array_equal(
            np.asarray(warm_view._bitmap_dev),
            np.asarray(cold_view._bitmap_dev),
        )
        return warm_view, cold_view

    def _set_node(self, ls, i, **kw):
        ls.update_adjacency_database(
            AdjacencyDatabase(
                this_node_name=f"r{i:03d}",
                adjacencies=self.ring_adjs(i, **{
                    k: v for k, v in kw.items() if k in ("metric", "drop")
                }),
                is_overloaded=kw.get("is_overloaded", False),
                node_label=1000 + i,
                area="0",
            )
        )

    def test_metric_decrease_warm_starts(self):
        warm, _ = self._rebuild_pair(
            lambda ls: self._set_node(
                ls, 0, metric=lambda a, b: 5 if b == 1 else 20
            )
        )
        assert warm.warm

    def test_metric_increase_warm_starts_down(self):
        warm, _ = self._rebuild_pair(
            lambda ls: self._set_node(
                ls, 0, metric=lambda a, b: 90 if b == 1 else 20
            )
        )
        assert warm.warm
        assert warm.warm_mode == "worsen"

    def test_single_link_down_warm_bit_exact(self):
        warm, _ = self._rebuild_pair(
            lambda ls: self._set_node(ls, 0, drop=1)
        )
        assert warm.warm
        assert warm.warm_mode == "worsen"

    def test_multi_link_down_warm_bit_exact(self):
        def mutate(ls):
            self._set_node(ls, 0, drop=1)
            self._set_node(ls, 20, drop=-1)
            self._set_node(ls, 40, drop=2)

        warm, _ = self._rebuild_pair(mutate)
        assert warm.warm
        assert warm.warm_mode == "worsen"

    def test_mixed_change_warm_starts_down(self):
        # one link worsens while another improves in the SAME delta:
        # neither the improvement-only gate nor a naive "pure worsening"
        # gate fires, but the affected-set argument still holds (the
        # improved edge only loosens the upper bound)
        def mutate(ls):
            self._set_node(
                ls, 0, metric=lambda a, b: 90 if b == 1 else 20
            )
            self._set_node(
                ls, 32, metric=lambda a, b: 5 if b == 33 else 20
            )

        warm, _ = self._rebuild_pair(mutate)
        assert warm.warm
        assert warm.warm_mode == "worsen"

    def test_link_down_warm_then_up_warm(self):
        import numpy as np

        ls = self.ring_ls()
        ps = prefix_state_with(("r063", "0", PrefixEntry(prefix=PFX)))
        dests = fleet_destinations(ls, ps)
        cache = FleetViewCache()
        v1 = cache.view(ls, dests)
        # link r000-r001 down: a WORSENING change -> warm-down rebuild
        self._set_node(ls, 0, drop=1)
        v2 = cache.view(ls, dests)
        assert v2.warm
        assert v2.warm_mode == "worsen"
        # link back up: flap recovery -> improvement-direction warm
        self._set_node(ls, 0)
        v3 = cache.view(ls, dests)
        assert v3.warm
        assert v3.warm_mode == "improve"
        # warm result equals v1 (same topology as the original)
        np.testing.assert_array_equal(self._dists(v3), self._dists(v1))
        # and the daemon-level answer stays correct against the host
        # oracle at BOTH ends of the flap
        assert_fleet_parity(
            {"0": ls}, ps, nodes=[f"r{i:03d}" for i in (0, 1, 2, 31, 63)]
        )

    def test_link_down_warm_matches_host_oracle(self):
        # the WARM-DOWN product itself (same persistent solver cache,
        # so the second build really warms) must answer route builds
        # identically to the per-node host Dijkstra oracle
        ls = self.ring_ls()
        ps = prefix_state_with(("r063", "0", PrefixEntry(prefix=PFX)))
        nodes = [f"r{i:03d}" for i in (0, 1, 2, 31, 63)]
        solver = SpfSolver("r000")
        solver.fleet_route_dbs({"0": ls}, ps, nodes=nodes)
        self._set_node(ls, 0, drop=1)
        fleet = solver.fleet_route_dbs({"0": ls}, ps, nodes=nodes)
        view = solver.fleet._views.get(ls)
        assert view is not None and view.warm_mode == "worsen"
        for node in nodes:
            host = SpfSolver(node).build_route_db({"0": ls}, ps)
            assert fleet[node].unicast_routes == host.unicast_routes, node
            assert fleet[node].mpls_routes == host.mpls_routes, node

    def test_rebuild_counters_track_warm_hits(self):
        from openr_tpu.decision.spf_solver import DeviceSpfBackend, SpfSolver

        ls = self.ring_ls()
        ps = prefix_state_with(("r063", "0", PrefixEntry(prefix=PFX)))
        solver = SpfSolver(
            "r000",
            spf_backend=DeviceSpfBackend(
                min_device_nodes=1, min_device_sources=1
            ),
        )
        solver.fleet_route_dbs({"0": ls}, ps, nodes=["r000"])
        assert solver.counters.get("decision.fleet_rebuild_cold") == 1
        assert "decision.fleet_rebuild_warm" not in solver.counters
        self._set_node(ls, 0, metric=lambda a, b: 5 if b == 1 else 20)
        solver.fleet_route_dbs({"0": ls}, ps, nodes=["r000"])
        assert solver.counters.get("decision.fleet_rebuild_warm") == 1
        assert "decision.fleet_rebuild_warm_down" not in solver.counters
        # a cached re-read computes nothing and bumps nothing
        solver.fleet_route_dbs({"0": ls}, ps, nodes=["r000"])
        assert solver.counters.get("decision.fleet_rebuild_warm") == 1
        # a worsening change bumps warm AND the direction-split counter
        self._set_node(ls, 0, drop=1)
        solver.fleet_route_dbs({"0": ls}, ps, nodes=["r000"])
        assert solver.counters.get("decision.fleet_rebuild_warm") == 2
        assert solver.counters.get("decision.fleet_rebuild_warm_down") == 1

    def test_drain_set_warm_down_clear_warm_up(self):
        ls = self.ring_ls()
        ps = prefix_state_with(("r063", "0", PrefixEntry(prefix=PFX)))
        dests = fleet_destinations(ls, ps)
        cache = FleetViewCache()
        cache.view(ls, dests)
        self._set_node(ls, 5, is_overloaded=True)
        v2 = cache.view(ls, dests)
        # draining worsens transit distances: warm-down path
        assert v2.warm
        assert v2.warm_mode == "worsen"
        self._set_node(ls, 5)
        v3 = cache.view(ls, dests)
        assert v3.warm  # un-draining only improves distances
        assert v3.warm_mode == "improve"

    def test_drain_warm_bit_exact(self):
        warm, _ = self._rebuild_pair(
            lambda ls: self._set_node(ls, 5, is_overloaded=True)
        )
        assert warm.warm
        assert warm.warm_mode == "worsen"

    def test_ell_fallback_never_warms(self):
        # small (non-banded) topology + improvement-only change: the
        # gate passes but the ELL kernel ignores dist0, so the view must
        # NOT claim warm (it would poison _warm_hints with cold counts)
        ls = square()
        ps = prefix_state_with(("4", "0", PrefixEntry(prefix=PFX)))
        dests = fleet_destinations(ls, ps)
        cache = FleetViewCache()
        cache.view(ls, dests)
        ls.update_adjacency_database(
            AdjacencyDatabase(
                this_node_name="1",
                adjacencies=[adj("1", "2", metric=5), adj("1", "3")],
                node_label=101,
                area="0",
            )
        )
        v2 = cache.view(ls, dests)
        assert not v2.warm
        # hint routing follows what actually ran: the cold (ELL) sweep
        # count must land in _hints, never in _warm_hints (an inherited
        # cold count there would oversize every later banded warm seed)
        key = (v2.csr.n_nodes, v2.csr.n_edges)
        assert key not in cache._warm_hints
        assert cache._hints.get(key) == v2.sweep_hint

    def test_ell_fallback_link_down_stays_cold_and_correct(self):
        # worsening change on a small (non-banded) topology: no runner
        # with a banded graph to propagate the affected set over, so the
        # rebuild cold-starts — and the product still matches the host
        # oracle after the link removal
        ls = square()
        ps = prefix_state_with(("4", "0", PrefixEntry(prefix=PFX)))
        dests = fleet_destinations(ls, ps)
        cache = FleetViewCache()
        cache.view(ls, dests)
        ls.update_adjacency_database(
            AdjacencyDatabase(
                this_node_name="1",
                adjacencies=[adj("1", "2")],  # 1-3 link dropped
                node_label=101,
                area="0",
            )
        )
        v2 = cache.view(ls, fleet_destinations(ls, ps))
        assert not v2.warm
        assert v2.warm_mode is None
        assert_fleet_parity({"0": ls}, ps)

    def test_dest_change_blocks_warm(self):
        ls = self.ring_ls()
        # label-free dest control is impossible here (every ring node is
        # labeled), so change the ADVERTISER set size via a node whose
        # label is already a dest: drop a prefix advertised by a node
        # OUTSIDE the label set — instead, flip dest equality by asking
        # with an explicitly different dest list
        ps = prefix_state_with(("r063", "0", PrefixEntry(prefix=PFX)))
        cache = FleetViewCache()
        dests = fleet_destinations(ls, ps)
        cache.view(ls, dests)
        self._set_node(ls, 0, metric=lambda a, b: 5 if b == 1 else 20)
        v2 = cache.view(ls, dests[:-1])  # same topology, fewer dests
        assert not v2.warm
