"""Banded relax kernel + reduced all-sources product vs the oracle.

The band-augmented kernel (ops.banded) must be bit-identical to the
bucketed-ELL kernel / host Dijkstra on every semantic axis: metrics,
drain (overload) transit rules incl. the own-source exception, down
links, per-row exclusion masks, uint16 distance mode, and the
convergence verdict.  The reduced all-sources product (ops.allsources)
must reproduce forward per-source distances and the reference's
LFA-free ECMP next-hop sets from ONE reverse-SSSP call.

Reference semantics anchored at openr/decision/LinkState.cpp:809-878
(runSpf) and Decision.cpp:1296-1300 (getNextHopsThrift ECMP condition).
"""

from __future__ import annotations

import numpy as np
import pytest

from benchmarks import synthetic
from openr_tpu.ops import banded as bd
from openr_tpu.ops import sssp as ops
from openr_tpu.ops.sssp import INF32


def to_i32(dist) -> np.ndarray:
    """Normalize a reduced-product distance matrix to int32/INF32: the
    product returns raw uint16 (INF16 sentinel) when the banded kernel
    runs in small-distance mode (ops.allsources contract)."""
    from openr_tpu.decision.fleet import _row_i32

    return _row_i32(np.asarray(dist))


def oracle(topo, sources, extra_mask=None):
    import jax.numpy as jnp

    if extra_mask is None:
        dist, dag = ops.spf_forward_ell(
            np.asarray(sources, np.int32),
            topo.ell,
            topo.edge_src,
            topo.edge_dst,
            topo.edge_metric,
            topo.edge_up,
            topo.node_overloaded,
        )
    else:
        dist, dag = ops.spf_forward_ell_masked(
            np.asarray(sources, np.int32),
            topo.ell,
            topo.edge_src,
            topo.edge_dst,
            topo.edge_metric,
            topo.edge_up,
            topo.node_overloaded,
            jnp.asarray(extra_mask),
        )
    return np.asarray(dist), np.asarray(dag)


def assert_matches_oracle(topo, sources, extra_mask=None):
    odist, odag = oracle(topo, sources, extra_mask)
    dist, dag = topo.runner.forward(
        np.asarray(sources, np.int32), extra_edge_mask=extra_mask
    )
    n, e = topo.n_nodes, topo.n_edges
    np.testing.assert_array_equal(dist[:, :n], odist[:, :n])
    np.testing.assert_array_equal(dag[:, :e], odag[:, :e])


class TestBandedKernel:
    def test_grid_all_bands(self):
        g = synthetic.grid(8)
        assert g.banded is not None
        assert set(g.banded.offsets) == {1, 8, 56, 63}
        assert_matches_oracle(g, np.arange(16))

    def test_wan_ring_chords(self):
        w = synthetic.wan(512, chords=2, seed=3)
        assert w.banded is not None
        assert w.banded.resid_nbr.shape[1] == 4  # uniform chord degree
        assert_matches_oracle(w, np.arange(24))

    def test_fattree_falls_back_to_ell(self):
        ft = synthetic.fat_tree(
            pods=4, planes=2, ssw_per_plane=4, rsw_per_pod=8
        )
        assert ft.banded is None
        assert_matches_oracle(ft, np.arange(12))  # ELL fixed-sweep path

    def test_drain_semantics_and_down_links(self):
        w = synthetic.wan(256, chords=2, seed=5)
        w.node_overloaded[[3, 77, 130]] = True
        w.edge_up[np.arange(0, w.n_edges, 17)] = False
        # sources include an overloaded node (the own-source exception)
        assert_matches_oracle(w, np.asarray([0, 3, 77, 9]))

    def test_masked_rows(self):
        w = synthetic.wan(256, chords=2, seed=5)
        rng = np.random.default_rng(0)
        mask = np.ones((6, w.edge_capacity), dtype=bool)
        for r in range(6):
            mask[r, rng.integers(0, w.n_edges, 5)] = False
        assert_matches_oracle(w, np.zeros(6, np.int32), extra_mask=mask)

    def test_uint16_mode_engages_and_matches(self):
        w = synthetic.wan(512, chords=2, seed=3)
        assert w.runner.small_dist  # metrics 1..10 qualify
        assert_matches_oracle(w, np.arange(16))

    def test_large_metrics_disable_uint16(self):
        w = synthetic.wan(256, chords=2, seed=1)
        w.edge_metric[: w.n_edges] = 10_000  # above the uint16 gate
        assert not w.runner.small_dist
        assert_matches_oracle(w, np.arange(8))

    def test_insufficient_sweeps_detected(self):
        w = synthetic.wan(512, chords=2, seed=3)
        _, _, ok = w.runner.run_once(np.arange(4, dtype=np.int32), 1)
        assert not bool(ok)

    def test_hint_doubles_until_converged(self):
        w = synthetic.wan(512, chords=2, seed=4)
        w.runner.hint = 1
        assert_matches_oracle(w, np.arange(4))
        assert w.runner.hint > 1

    def test_chord_mode_auto_pick(self):
        """Chord-rich small worlds run the two-pass Jacobi supersweep;
        band-dominated grids keep the sequential sweep with composed
        levels (round-5 tune).  The oracle tests above exercise BOTH
        supersweeps (wan picks chord mode, grid sequential) — this pins
        the auto-pick itself."""
        w = synthetic.wan(512, chords=2, seed=3)
        assert w.runner.chord_mode
        assert w.runner.depth == 0
        g = synthetic.grid(8)
        assert not g.runner.chord_mode
        assert g.runner.depth == 2
        # explicit depth bypasses the auto-pick
        from openr_tpu.ops.banded import SpfRunner

        r = SpfRunner(
            w.ell,
            w.banded,
            w.edge_src,
            w.edge_dst,
            w.edge_metric,
            w.edge_up,
            w.node_overloaded,
            w.n_edges,
            depth=1,
        )
        assert not r.chord_mode and r.depth == 1

    def test_parallel_band_links_demoted_to_residual(self):
        # duplicate ring links (parallel edges on the same band offset)
        # must not collide in the band table
        n = 128
        ids = np.arange(n, dtype=np.int32)
        ring = np.stack([ids, (ids + 1) % n], axis=1)
        links = np.concatenate([ring, ring, ring[:, ::-1]])
        metrics = np.concatenate(
            [
                np.full(n, 5, np.int32),
                np.full(n, 3, np.int32),  # parallel, cheaper
                np.full(n, 4, np.int32),
            ]
        )
        topo = synthetic.Topology.from_links("ringpar", n, links, metrics)
        if topo.banded is not None:
            assert_matches_oracle(topo, np.arange(8))


class TestCsrRunnerIntegration:
    def test_csr_banded_matches_host(self):
        """CsrTopology on a ring topology picks up bands and reproduces
        the host-oracle SpfResults through run_batched_spf."""
        from openr_tpu.decision import LinkState
        from openr_tpu.decision.csr import CsrTopology
        from openr_tpu.utils.topo import ring_topology

        dbs = ring_topology(64)
        ls = LinkState()
        for db in dbs:
            ls.update_adjacency_database(db)
        csr = CsrTopology.from_link_state(ls)
        assert csr.banded is not None
        sources = [dbs[i].this_node_name for i in (0, 7, 33)]
        dist, dag = csr.run_batched_spf(sources)
        results = csr.to_spf_results(sources, dist, dag)
        for src in sources:
            host = ls.run_spf(src)
            got = results[src]
            assert set(got) == set(host)
            for node, res in host.items():
                assert got[node].metric == res.metric


class TestReducedAllSources:
    def _setup(self, topo, n_prefixes=24, seed=11):
        from openr_tpu.ops import allsources as asrc

        rng = np.random.default_rng(seed)
        dests = np.sort(
            rng.choice(topo.n_nodes, size=n_prefixes, replace=False)
        ).astype(np.int32)
        rev = synthetic.reversed_topology(topo)
        out = asrc.build_out_ell(
            topo.edge_src, topo.edge_dst, topo.n_edges, topo.n_nodes
        )
        return asrc, dests, rev, out

    def test_reverse_distances_match_forward(self):
        w = synthetic.wan(256, chords=2, seed=9)
        asrc, dests, rev, out = self._setup(w)
        dist, bitmap, ok = asrc.reduced_all_sources(
            dests, rev.runner, out, w.edge_metric, w.edge_up,
            w.node_overloaded,
        )
        assert bool(ok)
        dist = to_i32(dist)  # [N, P] native layout
        # forward oracle over a sample of routers
        sample = np.asarray([0, 3, 100, 255], np.int32)
        odist, _ = oracle(w, sample)
        for i, v in enumerate(sample):
            np.testing.assert_array_equal(dist[v], odist[i, dests])

    def test_reverse_respects_drain_semantics(self):
        w = synthetic.wan(256, chords=2, seed=9)
        w.node_overloaded[[5, 60]] = True
        w.edge_up[np.arange(0, w.n_edges, 13)] = False
        asrc, dests, rev, out = self._setup(w)
        # overloaded nodes appear BOTH as routers (origin exception) and
        # among the destinations
        dests = np.unique(np.concatenate([dests, [5, 60]])).astype(np.int32)
        dist, _, ok = asrc.reduced_all_sources(
            dests, rev.runner, out, w.edge_metric, w.edge_up,
            w.node_overloaded,
        )
        assert bool(ok)
        dist = to_i32(dist)  # [N, P]
        sample = np.asarray([0, 5, 60, 200], np.int32)
        odist, _ = oracle(w, sample)
        for i, v in enumerate(sample):
            np.testing.assert_array_equal(dist[v], odist[i, dests])

    def test_non_banded_topology_uses_ell_fallback(self):
        """reduced_all_sources must work when build_banded returns None
        (ELL fallback pads dist to node_capacity — regression: shape
        mismatch crash in the bitmap pass)."""
        ft = synthetic.fat_tree(
            pods=4, planes=2, ssw_per_plane=4, rsw_per_pod=8
        )
        assert ft.banded is None
        asrc, dests, rev, out = self._setup(ft, n_prefixes=8)
        dist, bitmap, ok = asrc.reduced_all_sources(
            dests, rev.runner, out, ft.edge_metric, ft.edge_up,
            ft.node_overloaded,
        )
        assert bool(ok)
        assert np.asarray(bitmap).shape[0] == ft.n_nodes
        dist = np.asarray(dist)  # [N_cap, P]
        sample = np.asarray([0, 9, 30], np.int32)
        odist, _ = oracle(ft, sample)
        for i, v in enumerate(sample):
            np.testing.assert_array_equal(dist[v], odist[i, dests])

    def test_bitmap_excludes_drained_neighbor(self):
        """Ring with an overloaded node: the coincidental distance
        equality through the drained neighbor must NOT set its bit —
        the reference draws ECMP neighbors from the drain-respecting
        source tree (Decision.cpp:1182-1260).  Regression for the
        round-4 review repro (bitmap said {1, 63}, SP-DAG says {63})."""
        from openr_tpu.ops import allsources as asrc

        n = 64
        ids = np.arange(n, dtype=np.int32)
        links = np.stack([ids, (ids + 1) % n], axis=1)
        w = synthetic.Topology.from_links(
            "ring64", n, links, np.ones(len(links), np.int32)
        )
        w.node_overloaded[1] = True
        dests = np.asarray([32], np.int32)
        rev = synthetic.reversed_topology(w)
        out = asrc.build_out_ell(w.edge_src, w.edge_dst, w.n_edges, n)
        dist, bitmap, ok = asrc.reduced_all_sources(
            dests, rev.runner, out, w.edge_metric, w.edge_up,
            w.node_overloaded,
        )
        assert bool(ok)
        # router 0 -> dest 32: only the counter-clockwise neighbor (63)
        bits = int(np.asarray(bitmap)[0, 0, 0])
        slots = {b for b in range(32) if bits & (1 << b)}
        slot_names = sorted({1, 63})  # sorted unique out-neighbors of 0
        hops = {slot_names[s] for s in slots}
        assert hops == {63}, hops
        # and the drained node as DESTINATION still gets next-hops
        dests2 = np.asarray([1], np.int32)
        _, bm2, ok2 = asrc.reduced_all_sources(
            dests2, rev.runner, out, w.edge_metric, w.edge_up,
            w.node_overloaded,
        )
        assert bool(ok2)
        bits2 = int(np.asarray(bm2)[0, 0, 0])
        assert {slot_names[b] for b in range(32) if bits2 & (1 << b)} == {1}

    @pytest.mark.parametrize(
        "case",
        [
            "wan128",
            "ring_odd_n",
            "grid",
            "wan_chords",
            "ring_drained",
            "grid_drained",
        ],
    )
    def test_bitmap_matches_reference_ecmp_condition(self, case):
        """Bit s set for (v, p) iff out-slot s satisfies
        metric(v,u) + dist(u,p) == dist(v,p) with u usable as a transit
        (not drained, or u is p) — distances and the whole bitmap are
        checked against a numpy Bellman-Ford and a direct evaluation of
        the condition.  Cases cover every banded family: wan-shaped with
        chords, an odd-N ring (padding rows live), a grid, and a drained
        node on a ring and on a grid, which must never be a transit."""
        from openr_tpu.decision.csr import _build_out_slots
        from openr_tpu.ops import allsources as asrc

        topo, dests, drained = _ecmp_case(case)
        n, e = int(topo.n_nodes), int(topo.n_edges)
        if isinstance(topo, synthetic.Topology):
            runner = synthetic.reversed_topology(topo).runner
            out_slot, _ = _build_out_slots(topo.edge_src, topo.edge_dst, e)
        else:
            from openr_tpu.decision.fleet import _reverse_runner

            runner = _reverse_runner(topo)
            out_slot = topo.out_slot
        # the fused progressive program exists on banded topologies only
        assert runner.bg is not None
        out = asrc.build_out_ell(
            topo.edge_src, topo.edge_dst, e, n, out_slot=out_slot
        )
        dist, bitmap, ok = asrc.reduced_all_sources(
            dests, runner, out, topo.edge_metric, topo.edge_up,
            topo.node_overloaded,
        )
        assert bool(ok)
        dist = to_i32(dist)[:n]  # [N, P] native layout
        bitmap = np.asarray(bitmap)[:n]  # [N, P, W]

        src = np.asarray(topo.edge_src[:e], np.int64)
        dst = np.asarray(topo.edge_dst[:e], np.int64)
        met = np.asarray(topo.edge_metric[:e], np.int64)
        up = np.asarray(topo.edge_up[:e], bool)
        ov = np.asarray(topo.node_overloaded[:n], bool)
        ref = _reference_dist(n, src, dst, met, up, ov, dests)
        np.testing.assert_array_equal(dist, ref)
        on = _ecmp_edges(src, dst, met, up, ov, ref)
        for x in drained:
            # non-vacuous: without the drain, x is some router's next hop
            # toward another destination
            free = np.zeros_like(ov)
            free_on = _ecmp_edges(
                src, dst, met, up, free,
                _reference_dist(n, src, dst, met, up, free, dests),
            )
            assert free_on[dst == x][:, dests != x].any(), x
        slot = np.asarray(out_slot[:e], np.int64)
        want = np.zeros_like(bitmap)
        ei, pi = np.nonzero(on)
        np.bitwise_or.at(
            want,
            (src[ei], pi, slot[ei] // 32),
            (np.uint32(1) << (slot[ei] % 32)).astype(np.uint32),
        )
        np.testing.assert_array_equal(bitmap, want)

        # a drained node is next hop only toward itself
        got_bit = (bitmap[src, :, slot // 32] >> (slot % 32)[:, None]) & 1
        for x in drained:
            into_x = got_bit[dst == x]
            assert not into_x[:, dests != x].any(), x


def _csr_of(dbs, drain=()):
    from openr_tpu.decision.csr import CsrTopology
    from openr_tpu.decision.link_state import LinkState

    ls = LinkState()
    for db in dbs:
        db.is_overloaded = db.this_node_name in drain
        ls.update_adjacency_database(db)
    return CsrTopology.from_link_state(ls)


def _ecmp_case(case):
    """(topology, destination ids, drained node ids) of one case of
    test_bitmap_matches_reference_ecmp_condition."""
    from openr_tpu.utils.topo import grid_topology, ring_topology

    if case == "wan128":
        rng = np.random.default_rng(11)
        dests = np.sort(rng.choice(128, size=12, replace=False))
        return synthetic.wan(128, chords=2, seed=13), dests.astype(np.int32), []
    if case == "wan_chords":
        topo = synthetic.wan(96, chords=2, seed=3)
        return topo, np.asarray([0, 5, 17, 48, 95], np.int32), []
    if case == "ring_odd_n":
        return _csr_of(ring_topology(65)), np.asarray([0, 7, 31, 64], np.int32), []
    if case == "grid":
        return _csr_of(grid_topology(10)), np.arange(0, 100, 9, dtype=np.int32), []
    if case == "ring_drained":
        csr = _csr_of(ring_topology(65), drain={"r7"})
        return csr, np.asarray([0, 7, 40], np.int32), [csr.node_id["r7"]]
    assert case == "grid_drained"
    dbs = grid_topology(10)
    name = dbs[37].this_node_name
    csr = _csr_of(dbs, drain={name})
    return csr, np.asarray([0, 37, 99], np.int32), [csr.node_id[name]]


def _ecmp_edges(src, dst, met, up, ov, d):
    """[E, P] bool: edge v->u is an ECMP next hop of v toward p, i.e.
    metric(v,u) + d(u,p) == d(v,p) < INF and u is not a drained transit
    (Decision.cpp:1296-1300)."""
    d_u = d[dst]
    return (
        up[:, None]
        & (d[src] < INF32)
        & (met[:, None] + d_u == d[src])
        & (~ov[dst][:, None] | (d_u == 0))
    )


def _reference_dist(n, src, dst, met, up, ov, dests):
    """[N, P] dist(v -> dests[p]) by Bellman-Ford over the forward
    edges: a drained node ends paths but relays none (it may still be
    the origin or the destination)."""
    d = np.full((n, len(dests)), INF32, np.int64)
    d[dests, np.arange(len(dests))] = 0
    src, dst, met = src[up], dst[up], met[up]
    for _ in range(n):
        cand = np.minimum(met[:, None] + d[dst], INF32)
        cand[ov[dst][:, None] & (d[dst] != 0)] = INF32
        nxt = d.copy()
        np.minimum.at(nxt, src, cand)
        if np.array_equal(nxt, d):
            break
        d = nxt
    return d
