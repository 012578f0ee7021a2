"""The KSP2 cell (grid1k_ksp2.converge_ksp2) on the CPU at a tiny size,
an 8 x 8 grid with an interior daemon: the run as `perf.run` makes it,
its control and planted faults judged by the same comparison, and the
reference of `perf.reference_ksp2` against hand-computed routes and
against the program's own KSP2 route build."""

import dataclasses
import os
import random
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf import deployment, reference_ksp2, run  # noqa: E402

CELL = "grid1k_ksp2.converge_ksp2"
TINY = os.path.join(ROOT, "tests", "perf", "data", "grid_ksp2_tiny.json")
SEED = 2**31 + 77  # more than 32 signed bits hold
KSP2_METRICS = {
    "ksp2_trace_ms.converge_ksp2",
    "ksp2_relax_ms.converge_ksp2",
    "ksp2_decode_ms.converge_ksp2",
    "ksp2_rows.converge_ksp2",
}


def run_tiny(seed=SEED, seconds=1.0, trace=False):
    return run.run_cell(
        run.load_manifest(ROOT),
        CELL,
        seed,
        seconds,
        trace,
        ROOT,
        t_process=time.perf_counter(),
        config_file=TINY,
    )


# -- the cell end to end ------------------------------------------------------


def test_cell_runs_correct_with_its_metrics():
    result, report = run_tiny()
    assert result["correct"] and result["attempted"] > 0 and result["failed"] == 0
    e2e, _ = run.cell_metrics(run.load_manifest(ROOT), CELL)
    assert set(result["metrics"]) == {m["name"] for m in e2e} == {"setup_s", "converge_ms"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert report["window_compiles"]["executables"] == 0
    assert report["samples"]["events_in_window"] > 0


def test_traced_run_reads_the_ksp2_metrics():
    result, report = run_tiny(trace=True)
    assert result["correct"]
    _, layer = run.cell_metrics(run.load_manifest(ROOT), CELL)
    names = {m["name"] for m in layer if m["source"] != "device_trace"}
    assert KSP2_METRICS <= names
    # no device plane on the CPU: the device readers find nothing
    assert set(result["metrics"]) == names
    # one masked row for each of the 63 other nodes, every rebuild
    assert result["metrics"]["ksp2_rows.converge_ksp2"]["value"] == 63
    assert report["spans"]["roots"] > 0


@pytest.mark.parametrize("seed", [11, 2**31 + 5, 4242])
def test_control_in_the_programs_place_is_not_correct(seed):
    result, report = run_tiny(seed=seed)
    assert result["correct"]
    drv = report["driver"]
    drv.put_control()
    compared, correct = run.judge(drv)
    assert not correct
    assert set(compared) == set(result["compared"])


# -- faults planted where the FIB agent takes the routes ----------------------


def _drop_second_path(nhs):
    """A k=2 next hop dropped: the one of the highest metric, where the
    route has more than one metric."""
    worst = max(nhs, key=lambda nh: nh.metric)
    if worst.metric == min(nh.metric for nh in nhs):
        return nhs
    return [nh for nh in nhs if nh is not worst]


def _drop_label(nhs):
    """One label removed from the first stack: the top one, the node
    after the first hop."""
    out = list(nhs)
    for i, nh in enumerate(out):
        if nh.mpls_action is not None and nh.mpls_action.push_labels:
            labels = nh.mpls_action.push_labels[:-1]
            action = dataclasses.replace(nh.mpls_action, push_labels=labels)
            out[i] = dataclasses.replace(nh, mpls_action=action)
            break
    return out


def _metric_off_by_one(nhs):
    return [dataclasses.replace(nhs[0], metric=nhs[0].metric + 1), *nhs[1:]]


@pytest.mark.parametrize("fault", [_drop_second_path, _drop_label, _metric_off_by_one])
def test_planted_fault_is_not_correct(monkeypatch, fault):
    """Each fault alters every route the FIB agent is given once the
    set-up is done."""
    from openr_tpu.fib import fib
    from perf.drivers import ksp2_events

    real_add = fib.MockFibAgent.add_unicast_routes

    def altered(self, client_id, routes):
        for r in routes:
            r.next_hops = fault(list(r.next_hops))
        return real_add(self, client_id, routes)

    real_warm = ksp2_events.Driver.warm

    def warm(self):
        real_warm(self)
        monkeypatch.setattr(fib.MockFibAgent, "add_unicast_routes", altered)

    monkeypatch.setattr(ksp2_events.Driver, "warm", warm)
    result, _ = run_tiny(seconds=2.0)
    assert not result["correct"]
    assert result["compared"]["routes_wrong"]["value"] > 0


# -- the reference --------------------------------------------------------------


def _grid(n, metrics=None):
    cfg = {
        "generator": "grid",
        "params": {"n_side": n},
        "link_metric": 1,
        "prefixes_per_node": 1,
        "area": "0",
    }
    topo = deployment.build(cfg)
    if metrics is None:
        return topo
    # a metric per direction, drawn from the seed
    rng = random.Random(metrics)
    links = [(a, b, rng.randint(1, 20), rng.randint(1, 20)) for a, b, _, _ in topo.links]
    m = {}
    for a, b, mab, mba in links:
        m[(a, b)], m[(b, a)] = mab, mba
    adj = {
        n: [dataclasses.replace(x, metric=m[(n, x.other)]) for x in xs]
        for n, xs in topo.adj.items()
    }
    return deployment.Topology(topo.area, topo.nodes, links, adj, topo.prefixes)


def _hops(route, graph):
    """{(first hop, metric, node path after the first hop)}."""
    return {
        (
            nh.neighbor_node_name,
            nh.metric,
            tuple(
                graph.node_of_label[label]
                for label in reversed(nh.mpls_action.push_labels if nh.mpls_action else ())
            ),
        )
        for nh in route.next_hops
    }


def test_reference_on_a_3x3_grid_against_hand_computed_routes():
    """From the centre: a corner has two first paths, whose links leave
    it no second; a side neighbour has its direct link, then the two
    three-hop detours around the centre."""
    topo = _grid(3)
    g = reference_ksp2.Graph(topo)
    me = "node-1-1"
    routes = reference_ksp2.routes(g, me)
    assert len(routes) == 8
    p = {n: topo.prefixes[n][0] for n in topo.nodes}
    assert _hops(routes[p["node-0-0"]], g) == {
        ("node-0-1", 2, ("node-0-0",)),
        ("node-1-0", 2, ("node-0-0",)),
    }
    assert _hops(routes[p["node-0-1"]], g) == {
        ("node-0-1", 1, ()),
        ("node-1-0", 3, ("node-0-0", "node-0-1")),
        ("node-1-2", 3, ("node-0-2", "node-0-1")),
    }
    # label stacks: the destination's label at the bottom, node labels index + 1
    (nh,) = [x for x in routes[p["node-0-1"]].next_hops if x.neighbor_node_name == "node-1-0"]
    assert nh.mpls_action.push_labels == (2, 1)
    # node-0-0's link to node-0-1 down: one first path, no second
    down = [("node-0-0", "node-0-1")]
    routes = reference_ksp2.routes(g, me, down)
    assert _hops(routes[p["node-0-0"]], g) == {("node-1-0", 2, ("node-0-0",))}

    check = reference_ksp2.Checker(g, me)
    up = reference_ksp2.routes(g, me)
    assert check.n_wrong(up) == 0
    assert check.n_wrong(routes, down) == 0
    # one of two first paths, or one of two second paths, left out: not maximal
    for dest, dropped in (("node-0-0", "node-0-1"), ("node-0-1", "node-1-2")):
        cut = dict(up)
        cut[p[dest]] = reference_ksp2.Route(
            [x for x in up[p[dest]].next_hops if x.neighbor_node_name != dropped]
        )
        assert check.n_wrong(cut) == 1
    # the routes of one state are wrong in the other
    assert check.n_wrong(routes) > 0
    # a missing second path, a missing prefix and an extra one are each wrong
    cut = dict(routes)
    cut[p["node-0-1"]] = reference_ksp2.Route(
        [x for x in cut[p["node-0-1"]].next_hops if x.metric == 1]
    )
    assert check.n_wrong(cut, down) == 1
    assert check.n_wrong({k: v for k, v in routes.items() if k != p["node-2-2"]}, down) == 1
    assert check.n_wrong({**routes, p[me]: routes[p["node-0-1"]]}, down) == 1


def _program_routes(topo, me):
    """The program's KSP2 route build (SpfSolver on DeviceSpfBackend, the
    CPU here) over the topology, every other node's prefix advertised
    KSP2_ED_ECMP over SR_MPLS, as {prefix: route}."""
    from openr_tpu.decision.link_state import LinkState
    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.decision.spf_solver import DeviceSpfBackend, SpfSolver
    from openr_tpu.types import (
        Adjacency,
        AdjacencyDatabase,
        PrefixEntry,
        PrefixForwardingAlgorithm,
        PrefixForwardingType,
    )

    ls = LinkState()
    ps = PrefixState()
    for node in topo.nodes:
        ls.update_adjacency_database(
            AdjacencyDatabase(
                this_node_name=node,
                adjacencies=[
                    Adjacency(
                        other_node_name=a.other,
                        if_name=a.if_name,
                        other_if_name=a.other_if_name,
                        metric=a.metric,
                        next_hop_v6=a.next_hop_v6,
                    )
                    for a in topo.adj[node]
                ],
                area=topo.area,
                node_label=topo.index[node] + 1,
            )
        )
        for p in topo.prefixes[node]:
            ps.update_prefix(
                node,
                topo.area,
                PrefixEntry(
                    prefix=p,
                    forwarding_type=PrefixForwardingType.SR_MPLS,
                    forwarding_algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP,
                ),
            )
    backend = DeviceSpfBackend(min_device_nodes=1, min_device_sources=1)
    db = SpfSolver(me, spf_backend=backend).build_route_db({topo.area: ls}, ps)
    return {
        p: reference_ksp2.Route(sorted(r.nexthops, key=repr))
        for p, r in db.unicast_routes.items()
    }


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_program_matches_the_reference_on_grids_with_random_metrics(seed):
    """6 x 6 grids with a metric of 1-20 per direction: no two paths tie
    on these seeds, so the program's routes are the reference's, next
    hop by next hop."""
    topo = _grid(6, metrics=seed)
    g = reference_ksp2.Graph(topo)
    me = "node-2-3"
    got = _program_routes(topo, me)
    want = reference_ksp2.routes(g, me)
    assert set(got) == set(want)
    for p in want:
        assert _hops(got[p], g) == _hops(want[p], g), p
        assert {(nh.if_name, nh.address) for nh in got[p].next_hops} == {
            (nh.if_name, nh.address) for nh in want[p].next_hops
        }
    assert reference_ksp2.Checker(g, me).n_wrong(got) == 0
