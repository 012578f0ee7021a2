"""The plain reference on graphs small enough to check by hand."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf import deployment, reference  # noqa: E402


def _topo(pairs, metric=1):
    names = sorted({n for p in pairs for n in p})
    adj = {n: [] for n in names}
    links = []
    for a, b in pairs:
        links.append((a, b, metric, metric))
        adj[a].append(deployment.Adj(b, f"if_{a}_{b}", f"if_{b}_{a}", metric, f"fe80::{b}"))
        adj[b].append(deployment.Adj(a, f"if_{b}_{a}", f"if_{a}_{b}", metric, f"fe80::{a}"))
    prefixes = {n: [f"p-{n}"] for n in names}
    return deployment.Topology("0", names, links, adj, prefixes)


SQUARE = [("a", "b"), ("b", "d"), ("a", "c"), ("c", "d")]


def test_square_routes_are_ecmp_at_the_far_corner():
    g = reference.Graph(_topo(SQUARE))
    r = reference.routes(g, "a")
    assert set(r) == {"p-b", "p-c", "p-d"}
    assert r["p-b"] == {("b", "if_a_b", "fe80::b", 1)}
    assert r["p-d"] == {("b", "if_a_b", "fe80::b", 2), ("c", "if_a_c", "fe80::c", 2)}


def test_square_link_down_moves_and_withdraws():
    g = reference.Graph(_topo(SQUARE))
    r = reference.routes(g, "a", [("b", "a")])
    assert r["p-b"] == {("c", "if_a_c", "fe80::c", 3)}
    assert r["p-d"] == {("c", "if_a_c", "fe80::c", 2)}
    g2 = reference.Graph(_topo([("a", "b"), ("c", "d")]))
    assert "p-c" not in reference.routes(g2, "a")


def test_what_if_counts_unreachable_and_degraded_pairs():
    g = reference.Graph(_topo(SQUARE))
    # d's two links fail: d unreachable from a and from b, and b -> c
    # keeps its metric 2 through a; a-b fails: a -> b and b -> a go
    # from 1 to 3 around the square
    groups = [[["d", "b"], ["d", "c"]], [["a", "b"]]]
    assert reference.what_if(g, ["a", "b"], groups) == [(2, 0), (0, 2)]
    # the control fails one direction only, and reads otherwise
    assert reference.what_if(g, ["a", "b"], groups, one_direction=True) != [(2, 0), (0, 2)]


def test_fabric_generator_matches_the_published_sizes():
    cfg = deployment.load_config("fabric10k")
    topo = deployment.build(cfg)
    # F4: 189 pods of 4 fsw + 48 rsw, 4 planes of 48 ssw
    assert len(topo.nodes) == 10_020 and len(topo.links) == 72_576
    assert max(len(a) for a in topo.adj.values()) == 189
    grid = deployment.build(deployment.load_config("grid10k"))
    assert len(grid.nodes) == 10_000 and len(grid.links) == 19_800


@pytest.mark.parametrize("name", ["fabric10k", "grid10k"])
def test_warm_links_cover_every_rewire_key_of_the_remote_links(name):
    from perf.drivers import link_events

    cfg = deployment.load_config(name)
    topo = deployment.build(cfg)
    links = [(a, b) for a, b, _, _ in topo.links if cfg["daemon_node"] not in (a, b)]
    warm = link_events.warm_links(topo, links)
    later = {n: sorted(x.other for x in adj) for n, adj in topo.adj.items()}
    keys = {link_events.rewire_key(topo, a, b, later) for a, b in links}
    assert {link_events.rewire_key(topo, a, b, later) for a, b in warm} == keys
    assert len(warm) == len(keys)
