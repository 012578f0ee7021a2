"""The CSR mirror's node-pair index (CsrTopology.pair_edge_ids) and the
SRLG what-if resolve that reads it.

The index maps a node pair to the directed edge ids of every link between
the pair.  It is built once per edge-array state and must equal a walk of
`edge_links` after every kind of mirror refresh; `what_if` must resolve
scenario links to the same masks and the same known / unknown lists as
that walk."""

from __future__ import annotations

import copy

import numpy as np
import pytest

from openr_tpu.decision.csr import PAIR_INDEX_BUILDS, CsrTopology
from openr_tpu.decision.decision import Decision
from openr_tpu.decision.link_state import LinkState
from openr_tpu.decision.protection_api import what_if
from openr_tpu.decision.spf_solver import DeviceSpfBackend
from openr_tpu.ops import protection as prot
from openr_tpu.runtime.queue import ReplicateQueue
from openr_tpu.serving.backend import EngineBatchBackend
from openr_tpu.types import Adjacency, AdjacencyDatabase
from openr_tpu.utils.topo import fat_tree_topology


def _oracle_pair_edge_ids(csr: CsrTopology) -> dict[tuple[str, str], list[int]]:
    """(sorted node pair) -> directed edge ids of every parallel link
    between them: one O(E) walk of edge_links."""
    out: dict[tuple[str, str], list[int]] = {}
    for e, pair in enumerate(csr.edge_links):
        if pair is None:  # retired freelist slot
            continue
        link = pair[0]
        key = (link.n1, link.n2) if link.n1 <= link.n2 else (link.n2, link.n1)
        out.setdefault(key, []).append(e)
    return out


def _index_by_names(csr: CsrTopology, bump=None) -> dict[tuple[str, str], list[int]]:
    """pair_edge_ids() decoded into the oracle's shape."""
    keys, ids = csr.pair_edge_ids(bump)
    assert np.all(np.diff(keys) >= 0)
    out: dict[tuple[str, str], list[int]] = {}
    for k, e in zip(keys.tolist(), ids.tolist()):
        lo, hi = divmod(k, csr.node_capacity)
        out.setdefault((csr.node_names[lo], csr.node_names[hi]), []).append(e)
    return out


def _adj(me: str, other: str, tag: str = "") -> Adjacency:
    return Adjacency(
        other_node_name=other,
        if_name=f"if{tag}_{me}_{other}",
        other_if_name=f"if{tag}_{other}_{me}",
        metric=1,
        next_hop_v6=f"fe80::{len(me)}:{len(other)}{tag}",
    )


class Fabric:
    """A small fat-tree whose adjacency databases are edited and
    republished into one LinkState."""

    def __init__(self) -> None:
        self.dbs = {db.this_node_name: db for db in fat_tree_topology(2)}
        self.ls = LinkState()
        for db in self.dbs.values():
            self.ls.update_adjacency_database(copy.deepcopy(db))

    def publish(self, *names: str) -> None:
        for name in names:
            self.ls.update_adjacency_database(copy.deepcopy(self.dbs[name]))

    def link(self, a: str, b: str, tag: str = "") -> None:
        self.dbs[a].adjacencies.append(_adj(a, b, tag))
        self.dbs[b].adjacencies.append(_adj(b, a, tag))

    def unlink(self, a: str, b: str) -> None:
        for x, y in ((a, b), (b, a)):
            self.dbs[x].adjacencies = [
                adj for adj in self.dbs[x].adjacencies if adj.other_node_name != y
            ]


def _fresh(fab: Fabric, csr: CsrTopology, bump) -> bool:
    return True  # no refresh: the mirror as built


def _parallel(fab: Fabric, csr: CsrTopology, bump) -> bool:
    fab.link("fsw-0-0", "rsw-0-0", tag="2")
    fab.publish("fsw-0-0", "rsw-0-0")
    csr.refresh(fab.ls)  # a rewire or a rebuild: the edge arrays change
    pair = _index_by_names(csr, bump)[("fsw-0-0", "rsw-0-0")]
    assert len(pair) == 4 and pair == sorted(pair)
    return False


def _link_down(fab: Fabric, csr: CsrTopology, bump) -> bool:
    adj = next(
        a for a in fab.dbs["fsw-0-0"].adjacencies if a.other_node_name == "rsw-0-0"
    )
    adj.is_overloaded = True
    fab.publish("fsw-0-0")
    assert csr.refresh(fab.ls) and csr.rewire_seq == 0
    # a down link keeps its slots, so it still resolves
    down = _index_by_names(csr, bump)[("fsw-0-0", "rsw-0-0")]
    assert len(down) == 2 and not csr.edge_up[down].any()
    return True


def _rewire(fab: Fabric, csr: CsrTopology, bump) -> bool:
    # an OCS swap: two links retire and two new ones take their slots
    n_edges = csr.n_edges
    fab.unlink("fsw-0-0", "rsw-0-0")
    fab.unlink("fsw-1-0", "rsw-1-0")
    fab.link("fsw-0-0", "rsw-1-0")
    fab.link("fsw-1-0", "rsw-0-0")
    fab.publish("fsw-0-0", "rsw-0-0", "fsw-1-0", "rsw-1-0")
    assert csr.refresh(fab.ls) and csr.rewire_seq == 1
    assert csr.n_edges == n_edges and csr._free_slots == []
    index = _index_by_names(csr, bump)
    assert ("fsw-0-0", "rsw-0-0") not in index
    assert ("fsw-0-0", "rsw-1-0") in index
    return False


def _node_added(fab: Fabric, csr: CsrTopology, bump) -> bool:
    fab.dbs["rsw-9-0"] = AdjacencyDatabase(this_node_name="rsw-9-0", node_label=99)
    fab.link("fsw-1-1", "rsw-9-0")
    fab.publish("rsw-9-0", "fsw-1-1")
    assert not csr.refresh(fab.ls)
    assert csr._pair_index is None  # the full rebuild reset it
    assert ("fsw-1-1", "rsw-9-0") in _index_by_names(csr, bump)
    return False


@pytest.mark.parametrize(
    "step", [_fresh, _parallel, _link_down, _rewire, _node_added],
    ids=["fresh", "parallel_links", "link_down", "ocs_rewire", "node_added"],
)
def test_pair_index_equals_oracle_walk(step):
    fab = Fabric()
    csr = CsrTopology.from_link_state(fab.ls)
    builds: list[str] = []
    keys, ids = csr.pair_edge_ids(builds.append)
    assert builds == [PAIR_INDEX_BUILDS]
    assert _index_by_names(csr) == _oracle_pair_edge_ids(csr)
    kept = step(fab, csr, builds.append)
    assert _index_by_names(csr, builds.append) == _oracle_pair_edge_ids(csr)
    # an unchanged edge-array state keeps the index; any other builds it
    # once
    again = csr.pair_edge_ids(builds.append)
    assert (again[0] is keys and again[1] is ids) is kept
    assert builds == [PAIR_INDEX_BUILDS] * (1 if kept else 2)


def test_what_if_resolves_as_the_oracle_walk(monkeypatch):
    fab = Fabric()
    fab.link("fsw-0-0", "rsw-0-0", tag="2")  # parallel links fail together
    fab.publish("fsw-0-0", "rsw-0-0")
    csr = CsrTopology.from_link_state(fab.ls)
    scenarios = [
        [("rsw-0-0", "fsw-0-0"), ("fsw-0-1", "ssw-1-0")],
        [("fsw-0-0", "nope")],  # a name the mirror does not know
        [("rsw-0-0", "rsw-0-1"), ("ssw-0-0", "fsw-1-0")],  # not adjacent
        [("fsw-1-1", "rsw-1-3"), ("rsw-1-3", "fsw-1-1"), ("x", "y")],
        [],
    ]

    pairs = _oracle_pair_edge_ids(csr)
    want_masks = np.ones((len(scenarios) + 1, csr.edge_capacity), dtype=bool)
    want_resolved = []
    for f, links in enumerate(scenarios):
        known, unknown = [], []
        for a, b in links:
            ids = pairs.get((a, b) if a <= b else (b, a))
            if ids:
                want_masks[f + 1, ids] = False
                known.append([a, b])
            else:
                unknown.append([a, b])
        want_resolved.append({"links": known, "unknown_links": unknown})
    assert want_resolved[2]["unknown_links"] == [
        ["rsw-0-0", "rsw-0-1"]
    ] and want_resolved[2]["links"] == [["ssw-0-0", "fsw-1-0"]]

    seen = []
    kernel = prot.srlg_what_if

    def recording(*args, **kwargs):
        seen.append(np.array(args[6]))
        return kernel(*args, **kwargs)

    monkeypatch.setattr(prot, "srlg_what_if", recording)
    rows = what_if(fab.ls, scenarios, sources=["rsw-0-0", "rsw-1-2"], csr=csr)
    assert len(seen) == 1 and np.array_equal(seen[0], want_masks)
    assert [
        {"links": r["links"], "unknown_links": r["unknown_links"]} for r in rows
    ] == want_resolved
    assert [r["scenario"] for r in rows] == list(range(len(scenarios)))
    # a scenario that resolves no link fails nothing
    assert rows[1] == {**want_resolved[1], "scenario": 1,
                       "newly_unreachable_pairs": 0, "degraded_pairs": 0}
    # the same query on a fresh mirror answers the same rows
    assert what_if(fab.ls, scenarios, sources=["rsw-0-0", "rsw-1-2"]) == rows


def _decision_what_if_builds(ls: LinkState, scenarios, calls: int) -> tuple:
    kvq, routeq = ReplicateQueue(), ReplicateQueue()
    d = Decision(
        "rsw-0-0",
        kvq.get_reader(),
        None,
        routeq,
        debounce_min_s=600,
        debounce_max_s=600,
        spf_backend=DeviceSpfBackend(min_device_nodes=1, min_device_sources=1),
    )
    d.run()
    try:
        d.run_in_event_base_thread(
            lambda: d.area_link_states.__setitem__("0", ls)
        ).result()
        before = d.get_counters()[PAIR_INDEX_BUILDS]
        rows = [d.what_if(scenarios) for _ in range(calls)]
        return before, d.get_counters()[PAIR_INDEX_BUILDS], rows
    finally:
        kvq.close()
        routeq.close()
        d.stop()
        d.wait_until_stopped(5)


def _engine_what_if_builds(ls: LinkState, scenarios, calls: int) -> tuple:
    counters: dict[str, int] = {}

    def bump(name: str, n: int = 1) -> None:
        counters[name] = counters.get(name, 0) + n

    backend = EngineBatchBackend(
        {"0": ls},
        spf_backend=DeviceSpfBackend(min_device_nodes=1, min_device_sources=1),
        bump=bump,
    )
    rows = [
        backend.run_what_if("0", ["rsw-0-0"], scenarios, ls.version)
        for _ in range(calls)
    ]
    return 0, counters.get(PAIR_INDEX_BUILDS, 0), rows


@pytest.mark.parametrize(
    "run", [_decision_what_if_builds, _engine_what_if_builds],
    ids=["decision", "engine_backend"],
)
def test_what_if_builds_the_pair_index_once_per_mirror_version(run):
    fab = Fabric()
    scenarios = [[("fsw-0-0", "rsw-0-0")], [("fsw-0-0", "ssw-0-0")]]
    before, after, rows = run(fab.ls, scenarios, 2)
    assert before == 0
    assert after == 1
    assert rows[0] == rows[1] and rows[0][0]["links"] == [["fsw-0-0", "rsw-0-0"]]
