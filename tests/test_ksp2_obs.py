"""The KSP2 pre-pass's spans and counters: `decision.ksp2` with its
children `ksp2.trace`, `ksp2.relax` and `ksp2.decode` once per route
build that computes paths; `decision.ksp2_rows` (masked rows run),
`decision.ksp2_paths` (k=1 plus k=2 paths traced) and
`decision.ksp2_decoded_nodes` (the nodes the k=2 traces decoded from the
rows), pre-seeded so that both wire surfaces list them; and the route
build unchanged by them."""

from __future__ import annotations

import copy
import json
import os
import zlib

import pytest

from openr_tpu.decision.link_state import LinkState
from openr_tpu.decision.prefix_state import PrefixState
from openr_tpu.decision.spf_solver import DeviceSpfBackend, SpfSolver
from openr_tpu.obs import trace as obs
from openr_tpu.types import (
    PrefixEntry,
    PrefixForwardingAlgorithm,
    PrefixForwardingType,
)
from openr_tpu.utils.topo import grid_topology

GOLDEN = os.path.join(os.path.dirname(__file__), "golden", "ksp2_grid6_route_db.json")
ME = "node-2-2"
CHILDREN = ("ksp2.trace", "ksp2.relax", "ksp2.decode")


def ksp2_grid(n: int = 6):
    """(adjacency databases by node, LinkState, PrefixState) of an n x n
    grid whose every node advertises one /64 as KSP2_ED_ECMP over
    SR_MPLS, node labels 100 + index, link-local addresses from the
    names (the grid helper's hash them)."""
    dbs = {db.this_node_name: db for db in grid_topology(n)}
    ls = LinkState()
    ps = PrefixState()
    for i, node in enumerate(sorted(dbs)):
        dbs[node].node_label = 100 + i
        for adj in dbs[node].adjacencies:
            adj.next_hop_v6 = f"fe80::{zlib.crc32(f'{node}|{adj.other_node_name}'.encode()):x}"
        ls.update_adjacency_database(dbs[node])
        ps.update_prefix(
            node,
            "0",
            PrefixEntry(
                prefix=f"fc00:{i:x}::/64",
                forwarding_type=PrefixForwardingType.SR_MPLS,
                forwarding_algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP,
            ),
        )
    return dbs, ls, ps


def route_db_text(db) -> dict:
    """A route DB as text: every unicast and MPLS route's next hops, by
    their repr, sorted."""
    return {
        "unicast": {
            p: sorted(map(repr, r.nexthops)) for p, r in sorted(db.unicast_routes.items())
        },
        "mpls": {
            str(label): sorted(map(repr, r.nexthops))
            for label, r in sorted(db.mpls_routes.items())
        },
    }


def _solver():
    return SpfSolver(
        ME, spf_backend=DeviceSpfBackend(min_device_nodes=1, min_device_sources=1)
    )


def _flap(dbs, ls, a, b, down):
    for x, y in ((a, b), (b, a)):
        db = copy.deepcopy(dbs[x])
        if down:
            db.adjacencies = [adj for adj in db.adjacencies if adj.other_node_name != y]
        ls.update_adjacency_database(db)


def _traced_build(tracer, solver, ls, ps):
    root = tracer.root("test.build")
    with tracer.activate([root]):
        solver.build_route_db({"0": ls}, ps)
    tracer.finish(root)
    return root


def _named(span, name):
    out = [span] if span.name == name else []
    for c in span.children:
        out += _named(c, name)
    return out


@pytest.fixture
def tracer():
    tr = obs.enable(ring=64)
    yield tr
    obs.disable()


def test_ksp2_span_and_its_children_once_per_build(tracer):
    dbs, ls, ps = ksp2_grid()
    solver = _solver()
    roots = [_traced_build(tracer, solver, ls, ps)]
    _flap(dbs, ls, "node-4-4", "node-4-5", True)
    roots.append(_traced_build(tracer, solver, ls, ps))
    for root in roots:
        (ksp2,) = _named(root, "decision.ksp2")
        assert ksp2.t_end_us is not None
        # (a decision.spf miss for the source may come first)
        assert [c.name for c in ksp2.children if c.name in CHILDREN] == list(CHILDREN)
        for name in CHILDREN:
            assert len(_named(root, name)) == 1
    # nothing to compute: the paths of this topology version are cached
    again = _traced_build(tracer, solver, ls, ps)
    assert _named(again, "decision.ksp2") == []


def test_ksp2_counters_count_rows_and_paths():
    _dbs, ls, ps = ksp2_grid()
    solver = _solver()
    assert solver.counters["decision.ksp2_rows"] == 0
    assert solver.counters["decision.ksp2_paths"] == 0
    solver.build_route_db({"0": ls}, ps)
    dests = [n for n in ls.node_names if n != ME]
    first = {d: ls.get_kth_paths(ME, d, 1) for d in dests}
    second = {d: ls.get_kth_paths(ME, d, 2) for d in dests}
    # one masked row for every destination with a first path
    assert solver.counters["decision.ksp2_rows"] == sum(1 for d in dests if first[d])
    assert solver.counters["decision.ksp2_paths"] == sum(
        len(first[d]) + len(second[d]) for d in dests
    )


def test_ksp2_decoded_nodes_counts_the_lazy_walk():
    """Each masked row decodes only the nodes its k=2 traces read: at
    least one a traced path, at most every node of every row."""
    _dbs, ls, ps = ksp2_grid(8)
    solver = _solver()
    assert solver.counters["decision.ksp2_decoded_nodes"] == 0
    solver.build_route_db({"0": ls}, ps)
    dests = [n for n in ls.node_names if n != ME]
    second = sum(len(ls.get_kth_paths(ME, d, 2)) for d in dests)
    rows = solver.counters["decision.ksp2_rows"]
    decoded = solver.counters["decision.ksp2_decoded_nodes"]
    assert rows > 0 and second > 0
    assert second <= decoded <= rows * len(ls.node_names)


def test_ksp2_counters_on_both_wire_surfaces():
    """Pre-seeded: one getCounters on the native ctrl server and on the
    fb303 shim lists both before any KSP2 route is built."""
    from openr_tpu.ctrl import CtrlClient
    from openr_tpu.interop import thrift_binary as tb
    from openr_tpu.interop.shim import ThriftBinaryShim
    from openr_tpu.kvstore import InProcessTransport
    from openr_tpu.main import OpenrDaemon
    from openr_tpu.spark import MockIoProvider
    from test_system import make_config
    from test_thrift_binary import _call_ok

    family = {
        "decision.ksp2_rows",
        "decision.ksp2_paths",
        "decision.ksp2_decoded_nodes",
    }
    daemon = OpenrDaemon(
        make_config("solo", ctrl_port=0),
        io_provider=MockIoProvider().endpoint("solo"),
        kvstore_transport=InProcessTransport().bind("solo"),
    )
    daemon.start()
    try:
        client = CtrlClient(port=daemon.ctrl_port)
        try:
            native = client.call("getCounters")
        finally:
            client.close()
        shim = ThriftBinaryShim(
            daemon.kvstore,
            port=0,
            node_name="solo",
            counters_fn=daemon.ctrl_server.handler._all_counters,
        )
        shim.run()
        try:
            shimmed = _call_ok(
                shim.port,
                "getCounters",
                44,
                b"\x00",
                ("map", tb.T_STRING, tb.T_I64),
                dec=lambda m: {k.decode(): v for k, v in m.items()},
            )
        finally:
            shim.stop()
            shim.wait_until_stopped(5)
    finally:
        daemon.stop()
    assert {k: native[k] for k in family} == dict.fromkeys(family, 0)
    assert {k: shimmed[k] for k in family} == dict.fromkeys(family, 0)


def test_route_db_unchanged_with_tracing_off():
    """The 6 x 6 KSP2 grid's route DB, before and after a flap, as the
    program built it before these spans and counters existed."""
    assert obs.TRACE is None
    dbs, ls, ps = ksp2_grid()
    solver = _solver()
    got = [route_db_text(solver.build_route_db({"0": ls}, ps))]
    _flap(dbs, ls, "node-4-4", "node-4-5", True)
    got.append(route_db_text(solver.build_route_db({"0": ls}, ps)))
    with open(GOLDEN) as f:
        assert got == json.load(f)
