"""The spans inside Decision, the what-if call, the scheduler's staged
wait and the ctrl reply (openr_tpu/obs): each opens under its parent on
a live daemon with sane self times, the fan-in helper always finishes
its children, `ctrl.reply` is a root beside `serving.query`, live spans
reach a CPU profiler trace's host plane, and `decision.rebuilds` counts
route rebuilds.
"""

from __future__ import annotations

import glob
import os
import time

import pytest

from openr_tpu.obs import trace as _trace
from openr_tpu.runtime.eventbase import OpenrEventBase

from test_system import make_config, wait_for

PFX = "::9:0/112"


@pytest.fixture
def tracer():
    tr = _trace.enable(sample_every=1, ring=1024)
    yield tr
    _trace.disable()


def _adj(me, other, metric):
    from openr_tpu.types import Adjacency

    return Adjacency(
        other_node_name=other,
        if_name=f"{me}/{other}",
        other_if_name=f"{other}/{me}",
        metric=metric,
        next_hop_v6=f"fe80::{1 if other == 'solo' else 2}",
    )


def _publish_link(d, version: int, metric: int, with_prefix: bool = False):
    """solo<->peer at `metric` (both ends at `version`): a link event."""
    from openr_tpu.serializer import dumps
    from openr_tpu.types import (
        AdjacencyDatabase,
        PrefixDatabase,
        PrefixEntry,
        Value,
        adj_key,
        prefix_key,
    )

    kvs = {
        adj_key(me): Value(
            version,
            me,
            dumps(AdjacencyDatabase(me, [_adj(me, other, metric)])),
        )
        for me, other in (("solo", "peer"), ("peer", "solo"))
    }
    if with_prefix:
        kvs[prefix_key("peer", PFX, "0")] = Value(
            1, "peer", dumps(PrefixDatabase("peer", [PrefixEntry(prefix=PFX)]))
        )
    d.kvstore.set_key_vals("0", kvs)


@pytest.fixture(scope="module")
def daemon():
    from openr_tpu.kvstore import InProcessTransport
    from openr_tpu.main import OpenrDaemon
    from openr_tpu.spark import MockIoProvider

    d = OpenrDaemon(
        make_config("solo", ctrl_port=0),
        io_provider=MockIoProvider().endpoint("solo"),
        kvstore_transport=InProcessTransport().bind("solo"),
    )
    d.start()
    try:
        _publish_link(d, 1, 10, with_prefix=True)
        assert wait_for(
            lambda: d.decision.get_counters()["decision.rebuilds"] >= 1, 15
        )
        yield d
    finally:
        d.stop()


def _dur(sp: dict) -> int:
    return sp["duration_us"]


def _self_us(sp: dict, kids) -> int:
    return _dur(sp) - sum(_dur(c) for c in sp["children"] if c["name"] in kids)


def _all(tree: dict, name: str) -> list:
    out = [tree] if tree["name"] == name else []
    for c in tree["children"]:
        out.extend(_all(c, name))
    return out


class TestDecisionSplit:
    def test_link_event_splits_decision_into_spf_build_and_diff(
        self, daemon, tracer
    ):
        _publish_link(daemon, 2, 20)

        def decision_stage():
            for t in tracer.dump(256):
                if t["name"] == "kvstore.publication":
                    for dec in _all(t, "decision"):
                        if _all(dec, "decision.route_build"):
                            return dec
            return None

        assert wait_for(lambda: decision_stage() is not None, 15)
        dec = decision_stage()
        kids = {c["name"]: c for c in dec["children"]}
        assert {"decision.route_build", "decision.route_diff"} <= set(kids)
        build = kids["decision.route_build"]
        # the host Dijkstra misses once for this router; the per-prefix
        # hits open no span
        spf = [c for c in build["children"] if c["name"] == "decision.spf"]
        assert len(spf) == 1
        assert _dur(spf[0]) >= 0
        assert _self_us(build, {"decision.spf"}) >= 0
        assert _dur(kids["decision.route_diff"]) >= 0
        assert _self_us(dec, {"decision.route_build", "decision.route_diff"}) >= 0

    def test_rebuild_counter_counts_one_per_rebuild(self, daemon):
        dec = daemon.decision

        def rebuilds():
            return dec.get_counters()["decision.rebuilds"]

        before = rebuilds()
        for i in range(3):
            dec.run_in_event_base_thread(
                lambda: dec.rebuild_routes("TEST")
            ).result(10)
            assert rebuilds() == before + i + 1


class TestWhatIfSpans:
    def test_query_stages_and_reply_root(self, daemon, tracer):
        from openr_tpu.ctrl import CtrlClient

        client = CtrlClient(port=daemon.ctrl_port)
        try:
            reply = client.call(
                "queryWhatIf",
                scenarios=[[["solo", "peer"]]],
                sources=["solo"],
            )
        finally:
            client.close()
        assert reply["result"][0]["newly_unreachable_pairs"] == 1

        def roots(name):
            return [t for t in tracer.dump(256) if t["name"] == name]

        assert wait_for(lambda: roots("ctrl.reply"), 10)
        (reply_root,) = roots("ctrl.reply")
        assert reply_root["tags"] == {"op": "what_if"}
        assert _dur(reply_root) >= 0
        (query,) = roots("serving.query")  # still a root of its own
        stages = {c["name"]: c for c in query["children"]}
        assert {"admission", "coalesce", "staged", "dispatch"} <= set(stages)
        assert "reply" not in stages
        # the stages tile the query: each starts where the last ended
        order = ["admission", "coalesce", "staged"]
        for a, b in zip(order, order[1:]):
            assert (
                stages[a]["t_offset_us"] + _dur(stages[a])
                == stages[b]["t_offset_us"]
            )
        assert stages["staged"]["t_offset_us"] + _dur(stages["staged"]) <= (
            stages["dispatch"]["t_offset_us"]
        )
        dispatch = stages["dispatch"]
        kids = [c["name"] for c in dispatch["children"]]
        for name in ("whatif.resolve", "whatif.relax", "whatif.reduce"):
            assert kids.count(name) == 1, kids
        waits = [c for c in dispatch["children"] if c["name"] == "eventbase.wait"]
        # the backend's epoch check and the what-if closure each wait
        # for Decision's loop
        assert len(waits) == 2
        assert all(w["tags"] == {"loop": "decision"} for w in waits)
        for c in dispatch["children"]:
            assert _dur(c) >= 0
        assert _self_us(dispatch, set(kids)) >= 0


class TestFanIn:
    def test_finishes_every_child_even_when_the_body_raises(self, tracer):
        a, b = tracer.root("a"), tracer.root("b")
        seen = []
        with pytest.raises(RuntimeError):
            with tracer.fan_in([a, b, a], "stage", k=1) as kids:
                seen.append(tracer.scope())
                raise RuntimeError("boom")
        assert len(kids) == 2  # duplicates fold
        assert seen == [tuple(kids)]
        assert tracer.scope() == ()
        for parent, kid in zip((a, b), kids):
            assert parent.children == [kid]
            assert kid.name == "stage" and kid.tags == {"k": 1}
            assert kid.t_end_us is not None and kid.t_end_us >= kid.t_start_us

    def test_child_nests_under_the_active_scope(self, tracer):
        root = tracer.root("r")
        with tracer.activate((root,)):
            with tracer.child("outer"):
                with tracer.child("inner"):
                    pass
        (outer,) = root.children
        assert [c.name for c in outer.children] == ["inner"]
        assert outer.t_end_us >= outer.children[0].t_end_us

    def test_handoff_records_its_wait_for_the_loop(self, tracer):
        evb = OpenrEventBase("obs-wait")
        evb.run()
        try:
            root = tracer.root("r")
            with tracer.activate((root,)):
                fut = evb.run_in_event_base_thread(lambda: time.sleep(0.001))
            fut.result(5)
            (wait,) = [c for c in root.children if c.name == "eventbase.wait"]
            assert wait.tags == {"loop": "obs-wait"}
            assert wait.t_end_us >= wait.t_start_us
        finally:
            evb.stop()
            evb.wait_until_stopped(5)


class TestProfilerAnnotation:
    def test_live_span_lands_on_the_host_plane(self, tracer, tmp_path):
        import jax
        from jax.profiler import ProfileData

        root = tracer.root("r")
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tracer.activate((root,)):
                with tracer.child("obs.annotated"):
                    time.sleep(0.02)
        finally:
            jax.profiler.stop_trace()
        (span,) = root.children
        (path,) = glob.glob(
            os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True
        )
        plane = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
        assert plane is not None
        events = [
            ev
            for line in plane.lines
            for ev in line.events
            if ev.name == "obs.annotated"
        ]
        assert len(events) == 1  # one annotation, not one per parent
        span_ns = (span.t_end_us - span.t_start_us) * 1000
        assert abs(events[0].duration_ns - span_ns) < 1e6
