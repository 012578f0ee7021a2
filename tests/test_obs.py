"""End-to-end tracing + shared histograms (openr_tpu/obs): the
log-bucketed histogram unit contract, the tracer's arming discipline
(zero hooks unarmed), queue/eventbase span carry, the armed serving
query span tree down to the engine rung, the kvstore->decision->fib
flap trace through a live daemon, and the determinism contract that
lets the chaos fuzzer ingest span structures as coverage tokens.
"""

from __future__ import annotations

import threading
import time

import pytest

from openr_tpu.obs import OBS_COUNTER_KEYS, Histogram, export_histogram
from openr_tpu.obs import trace as _trace
from openr_tpu.obs.trace import Span, Tracer
from openr_tpu.runtime.eventbase import OpenrEventBase
from openr_tpu.runtime.queue import ReplicateQueue, RWQueue

from test_system import wait_for


@pytest.fixture
def tracer():
    """Arm tracing for one test; ALWAYS disarm after (tier-1 runs
    unarmed, and a leaked tracer would silently trace every later
    test)."""
    tr = _trace.enable(sample_every=1, ring=256)
    yield tr
    _trace.disable()


class TestHistogram:
    def test_power_of_two_buckets_bound_percentiles(self):
        h = Histogram()
        for v in (1, 3, 100, 1000, 100_000):
            h.record_us(v)
            p = h.percentile_us(100.0)
            # the reported percentile is the bucket's upper bound:
            # never below the true value, less than 2x above it
            assert v <= p < 2 * v, (v, p)

    def test_percentiles_are_monotone(self):
        h = Histogram()
        for i in range(1000):
            h.record_us(i + 1)
        p50, p99, p999 = (
            h.percentile_us(50),
            h.percentile_us(99),
            h.percentile_us(99.9),
        )
        assert 0 < p50 <= p99 <= p999

    def test_empty_and_zero(self):
        h = Histogram()
        assert h.percentile_us(99) == 0
        h.record_us(0)
        assert h.percentile_us(99) == 0  # zero-bucket upper bound

    def test_merge_sums_counts(self):
        a, b = Histogram(), Histogram()
        a.record_us(10)
        b.record_us(10)
        b.record_us(100_000)
        a.merge(b)
        counts, n = a.snapshot()
        assert sum(counts) == n == 3
        assert a.percentile_us(99) >= 100_000

    def test_export_emits_wire_keys(self):
        h = Histogram()
        for v in (100, 200, 3000):
            h.record_us(v)
        counters: dict = {}
        export_histogram(counters, "fam", h)
        assert counters["fam.hist_us.count"] == 3
        assert set(counters) >= {"fam.p50_us", "fam.p99_us", "fam.p999_us"}
        # non-empty buckets dump for offline re-aggregation
        bucket_total = sum(
            v for k, v in counters.items() if ".hist_us.b" in k
        )
        assert bucket_total == 3


class TestArmingDiscipline:
    def test_unarmed_by_default_and_queues_allocate_nothing(self):
        # tier-1 runs without OPENR_TRACE: the module constant is None
        # and a queue that moves items allocates NO token storage
        assert _trace.TRACE is None
        q: RWQueue = RWQueue()
        q.push(1)
        assert q.get() == 1
        assert q._obs_tokens is None

    def test_enable_disable_round_trip(self):
        assert _trace.TRACE is None
        tr = _trace.enable(sample_every=2, ring=8)
        try:
            assert _trace.TRACE is tr
            assert tr.sample_every == 2
        finally:
            _trace.disable()
        assert _trace.TRACE is None

    def test_maybe_child_unarmed_is_shared_noop(self):
        assert _trace.maybe_child("x") is _trace.maybe_child("y")

    def test_obs_stats_unarmed_answers_zeroed_shape(self):
        from openr_tpu.obs import ObsStats

        stats = ObsStats()
        assert stats.get_counters() == {k: 0 for k in OBS_COUNTER_KEYS}
        assert stats.dump_traces() == []
        assert stats.span_samples() == []


class TestTracerUnit:
    def test_deterministic_modulo_sampling(self, tracer):
        tr = _trace.enable(sample_every=3)
        roots = [tr.root("r") for _ in range(9)]
        kept = [r for r in roots if r is not None]
        assert len(kept) == 3  # roots 1, 4, 7 (1-in-3, modulo counter)
        c = tr.get_counters()
        assert c["obs.traces_started"] == 3
        assert c["obs.traces_sampled_out"] == 6

    def test_ring_is_bounded_with_eviction_ledger(self, tracer):
        tr = _trace.enable(ring=4)
        for i in range(7):
            sp = tr.root("r", i=i)
            tr.finish(sp)
        assert len(tr.dump(100)) == 4
        c = tr.get_counters()
        assert c["obs.traces_finished"] == 7
        assert c["obs.trace_ring_evictions"] == 3

    def test_structure_is_child_order_independent(self, tracer):
        def build(order):
            root = Span("root")
            root.tags["outcome"] = "ok"
            for name in order:
                Span(name, parent=root)
                root.children.append(Span(name, parent=root))
                root.children[-1].notes["t"] = time.time()  # non-structural
            return root.structure()

        assert build(["a", "b", "c"]) == build(["c", "a", "b"])
        assert "outcome=ok" in build(["a"])
        assert "t=" not in build(["a"])  # notes excluded

    def test_root_extends_under_active_scope(self, tracer):
        outer = tracer.root("router.query")
        with tracer.activate((outer,)):
            inner = tracer.root("serving.query")
        assert inner.parent is outer
        assert outer.children == [inner]

    def test_fan_in_scope_annotates_every_span(self, tracer):
        a, b = tracer.root("a"), tracer.root("b")
        with tracer.activate((a, b)):
            tracer.annotate("engine.rung", "delta")
            tracer.event("epoch_retry")
        for sp in (a, b):
            assert sp.tags["engine.rung"] == "delta"
            assert [c.name for c in sp.children] == ["epoch_retry"]

    def test_bind_scope_carries_across_threads(self, tracer):
        root = tracer.root("r")
        seen = []

        def probe():
            seen.append(tracer.scope())

        with tracer.activate((root,)):
            bound = tracer.bind_scope(probe)
        t = threading.Thread(target=bound)
        t.start()
        t.join(5)
        assert seen == [(root,)]

    def test_eventbase_handoff_reactivates_scope(self, tracer):
        evb = OpenrEventBase("obs-test")
        evb.run()
        try:
            root = tracer.root("r")
            with tracer.activate((root,)):
                fut = evb.run_in_event_base_thread(tracer.scope)
            assert fut.result(5) == (root,)
        finally:
            evb.stop()
            evb.wait_until_stopped(5)


class TestQueueCarry:
    def test_put_get_carries_scope_across_threads(self, tracer):
        q: RWQueue = RWQueue()
        root = tracer.root("r")
        with tracer.activate((root,)):
            q.push("item")
        got = []

        def consumer():
            q.get(timeout=5)
            got.append(tracer.take_carried())

        t = threading.Thread(target=consumer)
        t.start()
        t.join(5)
        assert got == [(root,)]

    def test_pop_clears_stale_carried_token(self, tracer):
        q: RWQueue = RWQueue()
        root = tracer.root("r")
        with tracer.activate((root,)):
            q.push("traced")
        q.push("untraced")  # no scope
        q.get(timeout=5)
        q.get(timeout=5)
        # the second pop must CLEAR the first pop's token, or the
        # untraced item would adopt the traced item's span
        assert tracer.take_carried() == ()

    def test_items_pushed_while_disarmed_carry_nothing(self, tracer):
        _trace.disable()
        q: RWQueue = RWQueue()
        q.push("old")
        tr = _trace.enable()
        root = tr.root("r")
        with tr.activate((root,)):
            q.push("new")
        q.get(timeout=5)
        assert tr.take_carried() == ()  # disarmed-era item: no context
        q.get(timeout=5)
        assert tr.take_carried() == (root,)

    def test_bounded_shed_keeps_tokens_aligned(self, tracer):
        q: RWQueue = RWQueue(maxlen=2)
        root = tracer.root("r")
        with tracer.activate((root,)):
            for i in range(4):
                q.push(i)
        assert q.size() == 2
        assert len(q._obs_tokens) == 2
        assert q.get(timeout=5) == 2
        assert tracer.take_carried() == (root,)

    def test_replicate_queue_carries_to_every_reader(self, tracer):
        rq: ReplicateQueue = ReplicateQueue()
        readers = [rq.get_reader() for _ in range(2)]
        root = tracer.root("r")
        with tracer.activate((root,)):
            rq.push("x")
        for r in readers:
            r.get(timeout=5)
            assert tracer.take_carried() == (root,)


def _make_scheduler():
    from openr_tpu.decision.spf_solver import DeviceSpfBackend
    from openr_tpu.serving import EngineBatchBackend, QueryScheduler

    from test_spf_solver import square

    ls = square()
    backend = EngineBatchBackend(
        {"0": ls},
        spf_backend=DeviceSpfBackend(min_device_nodes=1, min_device_sources=1),
    )
    sched = QueryScheduler(backend)
    sched.run()
    return sched


class TestServingSpanTree:
    def test_unarmed_queries_open_no_spans(self):
        assert _trace.TRACE is None
        sched = _make_scheduler()
        try:
            res = sched.submit("paths", sources=("1",)).result(20)
            assert res.value["1"]
            counters = sched.get_counters()
            # the shared histogram replaced the sorted-deque gauges but
            # kept the wire keys (plus the new p999)
            assert counters["serving.p99_us"] >= counters["serving.p50_us"] > 0
            assert "serving.p999_us" in counters
            assert counters["serving.hist_us.count"] == 1
        finally:
            sched.stop()

    def test_armed_query_attributes_every_stage_and_the_rung(self, tracer):
        sched = _make_scheduler()
        try:
            res = sched.submit("paths", sources=("1",)).result(20)
            assert res.value["1"]
            assert wait_for(
                lambda: tracer.get_counters()["obs.traces_finished"] >= 1, 10
            )
            roots = [d for d in tracer.dump(16) if d["name"] == "serving.query"]
            assert roots, tracer.dump(16)
            tree = roots[-1]
            assert tree["tags"]["outcome"] == "ok"
            assert tree["tags"]["op"] == "paths"
            stages = {c["name"]: c for c in tree["children"]}
            assert {"admission", "coalesce", "dispatch", "staged"} <= set(
                stages
            )
            # the dispatch stage names the exact engine rung taken
            dispatch = stages["dispatch"]
            assert dispatch["tags"].get("engine.rung") in {
                "restage",
                "spf",
                "incremental",
                "delta",
                "rewire",
                "blocked",
            }, dispatch
            assert tree["duration_us"] is not None
        finally:
            sched.stop()

    def test_shed_query_closes_its_trace(self, tracer):
        from openr_tpu.serving import QueryShedError

        sched = _make_scheduler()
        try:
            sched.stop()  # closed admission -> every submit sheds
            fut = sched.submit("paths", sources=("1",))
            with pytest.raises(QueryShedError):
                fut.result(5)
            assert wait_for(
                lambda: any(
                    d["name"] == "serving.query"
                    and d["tags"].get("outcome") == "shed"
                    for d in tracer.dump(32)
                ),
                5,
            )
        finally:
            sched.stop()


class TestRouterSpanNesting:
    def test_router_trace_nests_scheduler_trace(self, tracer):
        from openr_tpu.serving import ReplicaRouter, SchedulerReplica

        sched = _make_scheduler()
        router = ReplicaRouter(
            [SchedulerReplica("rep-0", sched)], hedge_after_s=None
        )
        try:
            res = router.submit("paths", sources=("1",)).result(20)
            assert res.value["1"]
            assert wait_for(
                lambda: any(
                    d["name"] == "router.query" for d in tracer.dump(16)
                ),
                10,
            )
            tree = [
                d for d in tracer.dump(16) if d["name"] == "router.query"
            ][-1]
            assert tree["tags"]["outcome"] in {"ok", "hedge_win"}
            kids = {c["name"] for c in tree["children"]}
            # the dispatch edge and the replica's whole serving.query
            # tree hang under the ONE router trace (root-extends rule)
            assert "dispatch" in kids
            assert "serving.query" in kids
        finally:
            router.stop()


class TestFlapSpanTree:
    def test_publication_trace_attributes_decision_and_fib(self, tracer):
        from openr_tpu.kvstore import InProcessTransport
        from openr_tpu.main import OpenrDaemon
        from openr_tpu.serializer import dumps
        from openr_tpu.spark import MockIoProvider
        from openr_tpu.types import (
            Adjacency,
            AdjacencyDatabase,
            PrefixDatabase,
            PrefixEntry,
            Value,
            adj_key,
            prefix_key,
        )

        from test_system import make_config

        fabric = MockIoProvider()
        d = OpenrDaemon(
            make_config("solo", ctrl_port=0),
            io_provider=fabric.endpoint("solo"),
            kvstore_transport=InProcessTransport().bind("solo"),
        )
        d.start()
        try:
            # a topology event: a solo<->peer adjacency plus a prefix
            # advertised by the peer lands in kvstore, floods to internal
            # subscribers, rebuilds routes, programs fib — ONE trace must
            # attribute the whole pipeline
            def _adj(me, other):
                return Adjacency(
                    other_node_name=other,
                    if_name=f"{me}/{other}",
                    other_if_name=f"{other}/{me}",
                    metric=10,
                    next_hop_v6=f"fe80::{1 if other == 'solo' else 2}",
                )

            pfx = "::9:0/112"
            d.kvstore.set_key_vals(
                "0",
                {
                    adj_key("solo"): Value(
                        1,
                        "solo",
                        dumps(
                            AdjacencyDatabase(
                                "solo", [_adj("solo", "peer")]
                            )
                        ),
                    ),
                    adj_key("peer"): Value(
                        1,
                        "peer",
                        dumps(
                            AdjacencyDatabase(
                                "peer", [_adj("peer", "solo")]
                            )
                        ),
                    ),
                    prefix_key("peer", pfx, "0"): Value(
                        1,
                        "peer",
                        dumps(
                            PrefixDatabase(
                                "peer", [PrefixEntry(prefix=pfx)]
                            )
                        ),
                    ),
                },
            )

            def flap_trace():
                for t in tracer.dump(64):
                    if t["name"] != "kvstore.publication":
                        continue
                    names = {c["name"] for c in t["children"]}
                    if "decision" not in names:
                        continue
                    dec = [
                        c for c in t["children"] if c["name"] == "decision"
                    ][0]
                    if any(
                        g["name"] == "fib.program" for g in dec["children"]
                    ):
                        return t
                return None

            assert wait_for(lambda: flap_trace() is not None, 15)
            tree = flap_trace()
            assert tree["tags"]["area"] == "0"
            assert tree["duration_us"] is not None  # fib terminal closed it

            # the ctrl surface serves the same trees + the obs ledger
            from openr_tpu.ctrl import CtrlClient

            client = CtrlClient(port=d.ctrl_port)
            try:
                dumped = client.call("dumpTraces", n=64)
                assert any(
                    t["name"] == "kvstore.publication" for t in dumped
                )
                samples = client.call("getSpanSamples")
                assert samples and all("structure" in s for s in samples)
                counters = client.call("getCounters")
                assert counters["obs.traces_finished"] > 0
            finally:
                client.close()
        finally:
            d.stop()


class TestSpanStructureDeterminism:
    def test_same_seed_chaos_replay_has_identical_span_structure(
        self, tracer
    ):
        from openr_tpu.chaos import fuzz as fz

        t = fz.FuzzTimeline(
            seed=424242,
            events=[
                fz.FuzzEvent("fleet", "burst", {"q": 3}),
                fz.FuzzEvent("flap", "worsen", {"node": 5}),
                fz.FuzzEvent("fleet", "burst", {"q": 2}),
            ],
        )
        r1 = fz.run_timeline(t)
        r2 = fz.run_timeline(t)
        assert r1.ok and r2.ok, (r1.failures, r2.failures)

        span1 = {tok for tok in r1.fingerprint if tok.startswith("span:")}
        span2 = {tok for tok in r2.fingerprint if tok.startswith("span:")}
        # the fleet bursts produced traced queries, and the replay
        # reproduced their span trees BYTE-IDENTICALLY (stage names,
        # rungs, outcome tags; timers are excluded by design)
        assert span1, "armed fuzz run produced no span tokens"
        assert span1 == span2
        # the full fingerprint (counters + faults + spans) also agrees
        assert r1.fingerprint == r2.fingerprint

    def test_fingerprint_unarmed_has_no_span_tokens(self):
        from openr_tpu.chaos import fuzz as fz

        assert _trace.TRACE is None
        t = fz.FuzzTimeline(
            seed=424243,
            events=[fz.FuzzEvent("fleet", "burst", {"q": 2})],
        )
        r = fz.run_timeline(t)
        assert r.ok, r.failures
        assert not any(tok.startswith("span:") for tok in r.fingerprint)
