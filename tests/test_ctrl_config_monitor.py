"""Config validation, Monitor/Watchdog, and ctrl streaming tests."""

from __future__ import annotations

import threading
import time

import pytest

from openr_tpu.config import (
    AreaConf,
    ConfigError,
    OpenrConfig,
    config_from_dict,
)
from openr_tpu.ctrl import CtrlClient
from openr_tpu.kvstore import InProcessTransport
from openr_tpu.main import OpenrDaemon
from openr_tpu.monitor import LogSample, Monitor, Watchdog
from openr_tpu.runtime.eventbase import OpenrEventBase
from openr_tpu.runtime.queue import ReplicateQueue
from openr_tpu.spark import MockIoProvider
from openr_tpu.types import LinkEvent, Publication

from test_system import FAST_SPARK, make_config, wait_for


class TestConfig:
    def test_valid_roundtrip(self):
        cfg = config_from_dict(
            {
                "node_name": "node-1",
                "areas": [{"area_id": "a1", "neighbor_regexes": ["node-.*"]}],
                "openr_ctrl_port": 3018,
                "kvstore_config": {"flood_msg_per_sec": 100},
            }
        )
        assert cfg.node_name == "node-1"
        assert cfg.area_ids == ("a1",)
        assert cfg.kvstore_config.flood_msg_per_sec == 100
        assert cfg.to_dict()["node_name"] == "node-1"

    def test_validation_errors(self):
        with pytest.raises(ConfigError):
            OpenrConfig(node_name="").validate()
        with pytest.raises(ConfigError):
            OpenrConfig(node_name="bad name").validate()
        with pytest.raises(ConfigError):
            OpenrConfig(
                node_name="x", areas=[AreaConf("1"), AreaConf("1")]
            ).validate()
        with pytest.raises(ConfigError):
            OpenrConfig(
                node_name="x",
                areas=[AreaConf("1", interface_regexes=["["])],
            ).validate()

    def test_load_file(self, tmp_path):
        path = tmp_path / "cfg.json"
        path.write_text('{"node_name": "filenode"}')
        from openr_tpu.config import load_config

        assert load_config(str(path)).node_name == "filenode"


class TestMonitor:
    def test_event_logs_and_counters(self):
        logq: ReplicateQueue = ReplicateQueue()
        monitor = Monitor("n1", logq.get_reader(), counter_interval_s=0.05)
        monitor.run()
        try:
            logq.push(LogSample(event="NEIGHBOR_UP", neighbor="n2"))
            logq.push({"event": "ROUTE_CONVERGENCE", "duration_ms": 12})
            assert wait_for(lambda: len(monitor.get_event_logs()) == 2)
            assert "NEIGHBOR_UP" in monitor.get_event_logs()[0]
            time.sleep(0.1)
            counters = monitor.get_counters()
            assert "monitor.uptime_s" in counters
            assert counters.get("monitor.process_rss_bytes", 0) > 0
        finally:
            logq.close()
            monitor.stop()
            monitor.wait_until_stopped(5)


class TestWatchdog:
    def test_stall_detection(self):
        fired = []
        watchdog = Watchdog(
            interval_s=0.05,
            thread_timeout_s=0.2,
            # this test is about STALLS: the default 800MB RSS limit can
            # fire first when the suite's jax compilations grow the
            # shared pytest process past it (observed flake)
            max_memory_bytes=1 << 40,
            on_crash=fired.append,
        )
        evb = OpenrEventBase(name="victim")
        evb.run()
        try:
            watchdog.add_evb(evb)
            watchdog.check_once()
            assert not fired
            # stall the loop.  The callback delivery itself can lag under
            # CPU contention (observed flake: >0.2s to reach the loop, so
            # a single check saw a still-fresh heartbeat) — wait for the
            # stall to actually begin, then poll the watchdog to a
            # deadline instead of trusting one fixed-sleep check.
            blocker = threading.Event()
            stalled = threading.Event()

            def _stall():
                stalled.set()
                # the stall must OUTLIVE the polling deadline below with
                # margin, or a contended tail can release the loop and
                # refresh the heartbeat mid-poll
                blocker.wait(20.0)

            evb._loop.call_soon_threadsafe(_stall)
            assert stalled.wait(5.0), "stall callback never reached the loop"
            deadline = time.monotonic() + 5.0
            while not fired and time.monotonic() < deadline:
                time.sleep(0.05)
                watchdog.check_once()
            assert fired and "stalled" in fired[0]
        finally:
            # ALWAYS release the loop: an assertion failure above must
            # not leave the loop thread in blocker.wait through teardown
            blocker.set()
            evb.stop()
            evb.wait_until_stopped(5)

    def test_memory_limit(self):
        fired = []
        watchdog = Watchdog(max_memory_bytes=1, on_crash=fired.append)
        watchdog.check_once()
        assert fired and "memory" in fired[0]


@pytest.fixture
def daemon():
    fabric = MockIoProvider()
    d = OpenrDaemon(
        make_config("solo", ctrl_port=0),
        io_provider=fabric.endpoint("solo"),
        kvstore_transport=InProcessTransport().bind("solo"),
    )
    d.start()
    yield d
    d.stop()


class TestCtrlStreaming:
    def test_kvstore_snapshot_plus_stream(self, daemon):
        client = CtrlClient(port=daemon.ctrl_port)
        stream = client.stream("subscribeKvStore", area="0", prefixes=[])
        first = next(stream)  # snapshot (may be empty)
        assert isinstance(first, Publication)

        from openr_tpu.types import Value

        daemon.kvstore.set_key_vals(
            "0", {"stream-key": Value(1, "solo", b"sv")}
        )
        got = next(stream)
        assert "stream-key" in got.key_vals
        client.close()

    def test_long_poll_adj(self, daemon):
        client = CtrlClient(port=daemon.ctrl_port)
        result: list = []

        def poll():
            result.append(
                client.call("longPollKvStoreAdjArea", area="0", snapshot={})
            )

        # no adj keys yet -> long poll blocks until one appears
        thread = threading.Thread(target=poll)
        thread.start()
        time.sleep(0.3)
        assert thread.is_alive()
        daemon.netlink_events_queue.push(LinkEvent("ifx", 1, True))
        # an interface alone creates no adjacency; force one via kvstore
        from openr_tpu.serializer import dumps
        from openr_tpu.types import Adjacency, AdjacencyDatabase, Value, adj_key

        daemon.kvstore.set_key_vals(
            "0",
            {
                adj_key("solo"): Value(
                    1, "solo", dumps(AdjacencyDatabase("solo", []))
                )
            },
        )
        thread.join(timeout=5)
        assert not thread.is_alive() and result == [True]
        client.close()

    def test_unknown_method_error(self, daemon):
        client = CtrlClient(port=daemon.ctrl_port)
        with pytest.raises(RuntimeError, match="unknown method"):
            client.call("noSuchMethod")
        client.close()


class TestCtrlGapRpcs:
    """Round-3 ctrl/CLI surface additions (reference: dryrunConfig
    OpenrCtrlHandler.h:69-78, getMplsRoutesFiltered,
    withdrawPrefixesByType, breeze kvstore compare / tech-support)."""

    def test_dryrun_config_valid_and_invalid(self, daemon):
        import json as _json

        client = CtrlClient(port=daemon.ctrl_port)
        try:
            good = _json.dumps(make_config("dryrun-check").to_dict())
            parsed = client.call("dryrunConfig", file_contents=good)
            assert parsed["node_name"] == "dryrun-check"
            # nothing applied: the daemon keeps its own identity
            assert client.call("getMyNodeName") == "solo"
            with pytest.raises(RuntimeError):
                client.call("dryrunConfig", file_contents="{not json")
            bad = _json.dumps({"node_name": ""})
            with pytest.raises(RuntimeError):
                client.call("dryrunConfig", file_contents=bad)
        finally:
            client.close()

    def test_mpls_routes_filtered(self, daemon):
        client = CtrlClient(port=daemon.ctrl_port)
        try:
            routes = client.call("getMplsRoutesFiltered", labels=None)
            assert isinstance(routes, list)
            # label filter returns the subset
            if routes:
                lbl = routes[0].top_label
                only = client.call("getMplsRoutesFiltered", labels=[lbl])
                assert [r.top_label for r in only] == [lbl]
            assert client.call("getMplsRoutesFiltered", labels=[1 << 19]) == []
        finally:
            client.close()

    def test_withdraw_prefixes_by_type(self, daemon):
        from openr_tpu.types import PrefixEntry, PrefixType

        client = CtrlClient(port=daemon.ctrl_port)
        try:
            client.call(
                "advertisePrefixes",
                type=PrefixType.BREEZE,
                prefixes=[PrefixEntry(prefix="fc51::/64", type=PrefixType.BREEZE)],
            )
            assert client.call("getPrefixesByType", type=PrefixType.BREEZE)
            client.call("withdrawPrefixesByType", type=PrefixType.BREEZE)
            assert not client.call(
                "getPrefixesByType", type=PrefixType.BREEZE
            )
        finally:
            client.close()

    def test_breeze_tech_support_and_compare(self, daemon, capsys):
        from openr_tpu.cli import breeze

        rc = breeze.main(["-p", str(daemon.ctrl_port), "tech-support"])
        out = capsys.readouterr().out
        assert rc == 0
        for section in ("VERSION", "RUNNING CONFIG", "COUNTERS", "FIB ROUTES"):
            assert f"======== {section} ========" in out
        # compare against ITSELF: stores agree
        rc = breeze.main(
            ["-p", str(daemon.ctrl_port), "kvstore", "compare", "::1",
             "--other-port", str(daemon.ctrl_port)]
        )
        out = capsys.readouterr().out
        assert rc == 0
        assert "agree" in out

    def test_breeze_config_dryrun(self, daemon, tmp_path, capsys):
        import json as _json

        from openr_tpu.cli import breeze

        good = tmp_path / "good.conf"
        good.write_text(_json.dumps(make_config("x").to_dict()))
        rc = breeze.main(
            ["-p", str(daemon.ctrl_port), "config", "dryrun", str(good)]
        )
        assert rc == 0
        assert "VALID" in capsys.readouterr().out
        bad = tmp_path / "bad.conf"
        bad.write_text("{}")
        with pytest.raises(SystemExit):
            breeze.main(
                ["-p", str(daemon.ctrl_port), "config", "dryrun", str(bad)]
            )


class TestCtrlDeltaRpcs:
    """Round-5 RPC-delta closure vs the reference handler
    (OpenrCtrlHandler.h:53-381): persistent-store keys, build info,
    deprecated area-less aliases, spark GR flood, advertised-route and
    route-detail views."""

    def test_build_info(self, daemon):
        client = CtrlClient(port=daemon.ctrl_port)
        try:
            info = client.call("getBuildInfo")
            assert info["buildPackageName"] == "openr_tpu"
        finally:
            client.close()

    def test_config_key_roundtrip(self, daemon):
        client = CtrlClient(port=daemon.ctrl_port)
        try:
            client.call("setConfigKey", key="k1", value=b"\x01\x02")
            assert client.call("getConfigKey", key="k1") == b"\x01\x02"
            assert client.call("eraseConfigKey", key="k1") is True
            assert client.call("getConfigKey", key="k1") is None
            assert client.call("eraseConfigKey", key="k1") is False
        finally:
            client.close()

    def test_area_less_aliases_match_area_variants(self, daemon):
        client = CtrlClient(port=daemon.ctrl_port)
        try:
            assert client.call("getKvStorePeers") == client.call(
                "getKvStorePeersArea", area="0"
            )
            a = client.call("getKvStoreKeyVals", keys=[])
            b = client.call("getKvStoreKeyValsArea", area="0", keys=[])
            assert type(a) is type(b)
            assert client.call("getNeighbors") == client.call(
                "getSparkNeighbors"
            )
            assert client.call("getDecisionAdjacencyDbs") == client.call(
                "getDecisionAdjacenciesFiltered"
            )
        finally:
            client.close()

    def test_advertised_routes(self, daemon):
        from openr_tpu.types import PrefixEntry, PrefixType

        client = CtrlClient(port=daemon.ctrl_port)
        try:
            client.call(
                "advertisePrefixes",
                type=PrefixType.BREEZE,
                prefixes=[
                    PrefixEntry(prefix="fc61::/64", type=PrefixType.BREEZE)
                ],
            )
            rows = client.call("getAdvertisedRoutes")
            assert any(r["prefix"] == "fc61::/64" for r in rows)
            only = client.call(
                "getAdvertisedRoutesFiltered", prefixes=["fc61::/64"]
            )
            assert len(only) == 1 and only[0]["prefix"] == "fc61::/64"
            types = [t for t, _e in only[0]["routes"]]
            assert int(PrefixType.BREEZE) in types
            assert (
                client.call(
                    "getAdvertisedRoutesFiltered", prefixes=["fc62::/64"]
                )
                == []
            )
        finally:
            client.close()

    def test_route_detail_db(self, daemon):
        client = CtrlClient(port=daemon.ctrl_port)
        try:
            detail = client.call("getRouteDetailDb")
            assert set(detail) == {"unicastRoutes", "mplsRoutes"}
        finally:
            client.close()

    def test_flood_restarting_msg(self, daemon):
        client = CtrlClient(port=daemon.ctrl_port)
        try:
            client.call("floodRestartingMsg")  # no neighbors: no-op send
        finally:
            client.close()


class TestCounterRegistrySweep:
    """Wire-level counterpart of the counter-registry static rule: every
    counter family the modules bump must actually surface through one
    getCounters RPC, and every dumped key must follow the module.name
    convention the analyzer enforces (counter-name rule)."""

    def test_full_counter_set_is_dumpable(self, daemon):
        import re

        client = CtrlClient(port=daemon.ctrl_port)
        try:
            # fib's sync loop runs on its own thread; wait for the first
            # sync so the fib.* and fib.agent.* families are populated
            assert wait_for(
                lambda: client.call("getCounters").get(
                    "fib.sync_fib_calls", 0
                )
                > 0,
                timeout=10.0,
            ), "fib never completed its first sync"
            counters = client.call("getCounters")

            # one representative per wired family, including the two
            # wired in by this sweep (netlink events queue, fib agent)
            for key in (
                "kvstore.num_keys.0",
                "monitor.uptime_s",
                "queue.route_updates.writes",
                "queue.netlink_events.writes",
                "fib.sync_fib_calls",
                "fib.agent.sync_fib",
                # the device-residency engine pre-seeds its registry, so
                # the family is dumpable before any device query runs
                "device.engine.queries",
                # the edge-set rewire rung pre-seeds the same way: the
                # runbook's rewire ledger is scrapeable before any OCS
                # reconfiguration ever reaches the engine
                "device.engine.rewire_dispatches",
                "device.engine.rewire_fallbacks",
                # the query scheduler pre-seeds serving.* the same way,
                # and its admission RWQueue rides the daemon queue fabric
                "serving.admitted",
                "queue.serving_admission.overflows",
                # the blocked node-sharding rung pre-seeds mesh.blocked.*
                # in the engine's sub-registry before any product runs
                "mesh.blocked.products",
                # the TE optimizer pre-seeds te.* at construction, so the
                # family is dumpable before any optimizeMetrics runs
                "te.runs",
                # the schedule explorer pre-seeds sched.* at module
                # import, so the family is dumpable before any run
                "sched.schedules_explored",
                "sched.planted_finds",
            ):
                assert key in counters, f"{key} missing from getCounters"

            # the convention the counter-name rule enforces statically
            name_re = re.compile(r"[a-z][a-z0-9_]*(\.[a-z0-9_]+)+\Z")
            bad = [k for k in counters if not name_re.match(k)]
            assert not bad, f"non-conventional counter keys: {bad}"
        finally:
            client.close()

    def test_engine_family_on_both_wire_surfaces(self, daemon):
        """The full device.engine.* registry answers ONE getCounters on
        the native ctrl server AND the thrift-binary fb303 shim — no
        per-key plumbing, the engine rides _all_counters like any
        module."""
        from openr_tpu.device import ENGINE_COUNTER_KEYS
        from openr_tpu.interop import thrift_binary as tb
        from openr_tpu.interop.shim import ThriftBinaryShim
        from test_thrift_binary import _call_ok

        client = CtrlClient(port=daemon.ctrl_port)
        try:
            native = client.call("getCounters")
        finally:
            client.close()
        assert set(ENGINE_COUNTER_KEYS) <= set(native)

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
                41,
                b"\x00",
                ("map", tb.T_STRING, tb.T_I64),
                dec=lambda m: {k.decode(): v for k, v in m.items()},
            )
        finally:
            shim.stop()
            shim.wait_until_stopped(5)
        assert set(ENGINE_COUNTER_KEYS) <= set(shimmed)

    def test_decision_rebuild_family_on_both_wire_surfaces(self, daemon):
        """Decision pre-seeds its rebuild counters, so how often the
        incremental route rebuild engages (decision.incremental_rebuilds
        against decision.rebuilds, decision.dirty_nodes summed) answers
        ONE getCounters on the native ctrl server AND the fb303 shim
        before any route rebuild runs."""
        from openr_tpu.decision.decision import DECISION_COUNTER_KEYS
        from openr_tpu.interop import thrift_binary as tb
        from openr_tpu.interop.shim import ThriftBinaryShim
        from test_thrift_binary import _call_ok

        family = {"decision.incremental_rebuilds", "decision.dirty_nodes"}
        assert family <= set(DECISION_COUNTER_KEYS)

        client = CtrlClient(port=daemon.ctrl_port)
        try:
            native = client.call("getCounters")
        finally:
            client.close()
        assert family <= set(native)

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
        assert family <= set(shimmed)

    def test_serving_family_on_both_wire_surfaces(self, daemon):
        """The full serving.* registry (admission, coalescing, shedding,
        latency gauges) answers ONE getCounters on the native ctrl
        server AND the fb303 shim, convention-clean, with no per-key
        plumbing — the scheduler rides _all_counters like any module."""
        import re

        from openr_tpu.interop import thrift_binary as tb
        from openr_tpu.interop.shim import ThriftBinaryShim
        from openr_tpu.serving import SERVING_COUNTER_KEYS
        from test_thrift_binary import _call_ok

        client = CtrlClient(port=daemon.ctrl_port)
        try:
            native = client.call("getCounters")
        finally:
            client.close()
        assert set(SERVING_COUNTER_KEYS) <= set(native)
        # the admission queue is registered in the daemon fabric, so its
        # overflow ledger is on the same surface the runbook points at
        assert "queue.serving_admission.overflows" in native

        name_re = re.compile(r"[a-z][a-z0-9_]*(\.[a-z0-9_]+)+\Z")
        assert all(name_re.match(k) for k in SERVING_COUNTER_KEYS)

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
                42,
                b"\x00",
                ("map", tb.T_STRING, tb.T_I64),
                dec=lambda m: {k.decode(): v for k, v in m.items()},
            )
        finally:
            shim.stop()
            shim.wait_until_stopped(5)
        assert set(SERVING_COUNTER_KEYS) <= set(shimmed)

    def test_pipeline_family_on_both_wire_surfaces(self, daemon):
        """The pipelined blocked closure's ledger (prefetches issued,
        rounds overlapped, demotions to bulk, the overlap-fraction
        gauge) is pre-seeded in the blocked sub-registry, so the whole
        mesh.blocked.pipeline_* family answers ONE getCounters on the
        native ctrl server AND the fb303 shim before any closure ever
        runs — the runbook's pipeline_fallbacks check needs no warm-up
        query."""
        import re

        from openr_tpu.interop import thrift_binary as tb
        from openr_tpu.interop.shim import ThriftBinaryShim
        from openr_tpu.parallel.blocked import BLOCKED_COUNTER_KEYS
        from test_thrift_binary import _call_ok

        family = {k for k in BLOCKED_COUNTER_KEYS if ".pipeline_" in k}
        assert family == {
            "mesh.blocked.pipeline_rounds_overlapped",
            "mesh.blocked.pipeline_prefetch_issues",
            "mesh.blocked.pipeline_fallbacks",
            "mesh.blocked.pipeline_overlap_frac_est",
        }
        name_re = re.compile(r"[a-z][a-z0-9_]*(\.[a-z0-9_]+)+\Z")
        assert all(name_re.match(k) for k in family)

        client = CtrlClient(port=daemon.ctrl_port)
        try:
            native = client.call("getCounters")
        finally:
            client.close()
        assert family <= set(native)
        assert all(native[k] == 0 for k in family)  # pre-seeded, untouched

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
                47,
                b"\x00",
                ("map", tb.T_STRING, tb.T_I64),
                dec=lambda m: {k.decode(): v for k, v in m.items()},
            )
        finally:
            shim.stop()
            shim.wait_until_stopped(5)
        assert family <= set(shimmed)
        assert all(shimmed[k] == 0 for k in family)

    def test_router_family_on_both_wire_surfaces(self, daemon):
        """The replica-fleet front door pre-seeds serving.router.* and
        rides the same two surfaces: a ctrl server whose serving module
        is the ReplicaRouter (the fleet front-door posture), and the
        fb303 shim fed by that handler's merged dump.  The router's
        get_counters also rolls up its replicas' serving.* families, so
        one scrape covers the whole fleet."""
        import re

        from openr_tpu.ctrl import CtrlServer, OpenrCtrlHandler
        from openr_tpu.interop import thrift_binary as tb
        from openr_tpu.interop.shim import ThriftBinaryShim
        from openr_tpu.serving import (
            ReplicaRouter,
            ROUTER_COUNTER_KEYS,
            SchedulerReplica,
        )
        from test_thrift_binary import _call_ok

        router = ReplicaRouter(
            [SchedulerReplica("solo", daemon.serving)], hedge_after_s=None
        )
        handler = OpenrCtrlHandler("fleet-front", serving=router)
        server = CtrlServer(handler, port=0)
        server.run()
        try:
            client = CtrlClient(port=server.port)
            try:
                native = client.call("getCounters")
            finally:
                client.close()
        finally:
            server.stop()
            server.wait_until_stopped(5)
        # pre-seeded: the whole family dumps before any dispatch
        assert set(ROUTER_COUNTER_KEYS) <= set(native)
        # fleet roll-up: the replica's serving.* rides the same dump
        assert "serving.admitted" in native

        shim = ThriftBinaryShim(
            daemon.kvstore,
            port=0,
            node_name="solo",
            counters_fn=handler._all_counters,
        )
        shim.run()
        try:
            shimmed = _call_ok(
                shim.port,
                "getCounters",
                43,
                b"\x00",
                ("map", tb.T_STRING, tb.T_I64),
                dec=lambda m: {k.decode(): v for k, v in m.items()},
            )
        finally:
            shim.stop()
            shim.wait_until_stopped(5)
            router.stop()
        assert set(ROUTER_COUNTER_KEYS) <= set(shimmed)

        name_re = re.compile(r"[a-z][a-z0-9_]*(\.[a-z0-9_]+)+\Z")
        assert all(name_re.match(k) for k in ROUTER_COUNTER_KEYS)

    def test_mesh_blocked_family_on_both_wire_surfaces(self, daemon):
        """The full mesh.blocked.* registry (blocked node-sharded APSP
        rung: products, rounds, panel broadcasts, bytes, phase timers,
        fallbacks) answers ONE getCounters on the native ctrl server AND
        the fb303 shim, pre-seeded — dashboards see every key before the
        first product dispatches."""
        import re

        from openr_tpu.interop import thrift_binary as tb
        from openr_tpu.interop.shim import ThriftBinaryShim
        from openr_tpu.parallel.blocked import BLOCKED_COUNTER_KEYS
        from test_thrift_binary import _call_ok

        client = CtrlClient(port=daemon.ctrl_port)
        try:
            native = client.call("getCounters")
        finally:
            client.close()
        assert set(BLOCKED_COUNTER_KEYS) <= set(native)

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
                41,
                b"\x00",
                ("map", tb.T_STRING, tb.T_I64),
                dec=lambda m: {k.decode(): v for k, v in m.items()},
            )
        finally:
            shim.stop()
            shim.wait_until_stopped(5)
        assert set(BLOCKED_COUNTER_KEYS) <= set(shimmed)

        name_re = re.compile(r"[a-z][a-z0-9_]*(\.[a-z0-9_]+)+\Z")
        assert all(name_re.match(k) for k in BLOCKED_COUNTER_KEYS)

    def test_delta_family_on_both_wire_surfaces(self, daemon):
        """The incremental-delta families (decision.delta.* from the
        coalescer pre-seed, device.engine.delta_* from the engine rung)
        answer ONE getCounters on the native ctrl server AND the fb303
        shim from daemon start — before any delta update has run — so
        dashboards can alert on fallbacks/full_restages going non-zero
        without waiting for the first storm."""
        import re

        from openr_tpu.decision.delta import DELTA_COUNTER_KEYS
        from openr_tpu.device import ENGINE_COUNTER_KEYS
        from openr_tpu.interop import thrift_binary as tb
        from openr_tpu.interop.shim import ThriftBinaryShim
        from test_thrift_binary import _call_ok

        engine_delta = [
            k for k in ENGINE_COUNTER_KEYS
            if k.startswith("device.engine.delta_")
        ]
        assert engine_delta, "engine registry lost its delta_* family"

        client = CtrlClient(port=daemon.ctrl_port)
        try:
            native = client.call("getCounters")
        finally:
            client.close()
        assert set(DELTA_COUNTER_KEYS) <= set(native)
        assert set(engine_delta) <= set(native)

        name_re = re.compile(r"[a-z][a-z0-9_]*(\.[a-z0-9_]+)+\Z")
        assert all(name_re.match(k) for k in DELTA_COUNTER_KEYS)

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
                43,
                b"\x00",
                ("map", tb.T_STRING, tb.T_I64),
                dec=lambda m: {k.decode(): v for k, v in m.items()},
            )
        finally:
            shim.stop()
            shim.wait_until_stopped(5)
        assert set(DELTA_COUNTER_KEYS) <= set(shimmed)
        assert set(engine_delta) <= set(shimmed)

    def test_te_family_on_both_wire_surfaces(self, daemon):
        """The full te.* registry (runs, steps, round trips, accept /
        reject / abort ledgers, objective gauges) answers ONE getCounters
        on the native ctrl server AND the fb303 shim, pre-seeded at
        TeOptimizer construction — dashboards can alert on te.aborted or
        te.rejected going non-zero before the first optimizeMetrics ever
        runs."""
        import re

        from openr_tpu.interop import thrift_binary as tb
        from openr_tpu.interop.shim import ThriftBinaryShim
        from openr_tpu.te import TE_COUNTER_KEYS
        from test_thrift_binary import _call_ok

        client = CtrlClient(port=daemon.ctrl_port)
        try:
            native = client.call("getCounters")
        finally:
            client.close()
        assert set(TE_COUNTER_KEYS) <= set(native)

        name_re = re.compile(r"[a-z][a-z0-9_]*(\.[a-z0-9_]+)+\Z")
        assert all(name_re.match(k) for k in TE_COUNTER_KEYS)

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
        assert set(TE_COUNTER_KEYS) <= set(shimmed)
        # representative key round-trips the strict-binary i64 map intact
        assert shimmed["te.runs"] == native["te.runs"]

    def test_fuzz_family_on_both_wire_surfaces(self, daemon):
        """The chaos-fuzzer ledger (runs, mutations, crossovers, novel
        fingerprints, oracle failures, shrink steps) is pre-seeded in
        its own process-wide registry and rides _all_counters like any
        module, so the whole chaos.fuzz.* family answers ONE getCounters
        on the native ctrl server AND the fb303 shim before any fuzz
        session has run — a soak box's dashboard can alert on
        oracle_failures going non-zero with no warm-up query."""
        import re

        from openr_tpu.chaos.fuzz import FUZZ_COUNTER_KEYS
        from openr_tpu.interop import thrift_binary as tb
        from openr_tpu.interop.shim import ThriftBinaryShim
        from test_thrift_binary import _call_ok

        family = set(FUZZ_COUNTER_KEYS)
        assert {
            "chaos.fuzz.runs",
            "chaos.fuzz.mutations",
            "chaos.fuzz.crossovers",
            "chaos.fuzz.novel_fingerprints",
            "chaos.fuzz.oracle_failures",
            "chaos.fuzz.shrink_steps",
        } == family
        name_re = re.compile(r"[a-z][a-z0-9_]*(\.[a-z0-9_]+)+\Z")
        assert all(name_re.match(k) for k in family)

        client = CtrlClient(port=daemon.ctrl_port)
        try:
            native = client.call("getCounters")
        finally:
            client.close()
        assert family <= set(native)

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
                45,
                b"\x00",
                ("map", tb.T_STRING, tb.T_I64),
                dec=lambda m: {k.decode(): v for k, v in m.items()},
            )
        finally:
            shim.stop()
            shim.wait_until_stopped(5)
        assert family <= set(shimmed)
        # the family round-trips the strict-binary i64 map intact
        assert all(shimmed[k] == native[k] for k in family)

    def test_sched_family_on_both_wire_surfaces(self, daemon):
        """The schedule-explorer ledger (schedules explored, DPOR prunes,
        replays, shrinks, planted-bug finds) is pre-seeded in its own
        process-wide registry and rides _all_counters like chaos.fuzz,
        so the whole sched.* family answers ONE getCounters on the
        native ctrl server AND the fb303 shim before any exploration has
        run — a CI box can alert on planted_finds staying zero (the
        canary bug was not found) with no warm-up query."""
        import re

        from openr_tpu.analysis.sched import SCHED_COUNTER_KEYS, SchedCounters
        from openr_tpu.interop import thrift_binary as tb
        from openr_tpu.interop.shim import ThriftBinaryShim
        from test_thrift_binary import _call_ok

        family = set(SCHED_COUNTER_KEYS)
        assert {
            "sched.schedules_explored",
            "sched.dpor_prunes",
            "sched.replays",
            "sched.shrinks",
            "sched.planted_finds",
        } == family
        name_re = re.compile(r"[a-z][a-z0-9_]*(\.[a-z0-9_]+)+\Z")
        assert all(name_re.match(k) for k in family)
        # construction pre-seeds every key to zero (the process-wide
        # singleton the daemon exports may have been bumped by an earlier
        # in-process exploration, so the zero contract is asserted on a
        # fresh registry)
        assert SchedCounters().get_counters() == {k: 0 for k in family}

        client = CtrlClient(port=daemon.ctrl_port)
        try:
            native = client.call("getCounters")
        finally:
            client.close()
        assert family <= set(native)

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
                46,
                b"\x00",
                ("map", tb.T_STRING, tb.T_I64),
                dec=lambda m: {k.decode(): v for k, v in m.items()},
            )
        finally:
            shim.stop()
            shim.wait_until_stopped(5)
        assert family <= set(shimmed)
        # the family round-trips the strict-binary i64 map intact
        assert all(shimmed[k] == native[k] for k in family)

    def test_snapshot_family_on_both_wire_surfaces(self, daemon):
        """The engine-snapshot ledger (checkpoints taken, restore rungs,
        replayed events, accounted demotions, digest failures, manifest
        prewarms, fleet scale transitions) is pre-seeded in its own
        process-wide registry and rides _all_counters like chaos.fuzz,
        so the whole snapshot.* family answers ONE getCounters on the
        native ctrl server AND the fb303 shim before any snapshot is
        ever taken — an operator can alert on replay_fallbacks or
        digest_failures going non-zero with no warm-up query."""
        import re

        from openr_tpu.interop import thrift_binary as tb
        from openr_tpu.interop.shim import ThriftBinaryShim
        from openr_tpu.snapshot import SNAPSHOT_COUNTER_KEYS, SnapshotCounters
        from test_thrift_binary import _call_ok

        family = set(SNAPSHOT_COUNTER_KEYS)
        assert {
            "snapshot.taken",
            "snapshot.take_us",
            "snapshot.bytes",
            "snapshot.restores",
            "snapshot.restore_us",
            "snapshot.replayed_events",
            "snapshot.replay_fallbacks",
            "snapshot.digest_failures",
            "snapshot.manifest_programs",
            "snapshot.prewarmed_programs",
            "snapshot.scaleouts",
            "snapshot.scaleins",
        } == family
        name_re = re.compile(r"[a-z][a-z0-9_]*(\.[a-z0-9_]+)+\Z")
        assert all(name_re.match(k) for k in family)
        # construction pre-seeds every key to zero (the process-wide
        # singleton the daemon exports may have been bumped by an earlier
        # in-process take/restore, so the zero contract is asserted on a
        # fresh registry)
        assert SnapshotCounters().get_counters() == {k: 0 for k in family}

        client = CtrlClient(port=daemon.ctrl_port)
        try:
            native = client.call("getCounters")
        finally:
            client.close()
        assert family <= set(native)

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
                48,
                b"\x00",
                ("map", tb.T_STRING, tb.T_I64),
                dec=lambda m: {k.decode(): v for k, v in m.items()},
            )
        finally:
            shim.stop()
            shim.wait_until_stopped(5)
        assert family <= set(shimmed)
        # the family round-trips the strict-binary i64 map intact
        assert all(shimmed[k] == native[k] for k in family)

    def test_obs_family_on_both_wire_surfaces(self, daemon):
        """The tracing surface (ObsStats) answers the whole obs.*
        family as ZEROS on the native ctrl server AND the fb303 shim
        while OPENR_TRACE is off — the wire shape is arming-independent,
        so a dashboard scraping obs.traces_finished needs no knowledge
        of whether the box is armed.  The span dump RPCs answer empty
        lists the same way.  The shared-histogram percentile gauges
        (serving.p50_us et al) ride the serving family on the same two
        surfaces."""
        import re

        from openr_tpu.interop import thrift_binary as tb
        from openr_tpu.interop.shim import ThriftBinaryShim
        from openr_tpu.obs import OBS_COUNTER_KEYS
        from test_thrift_binary import _call_ok

        family = set(OBS_COUNTER_KEYS)
        assert {
            "obs.traces_started",
            "obs.traces_sampled_out",
            "obs.traces_finished",
            "obs.spans_total",
            "obs.trace_ring_evictions",
        } == family
        name_re = re.compile(r"[a-z][a-z0-9_]*(\.[a-z0-9_]+)+\Z")
        assert all(name_re.match(k) for k in family)

        client = CtrlClient(port=daemon.ctrl_port)
        try:
            native = client.call("getCounters")
            assert client.call("dumpTraces") == []
            assert client.call("getSpanSamples") == []
        finally:
            client.close()
        assert family <= set(native)
        assert all(native[k] == 0 for k in family)  # unarmed: zeroed
        # histogram percentile gauges ride the serving registry
        for key in ("serving.p50_us", "serving.p99_us", "serving.p999_us"):
            assert key in native, key

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
                53,
                b"\x00",
                ("map", tb.T_STRING, tb.T_I64),
                dec=lambda m: {k.decode(): v for k, v in m.items()},
            )
        finally:
            shim.stop()
            shim.wait_until_stopped(5)
        assert family <= set(shimmed)
        assert all(shimmed[k] == 0 for k in family)
        assert "serving.p50_us" in shimmed


class TestOptimizeMetricsWire:
    """The ctrl optimizeMetrics front-end end to end: a bad request is
    answered with a clean error envelope through the serving admission
    path — never a hang, never a silent drop (tests/test_te.py covers
    the optimizer itself; this pins the wire registration)."""

    def test_bad_demand_gets_clean_error(self, daemon):
        client = CtrlClient(port=daemon.ctrl_port)
        try:
            with pytest.raises(RuntimeError):
                client.call(
                    "optimizeMetrics",
                    area="0",
                    demand=[["no-such-node", "also-missing", 1.0]],
                    steps=2,
                )
            # the surface stays alive and dumpable after the error
            assert "te.runs" in client.call("getCounters")
        finally:
            client.close()
