#!/usr/bin/env python3
"""Chip smoke: the router's main path, once, on a TPU.

Default (one chip): one `OpenrDaemon` with its default device backend,
placed as rack switch rsw-0-0 of the fat-tree of BASELINE config #2
(`fabric_topology(96, planes=4, ssw_per_plane=24, rsw_per_pod=100)`:
10,080 switches, 95,232 directed adjacencies, one prefix per switch).
The fabric's adjacency and prefix databases reach it through its
KvStore, by full sync from a peer store over the in-process transport.
In order:

  1. cold route build: the FIB holds a route for every other switch's
     prefix, bit-exact (next hops, metrics) against a host-oracle
     `Decision` (HostSpfBackend) fed the same publications;
  2. fleet product: ctrl getRouteDb for another rack switch is answered
     from the reduced all-sources product on the device, bit-exact
     against the host solver for that switch;
  3. query burst: 64 concurrent ctrl queryPaths calls (one source each)
     through the serving scheduler and the engine, checked against the
     host SPF of each source;
  4. incremental rebuild: one uplink metric changed through ctrl
     setKvStoreKeyVals, served by the engine; FIB and the other
     switch's fleet view checked against the oracle again.

No Pallas kernel is on the TPU default (ops/pallas_kernels.py), so none
runs here.  It fails (exit 1) if any check fails, if no device work
happened, if the incremental rebuild did not reach the engine, or if any
fallback counter moved.  Which backend served the cold route build (the
dispatch policy's choice) is printed, not checked.  Timings printed on the way are first chip
readings, not benchmark results.

`--chips 4` runs only the cross-chip path: the fat-tree-10k route view
through the node-sharded blocked closure over a four-device mesh,
checked bit-exact against the single-chip fused product.

Without a TPU it exits 2 before doing anything.  The last stdout line is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

SELF = "rsw-0-0"  # the daemon's place in the fabric
OTHER = "rsw-0-1"  # the switch whose routes the fleet product answers
PEER = "fabric"  # the peer store holding the rest of the fabric's LSDB
AREA = "0"
N_QUERY_SOURCES = 64
FABRIC = dict(pods=96, planes=4, ssw_per_plane=24, rsw_per_pod=100)
TIMEOUT_S = 600.0

FALLBACK_COUNTERS = (
    "decision.device_fallbacks",
    "decision.route_rebuild_fallbacks",
    "decision.fleet_view_failures",
    "decision.fleet_warm_fallbacks",
    "serving.host_fallbacks",
    "device.engine.pallas_fallbacks",
    "mesh.blocked.fallbacks",
)


# the counter families the readings report
COUNTER_FAMILIES = (
    "decision.",
    "device.engine.",
    "mesh.blocked.",
    "serving.",
    "fib.",
    "monitor.process_rss_bytes",
)


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


def wait_for(cond, timeout_s: float, what: str) -> float:
    t0 = time.perf_counter()
    while not cond():
        if time.perf_counter() - t0 > timeout_s:
            raise SmokeFailure(f"timed out after {timeout_s:.0f}s: {what}")
        time.sleep(0.02)
    return time.perf_counter() - t0


# -- fabric and oracle --------------------------------------------------------


def fabric_key_vals(dbs) -> dict:
    """adj: + prefix: key-values of every switch, one prefix each."""
    from openr_tpu.serializer import dumps
    from openr_tpu.types import (
        PrefixDatabase,
        PrefixEntry,
        Value,
        adj_key,
        prefix_key,
    )

    kv = {}
    for i, db in enumerate(dbs):
        node = db.this_node_name
        kv[adj_key(node)] = Value(
            version=1, originator_id=node, value=dumps(db)
        )
        prefix = f"fc00:{i:x}::/64"
        pdb = PrefixDatabase(
            this_node_name=node, prefix_entries=[PrefixEntry(prefix=prefix)]
        )
        kv[prefix_key(node, prefix, AREA)] = Value(
            version=1, originator_id=node, value=dumps(pdb)
        )
    return kv


class Oracle:
    """Host-oracle Decision (HostSpfBackend) fed the same publications."""

    def __init__(self, config) -> None:
        from openr_tpu.decision.decision import Decision
        from openr_tpu.decision.spf_solver import HostSpfBackend
        from openr_tpu.runtime.queue import ReplicateQueue

        self.kvq = ReplicateQueue()
        self.routeq = ReplicateQueue()
        self._reader = self.routeq.get_reader()
        self.decision = Decision(
            config.node_name,
            self.kvq.get_reader(),
            None,
            self.routeq,
            debounce_min_s=0.001,
            debounce_max_s=0.005,
            enable_v4=config.enable_v4,
            spf_backend=HostSpfBackend(),
        )
        self.decision.run()

    def publish(self, key_vals: dict) -> None:
        from openr_tpu.types import Publication

        self.kvq.push(Publication(key_vals=dict(key_vals), area=AREA))
        self._reader.get(timeout=TIMEOUT_S)

    def routes(self) -> dict:
        def _get():
            return {
                prefix: frozenset(entry.nexthops)
                for prefix, entry in self.decision.route_db.unicast_routes.items()
                if not entry.do_not_install
            }

        return self.decision.run_in_event_base_thread(_get).result()

    def route_db_for(self, node: str) -> dict:
        """Host solver's route build for any node over the oracle state."""
        from openr_tpu.decision.spf_solver import HostSpfBackend, SpfSolver

        d = self.decision

        def _get():
            solver = SpfSolver(
                node,
                enable_v4=d.spf_solver.enable_v4,
                spf_backend=HostSpfBackend(),
            )
            db = solver.build_route_db(d.area_link_states, d.prefix_state)
            return {
                p: frozenset(e.nexthops) for p, e in db.unicast_routes.items()
            }

        return d.run_in_event_base_thread(_get).result()

    def spf(self, src: str):
        d = self.decision
        return d.run_in_event_base_thread(
            lambda: d.area_link_states[AREA].get_spf_result(src)
        ).result()

    def stop(self) -> None:
        self.kvq.close()
        self.routeq.close()
        self.decision.stop()
        self.decision.wait_until_stopped(5)


def fib_routes(daemon) -> dict:
    table = dict(daemon.fib_agent.unicast.get(786, {}))
    return {dest: frozenset(r.next_hops) for dest, r in table.items()}


def diff_summary(got: dict, want: dict) -> str:
    missing = sorted(set(want) - set(got))
    extra = sorted(set(got) - set(want))
    wrong = sorted(p for p in set(got) & set(want) if got[p] != want[p])
    return (
        f"{len(got)} routes vs {len(want)} expected: {len(missing)} missing "
        f"{missing[:3]}, {len(extra)} extra {extra[:3]}, {len(wrong)} "
        f"differ {wrong[:3]}"
    )


def wait_fib_matches(daemon, oracle, n_routes: int, what: str) -> float:
    want = oracle.routes()
    check(len(want) == n_routes, f"oracle holds {len(want)} routes")
    try:
        return wait_for(
            lambda: fib_routes(daemon) == want, TIMEOUT_S, what
        )
    except SmokeFailure:
        raise SmokeFailure(
            f"{what}: FIB != oracle: {diff_summary(fib_routes(daemon), want)}"
        ) from None


# -- single chip --------------------------------------------------------------


def run_single_chip(fabric: dict, stats) -> dict:
    from openr_tpu.ctrl import CtrlClient
    from openr_tpu.kvstore import InProcessTransport, KvStore
    from openr_tpu.main import OpenrDaemon, fleet_node_config
    from openr_tpu.runtime.queue import ReplicateQueue
    from openr_tpu.spark import MockIoProvider
    from openr_tpu.types import PeerSpec
    from openr_tpu.utils.topo import fabric_topology

    readings: dict = {}
    dbs = fabric_topology(
        fabric["pods"],
        planes=fabric["planes"],
        ssw_per_plane=fabric["ssw_per_plane"],
        rsw_per_pod=fabric["rsw_per_pod"],
    )
    n = len(dbs)
    n_adj = sum(len(db.adjacencies) for db in dbs)
    say(f"fabric: {n} switches, {n_adj} directed adjacencies; daemon {SELF}")
    kv = fabric_key_vals(dbs)

    config = fleet_node_config(SELF)
    kv_fabric = InProcessTransport()
    daemon = OpenrDaemon(
        config,
        io_provider=MockIoProvider().endpoint(SELF),
        kvstore_transport=kv_fabric.bind(f"fe80::{SELF}"),
        spark_v6_addr=f"fe80::{SELF}",
    )
    kv_fabric.register(f"fe80::{SELF}", daemon.kvstore)
    peer_queues = [ReplicateQueue() for _ in range(3)]
    peer = KvStore(
        PEER,
        peer_queues[0],
        peer_queues[1],
        peer_queues[2].get_reader(),
        transport=kv_fabric.bind(f"fe80::{PEER}"),
        areas=[AREA],
    )
    kv_fabric.register(f"fe80::{PEER}", peer)
    oracle = Oracle(config)
    clients: list = []
    try:
        daemon.start()
        peer.run()
        peer.set_key_vals(AREA, dict(kv))
        ctrl = CtrlClient(port=daemon.ctrl_port, timeout_s=TIMEOUT_S)
        clients.append(ctrl)

        # -- 1. cold route build ------------------------------------------
        c0 = stats.snapshot()
        q0 = engine_counters(daemon)
        t0 = time.perf_counter()
        daemon.kvstore.add_peers(AREA, {PEER: PeerSpec(f"fe80::{PEER}")})
        peer.add_peers(AREA, {SELF: PeerSpec(f"fe80::{SELF}")})
        wait_for(
            lambda: len(daemon.fib_agent.unicast.get(786, {})) >= n - 1,
            TIMEOUT_S,
            f"FIB to hold {n - 1} routes",
        )
        readings["cold_route_build_s"] = time.perf_counter() - t0
        oracle.publish(kv)
        wait_fib_matches(daemon, oracle, n - 1, "cold route build")
        readings["fib_routes"] = len(fib_routes(daemon))
        readings["cold_route_build_compile_s"] = (
            stats.snapshot()["compile_or_load_s"] - c0["compile_or_load_s"]
        )
        readings["cold_route_build_engine_queries"] = (
            engine_counters(daemon)["device.engine.queries"]
            - q0["device.engine.queries"]
        )
        readings["cold_route_build_served_by"] = (
            "device engine"
            if readings["cold_route_build_engine_queries"]
            else "host Dijkstra (dispatch policy)"
        )
        say(
            f"cold route build: FIB holds {readings['fib_routes']} routes, "
            f"bit-exact vs host oracle; {readings['cold_route_build_s']:.3f}s, "
            f"served by {readings['cold_route_build_served_by']}"
        )

        # -- 2. fleet product (any-node route query) -----------------------
        readings["fleet_product_s"], readings["fleet_product_compile_s"] = (
            fleet_route_check(ctrl, oracle, stats, "cold")
        )

        # -- 3. query burst -------------------------------------------------
        sources = sorted(db.this_node_name for db in dbs)[
            :: max(1, n // N_QUERY_SOURCES)
        ][:N_QUERY_SOURCES]
        c0 = stats.snapshot()
        q0 = engine_counters(daemon)
        t0 = time.perf_counter()
        replies = query_burst(daemon.ctrl_port, sources, clients)
        readings["query_burst_s"] = time.perf_counter() - t0
        readings["query_burst_compile_s"] = (
            stats.snapshot()["compile_or_load_s"] - c0["compile_or_load_s"]
        )
        readings["query_burst_engine_queries"] = (
            engine_counters(daemon)["device.engine.queries"]
            - q0["device.engine.queries"]
        )
        for src, reply in zip(sources, replies):
            check_paths(src, reply, oracle.spf(src), n)
        say(
            f"query burst: {len(sources)} queryPaths answered, match host "
            f"SPF; {readings['query_burst_s']:.3f}s, "
            f"{readings['query_burst_engine_queries']} engine queries"
        )

        # -- 4. incremental rebuild ----------------------------------------
        change = uplink_metric_change(dbs)
        c0 = stats.snapshot()
        q0 = engine_counters(daemon)
        before = fib_routes(daemon)
        t0 = time.perf_counter()
        ctrl.call("setKvStoreKeyVals", key_vals=change, area=AREA)
        wait_for(
            lambda: fib_routes(daemon) != before,
            TIMEOUT_S,
            "FIB to take the metric change",
        )
        readings["incremental_rebuild_s"] = time.perf_counter() - t0
        oracle.publish(change)
        wait_fib_matches(daemon, oracle, n - 1, "incremental rebuild")
        readings["incremental_rebuild_compile_s"] = (
            stats.snapshot()["compile_or_load_s"] - c0["compile_or_load_s"]
        )
        readings["incremental_rebuild_engine_queries"] = (
            engine_counters(daemon)["device.engine.queries"]
            - q0["device.engine.queries"]
        )
        check(
            readings["incremental_rebuild_engine_queries"] >= 1,
            "the incremental rebuild did not reach the engine",
        )
        changed = sum(1 for p, nh in fib_routes(daemon).items() if before[p] != nh)
        say(
            f"incremental rebuild: {changed} routes changed, bit-exact vs "
            f"host oracle; {readings['incremental_rebuild_s']:.3f}s, "
            f"{readings['incremental_rebuild_engine_queries']} engine queries"
        )
        readings["fleet_product_after_change_s"], _ = fleet_route_check(
            ctrl, oracle, stats, "after the change"
        )

        counters = ctrl.call("getCounters")
    finally:
        for c in clients:
            c.close()
        oracle.stop()
        peer.stop()
        peer.wait_until_stopped(5)
        for q in peer_queues:
            q.close()
        daemon.stop()

    readings["counters"] = {
        k: v for k, v in sorted(counters.items()) if k.startswith(COUNTER_FAMILIES)
    }
    check(
        counters.get("decision.fleet_rebuild_cold", 0) >= 1,
        "no cold fleet product ran",
    )
    check(
        counters.get("device.engine.dispatches", 0) > 0
        and counters.get("device.engine.queries", 0) > 0,
        "the engine dispatched nothing",
    )
    moved = {k: counters.get(k, 0) for k in FALLBACK_COUNTERS if counters.get(k, 0)}
    check(not moved, f"fallback counters moved: {moved}")
    return readings


def engine_counters(daemon) -> dict:
    return daemon.decision.spf_solver.spf.engine.get_counters()


def fleet_route_check(ctrl, oracle, stats, when: str) -> tuple:
    """ctrl getRouteDb for OTHER: answered from the fleet product."""
    c0 = stats.snapshot()
    t0 = time.perf_counter()
    db = ctrl.call("getRouteDb", node=OTHER)
    wall = time.perf_counter() - t0
    compile_s = stats.snapshot()["compile_or_load_s"] - c0["compile_or_load_s"]
    got = {
        p: frozenset(e.nexthops) for p, e in db.unicast_routes.items()
    }
    want = oracle.route_db_for(OTHER)
    check(
        got == want,
        f"fleet route view of {OTHER} ({when}) != host: "
        f"{diff_summary(got, want)}",
    )
    say(
        f"fleet product ({when}): {OTHER}'s {len(got)} routes bit-exact vs "
        f"host; {wall:.3f}s wall, {compile_s:.3f}s of it compiling or loading programs"
    )
    return wall, compile_s


def query_burst(port: int, sources: list, clients: list) -> list:
    from openr_tpu.ctrl import CtrlClient

    replies: list = [None] * len(sources)
    errors: list = []
    barrier = threading.Barrier(len(sources))

    def one(i: int, src: str) -> None:
        client = CtrlClient(port=port, timeout_s=TIMEOUT_S)
        clients.append(client)
        try:
            barrier.wait(timeout=60)
            replies[i] = client.call("queryPaths", sources=[src])
        except Exception as e:  # reported below, on the main thread
            errors.append(f"{src}: {type(e).__name__}: {e}")

    threads = [
        threading.Thread(target=one, args=(i, s), daemon=True)
        for i, s in enumerate(sources)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(TIMEOUT_S)
    check(not errors, f"queryPaths failed: {errors[:3]}")
    check(all(r is not None for r in replies), "queryPaths unanswered")
    return replies


def check_paths(src: str, reply: dict, host, n: int) -> None:
    spf = reply["result"][src]
    check(len(spf) == n, f"queryPaths({src}) answered {len(spf)} of {n}")
    for dest, r in host.items():
        got = spf.get(dest)
        check(
            got is not None
            and got["metric"] == int(r.metric)
            and got["nextHops"] == sorted(r.next_hops),
            f"queryPaths({src}) -> {dest}: {got} vs host "
            f"metric={r.metric} nextHops={sorted(r.next_hops)}",
        )


def uplink_metric_change(dbs) -> dict:
    """SELF's adjacency database, version 2, first uplink at metric 5."""
    import copy

    from openr_tpu.serializer import dumps
    from openr_tpu.types import Value, adj_key

    db = copy.deepcopy(next(d for d in dbs if d.this_node_name == SELF))
    db.adjacencies[0].metric = 5
    return {
        adj_key(SELF): Value(version=2, originator_id=SELF, value=dumps(db))
    }


# -- four chips ---------------------------------------------------------------


def run_four_chips(fabric: dict, n_devices: int) -> dict:
    """The node-sharded blocked closure over all devices vs the
    single-chip fused product, same process, same topology."""
    import jax
    import numpy as np

    from openr_tpu.decision.fleet import FleetViewCache, _row_i32
    from openr_tpu.decision.link_state import LinkState
    from openr_tpu.device import DeviceResidencyEngine
    from openr_tpu.utils.topo import fabric_topology

    dbs = fabric_topology(
        fabric["pods"],
        planes=fabric["planes"],
        ssw_per_plane=fabric["ssw_per_plane"],
        rsw_per_pod=fabric["rsw_per_pod"],
    )

    def view(node_shard: str):
        ls = LinkState()
        for db in dbs:
            ls.update_adjacency_database(db)
        os.environ["OPENR_NODE_SHARD"] = node_shard
        try:
            eng = DeviceResidencyEngine()
            nodes = sorted(ls.node_names)
            t0 = time.perf_counter()
            v = FleetViewCache().view(ls, nodes, engine=eng)
            jax.block_until_ready((v._dist_dev, v._bitmap_dev))
            wall = time.perf_counter() - t0
        finally:
            del os.environ["OPENR_NODE_SHARD"]
        return v, eng, wall, nodes

    vb, eb, wall_b, nodes = view("1")
    check(vb.node_sharded, "the blocked rung did not engage")
    mesh = eb.blocked.mesh()
    devs = {d.id for d in mesh.devices.flat}
    check(len(devs) == n_devices, f"mesh spans {len(devs)} devices")
    shards = {s.device.id for s in vb._dist_dev.addressable_shards}
    check(len(shards) == n_devices, f"result shards on {sorted(shards)}")
    bc = eb.blocked.get_counters()
    check(
        bc["mesh.blocked.fallbacks"] == 0
        and bc["mesh.blocked.pipeline_fallbacks"] == 0,
        f"blocked rung fell back: {bc}",
    )
    say(
        f"blocked closure: {len(nodes)} nodes over a "
        f"{dict(mesh.shape)} mesh of {len(devs)} devices, "
        f"{bc['mesh.blocked.rounds']} rounds; {wall_b:.3f}s incl. compiles"
    )

    vf, ef, wall_f, _ = view("0")
    check(not vf.node_sharded, "the fused product did not serve")
    n = len(nodes)
    db_ = _row_i32(np.asarray(jax.device_get(vb._dist_dev)))[:n]
    df_ = _row_i32(np.asarray(jax.device_get(vf._dist_dev)))[:n]
    check(np.array_equal(db_, df_), "blocked distances != fused product")
    bb = np.asarray(jax.device_get(vb._bitmap_dev))[:n]
    bf = np.asarray(jax.device_get(vf._bitmap_dev))[:n]
    check(np.array_equal(bb, bf), "blocked ECMP bitmap != fused product")
    say(
        f"fused product on one chip: bit-exact vs the blocked closure "
        f"(dist {db_.shape}, bitmap {bb.shape}); {wall_f:.3f}s incl. compiles"
    )
    return {
        "blocked_view_s": wall_b,
        "fused_view_s": wall_f,
        "mesh_devices": sorted(devs),
        "shard_devices": sorted(shards),
        "blocked_counters": bc,
    }


# -- entry --------------------------------------------------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = parser.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
            "nothing was run",
            file=sys.stderr,
        )
        return 2
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    say(f"device: {json.dumps(device)}")
    if len(devices) < args.chips:
        print(
            f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
            f"JAX sees {len(devices)}",
            file=sys.stderr,
        )
        return 2

    from openr_tpu.utils.compile_cache import CompileStats, configure_compile_cache

    cache_dir = configure_compile_cache()
    stats = CompileStats()
    say(f"compile cache: {cache_dir}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            readings = run_four_chips(FABRIC, args.chips)
        else:
            readings = run_single_chip(FABRIC, stats)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    readings["total_s"] = time.perf_counter() - t0
    readings["compile"] = stats.snapshot()
    say(
        "compile: "
        + json.dumps(readings["compile"])
        + (
            " (cache hits: yes)"
            if readings["compile"]["cache_hits"]
            else " (cache hits: none)"
        )
    )
    say(
        "first chip readings (not benchmark results): "
        + json.dumps(readings, sort_keys=True, default=str)
    )
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
