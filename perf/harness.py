"""One daemon of the system under test, fed its deployment by KvStore.

Set-up as a router comes up in a fabric: an `OpenrDaemon` at the
configuration's node, with the configuration's Decision debounce, peers
with a KvStore that holds every other switch's adjacency and prefix
databases and full-syncs from it; boot convergence (the FIB holds a
route to every other node) ends the set-up's cold build.  Later link
events are published by that peer store and reach the daemon by flood,
as a remote switch's would.
"""

from __future__ import annotations

import time

from . import wire

PEER = "fabric"
FIB_CLIENT = 786  # the FIB agent's client id for Open/R routes
BOOT_TIMEOUT_S = 600.0


def _value(version: int, node: str, obj):
    from openr_tpu.serializer import dumps
    from openr_tpu.types import Value

    return Value(version=version, originator_id=node, value=dumps(obj))


class Harness:
    def __init__(self, cfg: dict, topo) -> None:
        from openr_tpu.config import AreaConf, DecisionConf, OpenrConfig
        from openr_tpu.kvstore import InProcessTransport, KvStore
        from openr_tpu.main import OpenrDaemon
        from openr_tpu.runtime.queue import ReplicateQueue
        from openr_tpu.spark import MockIoProvider

        self.cfg = cfg
        self.topo = topo
        self.node = cfg["daemon_node"]
        self.area = topo.area
        self.versions = {n: 1 for n in topo.nodes}
        self.parts: dict[str, float] = {}

        config = OpenrConfig(
            node_name=self.node,
            areas=[AreaConf(area_id=self.area)],
            openr_ctrl_port=0,
            decision_config=DecisionConf(**cfg["decision"]),
            enable_watchdog=False,
            node_label=0,
        ).validate()
        transport = InProcessTransport()
        self.daemon = OpenrDaemon(
            config,
            io_provider=MockIoProvider().endpoint(self.node),
            kvstore_transport=transport.bind(f"fe80::{self.node}"),
            spark_v6_addr=f"fe80::{self.node}",
        )
        transport.register(f"fe80::{self.node}", self.daemon.kvstore)
        self._peer_queues = [ReplicateQueue() for _ in range(3)]
        self.peer = KvStore(
            PEER,
            self._peer_queues[0],
            self._peer_queues[1],
            self._peer_queues[2].get_reader(),
            transport=transport.bind(f"fe80::{PEER}"),
            areas=[self.area],
        )
        transport.register(f"fe80::{PEER}", self.peer)
        self.ctrl = None
        self.fib_stream = None
        self._started = False

    # -- the deployment as the program's KvStore values -----------------------

    def adj_db(self, node: str, down=frozenset()):
        from openr_tpu.types import Adjacency, AdjacencyDatabase

        return AdjacencyDatabase(
            this_node_name=node,
            adjacencies=[
                Adjacency(
                    other_node_name=a.other,
                    if_name=a.if_name,
                    other_if_name=a.other_if_name,
                    metric=a.metric,
                    next_hop_v6=a.next_hop_v6,
                )
                for a in self.topo.adj[node]
                if frozenset((node, a.other)) not in down
            ],
            area=self.area,
            node_label=self.topo.index[node] + 1,
        )

    def key_vals(self) -> dict:
        from openr_tpu.types import PrefixDatabase, PrefixEntry, adj_key, prefix_key

        kv = {}
        for node in self.topo.nodes:
            kv[adj_key(node)] = _value(1, node, self.adj_db(node))
            for p in self.topo.prefixes[node]:
                pdb = PrefixDatabase(
                    this_node_name=node, prefix_entries=[PrefixEntry(prefix=p)]
                )
                kv[prefix_key(node, p, self.area)] = _value(1, node, pdb)
        return kv

    def link_event_key_vals(self, a: str, b: str, down) -> dict:
        """Both ends' adjacency databases, one version up, with the links
        in `down` left out."""
        from openr_tpu.types import adj_key

        kv = {}
        for node in (a, b):
            self.versions[node] += 1
            kv[adj_key(node)] = _value(
                self.versions[node], node, self.adj_db(node, down)
            )
        return kv

    # -- lifecycle -------------------------------------------------------------

    def start(self) -> None:
        from openr_tpu.types import PeerSpec

        t0 = time.perf_counter()
        kv = self.key_vals()
        self.parts["key_values_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        self._started = True
        self.daemon.start()
        self.peer.run()
        self.peer.set_key_vals(self.area, kv)
        self.fib_stream = self.daemon.fib_updates_queue.get_reader()
        self.ctrl = wire.Client(self.daemon.ctrl_port)
        self.parts["daemon_start_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.daemon.kvstore.add_peers(self.area, {PEER: PeerSpec(f"fe80::{PEER}")})
        self.peer.add_peers(self.area, {self.node: PeerSpec(f"fe80::{self.node}")})
        want = sum(len(self.topo.prefixes[n]) for n in self.topo.nodes if n != self.node)
        deadline = time.perf_counter() + BOOT_TIMEOUT_S
        while len(self.fib_table()) < want:
            if time.perf_counter() > deadline:
                raise RuntimeError(
                    f"boot convergence: FIB holds {len(self.fib_table())} of {want} routes"
                )
            time.sleep(0.01)
        # settled: no route update for a whole debounce ceiling, twice
        quiet = 0
        while quiet < 2:
            time.sleep(self.cfg["decision"]["debounce_max_ms"] / 1000.0 + 0.05)
            quiet = quiet + 1 if self.drain_fib_stream() == 0 else 0
        self.parts["full_sync_and_cold_build_s"] = time.perf_counter() - t0

    def drain_fib_stream(self) -> int:
        n = 0
        while self.fib_stream.size():
            self.fib_stream.get(timeout=0)
            n += 1
        return n

    def publish(self, kv: dict) -> None:
        self.peer.set_key_vals(self.area, kv)

    def fib_table(self) -> dict:
        """A snapshot of the routes the FIB agent holds (routes are
        replaced, never mutated, so a shallow copy is a snapshot)."""
        agent = self.daemon.fib_agent
        with agent._lock:
            return dict(agent.unicast.get(FIB_CLIENT, {}))

    def counters(self) -> dict:
        return self.ctrl.call("getCounters")

    def stop(self) -> None:
        if not self._started:
            return
        self._started = False
        if self.ctrl is not None:
            self.ctrl.close()
        self.peer.stop()
        self.peer.wait_until_stopped(10)
        for q in self._peer_queues:
            q.close()
        self.daemon.stop()


def canonical_fib(table: dict) -> dict:
    """FIB routes in the reference's form:
    {prefix: frozenset((neighbour, if_name, address, metric))}."""
    return {
        dest: frozenset(
            (nh.neighbor_node_name, nh.if_name, nh.address, int(nh.metric))
            for nh in route.next_hops
        )
        for dest, route in table.items()
    }
