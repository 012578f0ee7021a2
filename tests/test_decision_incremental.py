"""Decision's incremental route rebuild against a full build.

Seeded sequences of events on a small F4-shaped fabric (4 pods) and an
8 x 8 grid, on the host Dijkstra and on the device backend (CPU JAX).
After every event: Decision's route DB (unicast and MPLS) equals a fresh
`build_route_db` of the same state on the host oracle, the update it
pushed equals `calculate_update`'s, and `decision.incremental_rebuilds`
moved exactly where the event may take the incremental path.
"""

from __future__ import annotations

import os
import random
import time

import pytest

from openr_tpu.decision.decision import DECISION_COUNTER_KEYS, Decision
from openr_tpu.decision.prefix_state import PrefixState
from openr_tpu.decision.rib import (
    DecisionRouteDb,
    DecisionRouteUpdate,
    RibMplsEntry,
    RibUnicastEntry,
)
from openr_tpu.decision.rib_policy import (
    RibPolicyConfig,
    RibPolicyStatementConfig,
    RibRouteActionWeight,
)
from openr_tpu.decision.spf_solver import DeviceSpfBackend, HostSpfBackend, SpfSolver
from openr_tpu.runtime.queue import ReplicateQueue
from openr_tpu.serializer import dumps
from openr_tpu.types import (
    Adjacency,
    AdjacencyDatabase,
    NextHop,
    PrefixDatabase,
    PrefixEntry,
    PrefixForwardingAlgorithm,
    PrefixForwardingType,
    Publication,
    Value,
    adj_key,
    prefix_key,
)

AREA = "0"
ANYCAST = "fd00::/64"
KSP2 = "fd01::/64"
STATIC = "fd02::/64"


class Net:
    """The fabric as every switch would advertise it."""

    def __init__(self, me: str, nodes: list[str], links: list[tuple[str, str]]):
        self.me = me
        self.nodes = list(nodes)
        self.nbrs: dict[str, set[str]] = {n: set() for n in nodes}
        self.metric: dict[tuple[str, str], int] = {}
        for a, b in links:
            self.link(a, b)
        self.down: set[frozenset] = set()
        self.overloaded: set[str] = set()
        self.labels = {n: 100 + i for i, n in enumerate(nodes)}
        self.prefix = {n: f"fc00:{i}::/64" for i, n in enumerate(nodes)}

    def link(self, a: str, b: str) -> None:
        self.nbrs.setdefault(a, set()).add(b)
        self.nbrs.setdefault(b, set()).add(a)
        self.metric[(a, b)] = self.metric[(b, a)] = 1

    def drop(self, node: str) -> None:
        self.nodes.remove(node)
        for o in self.nbrs.pop(node):
            self.nbrs[o].discard(node)
            del self.metric[(node, o)], self.metric[(o, node)]

    @property
    def remote_links(self) -> list[tuple[str, str]]:
        return sorted(
            (a, b)
            for (a, b) in self.metric
            if a < b and self.me not in (a, b)
        )

    @property
    def neighbours(self) -> list[str]:
        return sorted(self.nbrs[self.me])

    @property
    def far(self) -> list[str]:
        return [
            n for n in self.nodes if n != self.me and n not in self.nbrs[self.me]
        ]

    def adj_db(self, node: str) -> AdjacencyDatabase:
        return AdjacencyDatabase(
            this_node_name=node,
            adjacencies=[
                Adjacency(
                    other_node_name=o,
                    if_name=f"{node}/{o}",
                    other_if_name=f"{o}/{node}",
                    metric=self.metric[(node, o)],
                    next_hop_v6=f"fe80::{o}",
                )
                for o in sorted(self.nbrs[node])
                if frozenset((node, o)) not in self.down
            ],
            node_label=self.labels[node],
            is_overloaded=node in self.overloaded,
            area=AREA,
        )

    def adjs(self, *nodes: str) -> Publication:
        return Publication(
            key_vals={
                adj_key(n): Value(version=1, originator_id=n, value=dumps(self.adj_db(n)))
                for n in nodes
            },
            area=AREA,
        )

    @staticmethod
    def prefix_pub(node: str, entry: PrefixEntry, withdraw=False) -> Publication:
        db = PrefixDatabase(
            this_node_name=node, prefix_entries=[entry], delete_prefix=withdraw
        )
        return Publication(
            key_vals={
                prefix_key(node, entry.prefix, AREA): Value(
                    version=1, originator_id=node, value=dumps(db)
                )
            },
            area=AREA,
        )

    def boot(self) -> Publication:
        pub = self.adjs(*self.nodes)
        for n in self.nodes:
            pub.key_vals.update(
                self.prefix_pub(n, PrefixEntry(prefix=self.prefix[n])).key_vals
            )
        return pub


def fabric(pods=4, planes=2, ssw=2, rsw=3) -> Net:
    nodes = [f"ssw-{p}-{s}" for p in range(planes) for s in range(ssw)]
    links = []
    for pod in range(pods):
        nodes += [f"fsw-{pod}-{f}" for f in range(planes)]
        nodes += [f"rsw-{pod}-{r}" for r in range(rsw)]
        for f in range(planes):
            links += [(f"fsw-{pod}-{f}", f"ssw-{f}-{s}") for s in range(ssw)]
            links += [(f"fsw-{pod}-{f}", f"rsw-{pod}-{r}") for r in range(rsw)]
    return Net("rsw-0-0", nodes, links)


def grid(n=8) -> Net:
    nodes = [f"node-{r}-{c}" for r in range(n) for c in range(n)]
    links = [(f"node-{r}-{c}", f"node-{r}-{c + 1}") for r in range(n) for c in range(n - 1)]
    links += [(f"node-{r}-{c}", f"node-{r + 1}-{c}") for r in range(n - 1) for c in range(n)]
    return Net("node-0-0", nodes, links)


def _canon(update: DecisionRouteUpdate) -> tuple:
    mpls = {e.label: e for e in update.mpls_routes_to_update}
    assert len(mpls) == len(update.mpls_routes_to_update)
    return (
        update.unicast_routes_to_update,
        sorted(update.unicast_routes_to_delete),
        mpls,
        sorted(update.mpls_routes_to_delete),
    )


class Run:
    """One Decision, driven on its own thread one event at a time (the
    debounce is parked: each step rebuilds explicitly)."""

    def __init__(self, net: Net, backend) -> None:
        self.net = net
        self.kvq: ReplicateQueue = ReplicateQueue()
        self.routeq: ReplicateQueue = ReplicateQueue()
        self.reader = self.routeq.get_reader()
        self.decision = Decision(
            net.me,
            self.kvq.get_reader(),
            None,
            self.routeq,
            debounce_min_s=600,
            debounce_max_s=600,
            enable_rib_policy=True,
            spf_backend=backend,
        )
        self.decision.run()

    def stop(self) -> None:
        self.kvq.close()
        self.routeq.close()
        self.decision.stop()
        self.decision.wait_until_stopped(5)

    def on_thread(self, fn):
        return self.decision.run_in_event_base_thread(fn).result()

    def publish(self, pub: Publication):
        return lambda: self.on_thread(lambda: self.decision.process_publication(pub))

    def full_build(self) -> DecisionRouteDb:
        d = self.decision
        oracle = SpfSolver(self.net.me, spf_backend=HostSpfBackend())
        oracle.static_unicast_routes = dict(d.spf_solver.static_unicast_routes)
        oracle.static_mpls_routes = dict(d.spf_solver.static_mpls_routes)
        db = oracle.build_route_db(d.area_link_states, d.prefix_state)
        db = db if db is not None else DecisionRouteDb()
        if d.rib_policy is not None:
            d.rib_policy.apply_policy(db.unicast_routes)
        return db

    def step(self, apply, engaged) -> DecisionRouteDb:
        d = self.decision
        before = DecisionRouteDb(
            dict(d.route_db.unicast_routes), dict(d.route_db.mpls_routes)
        )
        n0 = d.counters["decision.incremental_rebuilds"]
        apply()
        self.on_thread(
            lambda: d.pending_updates.needs_route_update()
            and d.rebuild_routes("TEST")
        )
        update = self.reader.get(timeout=30)
        assert self.reader.size() == 0
        full = self.on_thread(self.full_build)
        assert d.route_db.unicast_routes == full.unicast_routes
        assert d.route_db.mpls_routes == full.mpls_routes
        assert _canon(update) == _canon(before.calculate_update(full))
        assert (d.counters["decision.incremental_rebuilds"] - n0 == 1) is engaged
        return full


def _backend(kind: str):
    if kind == "host":
        return HostSpfBackend()
    return DeviceSpfBackend(min_device_nodes=1, min_device_sources=1)


def _flap(run: Run, rng: random.Random, engaged=True) -> None:
    net = run.net
    a, b = rng.choice(net.remote_links)
    net.down.add(frozenset((a, b)))
    run.step(run.publish(net.adjs(a, b)), engaged)
    net.down.discard(frozenset((a, b)))
    run.step(run.publish(net.adjs(a, b)), engaged)


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("backend", ["host", "device"])
@pytest.mark.parametrize("topo", ["fabric", "grid"])
def test_incremental_rebuild_matches_full_build(topo, backend, seed):
    net = fabric() if topo == "fabric" else grid()
    rng = random.Random(seed)
    run = Run(net, _backend(backend))
    d = run.decision
    try:
        # no snapshot of an earlier build yet: full
        run.step(run.publish(net.boot()), False)
        # remote link down / up
        for _ in range(3):
            _flap(run, rng)
        # remote metric change, one direction
        a, b = rng.choice(net.remote_links)
        net.metric[(a, b)] = 3
        run.step(run.publish(net.adjs(a)), True)
        net.metric[(a, b)] = 1
        run.step(run.publish(net.adjs(a)), True)
        # overload set / clear: a far node's drain bit moves its own routes
        n = rng.choice(net.far)
        net.overloaded.add(n)
        run.step(run.publish(net.adjs(n)), True)
        net.overloaded.discard(n)
        run.step(run.publish(net.adjs(n)), True)
        # a neighbour's drain bit reaches every next-hop test: full
        n = rng.choice(net.neighbours)
        net.overloaded.add(n)
        run.step(run.publish(net.adjs(n)), False)
        net.overloaded.discard(n)
        run.step(run.publish(net.adjs(n)), False)
        # a far node cut off and back: unreachable in the new SPF only
        n = rng.choice(net.far)
        cut = {frozenset((n, o)) for o in net.nbrs[n]}
        net.down |= cut
        run.step(run.publish(net.adjs(n, *net.nbrs[n])), True)
        assert net.prefix[n] not in d.route_db.unicast_routes
        net.down -= cut
        run.step(run.publish(net.adjs(n, *net.nbrs[n])), True)
        # prefix withdraw / add
        n = rng.choice(net.far)
        entry = PrefixEntry(prefix=net.prefix[n])
        run.step(run.publish(net.prefix_pub(n, entry, withdraw=True)), True)
        run.step(run.publish(net.prefix_pub(n, entry)), True)
        # an anycast prefix from two far nodes, then flaps
        for n in rng.sample(net.far, 2):
            run.step(run.publish(net.prefix_pub(n, PrefixEntry(prefix=ANYCAST))), True)
        _flap(run, rng)
        # a KSP2 prefix, then flaps: recomputed on every incremental build
        ksp2 = PrefixEntry(
            prefix=KSP2,
            forwarding_type=PrefixForwardingType.SR_MPLS,
            forwarding_algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP,
        )
        run.step(run.publish(net.prefix_pub(rng.choice(net.far), ksp2)), True)
        assert KSP2 in d.route_db.unicast_routes
        _flap(run, rng)
        # node label change: forced
        n = rng.choice(net.far)
        net.labels[n] += 1000
        run.step(run.publish(net.adjs(n)), False)
        _flap(run, rng)
        # a shared label: forced, and full until it is gone
        x, y = rng.sample(net.far, 2)
        saved, net.labels[x] = net.labels[x], net.labels[y]
        run.step(run.publish(net.adjs(x)), False)
        _flap(run, rng, engaged=False)
        net.labels[x] = saved
        run.step(run.publish(net.adjs(x)), False)
        _flap(run, rng)
        # node add (label 0, so only the node set tells) and remove
        new = "new-0"
        net.nodes.append(new)
        net.labels[new] = 0
        a, b = rng.sample(net.far, 2)
        net.link(new, a)
        net.link(new, b)
        run.step(run.publish(net.adjs(new, a, b)), False)
        expired = Publication(expired_keys=[adj_key(new)], area=AREA)
        net.drop(new)
        run.step(run.publish(expired), False)
        # static unicast and MPLS routes: forced
        z = rng.choice(net.far)
        static = DecisionRouteUpdate(
            unicast_routes_to_update={
                p: RibUnicastEntry(
                    prefix=p, nexthops=frozenset({NextHop(address="fe80::dead")})
                )
                for p in (STATIC, net.prefix[z])
            },
            mpls_routes_to_update=[
                RibMplsEntry(label=60000, nexthops=frozenset({NextHop(address="fe80::beef")}))
            ],
        )
        run.step(lambda: run.on_thread(lambda: d.process_static_routes_update(static)), False)
        assert d.route_db.unicast_routes[STATIC].nexthops == frozenset(
            {NextHop(address="fe80::dead")}
        )
        # the computed route shadows the static one until it is withdrawn
        run.step(
            run.publish(net.prefix_pub(z, PrefixEntry(prefix=net.prefix[z]), withdraw=True)),
            True,
        )
        assert d.route_db.unicast_routes[net.prefix[z]].nexthops == frozenset(
            {NextHop(address="fe80::dead")}
        )
        _flap(run, rng)
        # RIB policy set: forced; later flaps reweight the dirty routes
        policy = RibPolicyConfig(
            statements=[
                RibPolicyStatementConfig(
                    name="t",
                    prefixes=[net.prefix[n] for n in net.far],
                    set_weight=RibRouteActionWeight(
                        default_weight=1, neighbor_to_weight={net.neighbours[0]: 7}
                    ),
                )
            ],
            ttl_secs=3600,
        )
        run.step(lambda: d.set_rib_policy(policy), False)
        for _ in range(2):
            _flap(run, rng)
        # the daemon's own adjacency database: forced
        nb = net.neighbours[0]
        net.metric[(net.me, nb)] = 2
        run.step(run.publish(net.adjs(net.me)), False)
        net.metric[(net.me, nb)] = 1
        run.step(run.publish(net.adjs(net.me)), False)
        _flap(run, rng)
        assert d.counters["decision.dirty_nodes"] > 0
    finally:
        run.stop()


def test_failed_incremental_rebuild_falls_back_to_full():
    net = grid(4)
    run = Run(net, HostSpfBackend())
    d = run.decision

    def fail_once(*args):
        del d.spf_solver.build_dirty_routes
        raise RuntimeError("injected")

    try:
        run.step(run.publish(net.boot()), False)
        d.spf_solver.build_dirty_routes = fail_once
        a, b = net.remote_links[0]
        net.down.add(frozenset((a, b)))
        run.step(run.publish(net.adjs(a, b)), False)
        assert d.counters["decision.route_rebuild_fallbacks"] == 1
        net.down.discard(frozenset((a, b)))
        run.step(run.publish(net.adjs(a, b)), True)
    finally:
        run.stop()


def test_counters_are_pre_seeded():
    run = Run(grid(2), HostSpfBackend())
    try:
        counters = run.decision.get_counters()
        for key in DECISION_COUNTER_KEYS:
            assert counters[key] == 0
    finally:
        run.stop()


def test_prefix_state_indexes_advertisers_and_ksp2():
    ps = PrefixState()
    ksp2 = PrefixEntry(
        prefix="fd01::/64",
        forwarding_type=PrefixForwardingType.SR_MPLS,
        forwarding_algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP,
    )
    assert ps.update_prefix("a", "0", PrefixEntry(prefix="fc00:1::/64"))
    assert ps.update_prefix("b", "0", PrefixEntry(prefix="fd01::/64"))
    assert ps.update_prefix("a", "0", ksp2)
    assert ps.prefixes_of("a", "0") == {"fc00:1::/64", "fd01::/64"}
    assert ps.ksp2_prefixes == {"fd01::/64"}
    assert ps.delete_prefix("a", "0", "fd01::/64") == {"fd01::/64"}
    assert ps.ksp2_prefixes == set()
    assert ps.prefixes_of("b", "0") == {"fd01::/64"}
    assert ps.delete_all_from_node("a", "0") == {"fc00:1::/64"}
    assert ps.prefixes_of("a", "0") == set()
    assert set(ps.prefixes) == {"fd01::/64"}


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_converge_cell_rebuilds_only_the_dirty_routes_at_a_tiny_size(monkeypatch):
    """fabric10k.converge rehearsed on a 2-pod fabric: correct, every
    rebuild in the window incremental, and a remote flap recomputes a
    few routes where a full build computes one per switch."""
    monkeypatch.syspath_prepend(ROOT)
    from perf import run as perf_run
    from perf.drivers import link_events

    phase = ["setup"]
    full = {"setup": 0, "window": 0}
    real_compute = Decision._compute_route_update
    real_window = link_events.Driver.window

    def compute(self):
        n0 = self.counters["decision.incremental_rebuilds"]
        update = real_compute(self)
        full[phase[0]] += self.counters["decision.incremental_rebuilds"] == n0
        return update

    def window(self, seconds):
        phase[0] = "window"
        return real_window(self, seconds)

    monkeypatch.setattr(Decision, "_compute_route_update", compute)
    monkeypatch.setattr(link_events.Driver, "window", window)
    result, _ = perf_run.run_cell(
        perf_run.load_manifest(ROOT),
        "fabric10k.converge",
        2**31 + 77,
        1.0,
        True,
        ROOT,
        t_process=time.perf_counter(),
        config_file=os.path.join(ROOT, "tests", "perf", "data", "fabric_tiny.json"),
    )
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0
    assert full["setup"] >= 1  # the cold build
    assert full["window"] == 0
    assert result["metrics"]["routes_per_build.converge"]["value"] <= 5
