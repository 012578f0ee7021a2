"""Closed-loop link events on a KSP2_ED_ECMP / SR_MPLS deployment.

The traffic of `link_events` (a remote link drawn uniformly from the
seed goes down, the next event brings it up, one event at a time, each
ended by the daemon's Fib programming the update that folds it), with
every node's prefix advertised as the configuration's
`prefix_forwarding` says: every event then rebuilds every route, each
over its edge-disjoint first and second shortest paths with a label
stack per next hop.

Set-up, before `link_events`' own warm-up: every node's prefix database
is republished through the peer store at version 2 with the
configuration's forwarding algorithm and type (the advertisement a
PrefixManager would send), and the set-up waits until the FIB holds
every other node's prefix with MPLS PUSH next hops and Fib stays quiet.
The warm-up's `queryPaths` sources are drawn from the nodes other than
the daemon's, so that all of them reach the SPF backend.  The window's
routes are compared with `perf.reference_ksp2`.
"""

from __future__ import annotations

import time

from .. import reference_ksp2
from ..harness import BOOT_TIMEOUT_S, _value
from . import link_events


class Driver(link_events.Driver):
    def _advertise(self) -> None:
        from openr_tpu.types import (
            PrefixDatabase,
            PrefixEntry,
            PrefixForwardingAlgorithm,
            PrefixForwardingType,
            prefix_key,
        )

        fwd = self.h.cfg["prefix_forwarding"]
        algorithm = PrefixForwardingAlgorithm[fwd["algorithm"]]
        ftype = PrefixForwardingType[fwd["type"]]
        kv = {}
        for node in self.topo.nodes:
            for p in self.topo.prefixes[node]:
                entry = PrefixEntry(
                    prefix=p, forwarding_algorithm=algorithm, forwarding_type=ftype
                )
                pdb = PrefixDatabase(this_node_name=node, prefix_entries=[entry])
                kv[prefix_key(node, p, self.topo.area)] = _value(2, node, pdb)
        self.h.publish(kv)

    def _labelled(self) -> bool:
        """The FIB holds every other node's prefix, each route with a
        label-stack next hop."""
        h = self.h
        table = h.fib_table()
        want = sum(len(self.topo.prefixes[n]) for n in self.topo.nodes if n != h.node)
        return len(table) == want and all(
            any(nh.mpls_action is not None for nh in r.next_hops) for r in table.values()
        )

    def warm(self) -> None:
        h = self.h
        self._advertise()
        deadline = time.perf_counter() + BOOT_TIMEOUT_S
        while not self._labelled():
            if time.perf_counter() > deadline:
                raise RuntimeError("the FIB never held the advertised label-stack routes")
            time.sleep(0.05)
        # settled: no route update for a whole debounce ceiling, twice
        quiet = 0
        while quiet < 2:
            time.sleep(h.cfg["decision"]["debounce_max_ms"] / 1000.0 + 0.05)
            quiet = quiet + 1 if h.drain_fib_stream() == 0 else 0
        n = self.traffic["engine_warm_sources"]
        others = [x for x in self.topo.nodes if x != h.node]
        sources = others[:: max(1, len(others) // n)][:n]
        h.ctrl.call("queryPaths", sources=sources, area=self.topo.area)
        for link in link_events.warm_links(self.topo, self.links):
            for down in (True, False):
                if self._event(link, down)["t1"] is None:
                    raise RuntimeError(f"warm-up link event did not converge: {link}")
        h.drain_fib_stream()

    def compare(self) -> dict:
        """routes_wrong: the most prefixes, over the compared events,
        whose FIB route breaks one of the reference's properties (a)-(d);
        events_unconverged: events whose route update never reached the
        FIB."""
        check = reference_ksp2.Checker(reference_ksp2.Graph(self.topo), self.h.node)
        worst = 0
        for e in self._compared():
            down = [e["down_after"]] if e["down_after"] else []
            worst = max(worst, check.n_wrong(e["fib"], down))
        return {"routes_wrong": worst, "events_unconverged": self.failed}

    def put_control(self) -> None:
        """The control in the program's place: each event's FIB holds
        the reference's routes of the state before the event (the
        freshness guarantee broken)."""
        graph = reference_ksp2.Graph(self.topo)
        for e in self.events:
            if e["t1"] is not None:
                before = [] if e["down"] else [e["link"]]
                e["fib"] = reference_ksp2.routes(graph, self.h.node, before)
