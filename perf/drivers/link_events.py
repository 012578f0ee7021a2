"""Closed-loop link events through the router's path.

One event at a time: a link drawn uniformly from the seed goes down
(both ends publish their adjacency databases at version+1 through the
peer store, which floods them to the daemon), and the next event brings
it back up.  An event ends when the daemon's Fib has programmed the
route update that folds it: the next item on the Fib's update stream
(`fib_updates_queue`), which Fib pushes for every update it programs,
routes changed or not.  The FIB is copied at each event's end and
compared after the window with the reference's routes for that state.

Set-up warms what a window's flap can compile.  The residency engine
applies a flap as masked writes padded to a power-of-two count of
entries, and as row writes into the ELL bucket of each end's in-degree.
A flap of link (a, b) re-ranks every neighbour of a named after b and
every neighbour of b named after a, plus the two slots themselves
(`rewire_key`).  The set-up flaps, down and up, the first remote link of
each (bucket, end degrees) key, the same links every run.

Traffic parameters (perf/traffic/<mix>.json): `engine_warm_sources`, the
sources of the one ctrl queryPaths of the set-up, which makes the
residency engine hold the graph as it does in a daemon that answers
queries; `event_timeout_s`; `compare_events`, how many events (drawn
from the seed) are compared; `limits`.
"""

from __future__ import annotations

import bisect
import random
import time
from collections import namedtuple

from .. import reference
from ..harness import canonical_fib

# the reference's routes in the FIB agent's form (for the control)
_Route = namedtuple("_Route", "next_hops")
_NextHop = namedtuple("_NextHop", "neighbor_node_name if_name address metric")


def rewire_key(topo, a: str, b: str, later) -> tuple:
    """(power-of-two bucket of the entries a flap of (a, b) rewrites,
    the two ends' degrees).  `later[n]` is n's sorted neighbour names."""
    moved = 2
    for u, v in ((a, b), (b, a)):
        names = later[u]
        moved += len(names) - bisect.bisect_right(names, v)
    bucket = 8
    while bucket < moved:
        bucket *= 2
    return bucket, tuple(sorted((len(topo.adj[a]), len(topo.adj[b]))))


def warm_links(topo, links) -> list:
    """The first link of `links` for each rewire key."""
    later = {n: sorted(x.other for x in adj) for n, adj in topo.adj.items()}
    seen: dict = {}
    for a, b in links:
        seen.setdefault(rewire_key(topo, a, b, later), (a, b))
    return list(seen.values())


class Driver:
    def __init__(self, h, topo, cfg, traffic, seed: int, root: str) -> None:
        self.h = h
        self.topo = topo
        self.traffic = traffic
        self.seed = seed
        node = cfg["daemon_node"]
        # remote links only: the daemon's own links change through its
        # LinkMonitor, not through the KvStore
        self.links = [(a, b) for a, b, _, _ in topo.links if node not in (a, b)]
        self.events: list[dict] = []
        self.down = None  # the link that is down now, if any
        self.t_end = 0.0

    # -- events ------------------------------------------------------------------

    def _next(self, rng: random.Random):
        if self.down is None:
            return self.links[rng.randrange(len(self.links))], True
        return self.down, False

    def _event(self, link, down: bool) -> dict:
        h = self.h
        downset = frozenset({frozenset(link)}) if down else frozenset()
        kv = h.link_event_key_vals(link[0], link[1], downset)
        rec = {"link": link, "down": down, "t0": time.perf_counter()}
        h.publish(kv)
        try:
            h.fib_stream.get(timeout=self.traffic["event_timeout_s"])
        except TimeoutError:
            rec["t1"] = None
            return rec
        rec["t1"] = time.perf_counter()
        self.down = link if down else None
        rec["fib"] = h.fib_table()
        rec["down_after"] = self.down
        return rec

    def warm(self) -> None:
        nodes = self.topo.nodes
        step = max(1, len(nodes) // self.traffic["engine_warm_sources"])
        sources = nodes[::step][: self.traffic["engine_warm_sources"]]
        self.h.ctrl.call("queryPaths", sources=sources, area=self.topo.area)
        for link in warm_links(self.topo, self.links):
            for down in (True, False):
                if self._event(link, down)["t1"] is None:
                    raise RuntimeError(f"warm-up link event did not converge: {link}")
        self.h.drain_fib_stream()

    def window(self, seconds: float) -> None:
        rng = random.Random(self.seed)
        t_start = time.perf_counter()
        self.t_end = t_start + seconds
        while time.perf_counter() < self.t_end:
            rec = self._event(*self._next(rng))
            self.events.append(rec)
            if rec["t1"] is None:
                break

    # -- results -----------------------------------------------------------------

    def _in_window(self) -> list[dict]:
        return [e for e in self.events if e["t1"] is not None and e["t1"] <= self.t_end]

    @property
    def attempted(self) -> int:
        return len(self.events)

    @property
    def failed(self) -> int:
        return sum(1 for e in self.events if e["t1"] is None)

    def metrics(self, seconds: float) -> dict:
        done = self._in_window()
        if not done:
            return {}
        return {"converge_ms": 1e3 * sum(e["t1"] - e["t0"] for e in done) / len(done)}

    def samples(self) -> dict:
        return {
            "events": len(self.events),
            "events_in_window": len(self._in_window()),
            "down_events": sum(1 for e in self.events if e["down"]),
        }

    def host_intervals(self) -> list:
        return [
            ("event", int(e["t0"] * 1e9), int(e["t1"] * 1e9))
            for e in self.events
            if e["t1"] is not None
        ]

    def _compared(self) -> list[dict]:
        done = [e for e in self.events if e["t1"] is not None]
        k = self.traffic["compare_events"]
        if len(done) <= k:
            return done
        return random.Random(self.seed + 1).sample(done, k)

    def compare(self) -> dict:
        """routes_wrong: the most prefixes, over the compared events,
        whose FIB entry differs from the reference's (missing, extra or
        other next hops or metric); events_unconverged: events whose
        route update never reached the FIB."""
        graph = reference.Graph(self.topo)
        node = self.h.node
        worst = 0
        for e in self._compared():
            want = reference.routes(graph, node, [e["down_after"]] if e["down_after"] else [])
            got = canonical_fib(e["fib"])
            worst = max(worst, _n_wrong(got, want))
        return {"routes_wrong": worst, "events_unconverged": self.failed}

    def put_control(self) -> None:
        """The control in the program's place: each event's FIB holds
        the reference's routes of the state before the event (the
        freshness guarantee broken)."""
        graph = reference.Graph(self.topo)
        for e in self.events:
            if e["t1"] is None:
                continue
            before = reference.routes(graph, self.h.node, [] if e["down"] else [e["link"]])
            e["fib"] = {
                p: _Route([_NextHop(*nh) for nh in sorted(hops)]) for p, hops in before.items()
            }


def _n_wrong(got: dict, want: dict) -> int:
    return sum(1 for p in set(got) | set(want) if got.get(p) != want.get(p))
