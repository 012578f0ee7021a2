"""Closed-loop what-if queries from clients in their own process.

`clients` clients (perf/clients.py) each send `method` (queryWhatIf)
with the run's `sources` (drawn from the seed once per run and shared by
every client, so the scheduler can coalesce them) and
`scenarios_per_query` shared-risk groups, each every link of one switch
drawn uniformly from the seed.  A query is timed at the client from send
to reply.  Set-up warms every batch shape the window can form: one query
of k * scenarios_per_query scenarios for each k up to `clients` (the
what-if program is not bucketed).  After the window a sample of
`compare_queries` answered queries, drawn from the seed, is compared
scenario by scenario with the reference: half of it, as far as there
are such, from queries answered in a batch of more than one, where a
fault between a batch's queries would show.
"""

from __future__ import annotations

import json
import random
import subprocess
import sys
import time

import numpy as np

from .. import reference


class Driver:
    def __init__(self, h, topo, cfg, traffic, seed: int, root: str) -> None:
        self.h = h
        self.topo = topo
        self.cfg = cfg
        self.traffic = traffic
        self.seed = seed
        self.root = root
        self.sources = random.Random(seed).sample(topo.nodes, traffic["sources"])
        self.proc = None
        self.result: dict = {}

    def _spec(self) -> dict:
        t = self.traffic
        return {
            "port": self.h.daemon.ctrl_port,
            "config": self.cfg["_path"],
            "seed": self.seed,
            "clients": t["clients"],
            "sources": self.sources,
            "scenarios_per_query": t["scenarios_per_query"],
            "method": t["method"],
            "area": self.topo.area,
        }

    def warm(self) -> None:
        t = self.traffic
        rng = random.Random(0)
        nodes = self.topo.nodes
        for k in range(1, t["clients"] + 1):
            switches = [
                nodes[rng.randrange(len(nodes))]
                for _ in range(k * t["scenarios_per_query"])
            ]
            self.h.ctrl.call(
                t["method"],
                area=self.topo.area,
                sources=self.sources,
                scenarios=[self.topo.srlg(s) for s in switches],
            )
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "perf.clients"],
            cwd=self.root,
            stdin=subprocess.PIPE,
            stdout=subprocess.PIPE,
            text=True,
        )
        self.proc.stdin.write(json.dumps(self._spec()) + "\n")
        self.proc.stdin.flush()
        if self.proc.stdout.readline().strip() != "ready":
            self.stop()
            raise RuntimeError("query clients did not start")

    def window(self, seconds: float) -> None:
        out, _ = self.proc.communicate(
            f"go {seconds}\n", timeout=seconds + self.traffic["reply_timeout_s"]
        )
        self.result = json.loads(out.strip().splitlines()[-1])

    def stop(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()

    # -- results -----------------------------------------------------------------

    def _queries(self) -> list[dict]:
        return self.result.get("queries", [])

    def _ok(self) -> list[dict]:
        return [q for q in self._queries() if "rows" in q]

    @property
    def attempted(self) -> int:
        return len(self._queries())

    @property
    def failed(self) -> int:
        return self.attempted - len(self._ok()) + len(self.result.get("errors", []))

    def metrics(self, seconds: float) -> dict:
        ok = self._ok()
        if not ok:
            return {}
        lat = np.asarray([q["t_recv"] - q["t_send"] for q in ok])
        done = sum(1 for q in ok if q["t_recv"] <= self.result["t_end"])
        return {
            "query_p95_ms": float(np.percentile(lat, 95) * 1e3),
            "query_rate": done / seconds,
        }

    def samples(self) -> dict:
        ok = self._ok()
        return {
            "queries": self.attempted,
            "answered": len(ok),
            "answered_in_window": sum(
                1 for q in ok if q["t_recv"] <= self.result["t_end"]
            ),
            "mean_batch": float(np.mean([q["batch"] for q in ok])) if ok else 0.0,
            "client_errors": self.result.get("errors", []),
        }

    def host_intervals(self) -> list:
        return [
            ("query", int(q["t_send"] * 1e9), int(q["t_recv"] * 1e9))
            for q in self._queries()
        ]

    def _compared(self) -> list[dict]:
        ok = self._ok()
        k = self.traffic["compare_queries"]
        rng = random.Random(self.seed + 1)
        multi = [q for q in ok if q["batch"] > 1]
        pick = rng.sample(multi, min(len(multi), k // 2))
        chosen = {id(q) for q in pick}
        rest = [q for q in ok if id(q) not in chosen]
        return pick + rng.sample(rest, min(len(rest), k - len(pick)))

    def _rows_wrong(self) -> int:
        graph = reference.Graph(self.topo)
        wrong = 0
        for q in self._compared():
            scenarios = [self.topo.srlg(s) for s in q["switches"]]
            want = reference.what_if(graph, self.sources, scenarios)
            got = [(r[0], r[1]) for r in q["rows"]]
            # every link of each scenario resolved, none unknown
            wrong += sum(
                1 for r, sc in zip(q["rows"], scenarios) if r[2] != len(sc) or r[3] != 0
            )
            wrong += abs(len(got) - len(want))
            wrong += sum(1 for g, w in zip(got, want) if g != w)
        return wrong

    def compare(self) -> dict:
        """rows_wrong: compared scenario rows whose newly-unreachable or
        degraded pair counts (or resolved links) differ from the
        reference; unanswered: clients whose query never came back."""
        return {
            "rows_wrong": self._rows_wrong(),
            "unanswered": len(self.result.get("errors", [])),
        }

    def put_control(self) -> None:
        """The control in the program's place: every answered query's
        rows hold the reference's counts with each shared-risk group
        failed in one direction only (the group-fails-whole guarantee
        broken), every link resolved.  Only the queries `compare` reads
        (the same draw) are filled."""
        graph = reference.Graph(self.topo)
        for q in self._compared():
            scenarios = [self.topo.srlg(s) for s in q["switches"]]
            counts = reference.what_if(graph, self.sources, scenarios, one_direction=True)
            q["rows"] = [[u, d, len(sc), 0] for (u, d), sc in zip(counts, scenarios)]
