"""Closed-loop query clients, in a process of their own.

`python -m perf.clients` reads one JSON line of parameters on stdin,
builds the deployment's topology (plain data) and its query streams from
the seed, connects one socket per client to the daemon's ctrl port and
prints `ready`.  On `go <seconds>` every client sends its next query as
soon as its last one is answered, until the window closes; the queries
in flight then are waited for.  One JSON line of per-query records goes
to stdout.  This process never imports JAX or the program, so clients
do not share the daemon's GIL.
"""

from __future__ import annotations

import json
import random
import sys
import threading
import time

from . import deployment, wire


def query_stream(topo, seed: int, client: int, per_query: int):
    """Endless scenarios of one client: `per_query` switches per query,
    each drawn uniformly, each failing every link it has."""
    rng = random.Random(seed * 1009 + client)
    nodes = topo.nodes
    while True:
        yield [nodes[rng.randrange(len(nodes))] for _ in range(per_query)]


def _client(spec, topo, conn, c, start, deadline, out, errors) -> None:
    stream = query_stream(topo, spec["seed"], c, spec["scenarios_per_query"])
    i = 0
    start.wait()
    try:
        while time.perf_counter() < deadline[0]:
            switches = next(stream)
            rec = {"client": c, "i": i, "switches": switches}
            rec["t_send"] = time.perf_counter()
            try:
                reply = conn.call(
                    spec["method"],
                    area=spec["area"],
                    sources=spec["sources"],
                    scenarios=[topo.srlg(s) for s in switches],
                )
                rec["t_recv"] = time.perf_counter()
                rec["batch"] = reply["batchSize"]
                rec["rows"] = [
                    [
                        r["newly_unreachable_pairs"],
                        r["degraded_pairs"],
                        len(r["links"]),
                        len(r["unknown_links"]),
                    ]
                    for r in reply["result"]
                ]
            except wire.WireError as e:  # shed or failed: an explicit error reply
                rec["t_recv"] = time.perf_counter()
                rec["error"] = str(e)[:200]
            out.append(rec)
            i += 1
    except Exception as e:  # a lost connection: reported, not hidden
        errors.append(f"client {c}: {type(e).__name__}: {e}")
    finally:
        conn.close()


def main() -> int:
    spec = json.loads(sys.stdin.readline())
    topo = deployment.build(deployment.load_config(spec["config"]))
    start = threading.Event()
    deadline = [float("inf")]
    out: list = []
    errors: list = []
    conns = [wire.Client(spec["port"]) for _ in range(spec["clients"])]
    threads = [
        threading.Thread(
            target=_client,
            args=(spec, topo, conn, c, start, deadline, out, errors),
        )
        for c, conn in enumerate(conns)
    ]
    for t in threads:
        t.start()
    print("ready", flush=True)
    go = sys.stdin.readline().split()
    t0 = time.perf_counter()
    deadline[0] = t0 + float(go[1])
    start.set()
    for t in threads:
        t.join()
    print(
        json.dumps(
            {"t_start": t0, "t_end": deadline[0], "queries": out, "errors": errors}
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
