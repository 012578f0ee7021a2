"""A benchmark deployment as plain data, built from its configuration file.

`perf/configs/<name>.json` names a generator under `perf/topologies/`
and its parameters, the link metric, the daemon's node, the prefixes
per node and the guarantees.  `Topology` is what both sides read: the
harness turns it into the program's KvStore values, and the reference
(`perf.reference`) computes from it directly.  Nothing here imports the
program.
"""

from __future__ import annotations

import importlib
import ipaddress
import json
import os
import zlib
from dataclasses import dataclass, field

PERF_DIR = os.path.dirname(os.path.abspath(__file__))


def load_config(name_or_path: str) -> dict:
    """A configuration by name (`perf/configs/<name>.json`) or by path."""
    path = name_or_path
    if not path.endswith(".json"):
        path = os.path.join(PERF_DIR, "configs", f"{name_or_path}.json")
    with open(path) as f:
        return json.load(f)


def next_hop_v6(me: str, other: str) -> str:
    """Deterministic link-local address of `other` as seen from `me`."""
    return f"fe80::{zlib.crc32(f'{me}|{other}'.encode()):x}"


@dataclass(frozen=True)
class Adj:
    other: str
    if_name: str
    other_if_name: str
    metric: int
    next_hop_v6: str


@dataclass
class Topology:
    """One area: nodes in sorted order, undirected links with a metric
    per direction, each node's adjacencies in generation order, one
    prefix per node."""

    area: str
    nodes: list[str]
    links: list[tuple[str, str, int, int]]  # (a, b, metric a->b, metric b->a)
    adj: dict[str, list[Adj]] = field(default_factory=dict)
    prefixes: dict[str, list[str]] = field(default_factory=dict)

    def __post_init__(self) -> None:
        self.index = {n: i for i, n in enumerate(self.nodes)}

    def srlg(self, node: str) -> list[list[str]]:
        """The shared-risk group of one switch: every link it has."""
        return [[node, a.other] for a in self.adj[node]]


def build(cfg: dict) -> Topology:
    """The configuration's topology (plain data, deterministic)."""
    gen = importlib.import_module(f"perf.topologies.{cfg['generator']}")
    names, pairs = gen.links(**cfg["params"])
    metric = int(cfg["link_metric"])
    adj: dict[str, list[Adj]] = {n: [] for n in names}
    links = []
    for a, b in pairs:
        links.append((a, b, metric, metric))
        adj[a].append(Adj(b, f"if_{a}_{b}", f"if_{b}_{a}", metric, next_hop_v6(a, b)))
        adj[b].append(Adj(a, f"if_{b}_{a}", f"if_{a}_{b}", metric, next_hop_v6(b, a)))
    nodes = sorted(adj)
    k = int(cfg["prefixes_per_node"])
    prefixes = {
        n: [str(ipaddress.ip_network(f"fc00:{i:x}:{j:x}::/64")) for j in range(k)]
        for i, n in enumerate(nodes)
    }
    return Topology(cfg["area"], nodes, links, adj, prefixes)
