"""Three-tier fat-tree fabric (Facebook's F4 data-center fabric, whose
ssw/fsw/rsw tiers the reference's createFabric,
openr/decision/tests/RoutingBenchmarkUtils.h:320, names), as
openr_tpu/utils/topo.py `fabric_topology` builds it: each pod has one
fabric switch (fsw) per plane, fsw p of a pod uplinks to every spine
(ssw) of plane p and downlinks to every rack switch (rsw) of its pod."""

from __future__ import annotations


def links(pods: int, planes: int, ssw_per_plane: int, rsw_per_pod: int) -> tuple:
    """(node names, undirected links as (a, b)) in generation order."""
    nodes: list[str] = []
    out: list[tuple[str, str]] = []
    for plane in range(planes):
        for s in range(ssw_per_plane):
            nodes.append(f"ssw-{plane}-{s}")
    for pod in range(pods):
        for f in range(planes):
            fsw = f"fsw-{pod}-{f}"
            nodes.append(fsw)
            for s in range(ssw_per_plane):
                out.append((fsw, f"ssw-{f}-{s}"))
            for r in range(rsw_per_pod):
                if f == 0:
                    nodes.append(f"rsw-{pod}-{r}")
                out.append((fsw, f"rsw-{pod}-{r}"))
    return nodes, out
