"""n x n grid (reference: createGrid,
openr/decision/tests/RoutingBenchmarkUtils.h), as openr_tpu/utils/topo.py
`grid_topology` builds it: node-r-c linked to its right and lower
neighbours."""

from __future__ import annotations


def links(n_side: int) -> tuple:
    """(node names, undirected links as (a, b)) in generation order."""
    nodes: list[str] = []
    out: list[tuple[str, str]] = []
    for r in range(n_side):
        for c in range(n_side):
            nodes.append(f"node-{r}-{c}")
            if c + 1 < n_side:
                out.append((f"node-{r}-{c}", f"node-{r}-{c + 1}"))
            if r + 1 < n_side:
                out.append((f"node-{r}-{c}", f"node-{r + 1}-{c}"))
    return nodes, out
