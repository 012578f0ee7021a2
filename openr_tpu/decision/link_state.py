"""Link-state graph: the host-side topology model.

Functional equivalent of the reference's LinkState
(openr/decision/LinkState.{h,cpp}) with identical semantics:

- only bidirectional links exist (both ends advertise the adjacency with
  matching interface names — maybeMakeLink, LinkState.cpp:703)
- HoldableValue-based ordered-FIB holds (RFC 6976 style) on link metrics,
  link overloads and node overloads (LinkState.cpp:53-120)
- updateAdjacencyDatabase computes a precise topology/attribute diff via
  ordered link-set merge (LinkState.cpp:565-717)
- SPF keeps ECMP ties: the relax step uses >= so equal-cost predecessors and
  first-hop sets accumulate (runSpf, LinkState.cpp:809-878)
- k-edge-disjoint paths via repeated SPF with link exclusion
  (getKthPaths/traceOnePath, LinkState.cpp:763-793,399-418)
- SPF and k-path results are memoized until the topology changes

The per-source Dijkstra here is the *conformance oracle* and the
small-topology fast path; bulk computation (all sources at once) runs on TPU
through openr_tpu.ops (see openr_tpu.decision.csr for the tensor mirror),
which must produce bit-identical distances / first-hop sets.
"""

from __future__ import annotations

import heapq
from dataclasses import dataclass, field
from typing import Generic, Iterable, Optional, TypeVar

from ..obs import trace as _trace
from ..types import Adjacency, AdjacencyDatabase

INF = float("inf")

T = TypeVar("T")


class HoldableValue(Generic[T]):
    """Reference: openr/decision/LinkState.cpp:53-120.

    updateValue() holds the previous value for `ttl` decrements (hold-up ttl
    when the change improves reachability, hold-down otherwise); an update
    while a hold is active cancels the hold (fast fallback)."""

    __slots__ = ("_val", "_held_val", "_hold_ttl", "_is_bringing_up")

    _NO_HOLD = object()  # sentinel: held value may legitimately be False/0

    def __init__(self, val: T, is_bringing_up=None) -> None:
        self._val = val
        self._held_val = HoldableValue._NO_HOLD
        self._hold_ttl = 0
        # (old, new) -> bool: does this change "bring up" (improve) things?
        if is_bringing_up is None:
            # bool specialization: True->False is bringing up (un-overloading)
            # metric specialization: lower metric is bringing up
            def is_bringing_up(old, new):
                if isinstance(old, bool):
                    return old and not new
                return new < old

        self._is_bringing_up = is_bringing_up

    def set(self, val: T) -> None:
        """Unconditional assignment (operator=): clears any hold."""
        self._val = val
        self._held_val = HoldableValue._NO_HOLD
        self._hold_ttl = 0

    @property
    def value(self) -> T:
        return self._val if self._held_val is HoldableValue._NO_HOLD else self._held_val

    def has_hold(self) -> bool:
        return self._held_val is not HoldableValue._NO_HOLD

    def decrement_ttl(self) -> bool:
        if self.has_hold():
            self._hold_ttl -= 1
            if self._hold_ttl == 0:
                self._held_val = HoldableValue._NO_HOLD
                return True
        return False

    def update_value(self, val: T, hold_up_ttl: int, hold_down_ttl: int) -> bool:
        """Returns True iff the *visible* value changed."""
        if val != self._val:
            if self.has_hold():
                # fall back to fast update to avoid longer transient loops
                self._held_val = HoldableValue._NO_HOLD
                self._hold_ttl = 0
            else:
                ttl = (
                    hold_up_ttl
                    if self._is_bringing_up(self._val, val)
                    else hold_down_ttl
                )
                if ttl != 0:
                    self._held_val = self._val
                    self._hold_ttl = ttl
            self._val = val
            return not self.has_hold()
        return False


class Link:
    """A single bidirectional network link (reference: openr/decision/
    LinkState.h:82-175).  One object shared by both endpoint nodes; keyed by
    the unordered pair of (node, iface) ordered pairs."""

    __slots__ = (
        "area",
        "n1",
        "n2",
        "if1",
        "if2",
        "_metric1",
        "_metric2",
        "_overload1",
        "_overload2",
        "adj_label1",
        "adj_label2",
        "nh_v4_1",
        "nh_v4_2",
        "nh_v6_1",
        "nh_v6_2",
        "weight1",
        "weight2",
        "_hold_up_ttl",
        "ordered_names",
        "_hash",
    )

    def __init__(
        self,
        area: str,
        node1: str,
        adj1: Adjacency,
        node2: str,
        adj2: Adjacency,
        metric_inc1: int = 0,
        metric_inc2: int = 0,
    ) -> None:
        self.area = area
        self.n1 = node1
        self.n2 = node2
        self.if1 = adj1.if_name
        self.if2 = adj2.if_name
        # soft-drain: each endpoint's nodeMetricIncrementVal is folded into
        # the metric it originates, so every consumer of metric_from_node()
        # (host Dijkstra and the CSR device mirror alike) sees the drained
        # cost without a separate lookup
        self._metric1 = HoldableValue(adj1.metric + metric_inc1)
        self._metric2 = HoldableValue(adj2.metric + metric_inc2)
        self._overload1 = HoldableValue(adj1.is_overloaded)
        self._overload2 = HoldableValue(adj2.is_overloaded)
        self.adj_label1 = adj1.adj_label
        self.adj_label2 = adj2.adj_label
        self.nh_v4_1 = adj1.next_hop_v4
        self.nh_v4_2 = adj2.next_hop_v4
        self.nh_v6_1 = adj1.next_hop_v6
        self.nh_v6_2 = adj2.next_hop_v6
        # UCMP adjacency weights (SP_UCMP_ADJ_WEIGHT_PROPAGATION);
        # captured at link construction like the label/next-hop fields
        self.weight1 = adj1.weight
        self.weight2 = adj2.weight
        self._hold_up_ttl = 0
        a, b = (self.n1, self.if1), (self.n2, self.if2)
        self.ordered_names = (a, b) if a <= b else (b, a)
        self._hash = hash(self.ordered_names)

    # -- identity -----------------------------------------------------------

    def __hash__(self) -> int:
        return self._hash

    def __eq__(self, other) -> bool:
        return isinstance(other, Link) and self.ordered_names == other.ordered_names

    def __lt__(self, other: "Link") -> bool:
        return self.ordered_names < other.ordered_names

    def __repr__(self) -> str:
        return f"Link({self.area} - {self.n1}%{self.if1} <---> {self.n2}%{self.if2})"

    # -- endpoint-keyed accessors ------------------------------------------

    def _side(self, node: str) -> int:
        if node == self.n1:
            return 1
        if node == self.n2:
            return 2
        raise ValueError(f"{node} not an endpoint of {self!r}")

    def other_node_name(self, node: str) -> str:
        return self.n2 if self._side(node) == 1 else self.n1

    def first_node_name(self) -> str:
        return self.ordered_names[0][0]

    def second_node_name(self) -> str:
        return self.ordered_names[1][0]

    def iface_from_node(self, node: str) -> str:
        return self.if1 if self._side(node) == 1 else self.if2

    def weight_from_node(self, node: str) -> int:
        return self.weight1 if self._side(node) == 1 else self.weight2

    def metric_from_node(self, node: str) -> int:
        return (self._metric1 if self._side(node) == 1 else self._metric2).value

    def overload_from_node(self, node: str) -> bool:
        return (self._overload1 if self._side(node) == 1 else self._overload2).value

    def adj_label_from_node(self, node: str) -> int:
        return self.adj_label1 if self._side(node) == 1 else self.adj_label2

    def set_adj_label_from_node(self, node: str, label: int) -> None:
        if self._side(node) == 1:
            self.adj_label1 = label
        else:
            self.adj_label2 = label

    def nh_v4_from_node(self, node: str) -> str:
        return self.nh_v4_1 if self._side(node) == 1 else self.nh_v4_2

    def nh_v6_from_node(self, node: str) -> str:
        return self.nh_v6_1 if self._side(node) == 1 else self.nh_v6_2

    def set_nh_v4_from_node(self, node: str, nh: str) -> None:
        if self._side(node) == 1:
            self.nh_v4_1 = nh
        else:
            self.nh_v4_2 = nh

    def set_nh_v6_from_node(self, node: str, nh: str) -> None:
        if self._side(node) == 1:
            self.nh_v6_1 = nh
        else:
            self.nh_v6_2 = nh

    def set_metric_from_node(
        self, node: str, metric: int, hold_up_ttl: int, hold_down_ttl: int
    ) -> bool:
        hv = self._metric1 if self._side(node) == 1 else self._metric2
        return hv.update_value(metric, hold_up_ttl, hold_down_ttl)

    def set_overload_from_node(
        self, node: str, overload: bool, hold_up_ttl: int, hold_down_ttl: int
    ) -> bool:
        was_up = self.is_up()
        hv = self._overload1 if self._side(node) == 1 else self._overload2
        hv.update_value(overload, hold_up_ttl, hold_down_ttl)
        # simplex overloads unsupported: only report topo change on up<->down
        return was_up != self.is_up()

    # -- holds --------------------------------------------------------------

    def set_hold_up_ttl(self, ttl: int) -> None:
        self._hold_up_ttl = ttl

    def is_up(self) -> bool:
        return (
            self._hold_up_ttl == 0
            and not self._overload1.value
            and not self._overload2.value
        )

    def decrement_holds(self) -> bool:
        expired = False
        if self._hold_up_ttl != 0:
            self._hold_up_ttl -= 1
            expired |= self._hold_up_ttl == 0
        expired |= self._metric1.decrement_ttl()
        expired |= self._metric2.decrement_ttl()
        expired |= self._overload1.decrement_ttl()
        expired |= self._overload2.decrement_ttl()
        return expired

    def has_holds(self) -> bool:
        return (
            self._hold_up_ttl != 0
            or self._metric1.has_hold()
            or self._metric2.has_hold()
            or self._overload1.has_hold()
            or self._overload2.has_hold()
        )


@dataclass(slots=True)
class LinkStateChange:
    """Reference: LinkState::LinkStateChange (LinkState.h:306)."""

    topology_changed: bool = False
    link_attributes_changed: bool = False
    node_label_changed: bool = False

    def __or__(self, other: "LinkStateChange") -> "LinkStateChange":
        return LinkStateChange(
            self.topology_changed or other.topology_changed,
            self.link_attributes_changed or other.link_attributes_changed,
            self.node_label_changed or other.node_label_changed,
        )


@dataclass(slots=True)
class NodeSpfResult:
    """Reference: LinkState::NodeSpfResult (LinkState.h:210-260).

    path_links: (link, prev_node) pairs — SP-DAG in-edges toward this node.
    next_hops: first-hop neighbor node names of shortest paths from source.
    """

    metric: float
    path_links: list[tuple[Link, str]] = field(default_factory=list)
    next_hops: set[str] = field(default_factory=set)


SpfResult = dict[str, NodeSpfResult]
Path = list[Link]


def trace_one_path(
    src: str,
    dest: str,
    result: SpfResult,
    links_to_ignore: set[Link],
) -> Optional[Path]:
    """Extract one not-yet-visited shortest path from an SpfResult's
    path_links DAG, consuming its links (reference: LinkState::traceOnePath,
    LinkState.cpp:399-418).  Works on any SpfResult — host Dijkstra or
    device-kernel reconstruction."""
    if src == dest:
        return []
    for link, prev_node in result[dest].path_links:
        if link in links_to_ignore:
            continue
        links_to_ignore.add(link)
        path = trace_one_path(src, prev_node, result, links_to_ignore)
        if path is not None:
            path.append(link)
            return path
    return None


def path_a_in_path_b(a: Path, b: Path) -> bool:
    """True if path A appears contiguously inside path B
    (reference: LinkState::pathAInPathB, LinkState.h:396)."""
    if len(a) > len(b):
        return False
    for i in range(len(b) - len(a) + 1):
        if all(a[j] == b[i + j] for j in range(len(a))):
            return True
    return False


class LinkState:
    """Host-side link-state graph for one area."""

    def __init__(self, area: str = "0") -> None:
        self.area = area
        self._link_map: dict[str, set[Link]] = {}
        self._all_links: set[Link] = set()
        self._node_overloads: dict[str, HoldableValue] = {}
        self._adjacency_databases: dict[str, AdjacencyDatabase] = {}
        self._spf_results: dict[tuple[str, bool], SpfResult] = {}
        self._kth_path_results: dict[tuple[str, str, int], list[Path]] = {}
        # device mirror invalidation hook (set by csr.CsrTopology)
        self._version = 0

    # -- read API -----------------------------------------------------------

    def has_node(self, node: str) -> bool:
        return node in self._adjacency_databases

    def links_from_node(self, node: str) -> set[Link]:
        return self._link_map.get(node, set())

    def ordered_links_from_node(self, node: str) -> list[Link]:
        return sorted(self._link_map.get(node, set()))

    def is_node_overloaded(self, node: str) -> bool:
        hv = self._node_overloads.get(node)
        return hv is not None and hv.value

    def overloaded_nodes(self) -> frozenset[str]:
        return frozenset(n for n, hv in self._node_overloads.items() if hv.value)

    @property
    def all_links(self) -> set[Link]:
        return self._all_links

    def num_links(self) -> int:
        return len(self._all_links)

    def num_nodes(self) -> int:
        return len(self._link_map)

    def get_adjacency_databases(self) -> dict[str, AdjacencyDatabase]:
        return self._adjacency_databases

    @property
    def node_names(self) -> list[str]:
        return sorted(
            set(self._adjacency_databases.keys()) | set(self._link_map.keys())
        )

    @property
    def version(self) -> int:
        """Monotonic counter bumped on every topology change — used by the
        CSR device mirror to know when to refresh."""
        return self._version

    def has_holds(self) -> bool:
        return any(l.has_holds() for l in self._all_links) or any(
            hv.has_hold() for hv in self._node_overloads.values()
        )

    # -- graph mutation (reference: LinkState.cpp:421-447,565-737) ----------

    def _add_link(self, link: Link) -> None:
        self._link_map.setdefault(link.first_node_name(), set()).add(link)
        self._link_map.setdefault(link.second_node_name(), set()).add(link)
        self._all_links.add(link)

    def _remove_link(self, link: Link) -> None:
        self._link_map[link.first_node_name()].discard(link)
        self._link_map[link.second_node_name()].discard(link)
        self._all_links.discard(link)

    def _remove_node(self, node: str) -> None:
        links = self._link_map.pop(node, set())
        for link in links:
            other = link.other_node_name(node)
            self._link_map.get(other, set()).discard(link)
            self._all_links.discard(link)
        self._node_overloads.pop(node, None)

    def _update_node_overloaded(
        self, node: str, is_overloaded: bool, hold_up_ttl: int, hold_down_ttl: int
    ) -> bool:
        hv = self._node_overloads.get(node)
        if hv is not None:
            return hv.update_value(is_overloaded, hold_up_ttl, hold_down_ttl)
        self._node_overloads[node] = HoldableValue(is_overloaded)
        return False  # new node: not a link-state change

    def _maybe_make_link(self, node: str, adj: Adjacency) -> Optional[Link]:
        """Only bidirectional links: the far node must advertise the reverse
        adjacency with matching interface names
        (reference: maybeMakeLink, LinkState.cpp:703)."""
        other_db = self._adjacency_databases.get(adj.other_node_name)
        if other_db is None:
            return None
        for other_adj in other_db.adjacencies:
            if (
                node == other_adj.other_node_name
                and adj.other_if_name == other_adj.if_name
                and adj.if_name == other_adj.other_if_name
            ):
                return Link(
                    self.area,
                    node,
                    adj,
                    adj.other_node_name,
                    other_adj,
                    metric_inc1=self._metric_increment(node),
                    metric_inc2=self._metric_increment(adj.other_node_name),
                )
        return None

    def _metric_increment(self, node: str) -> int:
        """The node's current soft-drain increment (nodeMetricIncrementVal).
        Looked up from the stored database so both sides of a link get their
        own originator's value; update_adjacency_database stores the new db
        before rebuilding links, so a drain change flows through the ordinary
        metric diff (set_metric_from_node) and invalidates SPF memos."""
        db = self._adjacency_databases.get(node)
        return db.node_metric_increment_val if db is not None else 0

    def _get_ordered_link_set(self, adj_db: AdjacencyDatabase) -> list[Link]:
        links = []
        for adj in adj_db.adjacencies:
            link = self._maybe_make_link(adj_db.this_node_name, adj)
            if link is not None:
                links.append(link)
        links.sort()
        return links

    def _invalidate(self) -> None:
        self._spf_results.clear()
        self._kth_path_results.clear()
        self._version += 1

    def update_adjacency_database(
        self,
        new_adj_db: AdjacencyDatabase,
        hold_up_ttl: int = 0,
        hold_down_ttl: int = 0,
    ) -> LinkStateChange:
        """Reference: updateAdjacencyDatabase, LinkState.cpp:565-717."""
        change = LinkStateChange()
        node = new_adj_db.this_node_name
        assert new_adj_db.area == self.area, (new_adj_db.area, self.area)

        prior_db = self._adjacency_databases.get(node)
        self._adjacency_databases[node] = new_adj_db
        if prior_db is None:
            # node-set change: SPF memos stay valid (no links yet) but the
            # CSR device mirror must refresh its interning tables
            self._version += 1

        old_links = self.ordered_links_from_node(node)
        new_links = self._get_ordered_link_set(new_adj_db)

        change.topology_changed |= self._update_node_overloaded(
            node, new_adj_db.is_overloaded, hold_up_ttl, hold_down_ttl
        )
        prior_label = prior_db.node_label if prior_db is not None else 0
        change.node_label_changed = prior_label != new_adj_db.node_label

        i = j = 0
        while i < len(new_links) or j < len(old_links):
            if i < len(new_links) and (
                j >= len(old_links) or new_links[i] < old_links[j]
            ):
                # link came up: apply hold-up, add
                nl = new_links[i]
                nl.set_hold_up_ttl(hold_up_ttl)
                change.topology_changed |= nl.is_up()
                self._add_link(nl)
                i += 1
                continue
            if j < len(old_links) and (
                i >= len(new_links) or old_links[j] < new_links[i]
            ):
                ol = old_links[j]
                change.topology_changed |= ol.is_up()
                self._remove_link(ol)
                j += 1
                continue
            # same link: check attribute changes on the *existing* object
            nl, ol = new_links[i], old_links[j]
            if nl.metric_from_node(node) != ol.metric_from_node(node):
                change.topology_changed |= ol.set_metric_from_node(
                    node, nl.metric_from_node(node), hold_up_ttl, hold_down_ttl
                )
            if nl.overload_from_node(node) != ol.overload_from_node(node):
                change.topology_changed |= ol.set_overload_from_node(
                    node, nl.overload_from_node(node), hold_up_ttl, hold_down_ttl
                )
            if nl.adj_label_from_node(node) != ol.adj_label_from_node(node):
                change.link_attributes_changed = True
                ol.set_adj_label_from_node(node, nl.adj_label_from_node(node))
            if nl.nh_v4_from_node(node) != ol.nh_v4_from_node(node):
                change.link_attributes_changed = True
                ol.set_nh_v4_from_node(node, nl.nh_v4_from_node(node))
            if nl.nh_v6_from_node(node) != ol.nh_v6_from_node(node):
                change.link_attributes_changed = True
                ol.set_nh_v6_from_node(node, nl.nh_v6_from_node(node))
            i += 1
            j += 1

        if change.topology_changed:
            self._invalidate()
        return change

    def delete_adjacency_database(self, node: str) -> LinkStateChange:
        change = LinkStateChange()
        if node in self._adjacency_databases:
            self._remove_node(node)
            del self._adjacency_databases[node]
            self._invalidate()
            change.topology_changed = True
        return change

    def decrement_holds(self) -> LinkStateChange:
        change = LinkStateChange()
        for link in self._all_links:
            change.topology_changed |= link.decrement_holds()
        for hv in self._node_overloads.values():
            change.topology_changed |= hv.decrement_ttl()
        if change.topology_changed:
            self._invalidate()
        return change

    # -- SPF (reference: runSpf, LinkState.cpp:809-878) ---------------------

    def run_spf(
        self,
        src: str,
        use_link_metric: bool = True,
        links_to_ignore: Optional[set[Link]] = None,
    ) -> SpfResult:
        """Dijkstra with ECMP tie retention — the conformance oracle.

        Pop order is (metric, node_name); the relax step uses >= so all
        equal-cost predecessors/next-hops are kept.  Overloaded nodes other
        than the source are recorded but never relaxed from (drained)."""
        links_to_ignore = links_to_ignore or set()
        result: SpfResult = {}
        # heap entries: (metric, node_name); node state kept separately
        pending: dict[str, NodeSpfResult] = {src: NodeSpfResult(0)}
        heap: list[tuple[float, str]] = [(0, src)]
        while heap:
            metric, node = heapq.heappop(heap)
            state = pending.get(node)
            if state is None or node in result or metric > state.metric:
                continue  # stale heap entry
            result[node] = state
            del pending[node]
            if self.is_node_overloaded(node) and node != src:
                continue  # no transit through drained node
            for link in sorted(self.links_from_node(node)):
                other = link.other_node_name(node)
                if not link.is_up() or other in result or link in links_to_ignore:
                    continue
                m = link.metric_from_node(node) if use_link_metric else 1
                cand = metric + m
                other_state = pending.get(other)
                if other_state is None:
                    other_state = pending[other] = NodeSpfResult(cand)
                    heapq.heappush(heap, (cand, other))
                if other_state.metric >= cand:
                    if other_state.metric > cand:
                        other_state.metric = cand
                        other_state.path_links = []
                        other_state.next_hops = set()
                        heapq.heappush(heap, (cand, other))
                    other_state.path_links.append((link, node))
                    other_state.next_hops |= state.next_hops
                    if not other_state.next_hops:
                        other_state.next_hops.add(other)  # directly connected
        return result

    def get_spf_result(self, node: str, use_link_metric: bool = True) -> SpfResult:
        key = (node, use_link_metric)
        res = self._spf_results.get(key)
        if res is None:
            with _trace.maybe_child("decision.spf"):
                res = self.run_spf(node, use_link_metric)
            self._spf_results[key] = res
        return res

    def get_metric_from_a_to_b(
        self, a: str, b: str, use_link_metric: bool = True
    ) -> Optional[float]:
        if a == b:
            return 0
        res = self.get_spf_result(a, use_link_metric)
        return res[b].metric if b in res else None

    def get_hops_from_a_to_b(self, a: str, b: str) -> Optional[float]:
        return self.get_metric_from_a_to_b(a, b, use_link_metric=False)

    def get_max_hops_to_node(self, node: str) -> int:
        res = self.get_spf_result(node, use_link_metric=False)
        return max((int(r.metric) for r in res.values()), default=0)

    # -- k edge-disjoint paths (reference: LinkState.cpp:399-418,763-793) ---

    def _trace_one_path(
        self,
        src: str,
        dest: str,
        result: SpfResult,
        links_to_ignore: set[Link],
    ) -> Optional[Path]:
        return trace_one_path(src, dest, result, links_to_ignore)

    def get_kth_paths(self, src: str, dest: str, k: int) -> list[Path]:
        assert k >= 1
        key = (src, dest, k)
        cached = self._kth_path_results.get(key)
        if cached is not None:
            return cached
        links_to_ignore: set[Link] = set()
        for i in range(1, k):
            for path in self.get_kth_paths(src, dest, i):
                links_to_ignore.update(path)
        paths: list[Path] = []
        res = (
            self.get_spf_result(src, True)
            if not links_to_ignore
            else self.run_spf(src, True, links_to_ignore)
        )
        if dest in res:
            visited: set[Link] = set()
            path = self._trace_one_path(src, dest, res, visited)
            while path:
                paths.append(path)
                path = self._trace_one_path(src, dest, res, visited)
        self._kth_path_results[key] = paths
        return paths
