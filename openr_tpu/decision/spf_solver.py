"""SpfSolver: per-prefix route construction over SPF results.

Functional equivalent of the reference's SpfSolver::SpfSolverImpl
(openr/decision/Decision.cpp:164-1395): reachability filtering, best-route
selection, drained-node filtering, SP_ECMP / KSP2_ED_ECMP forwarding
algorithms, MPLS node/adjacency label routes, min-nexthop thresholds, and
static route overlays.

The route-selection control flow is data-dependent (per-prefix algorithm
switches, label stacks) so it runs on host over SPF results; the SPF results
themselves come through a pluggable backend seam (`SpfBackend`) so bulk
distance/DAG computation can run batched on TPU (openr_tpu.ops.sssp via
openr_tpu.decision.csr) while small topologies use the host oracle —
mirroring the reference's plugin seam for drop-in solvers
(openr/plugin/Plugin.h:23).
"""

from __future__ import annotations

import ipaddress
import logging
import math
import weakref
from dataclasses import dataclass, replace
from typing import Optional, Protocol

import numpy as np

from ..obs import trace as _trace
from ..types import (
    MplsAction,
    MplsActionCode,
    MplsRoute,
    NextHop,
    PrefixEntry,
    PrefixForwardingAlgorithm,
    PrefixForwardingType,
    PrefixType,
    UnicastRoute,
    normalize_prefix,
)
from .csr import PAIR_INDEX_BUILDS
from .delta import DELTA_COUNTER_KEYS
from .fleet import (
    INF32 as FLEET_INF,
    FleetRouteView,
    FleetViewCache,
    fleet_destinations,
)
from .link_state import LinkState, Path, SpfResult
from .prefix_state import NodeAndArea, PrefixEntries, PrefixState
from .rib import DecisionRouteDb, RibMplsEntry, RibUnicastEntry

log = logging.getLogger(__name__)

# the batched KSP2 pre-pass (DeviceSpfBackend.prefetch_kth_paths):
# masked device rows run, k=1 plus k=2 paths traced, and the nodes the
# k=2 traces decoded from those rows; pre-seeded into SpfSolver.counters
# like the delta family
KSP2_COUNTER_KEYS = (
    "decision.ksp2_rows",
    "decision.ksp2_paths",
    "decision.ksp2_decoded_nodes",
)

MPLS_LABEL_MIN = 16
MPLS_LABEL_MAX = (1 << 20) - 1


def is_mpls_label_valid(label: int) -> bool:
    """Reference: isMplsLabelValid (openr/common/Util.h)."""
    return MPLS_LABEL_MIN <= label <= MPLS_LABEL_MAX


def select_best_prefix_metrics(entries: PrefixEntries) -> set[NodeAndArea]:
    """Reference: selectBestPrefixMetrics (openr/common/Util.h:434,493):
    ordered compare on (path_preference desc, source_preference desc,
    distance asc); ties all kept."""
    best: Optional[tuple[int, int, int]] = None
    best_keys: set[NodeAndArea] = set()
    for key, entry in entries.items():
        m = entry.metrics
        t = (m.path_preference, m.source_preference, -m.distance)
        if best is None or t > best:
            best = t
            best_keys = {key}
        elif t == best:
            best_keys.add(key)
    return best_keys


def select_best_node_area(
    all_node_areas: set[NodeAndArea], my_node_name: str
) -> NodeAndArea:
    """Deterministic representative: prefer self, else smallest key
    (reference: selectBestNodeArea, openr/common/Util.cpp:902)."""
    for node_area in sorted(all_node_areas):
        if node_area[0] == my_node_name:
            return node_area
    return min(all_node_areas)


class BestRouteSelectionResult:
    """Reference: BestRouteSelectionResult (openr/decision/Decision.h:96)."""

    __slots__ = ("success", "all_node_areas", "best_node_area")

    def __init__(self) -> None:
        self.success = False
        self.all_node_areas: set[NodeAndArea] = set()
        self.best_node_area: NodeAndArea = ("", "")

    def has_node(self, node: str) -> bool:
        return any(n == node for n, _ in self.all_node_areas)


@dataclass(slots=True)
class RouteInputs:
    """What the daemon's own route build reads of each area besides the
    prefixes, the node labels and the static routes: its SPF result, the
    drained nodes, the node set and its own links.  Two snapshots name
    the nodes whose routes can differ between two builds
    (`dirty_nodes`).  A label or static route change forces a full
    build, so `labels_exclusive` is computed there and carried over."""

    spf: dict[str, SpfResult]
    overloaded: dict[str, frozenset[str]]
    nodes: dict[str, frozenset[str]]
    # (neighbour, iface, metric, nh v4, nh v6, up, adj label, weight)
    own_links: dict[str, tuple]
    # no node label is shared with another node, one of the daemon's
    # adjacency labels or a static MPLS route: each label's route then
    # depends on its own node alone
    labels_exclusive: bool = False

    def dirty_nodes(self, new: "RouteInputs") -> Optional[set[str]]:
        """The nodes reachable in only one snapshot, or whose metric,
        first hops or drain bit differ.  None where a change can move
        every route: the node set, the daemon's own links or a
        neighbour's entry (`spf[nh].metric` enters every next-hop test)
        changed, or a node label is not exclusive."""
        if not (
            self.labels_exclusive
            and self.nodes == new.nodes
            and self.own_links == new.own_links
        ):
            return None
        dirty: set[str] = set()
        for area, spf in new.spf.items():
            dirty |= self.overloaded[area] ^ new.overloaded[area]
            old = self.spf[area]
            if old is spf:
                continue
            dirty.update(old.keys() - spf.keys())
            for node, res in spf.items():
                prev = old.get(node)
                if (
                    prev is None
                    or prev.metric != res.metric
                    or prev.next_hops != res.next_hops
                ):
                    dirty.add(node)
        for links in new.own_links.values():
            if any(link[0] in dirty for link in links):
                return None
        return dirty


class SpfBackend(Protocol):
    """Seam for SPF computation: host Dijkstra oracle or batched TPU kernel."""

    def get_spf_result(self, link_state: LinkState, src: str) -> SpfResult: ...

    def get_kth_paths(
        self, link_state: LinkState, src: str, dest: str, k: int
    ) -> list: ...


class HostSpfBackend:
    """Memoized host Dijkstra (the reference's exact behavior)."""

    def get_spf_result(self, link_state: LinkState, src: str) -> SpfResult:
        return link_state.get_spf_result(src)

    def get_kth_paths(
        self, link_state: LinkState, src: str, dest: str, k: int
    ) -> list:
        return link_state.get_kth_paths(src, dest, k)


class DeviceSpfBackend:
    """TPU SPF backend over a persistent CSR/ELL device mirror.

    Replaces the reference's per-source sequential Dijkstra memo
    (openr/decision/LinkState.h:279-282).  Per LinkState it keeps ONE
    mirror that refreshes incrementally on topology version bumps
    (attribute flaps touch only the runtime arrays; edge-set changes
    rebuild tables at stable shapes, so compiled kernels are reused —
    csr.refresh).  Queries are LAZY: the hot path asks only for the
    daemon's own node per area (getNextHopsWithMetric), so each uncached
    source costs one small device call (distances + SP-DAG + bit-packed
    first hops); batch consumers (what-if, KSP, ctrl any-node queries)
    go through `prefetch` to amortize one call over many sources.

    Dispatch policy: batched questions (what-if fleets, all-sources
    tiles, KSP destination sets) go to the device, single questions to
    the host Dijkstra memo unless the graph is already resident in the
    engine.  The constants below were fit to an earlier measurement
    setup that no longer exists; they await re-derivation from chip
    runs (ROADMAP S5).

    - below `min_device_nodes` (tiny topologies): always host.
    - batches of >= `min_device_sources`: device.
    - smaller batches: host, unless the engine already holds the graph
      (see `_device_worthwhile`) or the topology is at/above
      `force_device_nodes`.
    """

    def __init__(
        self,
        min_device_nodes: int = 64,
        min_device_sources: int = 32,
        force_device_nodes: int = 131072,
        engine=None,
    ) -> None:
        self.min_device_nodes = min_device_nodes
        self.min_device_sources = min_device_sources
        self.force_device_nodes = force_device_nodes
        # device-residency engine (openr_tpu.device): resident graph
        # mirrors + bucketed program cache.  All SPF dispatch goes through
        # it; csr.spf_from remains only as the engine-less fallback.
        if engine is None:
            from ..device import DeviceResidencyEngine

            engine = DeviceResidencyEngine()
        self.engine = engine
        # Keyed on the LinkState object itself (weakly) rather than id():
        # ids are recycled after GC, so an id-keyed cache could serve
        # another topology's results and leaks entries for dead
        # LinkStates.
        self._mirrors: "weakref.WeakKeyDictionary[LinkState, object]" = (
            weakref.WeakKeyDictionary()
        )
        self._results: "weakref.WeakKeyDictionary[LinkState, tuple[int, dict[str, SpfResult]]]" = (
            weakref.WeakKeyDictionary()
        )
        # (src, dest, k) -> list[Path], version-guarded like _results
        self._kth_results: "weakref.WeakKeyDictionary[LinkState, tuple[int, dict]]" = (
            weakref.WeakKeyDictionary()
        )
        # topology fingerprint -> learned fixed-sweep hint (see _hint_key)
        self._hint_by_shape: dict[tuple, int] = {}
        # jitted sharded SPF step per Mesh (re-jitting per prefetch would
        # pay a full retrace+compile each call)
        self._mesh_steps: dict = {}

    def _mirror(self, link_state: LinkState):
        from .csr import CsrTopology

        csr = self._mirrors.get(link_state)
        if csr is None:
            csr = CsrTopology.from_link_state(link_state)
            # the relax depth is a property of the topology SHAPE, so a
            # fresh mirror of a same-shaped topology starts from the
            # learned fixed-sweep hint instead of re-learning it by
            # doubling (each failed guess costs a full device dispatch)
            learned = self._hint_by_shape.get(self._hint_key(csr))
            if learned is not None:
                csr._sweep_hint = learned
            self._mirrors[link_state] = csr
        elif csr.version != link_state.version:
            csr.refresh(link_state)
        return csr

    @staticmethod
    def _hint_key(csr) -> tuple:
        # node/edge COUNTS, not just padded capacities: capacities are
        # power-of-two roundings, and hints only ever grow — a deep
        # chain-like topology must not poison a shallow fabric that
        # happens to round to the same capacity bucket
        return (csr.n_nodes, csr.n_edges, csr.node_capacity, csr.edge_capacity)

    def _harvest_hint(self, csr) -> None:
        # max, not overwrite: two coexisting same-key topologies must not
        # ping-pong the stored value downward (a too-small seed costs a
        # failed dispatch; a too-large one only extra sweeps)
        key = self._hint_key(csr)
        self._hint_by_shape[key] = max(
            self._hint_by_shape.get(key, 0), csr._sweep_hint
        )

    def csr_mirror(self, link_state: LinkState):
        """Public access to the incrementally-maintained CSR mirror (used
        by the protection operator surface to avoid per-RPC rebuilds)."""
        return self._mirror(link_state)

    def _result_cache(self, link_state: LinkState) -> dict[str, SpfResult]:
        cached = self._results.get(link_state)
        if cached is None or cached[0] != link_state.version:
            cached = (link_state.version, {})
            self._results[link_state] = cached
        return cached[1]

    def _device_worthwhile(self, link_state: LinkState, n_sources: int) -> bool:
        """The measured dispatch policy (class docstring)."""
        n = link_state.num_nodes()
        if n < self.min_device_nodes:
            return False
        if (
            n_sources >= self.min_device_sources
            or n >= self.force_device_nodes
        ):
            return True
        # engine-warm branch: the batch crossover above prices in per-call
        # staging + jit-cache entry.  With the graph already resident in
        # the engine, a small-S dispatch pays only the padded bucket
        # program call, so the comparison flips in the device's favor.
        if self.engine is not None:
            csr = self._mirrors.get(link_state)
            if csr is not None and self.engine.has_residency(csr):
                return True
        return False

    def _spf_from(self, csr, sources: list[str], use_link_metric: bool = True):
        """SPF dispatch front-end: the engine serves from device residency
        (no per-call staging, bucketed programs); csr.spf_from is the
        engine-less host-staged fallback."""
        if self.engine is not None:
            return self.engine.spf_results(
                csr, sources, use_link_metric=use_link_metric
            )
        return csr.spf_from(sources, use_link_metric=use_link_metric)

    def prefetch(self, link_state: LinkState, sources: list[str]) -> None:
        """Compute many sources in one device call and cache them (host
        memo below the measured batch crossover)."""
        if link_state.num_nodes() < self.min_device_nodes:
            return
        cache = self._result_cache(link_state)
        missing = [
            s
            for s in sources
            if s not in cache and link_state.links_from_node(s)
        ]
        if not missing:
            return
        if not self._device_worthwhile(link_state, len(missing)):
            # small batch: the host memo answers ahead of wall-losing
            # small dispatches; results land in the same cache
            for s in missing:
                cache[s] = link_state.get_spf_result(s)
            return
        with _trace.maybe_child("decision.spf"):
            csr = self._mirror(link_state)
            cache.update(self._spf_from(csr, missing))
        self._harvest_hint(csr)

    def prefetch_via_mesh(
        self, link_state: LinkState, sources: list[str], mesh
    ) -> None:
        """Batch-prefetch over a multi-chip `jax.sharding.Mesh`: the
        source axis is sharded over the mesh's batch dimension
        (parallel/mesh.py spf_step_sharded), so the device side of an
        all-node route view on an n-chip mesh costs ~1/n of the
        single-chip call.  Results land in the same per-LinkState cache
        the solver reads, so build_route_db after a mesh prefetch never
        re-dispatches.

        The mesh step returns distances + SP-DAG only; first-hop sets are
        decoded host-side (to_spf_results' propagation fallback), which is
        fine for control-plane views at fabric scale but is NOT the
        per-tile 100k pipeline (that stays on the single-chip
        spf_forward_full path with device-bit-packed first hops)."""
        from ..parallel.mesh import spf_step_sharded

        if link_state.num_nodes() < self.min_device_nodes:
            return  # get_spf_result serves the host path below this size
        cache = self._result_cache(link_state)
        missing = [
            s
            for s in sources
            if s not in cache and link_state.links_from_node(s)
        ]
        if not missing:
            return
        csr = self._mirror(link_state)
        step = self._mesh_steps.get(mesh)
        if step is None:
            # jit once per mesh; re-jitting per prefetch would retrace
            # and recompile the sharded program every call
            step = self._mesh_steps[mesh] = spf_step_sharded(mesh)
        batch = mesh.devices.shape[0]
        src_ids = np.asarray(
            [csr.node_id[s] for s in missing], dtype=np.int32
        )
        pad = (-len(src_ids)) % batch
        if pad:
            src_ids = np.concatenate(
                [src_ids, np.zeros(pad, dtype=np.int32)]
            )
        dist, dag = step(
            src_ids,
            csr.ell,
            csr.edge_src,
            csr.edge_dst,
            csr.edge_metric,
            csr.edge_up,
            csr.node_overloaded,
        )
        cache.update(
            csr.to_spf_results(
                missing,
                np.asarray(dist)[: len(missing)],
                np.asarray(dag)[: len(missing)],
            )
        )

    def get_spf_result(self, link_state: LinkState, src: str) -> SpfResult:
        if link_state.num_nodes() < self.min_device_nodes:
            return link_state.get_spf_result(src)
        cache = self._result_cache(link_state)
        hit = cache.get(src)
        if hit is not None:
            return hit
        if not link_state.links_from_node(src):
            # isolated/unknown node: empty-but-self result via host path
            return link_state.get_spf_result(src)
        if not self._device_worthwhile(link_state, 1):
            # single-question miss below the measured crossover: host
            # memo (a batch prefetch would have populated the cache)
            res = link_state.get_spf_result(src)
            cache[src] = res
            return res
        # the miss: CSR mirror refresh, engine call and fetch
        with _trace.maybe_child("decision.spf"):
            csr = self._mirror(link_state)
            cache.update(self._spf_from(csr, [src]))
        self._harvest_hint(csr)
        return cache[src]

    # -- batched k-shortest edge-disjoint paths -----------------------------

    def _kth_cache(self, link_state: LinkState) -> dict:
        cached = self._kth_results.get(link_state)
        if cached is None or cached[0] != link_state.version:
            cached = (link_state.version, {})
            self._kth_results[link_state] = cached
        return cached[1]

    def get_kth_paths(
        self, link_state: LinkState, src: str, dest: str, k: int
    ) -> list:
        if link_state.num_nodes() < self.min_device_nodes:
            return link_state.get_kth_paths(src, dest, k)
        cache = self._kth_cache(link_state)
        hit = cache.get((src, dest, k))
        if hit is not None:
            return hit  # a batch prefetch populated it
        if not self._device_worthwhile(link_state, 1):
            # single-question miss below the measured batch crossover
            return link_state.get_kth_paths(src, dest, k)
        # single miss: batch of one (the solver prefetches the full
        # destination set ahead of per-prefix queries)
        self.prefetch_kth_paths(link_state, src, [dest])
        res = cache.get((src, dest, k))
        # [] is a valid answer (unreachable dest), not a miss
        return res if res is not None else link_state.get_kth_paths(
            src, dest, k
        )

    def prefetch_kth_paths(
        self, link_state: LinkState, src: str, dests: list[str]
    ) -> tuple[int, int]:
        """k=1 and k=2 edge-disjoint paths for many destinations in ONE
        masked device run.

        The reference recurses per destination — k=2 is a fresh
        LinkState::runSpf with that destination's first-path links excluded
        (LinkState.cpp:763-793).  The exclusion sets differ per
        destination, which is exactly the kernel's per-row mask axis
        (ops.sssp.spf_forward_ell_masked): row d = SPF from src with
        dest-d's first-path links down.

        Returns (masked rows run, paths traced, nodes decoded from the
        rows), which the solver counts as decision.ksp2_rows,
        decision.ksp2_paths and decision.ksp2_decoded_nodes."""
        if not self._device_worthwhile(link_state, len(dests)):
            return 0, 0, 0  # host recursion serves the per-prefix queries
        csr = self._mirror(link_state)
        if src not in csr.node_id:
            return 0, 0, 0  # unknown/linkless source: host fallback serves it
        cache = self._kth_cache(link_state)
        todo = [
            d for d in dests if (src, d, 1) not in cache or (src, d, 2) not in cache
        ]
        if not todo:
            return 0, 0, 0
        with _trace.maybe_child("decision.ksp2"):
            return self._compute_kth_paths(link_state, csr, cache, src, todo)

    def _compute_kth_paths(
        self, link_state: LinkState, csr, cache: dict, src: str, dests: list[str]
    ) -> tuple[int, int, int]:
        from .csr import RowPathView
        from .link_state import trace_one_path

        base = self.get_spf_result(link_state, src)
        n_paths = 0
        # k=1: trace from the (cached, device-computed) base SP-DAG
        need_second: list[tuple[str, set]] = []
        with _trace.maybe_child("ksp2.trace"):
            for dest in dests:
                if (src, dest, 1) not in cache:
                    paths = []
                    if dest in base:
                        visited: set = set()
                        # empty path (src == dest) is falsy and not
                        # collected, matching LinkState.get_kth_paths
                        while p := trace_one_path(src, dest, base, visited):
                            paths.append(p)
                    cache[(src, dest, 1)] = paths
                    n_paths += len(paths)
                if (src, dest, 2) not in cache:
                    ignore = {
                        link for path in cache[(src, dest, 1)] for link in path
                    }
                    if ignore:
                        need_second.append((dest, ignore))
                    else:
                        cache[(src, dest, 2)] = []
            if not need_second:
                return 0, n_paths, 0
            link_edges = csr.edges_of_links()
            mask = np.ones((len(need_second), csr.edge_capacity), dtype=bool)
            for row, (_dest, ignore) in enumerate(need_second):
                for link in ignore:
                    for e in link_edges.get(link, ()):
                        mask[row, e] = False
        with _trace.maybe_child("ksp2.relax"):
            dist, dag = csr.run_batched_spf(
                [src] * len(need_second), extra_edge_mask=mask
            )
        decoded = 0
        with _trace.maybe_child("ksp2.decode"):
            for row, (dest, _ignore) in enumerate(need_second):
                res = RowPathView(csr, dist[row], dag[row])
                paths = []
                if dest in res:
                    visited = set()
                    while p := trace_one_path(src, dest, res, visited):
                        paths.append(p)
                cache[(src, dest, 2)] = paths
                n_paths += len(paths)
                decoded += res.decoded
        return len(need_second), n_paths, decoded


class SpfSolver:
    """Reference: SpfSolver (openr/decision/Decision.h:199-266)."""

    def __init__(
        self,
        my_node_name: str,
        enable_v4: bool = True,
        bgp_dry_run: bool = False,
        enable_best_route_selection: bool = False,
        spf_backend: Optional[SpfBackend] = None,
        fleet_delta: Optional[bool] = None,
    ) -> None:
        self.my_node_name = my_node_name
        self.enable_v4 = enable_v4
        self.bgp_dry_run = bgp_dry_run
        self.enable_best_route_selection = enable_best_route_selection
        self.spf = spf_backend or HostSpfBackend()
        # degradation ladder rung 1: any device-backend dispatch failure
        # is served from this host oracle instead (memoized Dijkstra) —
        # route correctness is never hostage to the accelerator
        self._host_fallback: Optional[HostSpfBackend] = None
        # fleet-product views (reduced all-sources reverse-SSSP consumer;
        # active per build via build_route_db(fleet_views=...)).
        # `fleet_delta` opts in to the incremental delta rung
        # (decision.delta): None keeps the FleetViewCache default
        # (OPENR_FLEET_DELTA env), so direct constructions stay on the
        # legacy paths unless the daemon asks.
        self.fleet = FleetViewCache(delta=fleet_delta, bump=self._bump)
        self._fleet_views: dict[str, FleetRouteView] = {}
        # static route overlays (reference: Decision.cpp:372-425)
        self.static_unicast_routes: dict[str, list[NextHop]] = {}
        self.static_mpls_routes: dict[int, list[NextHop]] = {}
        # best-route selection cache (reference: bestRoutesCache_)
        self.best_routes_cache: dict[str, BestRouteSelectionResult] = {}
        # the decision.delta.* family is pre-seeded so both wire surfaces
        # expose it from daemon start even before the rung ever engages,
        # and so is the what-if pair index's (Decision.what_if bumps it)
        keys = DELTA_COUNTER_KEYS + KSP2_COUNTER_KEYS + (PAIR_INDEX_BUILDS,)
        self.counters: dict[str, int] = {k: 0 for k in keys}

    def _bump(self, counter: str, n: int = 1) -> None:
        self.counters[counter] = self.counters.get(counter, 0) + n

    # -- degradation ladder (device -> host oracle) --------------------------

    def _host_oracle(self, why: str) -> HostSpfBackend:
        """Account a device fallback and return the host oracle backend."""
        if self._host_fallback is None:
            self._host_fallback = HostSpfBackend()
        self._bump("decision.device_fallbacks")
        log.warning("decision: device SPF failed (%s); using host oracle", why)
        return self._host_fallback

    def _spf_result(self, link_state: LinkState, src: str):
        try:
            return self.spf.get_spf_result(link_state, src)
        except Exception:
            return self._host_oracle("get_spf_result").get_spf_result(
                link_state, src
            )

    def _kth_paths(self, link_state: LinkState, src: str, dest: str, k: int):
        try:
            return self.spf.get_kth_paths(link_state, src, dest, k)
        except Exception:
            return self._host_oracle("get_kth_paths").get_kth_paths(
                link_state, src, dest, k
            )

    # -- static route overlays ----------------------------------------------

    def update_static_unicast_routes(
        self,
        routes_to_update: list[UnicastRoute],
        routes_to_delete: list[str],
    ) -> None:
        for route in routes_to_update:
            self.static_unicast_routes[normalize_prefix(route.dest)] = list(
                route.next_hops
            )
        for prefix in routes_to_delete:
            self.static_unicast_routes.pop(normalize_prefix(prefix), None)

    def update_static_mpls_routes(
        self,
        routes_to_update: list[MplsRoute],
        routes_to_delete: list[int],
    ) -> None:
        for route in routes_to_update:
            self.static_mpls_routes[route.top_label] = list(route.next_hops)
        for label in routes_to_delete:
            self.static_mpls_routes.pop(label, None)

    # -- per-prefix route construction --------------------------------------

    def create_route_for_prefix_or_get_static_route(
        self,
        area_link_states: dict[str, LinkState],
        prefix_state: PrefixState,
        prefix: str,
    ) -> Optional[RibUnicastEntry]:
        """Reference: createRouteForPrefixOrGetStaticRoute
        (Decision.cpp:427-449): computed routes win over static."""
        route = self.create_route_for_prefix(area_link_states, prefix_state, prefix)
        if route is not None:
            return route
        nhs = self.static_unicast_routes.get(normalize_prefix(prefix))
        if nhs is not None:
            return RibUnicastEntry(
                prefix=normalize_prefix(prefix), nexthops=frozenset(nhs)
            )
        return None

    def create_route_for_prefix(
        self,
        area_link_states: dict[str, LinkState],
        prefix_state: PrefixState,
        prefix: str,
    ) -> Optional[RibUnicastEntry]:
        """Reference: createRouteForPrefix (Decision.cpp:445-613)."""
        self._bump("decision.get_route_for_prefix")
        prefix = normalize_prefix(prefix)
        self.best_routes_cache.pop(prefix, None)
        all_prefix_entries = prefix_state.prefixes.get(prefix)
        if not all_prefix_entries:
            return None

        # keep entries of reachable nodes only (per area)
        prefix_entries: PrefixEntries = dict(all_prefix_entries)
        for area, link_state in area_link_states.items():
            view = self._fleet_views.get(area)
            if view is not None and view.covers(self.my_node_name) and all(
                view.is_dest(node)
                for (node, parea) in prefix_entries
                if parea == area and view.covers(node)
            ):
                try:
                    # fleet product answers reachability without a per-
                    # source SPF: dist(me -> advertiser) < INF (fleet.py)
                    prefix_entries = {
                        (node, parea): entry
                        for (node, parea), entry in prefix_entries.items()
                        if area != parea
                        or (
                            view.covers(node)
                            and view.reachable(self.my_node_name, node)
                        )
                    }
                    continue
                except Exception:
                    # device row fetch died mid-query: fall through to the
                    # per-source path (itself host-oracle-backed)
                    self._bump("decision.device_fallbacks")
                    log.warning(
                        "decision: fleet view query failed for area %s; "
                        "per-source fallback",
                        area,
                    )
            my_spf = self._spf_result(link_state, self.my_node_name)
            prefix_entries = {
                (node, parea): entry
                for (node, parea), entry in prefix_entries.items()
                if area != parea or node in my_spf
            }
        if not prefix_entries:
            self._bump("decision.no_route_to_prefix")
            return None

        is_v4 = ipaddress.ip_network(prefix).version == 4
        if is_v4 and not self.enable_v4:
            self._bump("decision.skipped_unicast_route")
            return None

        has_bgp = has_non_bgp = False
        has_self_prepend_label = True
        for (node, _area), entry in prefix_entries.items():
            is_bgp = entry.type == PrefixType.BGP
            has_bgp |= is_bgp
            has_non_bgp |= not is_bgp
            if node == self.my_node_name:
                has_self_prepend_label &= entry.prepend_label is not None
        if has_bgp and has_non_bgp and not self.enable_best_route_selection:
            # mixed BGP/non-BGP advertisement is rejected (Decision.cpp:527)
            self._bump("decision.skipped_unicast_route")
            return None

        best = self.select_best_routes(prefix_entries, has_bgp, area_link_states)
        if not best.success:
            return None
        if not best.all_node_areas:
            self._bump("decision.no_route_to_prefix")
            return None
        self.best_routes_cache[prefix] = best

        # skip self-advertised prefixes unless advertised w/ prepend label
        # (Decision.cpp:570-579)
        if best.has_node(self.my_node_name) and not has_self_prepend_label:
            return None

        forwarding_type, forwarding_algo = self._forwarding_type_and_algorithm(
            prefix_entries, best.all_node_areas
        )
        if forwarding_algo != PrefixForwardingAlgorithm.KSP2_ED_ECMP:
            # SP_ECMP and both SP_UCMP_* algorithms share the
            # shortest-path machinery; UCMP only re-weights the set
            return self._select_best_paths_spf(
                prefix,
                best,
                prefix_entries,
                has_bgp,
                forwarding_type,
                area_link_states,
                forwarding_algo,
            )
        return self._select_best_paths_ksp2(
            prefix,
            best,
            prefix_entries,
            has_bgp,
            forwarding_type,
            area_link_states,
        )

    @staticmethod
    def _forwarding_type_and_algorithm(
        prefix_entries: PrefixEntries, best_node_areas: set[NodeAndArea]
    ) -> tuple[PrefixForwardingType, PrefixForwardingAlgorithm]:
        """Minimum over best entries — most-compatible wins (reference:
        getPrefixForwardingTypeAndAlgorithm, openr/common/Util.cpp)."""
        f_type: Optional[PrefixForwardingType] = None
        f_algo: Optional[PrefixForwardingAlgorithm] = None
        for node_area in best_node_areas:
            entry = prefix_entries[node_area]
            if f_type is None or entry.forwarding_type < f_type:
                f_type = entry.forwarding_type
            if f_algo is None or entry.forwarding_algorithm < f_algo:
                f_algo = entry.forwarding_algorithm
        assert f_type is not None and f_algo is not None
        return f_type, f_algo

    # -- best route selection -----------------------------------------------

    def select_best_routes(
        self,
        prefix_entries: PrefixEntries,
        has_bgp: bool,
        area_link_states: dict[str, LinkState],
    ) -> BestRouteSelectionResult:
        """Reference: selectBestRoutes (Decision.cpp:795-827)."""
        assert prefix_entries
        result = BestRouteSelectionResult()
        if self.enable_best_route_selection:
            # PrefixMetrics-ordered selection
            result.all_node_areas = select_best_prefix_metrics(prefix_entries)
            result.best_node_area = select_best_node_area(
                result.all_node_areas, self.my_node_name
            )
            result.success = True
        elif has_bgp:
            return self._run_best_path_selection_bgp(
                prefix_entries, area_link_states
            )
        else:
            result.all_node_areas = set(prefix_entries)
            result.best_node_area = min(result.all_node_areas)
            result.success = True
        return self._maybe_filter_drained_nodes(result, area_link_states)

    def _run_best_path_selection_bgp(
        self,
        prefix_entries: PrefixEntries,
        area_link_states: dict[str, LinkState],
    ) -> BestRouteSelectionResult:
        """BGP best-path selection over advertised MetricVectors
        (reference: runBestPathSelectionBgp, Decision.cpp:865-903):
        WINNER resets the ECMP set, TIE_WINNER re-points the best entry
        while keeping prior ties, TIE_LOOSER joins the set; TIE/ERROR
        abort the route.  The running `best_vector` is the cached
        comparison target, exactly as the reference's bestVector.

        Deviation for robustness: if no advertiser attached a MetricVector
        at all, fall back to the PrefixMetrics ordered compare (our
        PrefixEntry always carries metrics; the reference would throw on
        the unset thrift optional)."""
        from .metric_vector import CompareResult, compare_metric_vectors

        result = BestRouteSelectionResult()
        if all(e.mv is None for e in prefix_entries.values()):
            result.all_node_areas = select_best_prefix_metrics(prefix_entries)
            result.best_node_area = select_best_node_area(
                result.all_node_areas, self.my_node_name
            )
            result.success = True
            return self._maybe_filter_drained_nodes(result, area_link_states)

        best_vector = None
        # deterministic iteration (the reference walks an unordered_map)
        for node_area in sorted(prefix_entries):
            entry = prefix_entries[node_area]
            if entry.mv is None:
                # mixed mv/no-mv advertisement is not comparable
                # (reference: can_throw on the unset optional)
                log.error(
                    "BGP entry without metric vector from %s; skipping route",
                    node_area,
                )
                self._bump("decision.no_route_to_prefix")
                return BestRouteSelectionResult()
            cmp = (
                compare_metric_vectors(entry.mv, best_vector)
                if best_vector is not None
                else CompareResult.WINNER
            )
            if cmp in (CompareResult.TIE, CompareResult.ERROR):
                log.error(
                    "%s ordering BGP prefix entries; skipping route",
                    cmp.value,
                )
                self._bump("decision.no_route_to_prefix")
                return BestRouteSelectionResult()
            if cmp == CompareResult.WINNER:
                result.all_node_areas.clear()
            if cmp in (CompareResult.WINNER, CompareResult.TIE_WINNER):
                best_vector = entry.mv
                result.best_node_area = node_area
            if cmp in (
                CompareResult.WINNER,
                CompareResult.TIE_WINNER,
                CompareResult.TIE_LOOSER,
            ):
                result.all_node_areas.add(node_area)
        result.success = True
        return self._maybe_filter_drained_nodes(result, area_link_states)

    def _maybe_filter_drained_nodes(
        self,
        result: BestRouteSelectionResult,
        area_link_states: dict[str, LinkState],
    ) -> BestRouteSelectionResult:
        """Drop overloaded advertisers unless all are overloaded
        (reference: maybeFilterDrainedNodes, Decision.cpp:847-870)."""
        filtered = BestRouteSelectionResult()
        filtered.success = result.success
        filtered.best_node_area = result.best_node_area
        filtered.all_node_areas = {
            (node, area)
            for node, area in result.all_node_areas
            if not area_link_states[area].is_node_overloaded(node)
        }
        if not filtered.all_node_areas:
            return result
        if filtered.best_node_area not in filtered.all_node_areas:
            filtered.best_node_area = min(filtered.all_node_areas)
        return filtered

    @staticmethod
    def _min_nexthop_threshold(
        best: BestRouteSelectionResult, prefix_entries: PrefixEntries
    ) -> Optional[int]:
        """Max over best entries' min_nexthop (reference:
        getMinNextHopThreshold, Decision.cpp:830-845)."""
        threshold: Optional[int] = None
        for node_area in best.all_node_areas:
            mn = prefix_entries[node_area].min_nexthop
            if mn is not None and (threshold is None or mn > threshold):
                threshold = mn
        return threshold

    # -- SP_ECMP -------------------------------------------------------------

    def _select_best_paths_spf(
        self,
        prefix: str,
        best: BestRouteSelectionResult,
        prefix_entries: PrefixEntries,
        is_bgp: bool,
        forwarding_type: PrefixForwardingType,
        area_link_states: dict[str, LinkState],
        forwarding_algo: PrefixForwardingAlgorithm = (
            PrefixForwardingAlgorithm.SP_ECMP
        ),
    ) -> Optional[RibUnicastEntry]:
        """Reference: selectBestPathsSpf (Decision.cpp:905-963)."""
        is_v4 = ipaddress.ip_network(prefix).version == 4
        per_destination = forwarding_type == PrefixForwardingType.SR_MPLS

        # self-originated SR prefix w/ prepend label: compute next-hops to
        # the *other* advertisers (Decision.cpp:917-933)
        filtered_node_areas = set(best.all_node_areas)
        if best.has_node(self.my_node_name) and per_destination:
            for node_area, entry in prefix_entries.items():
                if (
                    node_area[0] == self.my_node_name
                    and entry.prepend_label is not None
                ):
                    # every self-advertised (node, area) must be excluded —
                    # a multi-area self anycast advertisement would otherwise
                    # keep one entry at SPF distance 0 and kill the route
                    filtered_node_areas.discard(node_area)

        min_metric, nexthop_nodes = self._get_next_hops_with_metric(
            filtered_node_areas, per_destination, area_link_states
        )
        if not nexthop_nodes:
            self._bump("decision.no_route_to_prefix")
            return None

        nexthops = self._get_next_hops(
            best.all_node_areas,
            is_v4,
            per_destination,
            min_metric,
            nexthop_nodes,
            None,
            area_link_states,
            prefix_entries,
        )
        if forwarding_algo != PrefixForwardingAlgorithm.SP_ECMP:
            nexthops = self._apply_ucmp_weights(
                forwarding_algo,
                filtered_node_areas,
                nexthops,
                area_link_states,
                prefix_entries,
            )
        return self._add_best_paths(
            prefix, best, prefix_entries, is_bgp, nexthops
        )

    def _apply_ucmp_weights(
        self,
        algo: PrefixForwardingAlgorithm,
        dst_node_areas: set[NodeAndArea],
        nexthops: set[NextHop],
        area_link_states: dict[str, LinkState],
        prefix_entries: PrefixEntries,
    ) -> set[NextHop]:
        """UCMP next-hop weights over the already-selected ECMP set
        (reference: the DecisionTest Ucmp tranche semantics).

        SP_UCMP_PREFIX_WEIGHT_PROPAGATION: every first-hop neighbor
        accumulates `PrefixEntry.weight` from each min-metric advertiser
        it reaches on a shortest path; parallel links to one neighbor
        share the neighbor's weight.  Attribution reuses
        getNextHopsWithMetric's per-destination keys, which are the
        documented parity surface between the host SPF and the fleet
        product (`_fleet_next_hops_with_metric`), so both backends
        assign identical weights.

        SP_UCMP_ADJ_WEIGHT_PROPAGATION: each next-hop takes its own
        first-hop adjacency weight (`Adjacency.weight` via the link).

        Weights are normalized by their gcd.  If no positive weight
        survives (no advertiser set one, or every weighted path lost
        the metric race), the set is returned unweighted — plain ECMP
        instead of a black hole."""
        link_w: dict[tuple[str, str], int] = {}
        if algo == PrefixForwardingAlgorithm.SP_UCMP_ADJ_WEIGHT_PROPAGATION:
            for area, link_state in area_link_states.items():
                for link in link_state.links_from_node(self.my_node_name):
                    link_w[(area, link.iface_from_node(self.my_node_name))] = (
                        link.weight_from_node(self.my_node_name)
                    )

        acc: dict[str, int] = {}
        if algo == PrefixForwardingAlgorithm.SP_UCMP_PREFIX_WEIGHT_PROPAGATION:
            _, per_dst = self._get_next_hops_with_metric(
                dst_node_areas, True, area_link_states
            )
            by_dst: dict[str, int] = {}
            for node, area in dst_node_areas:
                w = prefix_entries[(node, area)].weight or 0
                by_dst[node] = max(by_dst.get(node, 0), w)
            for (nh_name, dst_node), _dist in per_dst.items():
                acc[nh_name] = acc.get(nh_name, 0) + by_dst.get(dst_node, 0)

        raw: list[tuple[NextHop, int]] = []
        for nh in nexthops:
            if algo == PrefixForwardingAlgorithm.SP_UCMP_ADJ_WEIGHT_PROPAGATION:
                w = link_w.get((nh.area, nh.if_name), 0)
            else:
                w = acc.get(nh.neighbor_node_name, 0)
            raw.append((nh, max(w, 0)))
        norm = math.gcd(*(w for _nh, w in raw))
        if norm == 0:
            return nexthops
        return {replace(nh, weight=w // norm) for nh, w in raw}

    # -- KSP2_ED_ECMP --------------------------------------------------------

    def _select_best_paths_ksp2(
        self,
        prefix: str,
        best: BestRouteSelectionResult,
        prefix_entries: PrefixEntries,
        is_bgp: bool,
        forwarding_type: PrefixForwardingType,
        area_link_states: dict[str, LinkState],
    ) -> Optional[RibUnicastEntry]:
        """Reference: selectBestPathsKsp2 (Decision.cpp:966-1087)."""
        if forwarding_type != PrefixForwardingType.SR_MPLS:
            self._bump("decision.incompatible_forwarding_type")
            return None

        is_v4 = ipaddress.ip_network(prefix).version == 4
        nexthops: set[NextHop] = set()
        paths: list[tuple[str, Path]] = []  # (area, path)

        for area, link_state in area_link_states.items():
            # batched device prefetch of k=1/k=2 for every best node (one
            # masked kernel run instead of per-destination host recursion)
            self._prefetch_kth_paths(
                link_state, sorted({node for node, _ in best.all_node_areas})
            )
            # shortest paths first
            for node, best_area in sorted(best.all_node_areas):
                if node == self.my_node_name and best_area == area:
                    continue
                for path in self._kth_paths(
                    link_state, self.my_node_name, node, 1
                ):
                    paths.append((area, path))
            # second shortest, skipping those containing a first path
            # (anti double-spray, Decision.cpp:1006-1037)
            first_paths_size = len(paths)
            for node, best_area in sorted(best.all_node_areas):
                if area != best_area:
                    continue
                for sec_path in self._kth_paths(
                    link_state, self.my_node_name, node, 2
                ):
                    from .link_state import path_a_in_path_b

                    if any(
                        path_a_in_path_b(paths[i][1], sec_path)
                        for i in range(first_paths_size)
                    ):
                        continue
                    paths.append((area, sec_path))

        if not paths:
            return None

        for area, path in paths:
            link_state = area_link_states[area]
            adj_dbs = link_state.get_adjacency_databases()
            cost = 0
            labels: list[int] = []  # front == bottom of stack
            next_node = self.my_node_name
            ok = True
            for link in path:
                cost += link.metric_from_node(next_node)
                next_node = link.other_node_name(next_node)
                if next_node not in adj_dbs:
                    ok = False
                    break
                labels.insert(0, adj_dbs[next_node].node_label)
            if not ok:
                continue
            labels.pop()  # drop first-hop node's label (PHP)
            entry = prefix_entries.get((next_node, area))
            if entry is None:
                continue
            if entry.prepend_label is not None:
                if not is_mpls_label_valid(entry.prepend_label):
                    continue
                labels.insert(0, entry.prepend_label)

            first_link = path[0]
            mpls_action = (
                MplsAction(MplsActionCode.PUSH, push_labels=tuple(labels))
                if labels
                else None
            )
            nexthops.add(
                NextHop(
                    address=(
                        first_link.nh_v4_from_node(self.my_node_name)
                        if is_v4
                        else first_link.nh_v6_from_node(self.my_node_name)
                    ),
                    if_name=first_link.iface_from_node(self.my_node_name),
                    metric=cost,
                    mpls_action=mpls_action,
                    area=first_link.area,
                    neighbor_node_name=first_link.other_node_name(
                        self.my_node_name
                    ),
                )
            )

        return self._add_best_paths(
            prefix, best, prefix_entries, is_bgp, nexthops
        )

    def _add_best_paths(
        self,
        prefix: str,
        best: BestRouteSelectionResult,
        prefix_entries: PrefixEntries,
        is_bgp: bool,
        nexthops: set[NextHop],
    ) -> Optional[RibUnicastEntry]:
        """Reference: addBestPaths (Decision.cpp:1090-1150)."""
        min_nexthop = self._min_nexthop_threshold(best, prefix_entries)
        if min_nexthop is not None and min_nexthop > len(nexthops):
            return None

        # self-advertised anycast w/ prepend label: merge in the static
        # next-hops registered for that label (Decision.cpp:1113-1141)
        if best.has_node(self.my_node_name):
            prepend_label = next(
                (
                    entry.prepend_label
                    for (node, _a), entry in prefix_entries.items()
                    if node == self.my_node_name
                    and entry.prepend_label is not None
                ),
                None,
            )
            if prepend_label is not None:
                for nh in self.static_mpls_routes.get(prepend_label, ()):
                    nexthops.add(NextHop(address=nh.address, metric=0))

        return RibUnicastEntry(
            prefix=prefix,
            nexthops=frozenset(nexthops),
            best_prefix_entry=prefix_entries[best.best_node_area],
            best_area=best.best_node_area[1],
            do_not_install=is_bgp and self.bgp_dry_run,
        )

    # -- nexthop computation -------------------------------------------------

    def _get_min_cost_nodes(
        self, spf_result: SpfResult, dst_node_areas: set[NodeAndArea]
    ) -> tuple[float, set[str]]:
        """Reference: getMinCostNodes (Decision.cpp:1153-1178)."""
        shortest = float("inf")
        min_cost_nodes: set[str] = set()
        for dst_node, _area in dst_node_areas:
            res = spf_result.get(dst_node)
            if res is None:
                continue
            if shortest >= res.metric:
                if shortest > res.metric:
                    shortest = res.metric
                    min_cost_nodes = set()
                min_cost_nodes.add(dst_node)
        return shortest, min_cost_nodes

    def _get_next_hops_with_metric(
        self,
        dst_node_areas: set[NodeAndArea],
        per_destination: bool,
        area_link_states: dict[str, LinkState],
    ) -> tuple[float, dict[tuple[str, str], float]]:
        """Reference: getNextHopsWithMetric (Decision.cpp:1182-1228).
        Returns (min metric, {(nexthop node, dst | "") -> dist from nexthop
        to dst})."""
        nexthop_nodes: dict[tuple[str, str], float] = {}
        shortest = float("inf")
        for area, link_state in area_link_states.items():
            view = self._fleet_views.get(area)
            if view is not None and self._fleet_usable(view, dst_node_areas):
                try:
                    shortest = self._fleet_next_hops_with_metric(
                        view,
                        link_state,
                        dst_node_areas,
                        per_destination,
                        shortest,
                        nexthop_nodes,
                    )
                    continue
                except Exception:
                    self._bump("decision.device_fallbacks")
                    log.warning(
                        "decision: fleet next-hop query failed for area %s; "
                        "per-source fallback",
                        area,
                    )
            spf = self._spf_result(link_state, self.my_node_name)
            min_metric, min_cost_nodes = self._get_min_cost_nodes(
                spf, dst_node_areas
            )
            if shortest < min_metric:
                continue
            if shortest > min_metric:
                shortest = min_metric
                nexthop_nodes = {}
            if not min_cost_nodes:
                continue
            for dst_node in min_cost_nodes:
                dst_ref = dst_node if per_destination else ""
                for nh_name in spf[dst_node].next_hops:
                    nexthop_nodes[(nh_name, dst_ref)] = (
                        shortest - spf[nh_name].metric
                    )
        return shortest, nexthop_nodes

    def _fleet_usable(
        self, view: FleetRouteView, dst_node_areas: set[NodeAndArea]
    ) -> bool:
        """The fleet snapshot can answer this query iff it covers the
        querying node and every destination it knows about is in the
        product's destination set (nodes outside the area's graph are
        skipped by both paths identically)."""
        return view.covers(self.my_node_name) and all(
            view.is_dest(node) or not view.covers(node)
            for node, _area in dst_node_areas
        )

    def _fleet_next_hops_with_metric(
        self,
        view: FleetRouteView,
        link_state: LinkState,
        dst_node_areas: set[NodeAndArea],
        per_destination: bool,
        shortest: float,
        nexthop_nodes: dict[tuple[str, str], float],
    ) -> float:
        """One area's contribution to getNextHopsWithMetric, answered from
        the fleet product instead of a per-source SPF.

        Stores dist(nh -> dst) under each qualifying (nh, dst_ref) key —
        provably the value the host path stores (shortest - dist(me, nh))
        for every qualifying pair, see fleet.py module doc — so the
        unchanged _get_next_hops equality test
        (metric(link) + value == min_metric, Decision.cpp:1296-1300)
        selects identical links on either path."""
        me = self.my_node_name
        inf32 = FLEET_INF
        # min over reachable destinations (mirrors _get_min_cost_nodes)
        min_metric = float("inf")
        min_cost_nodes: set[str] = set()
        for dst_node, _area in dst_node_areas:
            if not view.covers(dst_node):
                continue
            d = view.dist(me, dst_node)
            if d >= inf32:
                continue
            if min_metric >= d:
                if min_metric > d:
                    min_metric = d
                    min_cost_nodes = set()
                min_cost_nodes.add(dst_node)
        if shortest < min_metric:
            return shortest
        if shortest > min_metric:
            shortest = min_metric
            nexthop_nodes.clear()
        for dst_node in min_cost_nodes:
            dst_ref = dst_node if per_destination else ""
            d_me = view.dist(me, dst_node)
            for link in link_state.links_from_node(me):
                if not link.is_up():
                    continue
                u = link.other_node_name(me)
                if not view.covers(u):
                    continue
                d_u = view.dist(u, dst_node)
                if d_u >= inf32:
                    continue
                # drain: overloaded neighbor only as the destination
                # itself (the d == 0 source exception of the kernels)
                if view.is_overloaded_id(u) and d_u != 0:
                    continue
                if link.metric_from_node(me) + d_u != d_me:
                    continue
                key = (u, dst_ref)
                prev = nexthop_nodes.get(key)
                if prev is None or d_u < prev:
                    nexthop_nodes[key] = d_u
        return shortest

    def _get_next_hops(
        self,
        dst_node_areas: set[NodeAndArea],
        is_v4: bool,
        per_destination: bool,
        min_metric: float,
        nexthop_nodes: dict[tuple[str, str], float],
        swap_label: Optional[int],
        area_link_states: dict[str, LinkState],
        prefix_entries: PrefixEntries,
    ) -> set[NextHop]:
        """Reference: getNextHopsThrift (Decision.cpp:1231-1338) — LFA-free
        ECMP: keep a link iff metric(link) + dist(neighbor, dst) equals the
        overall min metric."""
        assert nexthop_nodes
        nexthops: set[NextHop] = set()
        for area, link_state in area_link_states.items():
            adj_dbs = link_state.get_adjacency_databases()
            for link in link_state.links_from_node(self.my_node_name):
                dst_iter = (
                    sorted(dst_node_areas) if per_destination else [("", "")]
                )
                for dst_node, dst_area in dst_iter:
                    if dst_area and area != dst_area:
                        continue
                    neighbor = link.other_node_name(self.my_node_name)
                    dist = nexthop_nodes.get((neighbor, dst_node))
                    if dist is None or not link.is_up():
                        continue
                    # don't reach dst via a neighbor that is itself another
                    # destination (Decision.cpp:1285-1291)
                    if (
                        dst_node
                        and (neighbor, area) in dst_node_areas
                        and neighbor != dst_node
                    ):
                        continue
                    dist_over_link = (
                        link.metric_from_node(self.my_node_name) + dist
                    )
                    if dist_over_link != min_metric:
                        continue

                    mpls_action: Optional[MplsAction] = None
                    if swap_label is not None:
                        nh_is_dst = (neighbor, area) in dst_node_areas
                        mpls_action = MplsAction(
                            MplsActionCode.PHP
                            if nh_is_dst
                            else MplsActionCode.SWAP,
                            swap_label=None if nh_is_dst else swap_label,
                        )
                    if dst_node:
                        push_labels: list[int] = []
                        dst_entry = prefix_entries.get((dst_node, area))
                        if (
                            dst_entry is not None
                            and dst_entry.prepend_label is not None
                        ):
                            push_labels.append(dst_entry.prepend_label)
                            if not is_mpls_label_valid(push_labels[-1]):
                                continue
                        if dst_node != neighbor:
                            push_labels.append(adj_dbs[dst_node].node_label)
                            if not is_mpls_label_valid(push_labels[-1]):
                                continue
                        if push_labels:
                            assert mpls_action is None
                            mpls_action = MplsAction(
                                MplsActionCode.PUSH,
                                push_labels=tuple(push_labels),
                            )

                    nexthops.add(
                        NextHop(
                            address=(
                                link.nh_v4_from_node(self.my_node_name)
                                if is_v4
                                else link.nh_v6_from_node(self.my_node_name)
                            ),
                            if_name=link.iface_from_node(self.my_node_name),
                            metric=int(dist_over_link),
                            mpls_action=mpls_action,
                            area=link.area,
                            neighbor_node_name=neighbor,
                        )
                    )
        return nexthops

    # -- full route DB -------------------------------------------------------

    def build_route_db(
        self,
        area_link_states: dict[str, LinkState],
        prefix_state: PrefixState,
        my_node_name: Optional[str] = None,
        fleet_views: Optional[dict[str, FleetRouteView]] = None,
    ) -> Optional[DecisionRouteDb]:
        """Reference: buildRouteDb (Decision.cpp:615-793).  Source-
        parameterized: `my_node_name` may be any node (the axis the TPU
        backend batches over; see OpenrCtrlHandler getRouteDbComputed).

        With `fleet_views` (area -> FleetRouteView), SP_ECMP reachability
        and next-hop selection are answered from the reduced all-sources
        product instead of per-source SPF — the daemon consumer of
        ops.allsources (KSP2 prefixes still go through the per-source
        path machinery; the views don't carry per-destination masked
        re-runs)."""
        me = my_node_name or self.my_node_name
        if not any(ls.has_node(me) for ls in area_link_states.values()):
            return None
        self._bump("decision.route_build_runs")

        prev_me, self.my_node_name = self.my_node_name, me
        prev_fleet, self._fleet_views = (
            self._fleet_views,
            fleet_views or {},
        )
        try:
            route_db = DecisionRouteDb()
            self.best_routes_cache.clear()
            self._prefetch_ksp2_paths(area_link_states, prefix_state)

            for prefix in prefix_state.prefixes:
                route = self.create_route_for_prefix(
                    area_link_states, prefix_state, prefix
                )
                if route is not None:
                    route_db.add_unicast_route(route)

            for prefix, nhs in self.static_unicast_routes.items():
                if prefix in route_db.unicast_routes:
                    continue
                route_db.add_unicast_route(
                    RibUnicastEntry(prefix=prefix, nexthops=frozenset(nhs))
                )

            self._build_node_label_routes(area_link_states, route_db)
            self._build_adj_label_routes(area_link_states, route_db)

            for label, nhs in self.static_mpls_routes.items():
                if label not in route_db.mpls_routes:
                    route_db.add_mpls_route(
                        RibMplsEntry(label=label, nexthops=frozenset(nhs))
                    )
            return route_db
        finally:
            self.my_node_name = prev_me
            self._fleet_views = prev_fleet

    def _prefetch_ksp2_paths(
        self,
        area_link_states: dict[str, LinkState],
        prefix_state: PrefixState,
    ) -> None:
        """Batched KSP pre-pass: union every KSP2 prefix's advertising
        nodes (a superset of the best-route winners) and prefetch k=1/k=2
        for all of them in ONE masked device run per area — the
        per-prefix loop then only hits the backend's cache.  Without
        this, each prefix's miss dispatched its own masked kernel run
        (measured: 31 dispatches instead of 1 on the 32-prefix KSP2
        bench)."""
        me = self.my_node_name
        ksp2_dests: set[str] = set()
        for prefix in prefix_state.ksp2_prefixes:
            for (node, _area), entry in prefix_state.prefixes[prefix].items():
                if (
                    entry.forwarding_algorithm
                    == PrefixForwardingAlgorithm.KSP2_ED_ECMP
                    and node != me
                ):
                    ksp2_dests.add(node)
        if not ksp2_dests:
            return
        for link_state in area_link_states.values():
            self._prefetch_kth_paths(link_state, sorted(ksp2_dests))

    def _prefetch_kth_paths(self, link_state: LinkState, dests: list[str]) -> None:
        """The backend's batched k=1/k=2 prefetch, its masked rows,
        traced paths and decoded nodes counted.  Prefetch is an
        optimization: on a failure the per-path queries fall back to the
        host oracle one by one."""
        prefetch = getattr(self.spf, "prefetch_kth_paths", None)
        if prefetch is None:
            return
        try:
            rows, paths, decoded = prefetch(link_state, self.my_node_name, dests)
        except Exception:
            self._bump("decision.device_fallbacks")
            return
        self._bump("decision.ksp2_rows", rows)
        self._bump("decision.ksp2_paths", paths)
        self._bump("decision.ksp2_decoded_nodes", decoded)

    # -- incremental route rebuild ---------------------------------------------

    def route_inputs(self, area_link_states: dict[str, LinkState]) -> RouteInputs:
        """Snapshot what the daemon's own build reads of each area
        (RouteInputs), `labels_exclusive` left to the caller.  Decision's
        own build runs on no fleet view: views are set only for the
        length of a `build_route_db` call."""
        me = self.my_node_name
        spf: dict[str, SpfResult] = {}
        overloaded: dict[str, frozenset[str]] = {}
        nodes: dict[str, frozenset[str]] = {}
        own_links: dict[str, tuple] = {}
        for area, ls in area_link_states.items():
            spf[area] = self._spf_result(ls, me)
            overloaded[area] = ls.overloaded_nodes()
            nodes[area] = frozenset(ls.get_adjacency_databases())
            own_links[area] = tuple(
                (
                    link.other_node_name(me),
                    link.iface_from_node(me),
                    link.metric_from_node(me),
                    link.nh_v4_from_node(me),
                    link.nh_v6_from_node(me),
                    link.is_up(),
                    link.adj_label_from_node(me),
                    link.weight_from_node(me),
                )
                for link in ls.ordered_links_from_node(me)
            )
        return RouteInputs(
            spf=spf, overloaded=overloaded, nodes=nodes, own_links=own_links
        )

    def node_labels_exclusive(self, area_link_states: dict[str, LinkState]) -> bool:
        """No node label is shared with another node, one of the daemon's
        adjacency labels or a static MPLS route (RouteInputs)."""
        me = self.my_node_name
        node_labels: list[int] = []
        adj_labels: set[int] = set()
        for ls in area_link_states.values():
            node_labels += (
                db.node_label
                for db in ls.get_adjacency_databases().values()
                if db.node_label
            )
            adj_labels.update(
                link.adj_label_from_node(me) for link in ls.ordered_links_from_node(me)
            )
        distinct = set(node_labels)
        return (
            len(distinct) == len(node_labels)
            and distinct.isdisjoint(adj_labels)
            and distinct.isdisjoint(self.static_mpls_routes)
        )

    def build_dirty_routes(
        self,
        area_link_states: dict[str, LinkState],
        prefix_state: PrefixState,
        prefixes: set[str],
        nodes: set[str],
    ) -> tuple[
        dict[str, Optional[RibUnicastEntry]], dict[int, Optional[RibMplsEntry]]
    ]:
        """The daemon's routes that can differ from its last build, where
        `nodes` are the dirty nodes between the two builds' RouteInputs
        (the reference recomputes every route on a topology change,
        Decision.cpp rebuildRoutes).  Recomputes `prefixes` (changed
        advertisements), every prefix a dirty node advertises in any
        area, every KSP2_ED_ECMP prefix (a second disjoint path can
        change while no SPF entry does), and the node-label routes of
        the dirty nodes.  Returns ({prefix: route}, {label: route}),
        None where the build has no route."""
        dirty = set(prefixes) | prefix_state.ksp2_prefixes
        for node in nodes:
            for area in area_link_states:
                dirty |= prefix_state.prefixes_of(node, area)
        self._prefetch_ksp2_paths(area_link_states, prefix_state)
        unicast = {
            prefix: self.create_route_for_prefix_or_get_static_route(
                area_link_states, prefix_state, prefix
            )
            for prefix in dirty
        }
        mpls: dict[int, Optional[RibMplsEntry]] = {}
        for area, link_state in area_link_states.items():
            adj_dbs = link_state.get_adjacency_databases()
            for node in nodes:
                db = adj_dbs.get(node)
                if db is None or not is_mpls_label_valid(db.node_label):
                    continue
                mpls[db.node_label] = self._node_label_route(
                    node, area, db.node_label, area_link_states
                )
        return unicast, mpls

    def _build_fleet_views(
        self,
        area_link_states: dict[str, LinkState],
        prefix_state: PrefixState,
        explicit: bool,
    ) -> dict[str, FleetRouteView]:
        """Per-area fleet views.  `explicit` (operator asked for the fleet
        product by name) always computes; otherwise a cold view is only
        computed when the measured dispatch policy says the device round
        beats per-source work (DeviceSpfBackend docstring) — host backends
        never compute one implicitly."""
        views: dict[str, FleetRouteView] = {}
        mirror = getattr(self.spf, "csr_mirror", None)
        min_nodes = getattr(self.spf, "min_device_nodes", None)
        min_sources = getattr(self.spf, "min_device_sources", None)
        engine = getattr(self.spf, "engine", None)
        for area, ls in area_link_states.items():
            dests = fleet_destinations(ls, prefix_state)
            if not dests:
                continue
            if not explicit and not self.fleet.is_warm(ls, dests):
                if min_nodes is None or ls.num_nodes() < min_nodes:
                    continue
                if min_sources is not None and len(dests) < min_sources:
                    continue
            cached = self.fleet.is_warm(ls, dests)
            try:
                view = self.fleet.view(
                    ls,
                    dests,
                    csr=mirror(ls) if mirror is not None else None,
                    engine=engine,
                )
            except Exception:
                # fleet-product dispatch failed outright (mirror build or
                # both cold attempts): serve this area per-source off the
                # host oracle instead of dropping the rebuild
                self._bump("decision.device_fallbacks")
                self._bump("decision.fleet_view_failures")
                log.warning(
                    "decision: fleet product failed for area %s; "
                    "serving per-source from host oracle",
                    area,
                )
                continue
            if view is not None:
                views[area] = view
                if not cached:
                    # fb303-style observability: operators watch the
                    # warm-start hit rate of fleet rebuilds, split by
                    # change direction (link-DOWN warm starts are the
                    # newer, riskier gate)
                    self._bump(
                        "decision.fleet_rebuild_warm"
                        if view.warm
                        else "decision.fleet_rebuild_cold"
                    )
                    if view.warm_mode == "worsen":
                        self._bump("decision.fleet_rebuild_warm_down")
                    if getattr(view, "cold_fallback", False):
                        # warm-start gate blew up and the cache retried
                        # cold (ladder rung 2, FleetViewCache.view)
                        self._bump("decision.fleet_warm_fallbacks")
        return views

    def any_node_route_db(
        self,
        area_link_states: dict[str, LinkState],
        prefix_state: PrefixState,
        node: str,
    ) -> Optional[DecisionRouteDb]:
        """Any-node ctrl query (reference: getDecisionRouteDb,
        Decision.cpp:1510-1530), served from the fleet product when the
        per-area view is warm (zero device work) or worth computing under
        the measured dispatch policy; per-source path otherwise."""
        views = self._build_fleet_views(
            area_link_states, prefix_state, explicit=False
        )
        # the build touches the queried router and its neighbors: fetch
        # those distance columns in ONE device gather per area instead of
        # one taxed dispatch each
        for area, view in views.items():
            if not view.covers(node):
                continue
            ls = area_link_states[area]
            wanted = {node}
            for link in ls.links_from_node(node):
                wanted.add(link.other_node_name(node))
            try:
                view.prefetch_rows(sorted(wanted))
            except Exception:
                self._bump("decision.device_fallbacks")
        return self.build_route_db(
            area_link_states,
            prefix_state,
            my_node_name=node,
            fleet_views=views,
        )

    def fleet_route_dbs(
        self,
        area_link_states: dict[str, LinkState],
        prefix_state: PrefixState,
        nodes: Optional[list[str]] = None,
    ) -> dict[str, DecisionRouteDb]:
        """Fleet-wide route dump: ONE reverse-SSSP device round per area
        answers every requested router's route build (default: every node).
        This is the daemon consumer of the reduced all-sources product —
        the reference's equivalent is N sequential buildRouteDb calls
        (Decision.cpp:615-793) over the per-source SPF memo.

        Views are cached per (LinkState version, destination set), so a
        warm cache serves any-node ctrl queries with zero device work.
        This is the operator's EXPLICIT fleet request: views are computed
        regardless of backend (a cold compute at scale runs a P-source
        device round — and, first time, its XLA compile — on the calling
        thread; the implicit any-node path applies the dispatch policy
        instead, see _build_fleet_views)."""
        views = self._build_fleet_views(
            area_link_states, prefix_state, explicit=True
        )
        if nodes is None:
            nodes = sorted(
                {
                    n
                    for ls in area_link_states.values()
                    for n in ls.node_names
                }
            )
        # queries touch each router and its neighbors: fetch the distance
        # columns for the whole dump in one device gather per area
        for area, view in views.items():
            ls = area_link_states[area]
            wanted = set()
            for n in nodes:
                if not view.covers(n):
                    continue
                wanted.add(n)
                for link in ls.links_from_node(n):
                    wanted.add(link.other_node_name(n))
            try:
                view.prefetch_rows(sorted(wanted))
            except Exception:
                self._bump("decision.device_fallbacks")
        out: dict[str, DecisionRouteDb] = {}
        for node in nodes:
            db = self.build_route_db(
                area_link_states,
                prefix_state,
                my_node_name=node,
                fleet_views=views,
            )
            out[node] = db if db is not None else DecisionRouteDb()
        return out

    def _build_node_label_routes(
        self,
        area_link_states: dict[str, LinkState],
        route_db: DecisionRouteDb,
    ) -> None:
        """MPLS routes for every node label (Decision.cpp:655-745)."""
        label_to_node: dict[int, tuple[str, RibMplsEntry]] = {}
        for area, link_state in area_link_states.items():
            for node, adj_db in sorted(
                link_state.get_adjacency_databases().items()
            ):
                top_label = adj_db.node_label
                if top_label == 0:
                    continue
                if not is_mpls_label_valid(top_label):
                    self._bump("decision.skipped_mpls_route")
                    continue
                existing = label_to_node.get(top_label)
                if existing is not None:
                    self._bump("decision.duplicate_node_label")
                    # collision: smaller node name retained
                    # (Decision.cpp:679-689)
                    if existing[0] < node:
                        continue
                entry = self._node_label_route(
                    node, area, top_label, area_link_states
                )
                if entry is not None:
                    label_to_node[top_label] = (node, entry)
        for _label, (_node, entry) in label_to_node.items():
            route_db.add_mpls_route(entry)

    def _node_label_route(
        self,
        node: str,
        area: str,
        top_label: int,
        area_link_states: dict[str, LinkState],
    ) -> Optional[RibMplsEntry]:
        """One node label's MPLS route (Decision.cpp:690-745)."""
        if node == self.my_node_name:
            nh = NextHop(
                address="::",
                area=area,
                mpls_action=MplsAction(MplsActionCode.POP_AND_LOOKUP),
            )
            return RibMplsEntry(top_label, frozenset({nh}))
        min_metric, nexthop_nodes = self._get_next_hops_with_metric(
            {(node, area)}, False, area_link_states
        )
        if not nexthop_nodes:
            self._bump("decision.no_route_to_label")
            return None
        return RibMplsEntry(
            top_label,
            frozenset(
                self._get_next_hops(
                    {(node, area)},
                    False,
                    False,
                    min_metric,
                    nexthop_nodes,
                    top_label,
                    area_link_states,
                    {},
                )
            ),
        )

    def _build_adj_label_routes(
        self,
        area_link_states: dict[str, LinkState],
        route_db: DecisionRouteDb,
    ) -> None:
        """MPLS routes for our adjacency labels (Decision.cpp:748-775)."""
        for _area, link_state in area_link_states.items():
            for link in sorted(link_state.links_from_node(self.my_node_name)):
                top_label = link.adj_label_from_node(self.my_node_name)
                if top_label == 0:
                    continue
                if not is_mpls_label_valid(top_label):
                    self._bump("decision.skipped_mpls_route")
                    continue
                nh = NextHop(
                    address=link.nh_v6_from_node(self.my_node_name),
                    if_name=link.iface_from_node(self.my_node_name),
                    metric=link.metric_from_node(self.my_node_name),
                    mpls_action=MplsAction(MplsActionCode.PHP),
                    area=link.area,
                    neighbor_node_name=link.other_node_name(self.my_node_name),
                )
                route_db.add_mpls_route(
                    RibMplsEntry(top_label, frozenset({nh}))
                )
