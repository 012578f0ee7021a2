"""Decision module: KvStore publications in, route-update deltas out.

Functional equivalent of the reference's Decision event base
(openr/decision/Decision.{h,cpp}:1398-2050): fiber readers over the KvStore
publication and static-routes queues, per-key publication parsing
("adj:" / "prefix:" / "fibTime:"), pending-update batching with oldest-wins
perf events, debounced full/incremental route rebuild, cold-start hold,
RibPolicy application with TTL expiry, and ordered-FIB hold decrements.

A rebuild is full (every route, then a diff of the whole table) when it
is forced, else incremental: the nodes whose SPF entry or drain bit
changed since the last build (RouteInputs.dirty_nodes) name the routes
that can differ, and only those are recomputed and diffed.
"""

from __future__ import annotations

import logging
from typing import Optional

log = logging.getLogger(__name__)

from ..obs import trace as _trace
from ..runtime.async_util import AsyncDebounce
from ..runtime.eventbase import OpenrEventBase
from ..runtime.queue import QueueClosedError, ReplicateQueue, RQueue
from ..serializer import loads
from ..types import (
    ADJ_MARKER,
    AdjacencyDatabase,
    PerfEvents,
    Publication,
    PREFIX_MARKER,
    PrefixDatabase,
    add_perf_event,
    node_name_from_key,
    normalize_prefix,
    parse_prefix_key,
)
from .link_state import LinkState, LinkStateChange
from .prefix_state import PrefixState
from .rib import DecisionRouteDb, DecisionRouteUpdate
from .rib_policy import PolicyError, RibPolicy, RibPolicyConfig
from .spf_solver import HostSpfBackend, RouteInputs, SpfBackend, SpfSolver

FIB_TIME_MARKER = "fibTime:"

# pre-seeded into Decision.counters so both wire surfaces expose them
# from daemon start
DECISION_COUNTER_KEYS = (
    "decision.rebuilds",
    "decision.incremental_rebuilds",
    "decision.dirty_nodes",
)


class DecisionPendingUpdates:
    """Reference: detail::DecisionPendingUpdates
    (openr/decision/Decision.h:121-196, Decision.cpp:45-107)."""

    def __init__(self, my_node_name: str) -> None:
        self.my_node_name = my_node_name
        self.count = 0
        self.perf_events: Optional[PerfEvents] = None
        # forced: every route is rebuilt.  A remote topology change may
        # take the incremental path.
        self.needs_full_rebuild = False
        self.remote_topology_changed = False
        self.updated_prefixes: set[str] = set()

    def needs_route_update(self) -> bool:
        return (
            self.needs_full_rebuild
            or self.remote_topology_changed
            or bool(self.updated_prefixes)
        )

    def set_needs_full_rebuild(self) -> None:
        self.needs_full_rebuild = True

    def apply_link_state_change(
        self,
        node_name: str,
        change: LinkStateChange,
        perf_events: Optional[PerfEvents],
    ) -> None:
        if node_name == self.my_node_name:
            # link attribute changes only matter locally (nexthop/label)
            self.needs_full_rebuild |= (
                change.topology_changed
                or change.node_label_changed
                or change.link_attributes_changed
            )
        else:
            self.needs_full_rebuild |= change.node_label_changed
            self.remote_topology_changed |= change.topology_changed
        self._add_update(perf_events)

    def apply_prefix_state_change(
        self, change: set[str], perf_events: Optional[PerfEvents] = None
    ) -> None:
        self.updated_prefixes |= change
        self._add_update(perf_events)

    def reset(self) -> None:
        self.count = 0
        self.perf_events = None
        self.needs_full_rebuild = False
        self.remote_topology_changed = False
        self.updated_prefixes = set()

    def add_event(self, event: str) -> None:
        if self.perf_events is not None:
            add_perf_event(self.perf_events, self.my_node_name, event)

    def move_out_events(self) -> Optional[PerfEvents]:
        events, self.perf_events = self.perf_events, None
        return events

    def _add_update(self, perf_events: Optional[PerfEvents]) -> None:
        self.count += 1
        # keep the OLDEST event list in the batch for convergence measurement
        if self.perf_events is None or (
            perf_events is not None
            and perf_events.events
            and self.perf_events.events
            and self.perf_events.events[0].unix_ts_ms
            > perf_events.events[0].unix_ts_ms
        ):
            self.perf_events = (
                PerfEvents(list(perf_events.events)) if perf_events else PerfEvents()
            )
            self.add_event("DECISION_RECEIVED")


class Decision(OpenrEventBase):
    """The Decision event base."""

    def __init__(
        self,
        my_node_name: str,
        kvstore_updates: RQueue[Publication],
        static_routes_updates: Optional[RQueue[DecisionRouteUpdate]],
        route_updates_queue: ReplicateQueue[DecisionRouteUpdate],
        *,
        debounce_min_s: float = 0.01,
        debounce_max_s: float = 0.25,
        eor_time_s: Optional[float] = None,
        enable_v4: bool = True,
        enable_ordered_fib: bool = False,
        bgp_dry_run: bool = False,
        enable_best_route_selection: bool = False,
        enable_rib_policy: bool = False,
        spf_backend: Optional[SpfBackend] = None,
        fleet_delta: Optional[bool] = None,
    ) -> None:
        super().__init__(name="decision")
        self.my_node_name = my_node_name
        self._kvstore_updates = kvstore_updates
        self._static_routes_updates = static_routes_updates
        self._route_updates_queue = route_updates_queue
        self._debounce_bounds = (debounce_min_s, debounce_max_s)
        self._eor_time_s = eor_time_s
        self._enable_ordered_fib = enable_ordered_fib
        self._enable_rib_policy = enable_rib_policy

        self.spf_solver = SpfSolver(
            my_node_name,
            enable_v4=enable_v4,
            bgp_dry_run=bgp_dry_run,
            enable_best_route_selection=enable_best_route_selection,
            spf_backend=spf_backend,
            fleet_delta=fleet_delta,
        )
        self.area_link_states: dict[str, LinkState] = {}
        self.prefix_state = PrefixState()
        self.pending_updates = DecisionPendingUpdates(my_node_name)
        self.route_db = DecisionRouteDb()
        # what the last build read (None: the next build is full)
        self._route_inputs: Optional[RouteInputs] = None
        self.rib_policy: Optional[RibPolicy] = None
        self._rib_policy_timeout = None
        self._fib_times: dict[str, float] = {}  # node -> fib time (s)
        self._rebuild_debounced: Optional[AsyncDebounce] = None
        self._cold_start_pending = eor_time_s is not None
        self._ordered_fib_timeout = None
        # topology events admitted since the last route rebuild — the
        # serving layer's admission defer hint (QueryScheduler
        # defer_hint): while events are pending, freshly coalesced query
        # batches briefly hold so they pin the POST-storm epoch and ride
        # the delta-updated product instead of dispatching against a
        # topology about to be invalidated
        self._pending_events = 0
        # OPENR_TRACE: publication spans carried across kvstore_updates
        # and awaiting the (debounced) rebuild that folds them in.
        # Eventbase-thread only — no lock needed.
        self._trace_pending: list = []
        self.counters: dict[str, int] = {k: 0 for k in DECISION_COUNTER_KEYS}

    def _bump(self, counter: str, n: int = 1) -> None:
        self.counters[counter] = self.counters.get(counter, 0) + n

    def pending_event_hint(self) -> int:
        """Topology events admitted but not yet folded into routes —
        non-zero while a flap storm is mid-coalesce.  Thread-safe enough
        for its purpose (an int read; the serving defer wait is bounded
        either way)."""
        return self._pending_events

    def get_counters(self) -> dict[str, int]:
        """Module + solver counters merged (fb303-style export)."""
        out = dict(self.spf_solver.counters)
        for k, v in self.counters.items():
            out[k] = out.get(k, 0) + v
        return out

    # -- lifecycle -----------------------------------------------------------

    def run(self) -> None:
        super().run()
        self.wait_until_running()
        self.run_in_event_base_thread(self._setup).result()

    def _setup(self) -> None:
        self._rebuild_debounced = AsyncDebounce(
            self._debounce_bounds[0],
            self._debounce_bounds[1],
            lambda: self.rebuild_routes("DECISION_DEBOUNCE"),
        )
        if self._cold_start_pending:
            self.schedule_timeout(self._eor_time_s, self._cold_start_expired)
        self.add_fiber_task(self._kvstore_fiber(), name="kvStoreUpdates")
        if self._static_routes_updates is not None:
            self.add_fiber_task(self._static_routes_fiber(), name="staticRoutes")

    def _cold_start_expired(self) -> None:
        self._cold_start_pending = False
        self.pending_updates.set_needs_full_rebuild()
        self.rebuild_routes("COLD_START_UPDATE")

    async def _kvstore_fiber(self) -> None:
        while True:
            try:
                pub = await self._kvstore_updates.aget()
            except QueueClosedError:
                return
            tr = _trace.TRACE
            if tr is not None:
                self._trace_pending.extend(tr.take_carried())
            self.process_publication(pub)
            if self.pending_updates.needs_route_update():
                self._pending_events += 1
                self._rebuild_debounced()

    async def _static_routes_fiber(self) -> None:
        while True:
            try:
                update = await self._static_routes_updates.aget()
            except QueueClosedError:
                return
            self.process_static_routes_update(update)

    # -- publication processing ---------------------------------------------

    def process_publication(self, pub: Publication) -> None:
        """Reference: Decision::processPublication (Decision.cpp:1683-1790)."""
        area = pub.area
        if not area:
            log.error("decision: dropping publication without area")
            self._bump("decision.error")
            return
        link_state = self.area_link_states.setdefault(area, LinkState(area))

        if not pub.key_vals and not pub.expired_keys:
            return

        for key, val in pub.key_vals.items():
            if val.value is None:
                continue  # TTL-refresh only
            try:
                self._process_key_val(key, val, area, link_state)
            except Exception:  # corrupt value: skip key, keep the fiber alive
                # (reference: per-key try/catch, Decision.cpp:1786-1789)
                log.exception("decision: failed to process key %r", key)
                self._bump("decision.error")

        for key in pub.expired_keys:
            try:
                self._process_expired_key(key, area, link_state)
            except Exception:
                log.exception("decision: failed to process expired key %r", key)
                self._bump("decision.error")

    def _process_expired_key(
        self, key: str, area: str, link_state: LinkState
    ) -> None:
        node = node_name_from_key(key)
        if key.startswith(ADJ_MARKER):
            self.pending_updates.apply_link_state_change(
                node, link_state.delete_adjacency_database(node), None
            )
        elif key.startswith(PREFIX_MARKER):
            parsed = parse_prefix_key(key)
            if parsed is None:
                return
            pnode, _parea, prefix = parsed
            self.pending_updates.apply_prefix_state_change(
                self.prefix_state.delete_prefix(pnode, area, prefix), None
            )

    def _process_key_val(
        self, key: str, val, area: str, link_state: LinkState
    ) -> None:
        if key.startswith(ADJ_MARKER):
            adj_db = loads(val.value, AdjacencyDatabase)
            adj_db.area = area
            hold_up_ttl = hold_down_ttl = 0
            if self._enable_ordered_fib:
                hops = link_state.get_hops_from_a_to_b(
                    self.my_node_name, adj_db.this_node_name
                )
                if hops is not None:
                    hold_up_ttl = int(hops)
                    hold_down_ttl = (
                        link_state.get_max_hops_to_node(adj_db.this_node_name)
                        - hold_up_ttl
                    )
            self._bump("decision.adj_db_update")
            self.pending_updates.apply_link_state_change(
                adj_db.this_node_name,
                link_state.update_adjacency_database(
                    adj_db, hold_up_ttl, hold_down_ttl
                ),
                adj_db.perf_events,
            )
            if (
                self._enable_ordered_fib
                and link_state.has_holds()
                and self._ordered_fib_timeout is None
            ):
                self._schedule_ordered_fib_decrement()
        elif key.startswith(PREFIX_MARKER):
            prefix_db = loads(val.value, PrefixDatabase)
            if len(prefix_db.prefix_entries) != 1:
                self._bump("decision.error")
                return
            entry = prefix_db.prefix_entries[0]
            # ignore self-redistributed route reflection
            if (
                prefix_db.this_node_name == self.my_node_name
                and entry.area_stack
                and entry.area_stack[-1] in self.area_link_states
            ):
                return
            self._bump("decision.prefix_db_update")
            node = prefix_db.this_node_name
            change = (
                self.prefix_state.delete_prefix(node, area, entry.prefix)
                if prefix_db.delete_prefix
                else self.prefix_state.update_prefix(node, area, entry)
            )
            self.pending_updates.apply_prefix_state_change(
                change, prefix_db.perf_events
            )
        elif key.startswith(FIB_TIME_MARKER):
            try:
                self._fib_times[node_name_from_key(key)] = (
                    float(val.value.decode()) / 1000.0
                )
            except (ValueError, AttributeError):
                pass

    def process_static_routes_update(self, delta: DecisionRouteUpdate) -> None:
        """Reference: processStaticRoutesUpdate (Decision.cpp:1829-1864)."""
        if delta.unicast_routes_to_update or delta.unicast_routes_to_delete:
            to_update = [
                e.to_unicast_route() for e in delta.unicast_routes_to_update.values()
            ]
            self.spf_solver.update_static_unicast_routes(
                to_update, delta.unicast_routes_to_delete
            )
            change = {normalize_prefix(p) for p in delta.unicast_routes_to_update}
            change |= {
                normalize_prefix(p) for p in delta.unicast_routes_to_delete
            }
            self.pending_updates.apply_prefix_state_change(change, None)
            self.pending_updates.set_needs_full_rebuild()
        if delta.mpls_routes_to_update or delta.mpls_routes_to_delete:
            self.spf_solver.update_static_mpls_routes(
                [e.to_mpls_route() for e in delta.mpls_routes_to_update],
                delta.mpls_routes_to_delete,
            )
            self.pending_updates.set_needs_full_rebuild()
        if self._rebuild_debounced is not None:
            self._rebuild_debounced()

    # -- route rebuild -------------------------------------------------------

    def rebuild_routes(self, event: str) -> None:
        """Reference: rebuildRoutes (Decision.cpp:1866-1935)."""
        if self._cold_start_pending:
            return
        tr = _trace.TRACE
        pending, self._trace_pending = self._trace_pending, []
        if tr is not None and pending:
            # fan-in: the debounced rebuild folds every carried
            # publication at once — open a "decision" stage under each
            # and activate them all so the route push carries them on
            with tr.fan_in(pending, "decision", event=event):
                self._rebuild_routes_impl(event)
            return
        self._rebuild_routes_impl(event)

    def _rebuild_routes_impl(self, event: str) -> None:
        self._bump("decision.rebuilds")
        self.pending_updates.add_event(event)

        try:
            update = self._compute_route_update()
        except Exception:
            # degradation ladder bottom rung: the solver's own device->
            # host fallbacks should make this unreachable, but a rebuild
            # failure must NEVER drop the route publication — demote the
            # solver to the host oracle permanently and recompute full
            log.exception(
                "decision: route rebuild failed; recomputing on host oracle"
            )
            self.spf_solver._bump("decision.device_fallbacks")
            self._bump("decision.route_rebuild_fallbacks")
            self.spf_solver.spf = HostSpfBackend()
            self.pending_updates.set_needs_full_rebuild()
            update = self._compute_route_update()

        self.route_db.update(update)
        self.pending_updates.add_event("ROUTE_UPDATE")
        update.perf_events = self.pending_updates.move_out_events()
        self.pending_updates.reset()
        # the rebuild folded every admitted event (delta rung or full):
        # deferred query batches may pin the fresh epoch now
        self._pending_events = 0
        self._route_updates_queue.push(update)

    def _compute_route_update(self) -> DecisionRouteUpdate:
        """Full rebuild when forced, when there is no snapshot of the last
        build, while a RIB policy has lapsed (its timer rebuilds in full)
        or when the snapshots say a change can move every route; the
        incremental rebuild of the dirty routes otherwise."""
        solver = self.spf_solver
        pending = self.pending_updates
        prev, self._route_inputs = self._route_inputs, None
        with _trace.maybe_child("decision.route_build"):
            inputs = solver.route_inputs(self.area_link_states)
            dirty = None
            if (
                prev is not None
                and not pending.needs_full_rebuild
                and (self.rib_policy is None or self.rib_policy.is_active())
            ):
                dirty = prev.dirty_nodes(inputs)
            if dirty is None:
                db = solver.build_route_db(self.area_link_states, self.prefix_state)
                inputs.labels_exclusive = solver.node_labels_exclusive(
                    self.area_link_states
                )
            else:
                inputs.labels_exclusive = prev.labels_exclusive
                unicast, mpls = solver.build_dirty_routes(
                    self.area_link_states,
                    self.prefix_state,
                    pending.updated_prefixes,
                    dirty,
                )
        if dirty is None:
            if db is None:
                db, inputs = DecisionRouteDb(), None
            if self.rib_policy is not None:
                self.rib_policy.apply_policy(db.unicast_routes)
            with _trace.maybe_child("decision.route_diff"):
                update = self.route_db.calculate_update(db)
        else:
            self._bump("decision.incremental_rebuilds")
            self._bump("decision.dirty_nodes", len(dirty))
            if self.rib_policy is not None:
                self.rib_policy.apply_policy(
                    {p: r for p, r in unicast.items() if r is not None}
                )
            with _trace.maybe_child("decision.route_diff"):
                update = self.route_db.calculate_partial_update(unicast, mpls)
        self._route_inputs = inputs
        return update

    # -- ordered-FIB holds ---------------------------------------------------

    def _max_fib_time_s(self) -> float:
        return max(self._fib_times.values(), default=0.001)

    def _schedule_ordered_fib_decrement(self) -> None:
        self._ordered_fib_timeout = self.schedule_timeout(
            self._max_fib_time_s(), self._decrement_ordered_fib_holds
        )

    def _decrement_ordered_fib_holds(self) -> None:
        """Reference: decrementOrderedFibHolds (Decision.cpp:1938-1955)."""
        self._ordered_fib_timeout = None
        still_has_holds = False
        for link_state in self.area_link_states.values():
            self.pending_updates.apply_link_state_change(
                self.my_node_name, link_state.decrement_holds(), None
            )
            still_has_holds |= link_state.has_holds()
        if self.pending_updates.needs_route_update():
            self.rebuild_routes("ORDERED_FIB_HOLDS_EXPIRED")
        if still_has_holds:
            self._schedule_ordered_fib_decrement()

    # -- thread-safe control API (reference: Decision.cpp:1510-1680) ---------

    def get_route_db(self, node_name: str = "") -> DecisionRouteDb:
        """Compute any node's routes (reference: getDecisionRouteDb).
        Other-node queries go through the fleet-product path
        (spf_solver.any_node_route_db): a warm reduced all-sources view
        answers them with zero device work."""

        def _compute() -> DecisionRouteDb:
            target = node_name or self.my_node_name
            if target != self.my_node_name:
                db = self.spf_solver.any_node_route_db(
                    self.area_link_states, self.prefix_state, target
                )
            else:
                db = self.spf_solver.build_route_db(
                    self.area_link_states,
                    self.prefix_state,
                    my_node_name=target,
                )
            return db if db is not None else DecisionRouteDb()

        return self.run_in_event_base_thread(_compute).result()

    # Fleet dumps build one DecisionRouteDb per node and serialize as a
    # single response: an unbounded dump at 100k-node scale is a
    # multi-GB allocation on the Decision thread (the Watchdog RSS
    # limit would abort the daemon).  Operators page with `nodes=`.
    MAX_FLEET_DUMP_NODES = 8192

    def get_fleet_route_dbs(
        self, nodes: Optional[list[str]] = None
    ) -> dict[str, DecisionRouteDb]:
        """Fleet-wide route dump from ONE reverse-SSSP device round per
        area (spf_solver.fleet_route_dbs; consumer of ops.allsources).
        `nodes` defaults to every known node, bounded by
        MAX_FLEET_DUMP_NODES."""

        def _compute() -> dict[str, DecisionRouteDb]:
            if nodes is None:
                total = len(
                    {
                        n
                        for ls in self.area_link_states.values()
                        for n in ls.node_names
                    }
                )
                if total > self.MAX_FLEET_DUMP_NODES:
                    raise ValueError(
                        f"fleet dump of {total} nodes exceeds "
                        f"{self.MAX_FLEET_DUMP_NODES}; pass an explicit "
                        "node list (breeze: --nodes)"
                    )
            elif len(nodes) > self.MAX_FLEET_DUMP_NODES:
                raise ValueError(
                    f"fleet dump of {len(nodes)} nodes exceeds "
                    f"{self.MAX_FLEET_DUMP_NODES}"
                )
            return self.spf_solver.fleet_route_dbs(
                self.area_link_states, self.prefix_state, nodes=nodes
            )

        return self.run_in_event_base_thread(_compute).result()

    def get_adjacency_databases(
        self, select_areas: Optional[set[str]] = None
    ) -> list[AdjacencyDatabase]:
        def _get() -> list[AdjacencyDatabase]:
            out: list[AdjacencyDatabase] = []
            for area, ls in self.area_link_states.items():
                if not select_areas or area in select_areas:
                    out.extend(ls.get_adjacency_databases().values())
            return out

        return self.run_in_event_base_thread(_get).result()

    def what_if(
        self,
        scenarios: list[list[tuple[str, str]]],
        area: str = "0",
        sources: Optional[list[str]] = None,
    ) -> list[dict]:
        """Batched SRLG what-if failure analysis (operator surface over
        ops.protection.srlg_what_if; new capability vs the reference)."""

        def _compute() -> list[dict]:
            from .protection_api import what_if as run

            ls = self.area_link_states.get(area)
            if ls is None:
                return []
            # default the impact view to this router (all-sources at scale
            # is cubic output and would stall the Decision thread)
            srcs = sources if sources is not None else [self.my_node_name]
            csr = self._protection_csr(ls)
            if csr is not None:
                # build (and count) the mirror's pair index for this
                # edge-array state; the resolve in `run` then reads it
                csr.pair_edge_ids(self.spf_solver._bump)
            return run(ls, scenarios, srcs, csr=csr)

        return self.run_in_event_base_thread(_compute).result()

    def _protection_csr(self, ls):
        """Reuse the device backend's incrementally-maintained CSR mirror
        when available (spf_solver.DeviceSpfBackend.csr_mirror)."""
        mirror = getattr(self.spf_solver.spf, "csr_mirror", None)
        return mirror(ls) if mirror is not None else None

    def get_ti_lfa(self, node: str = "", area: str = "0") -> dict:
        """Per-adjacency TI-LFA backup analysis (operator surface over
        ops.protection.ti_lfa_backups; new capability vs the reference)."""

        def _compute() -> dict:
            from .protection_api import ti_lfa as run

            ls = self.area_link_states.get(area)
            if ls is None:
                return {"node": node or self.my_node_name, "error": "no area"}
            return run(
                ls, node or self.my_node_name, csr=self._protection_csr(ls)
            )

        return self.run_in_event_base_thread(_compute).result()

    def get_received_routes(self, **filters) -> list:
        return self.run_in_event_base_thread(
            lambda: self.prefix_state.get_received_routes_filtered(**filters)
        ).result()

    def set_rib_policy(self, cfg: RibPolicyConfig) -> None:
        if not self._enable_rib_policy:
            raise PolicyError("RibPolicy feature is not enabled")
        policy = RibPolicy(cfg)  # validate on caller thread

        def _set() -> None:
            self.rib_policy = policy
            if self._rib_policy_timeout is not None:
                self._rib_policy_timeout.cancel()
            self._rib_policy_timeout = self.schedule_timeout(
                policy.get_ttl_duration_s(), self._rib_policy_expired
            )
            self.pending_updates.set_needs_full_rebuild()
            self.rebuild_routes("RIB_POLICY_SET")

        self.run_in_event_base_thread(_set).result()

    def _rib_policy_expired(self) -> None:
        self._rib_policy_timeout = None
        self.pending_updates.set_needs_full_rebuild()
        self.rebuild_routes("RIB_POLICY_EXPIRED")

    def get_rib_policy(self) -> RibPolicyConfig:
        if not self._enable_rib_policy:
            raise PolicyError("RibPolicy feature is not enabled")

        def _get() -> RibPolicyConfig:
            if self.rib_policy is None:
                raise PolicyError("No RIB policy configured")
            return self.rib_policy.to_config()

        return self.run_in_event_base_thread(_get).result()

    def clear_rib_policy(self) -> None:
        if not self._enable_rib_policy:
            raise PolicyError("RibPolicy feature is not enabled")

        def _clear() -> None:
            if self.rib_policy is None:
                raise PolicyError("No RIB policy configured")
            self.rib_policy = None
            if self._rib_policy_timeout is not None:
                self._rib_policy_timeout.cancel()
                self._rib_policy_timeout = None
            self.pending_updates.set_needs_full_rebuild()
            self.rebuild_routes("RIB_POLICY_CLEARED")

        self.run_in_event_base_thread(_clear).result()
