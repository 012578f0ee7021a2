"""RIB entries, route DB, and route-update deltas.

Functional equivalents of the reference's RibEntry.h, RouteUpdate.h and
DecisionRouteDb (openr/decision/RibEntry.h, openr/decision/RouteUpdate.h,
openr/decision/Decision.cpp:109-160 calculateUpdate/update).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from ..serializer import register_type
from ..types import (
    MplsRoute,
    NextHop,
    PerfEvents,
    PrefixEntry,
    PrefixType,
    UnicastRoute,
)


@register_type
@dataclass(slots=True)
class RibUnicastEntry:
    """Reference: RibUnicastEntry (openr/decision/RibEntry.h:38-100)."""

    prefix: str  # canonical CIDR
    nexthops: frozenset[NextHop] = frozenset()
    best_prefix_entry: Optional[PrefixEntry] = None
    best_area: str = ""
    do_not_install: bool = False

    def __eq__(self, other) -> bool:
        # bestArea intentionally excluded, matching the reference's
        # operator== (RibEntry.h:66-70)
        return (
            isinstance(other, RibUnicastEntry)
            and self.prefix == other.prefix
            and self.best_prefix_entry == other.best_prefix_entry
            and self.do_not_install == other.do_not_install
            and self.nexthops == other.nexthops
        )

    def to_unicast_route(self) -> UnicastRoute:
        return UnicastRoute(
            dest=self.prefix,
            next_hops=sorted(self.nexthops, key=_nh_sort_key),
        )

    @property
    def is_bgp(self) -> bool:
        return (
            self.best_prefix_entry is not None
            and self.best_prefix_entry.type == PrefixType.BGP
        )


@register_type
@dataclass(slots=True)
class RibMplsEntry:
    """Reference: RibMplsEntry (openr/decision/RibEntry.h:102-145)."""

    label: int
    nexthops: frozenset[NextHop] = frozenset()

    def to_mpls_route(self) -> MplsRoute:
        return MplsRoute(
            top_label=self.label,
            next_hops=sorted(self.nexthops, key=_nh_sort_key),
        )


def _nh_sort_key(nh: NextHop):
    return (
        nh.address,
        nh.if_name or "",
        nh.metric,
        nh.neighbor_node_name or "",
        nh.area or "",
    )


@register_type
@dataclass(slots=True)
class DecisionRouteUpdate:
    """Delta published by Decision, consumed by Fib / PrefixManager / plugin
    (reference: openr/decision/RouteUpdate.h:23)."""

    unicast_routes_to_update: dict[str, RibUnicastEntry] = field(
        default_factory=dict
    )
    unicast_routes_to_delete: list[str] = field(default_factory=list)
    mpls_routes_to_update: list[RibMplsEntry] = field(default_factory=list)
    mpls_routes_to_delete: list[int] = field(default_factory=list)
    perf_events: Optional[PerfEvents] = None

    def add_route_to_update(self, route: RibUnicastEntry) -> None:
        assert route.prefix not in self.unicast_routes_to_update
        self.unicast_routes_to_update[route.prefix] = route

    def empty(self) -> bool:
        return not (
            self.unicast_routes_to_update
            or self.unicast_routes_to_delete
            or self.mpls_routes_to_update
            or self.mpls_routes_to_delete
        )


@register_type
@dataclass(slots=True)
class DecisionRouteDb:
    """Computed route state (reference: DecisionRouteDb,
    openr/decision/Decision.h:56-88)."""

    unicast_routes: dict[str, RibUnicastEntry] = field(default_factory=dict)
    mpls_routes: dict[int, RibMplsEntry] = field(default_factory=dict)

    def add_unicast_route(self, route: RibUnicastEntry) -> None:
        assert route.prefix not in self.unicast_routes, route.prefix
        self.unicast_routes[route.prefix] = route

    def add_mpls_route(self, route: RibMplsEntry) -> None:
        assert route.label not in self.mpls_routes, route.label
        self.mpls_routes[route.label] = route

    def calculate_update(self, new_db: "DecisionRouteDb") -> DecisionRouteUpdate:
        """Reference: DecisionRouteDb::calculateUpdate
        (openr/decision/Decision.cpp:111-147)."""
        unicast: dict[str, Optional[RibUnicastEntry]] = dict.fromkeys(
            self.unicast_routes
        )
        unicast.update(new_db.unicast_routes)
        mpls: dict[int, Optional[RibMplsEntry]] = dict.fromkeys(self.mpls_routes)
        mpls.update(new_db.mpls_routes)
        return self.calculate_partial_update(unicast, mpls)

    def calculate_partial_update(
        self,
        unicast: dict[str, Optional[RibUnicastEntry]],
        mpls: dict[int, Optional[RibMplsEntry]],
    ) -> DecisionRouteUpdate:
        """calculate_update over the given prefixes and labels only, each
        mapped to its new entry or to None where the new DB has none."""
        delta = DecisionRouteUpdate()
        for prefix, entry in unicast.items():
            old = self.unicast_routes.get(prefix)
            if entry is None:
                if old is not None:
                    delta.unicast_routes_to_delete.append(prefix)
            elif old is None or old != entry:
                delta.add_route_to_update(entry)
        for label, entry in mpls.items():
            old = self.mpls_routes.get(label)
            if entry is None:
                if old is not None:
                    delta.mpls_routes_to_delete.append(label)
            elif old is None or old != entry:
                delta.mpls_routes_to_update.append(entry)
        return delta

    def update(self, delta: DecisionRouteUpdate) -> None:
        """Apply a delta (reference: DecisionRouteDb::update,
        Decision.cpp:149-163)."""
        for prefix in delta.unicast_routes_to_delete:
            self.unicast_routes.pop(prefix, None)
        for prefix, entry in delta.unicast_routes_to_update.items():
            self.unicast_routes[prefix] = entry
        for label in delta.mpls_routes_to_delete:
            self.mpls_routes.pop(label, None)
        for entry in delta.mpls_routes_to_update:
            self.mpls_routes[entry.label] = entry
