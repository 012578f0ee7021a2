"""PrefixState: prefix -> {(node, area) -> PrefixEntry} with change deltas.

Functional equivalent of the reference's PrefixState
(openr/decision/PrefixState.{h,cpp}:22-71).
"""

from __future__ import annotations

from typing import Optional

from ..types import (
    PrefixEntry,
    PrefixForwardingAlgorithm,
    PrefixForwardingType,
    normalize_prefix,
)

NodeAndArea = tuple[str, str]
PrefixEntries = dict[NodeAndArea, PrefixEntry]


class PrefixState:
    def __init__(self) -> None:
        self._prefixes: dict[str, PrefixEntries] = {}
        # (node, area) -> the prefixes it advertises, and the prefixes
        # any advertiser forwards by KSP2_ED_ECMP: the incremental route
        # rebuild finds the routes a changed node can move without a
        # pass over every prefix
        self._by_node: dict[NodeAndArea, set[str]] = {}
        self._ksp2: set[str] = set()

    @property
    def prefixes(self) -> dict[str, PrefixEntries]:
        return self._prefixes

    @property
    def ksp2_prefixes(self) -> set[str]:
        return self._ksp2

    def prefixes_of(self, node: str, area: str) -> set[str]:
        return self._by_node.get((node, area), set())

    def update_prefix(
        self, node: str, area: str, entry: PrefixEntry
    ) -> set[str]:
        """Returns the set of changed prefixes (reference:
        PrefixState::updatePrefix, PrefixState.cpp:16-38)."""
        prefix = normalize_prefix(entry.prefix)
        entries = self._prefixes.setdefault(prefix, {})
        key = (node, area)
        if key in entries and entries[key] == entry:
            return set()
        entries[key] = entry
        self._by_node.setdefault(key, set()).add(prefix)
        self._index_ksp2(prefix, entries)
        return {prefix}

    def delete_prefix(self, node: str, area: str, prefix: str) -> set[str]:
        """Returns the changed prefix set; empty if (node, area) wasn't
        advertising (reference: PrefixState::deletePrefix)."""
        prefix = normalize_prefix(prefix)
        entries = self._prefixes.get(prefix)
        key = (node, area)
        if entries is None or entries.pop(key, None) is None:
            return set()
        advertised = self._by_node[key]
        advertised.discard(prefix)
        if not advertised:
            del self._by_node[key]
        if not entries:
            del self._prefixes[prefix]
        self._index_ksp2(prefix, entries)
        return {prefix}

    def delete_all_from_node(self, node: str, area: str) -> set[str]:
        """Withdraw everything a (node, area) advertised — used when a
        prefix DB key expires from the KvStore."""
        changed: set[str] = set()
        for prefix in list(self.prefixes_of(node, area)):
            changed |= self.delete_prefix(node, area, prefix)
        return changed

    def _index_ksp2(self, prefix: str, entries: PrefixEntries) -> None:
        if any(
            e.forwarding_algorithm == PrefixForwardingAlgorithm.KSP2_ED_ECMP
            for e in entries.values()
        ):
            self._ksp2.add(prefix)
        else:
            self._ksp2.discard(prefix)

    def get_received_routes_filtered(
        self,
        prefixes: Optional[list[str]] = None,
        node_name: Optional[str] = None,
        area_name: Optional[str] = None,
    ) -> list[tuple[str, list[tuple[NodeAndArea, PrefixEntry]]]]:
        """Reference: getReceivedRoutesFiltered (PrefixState.cpp:59-88)."""
        out: list[tuple[str, list[tuple[NodeAndArea, PrefixEntry]]]] = []
        targets = (
            [normalize_prefix(p) for p in prefixes]
            if prefixes is not None
            else sorted(self._prefixes)
        )
        for prefix in targets:
            entries = self._prefixes.get(prefix)
            if not entries:
                continue
            rows = [
                (na, e)
                for na, e in sorted(entries.items())
                if (node_name is None or na[0] == node_name)
                and (area_name is None or na[1] == area_name)
            ]
            if rows:
                out.append((prefix, rows))
        return out

    @staticmethod
    def has_conflicting_forwarding_info(entries: PrefixEntries) -> bool:
        """True if entries disagree on forwarding type/algorithm
        (reference: hasConflictingForwardingInfo)."""
        infos = {
            (e.forwarding_type, e.forwarding_algorithm) for e in entries.values()
        }
        return len(infos) > 1
