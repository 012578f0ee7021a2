"""Device-resident tensor mirror of the link-state graph.

The reference walks a pointer graph (LinkState::linkMap_) per Dijkstra run;
the TPU build mirrors the topology once into padded directed-edge arrays
(CSR-style, sorted by destination for segment ops) and batches every SPF
question over it (openr_tpu.ops.sssp).

Shape discipline: node/edge capacities are padded to power-of-two buckets so
incremental topology changes re-use compiled kernels; a rebuild only grows
capacity when the bucket overflows.  Padding edges carry edge_up=False and
point at the last padding node, keeping the dst-sorted invariant.

String node ids are interned to dense int32 here — nothing above this layer
touches the device, nothing below it sees a string.
"""

from __future__ import annotations

import bisect
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from .link_state import Link, LinkState, NodeSpfResult, SpfResult

# counter bumped each time pair_edge_ids() builds its index
PAIR_INDEX_BUILDS = "decision.whatif_pair_index_builds"


def _next_pow2(n: int, floor: int = 8) -> int:
    c = floor
    while c < n:
        c *= 2
    return c


def _build_out_slots(
    edge_src: np.ndarray,
    edge_dst: np.ndarray,
    n_edges: int,
    live: Optional[np.ndarray] = None,
) -> tuple[np.ndarray, int]:
    """out_slot[e] = rank of edge e's dst among src(e)'s sorted unique
    out-neighbors (parallel links share the slot); -1 for padding.
    Node ids are assigned in sorted-name order, so id rank == the
    reference's name-sorted neighbor ordering.  Vectorized numpy.

    `live` (the freelist's per-slot mask) excludes retired edge slots
    inside [:n_edges]: dead slots rank as padding (-1), never as
    out-neighbors of the padding node."""
    e_cap = len(edge_src)
    out_slot = np.full(e_cap, -1, dtype=np.int32)
    if n_edges == 0:
        return out_slot, 0
    if live is None:
        ids = np.arange(n_edges, dtype=np.int64)
    else:
        ids = np.flatnonzero(live[:n_edges]).astype(np.int64)
        if ids.size == 0:
            return out_slot, 0
    src = edge_src[ids].astype(np.int64)
    dst = edge_dst[ids].astype(np.int64)
    order = np.lexsort((dst, src))
    s_o, d_o = src[order], dst[order]
    new_grp = np.r_[True, s_o[1:] != s_o[:-1]]
    new_nbr = new_grp | np.r_[False, d_o[1:] != d_o[:-1]]
    nbr_rank = np.cumsum(new_nbr) - 1  # global distinct-neighbor counter
    grp_id = np.cumsum(new_grp) - 1
    first_rank = nbr_rank[new_grp]  # [n_groups]
    slots = (nbr_rank - first_rank[grp_id]).astype(np.int32)
    out_slot[ids[order]] = slots
    return out_slot, int(slots.max()) + 1


@dataclass
class RewireDelta:
    """One bounded in-place edge-set change (an OCS rewire) applied by
    CsrTopology._try_rewire.  Everything the device-residency engine
    needs to patch its mirror with masked writes instead of a restage:
    the rewritten edge-array slots (post-rewire values), the out_slot
    entries whose rank moved, and the full post-rewire contents of every
    re-encoded ELL destination row."""

    seq: int  # csr.rewire_seq after this rewire (contiguous chain)
    version: int  # LinkState.version the rewire landed at
    slots: np.ndarray  # [M] int32 — edge slots rewritten in place
    src: np.ndarray  # [M] int32
    dst: np.ndarray  # [M] int32
    metric: np.ndarray  # [M] int32
    up: np.ndarray  # [M] bool
    live: np.ndarray  # [M] bool
    out_idx: np.ndarray  # int32 — out_slot entries whose rank changed
    out_val: np.ndarray  # int32
    # [(bucket index, local row, nbr, w, eid, ok, transit_ok)] — full
    # post-rewire row contents in the ELL bucket layout
    ell_rows: list
    n_edges: int  # post-rewire high-water edge count
    max_out_slots: int  # post-rewire first-hop slot ceiling
    links_added: int
    links_removed: int


@dataclass
class CsrTopology:
    """Padded directed-edge arrays + host-side interning tables."""

    node_names: list[str]  # dense id -> name (sorted)
    node_id: dict[str, int]
    n_nodes: int  # real node count
    node_capacity: int
    edge_capacity: int
    # numpy host arrays (device transfer happens at kernel call sites)
    edge_src: np.ndarray  # [E_cap] int32
    edge_dst: np.ndarray  # [E_cap] int32
    edge_metric: np.ndarray  # [E_cap] int32
    edge_up: np.ndarray  # [E_cap] bool
    node_overloaded: np.ndarray  # [N_cap] bool
    # directed edge id -> (Link, from_node_name), or None for a retired
    # slot; len == n_edges (the high-water edge count)
    edge_links: list[Optional[tuple[Link, str]]]
    n_edges: int = 0
    version: int = -1  # LinkState.version this mirror was built from
    # edge-slot freelist (OCS rewires): live mask over [:n_edges] — a
    # retired slot keeps its position (styled like padding: src = dst =
    # pad node, up False) so the edge arrays, ELL tables and compiled
    # kernels all survive a bounded edge-set change in place
    edge_live: Optional[np.ndarray] = None  # [E_cap] bool
    n_live: int = 0  # live directed edges (2 x live links)
    rewire_seq: int = 0  # bumped once per applied in-place rewire
    _free_slots: list = field(default_factory=list)
    # bounded chain of RewireDeltas for engine consumption; a resident
    # that fell behind the window restages (engine._rewire_sync)
    _rewire_log: list = field(default_factory=list)
    # degree-bucketed ELL mirror (ops.sssp.EllGraph) — the production
    # relaxation tables; rebuilt with the edge arrays
    ell: object = None
    # out_slot[e]: index of edge e's destination among its source node's
    # sorted unique out-neighbors (-1 padding) — feeds the bit-packed
    # device first-hop kernel (ops.sssp.first_hops_ell)
    out_slot: Optional[np.ndarray] = None
    max_out_slots: int = 0  # max distinct out-neighbors over all nodes
    # adaptive fixed-sweep hint for the relax loops (see spf_from); grows
    # by doubling when a run fails to reach the fixed point
    _sweep_hint: int = 16
    # circulant-band decomposition (ops.banded.BandedGraph) — present when
    # the topology has band structure; drives the banded relax kernel
    banded: object = None
    _runner: object = None
    # (key, order, start) of in_edges(); a full rebuild resets it to None
    _in_edges: Optional[tuple] = None
    # (key, pair_keys, ids) of pair_edge_ids(); reset like _in_edges
    _pair_index: Optional[tuple] = None

    @property
    def runner(self):
        """ops.banded.SpfRunner over this mirror: band-aware fixed-sweep
        execution for dist/dag batches (KSP re-runs, what-if, TI-LFA).
        Reads the SAME numpy arrays the mirror refreshes in place, so
        attribute-only refreshes need no runner rebuild."""
        if self._runner is None:
            from ..ops.banded import SpfRunner

            self._runner = SpfRunner(
                self.ell,
                self.banded,
                self.edge_src,
                self.edge_dst,
                self.edge_metric,
                self.edge_up,
                self.node_overloaded,
                self.n_edges,
            )
            # device-pin the runtime arrays (re-staged by refresh())
            self._runner.stage()
        return self._runner

    # -- construction -------------------------------------------------------

    @classmethod
    def from_link_state(
        cls,
        ls: LinkState,
        node_capacity: Optional[int] = None,
        edge_capacity: Optional[int] = None,
    ) -> "CsrTopology":
        names = ls.node_names
        node_id = {n: i for i, n in enumerate(names)}
        n = len(names)
        n_cap = node_capacity or _next_pow2(n + 1)
        assert n_cap > n, "node capacity must exceed node count (padding node)"

        # two directed edges per link; deterministic order: sort by (dst, src)
        rows: list[tuple[int, int, int, bool, Link, str]] = []
        for link in sorted(ls.all_links):
            for u_name in (link.n1, link.n2):
                v_name = link.other_node_name(u_name)
                rows.append(
                    (
                        node_id[v_name],  # dst first: sort key
                        node_id[u_name],
                        link.metric_from_node(u_name),
                        link.is_up(),
                        link,
                        u_name,
                    )
                )
        rows.sort(key=lambda r: (r[0], r[1]))
        e = len(rows)
        assert all(r[2] >= 1 for r in rows), (
            "edge metrics must be >= 1 (distance-ordered DAG propagation "
            "and int32 distance math rely on positive metrics)"
        )
        e_cap = edge_capacity or _next_pow2(e)
        assert e_cap >= e

        pad_node = n_cap - 1
        edge_src = np.full(e_cap, pad_node, dtype=np.int32)
        edge_dst = np.full(e_cap, pad_node, dtype=np.int32)
        edge_metric = np.ones(e_cap, dtype=np.int32)
        edge_up = np.zeros(e_cap, dtype=bool)
        for i, (dst, src, metric, up, _link, _from) in enumerate(rows):
            edge_src[i] = src
            edge_dst[i] = dst
            edge_metric[i] = metric
            edge_up[i] = up

        node_overloaded = np.zeros(n_cap, dtype=bool)
        for name, i in node_id.items():
            node_overloaded[i] = ls.is_node_overloaded(name)
        edge_live = np.zeros(e_cap, dtype=bool)
        edge_live[:e] = True

        from ..ops.banded import build_banded
        from ..ops.sssp import build_ell

        ell = build_ell(
            edge_src, edge_dst, edge_metric, edge_up, node_overloaded, e
        )
        banded = build_banded(edge_src, edge_dst, e, n)
        out_slot, max_out_slots = _build_out_slots(edge_src, edge_dst, e)

        return cls(
            node_names=names,
            node_id=node_id,
            n_nodes=n,
            node_capacity=n_cap,
            edge_capacity=e_cap,
            edge_src=edge_src,
            edge_dst=edge_dst,
            edge_metric=edge_metric,
            edge_up=edge_up,
            node_overloaded=node_overloaded,
            edge_links=[(r[4], r[5]) for r in rows],
            n_edges=e,
            edge_live=edge_live,
            n_live=e,
            version=ls.version,
            ell=ell,
            banded=banded,
            out_slot=out_slot,
            max_out_slots=max_out_slots,
        )

    # directed-edge slots one rewire may touch before the masked-write
    # delta rivals a restage and the full rebuild is the cheaper path
    REWIRE_MAX_SLOTS = 256
    # RewireDeltas retained for engine catch-up; a resident more than
    # this many rewires behind restages instead of replaying
    REWIRE_LOG_DEPTH = 32

    def refresh(self, ls: LinkState) -> bool:
        """Bring the mirror to `ls.version`, in place when possible.

        Returns True when the mirror stayed in place: either only
        link/node ATTRIBUTES changed (metric, up, overload) — the edge
        arrays are updated in place and neither the ELL tables nor
        compiled kernels are touched, because the relaxation reads
        edge_up / node_overloaded at call time — or the edge-set change
        was a BOUNDED rewire (links added/removed/swapped within
        edge_capacity): retired slots are recycled through the edge-slot
        freelist, out_slot is re-ranked and only the affected ELL
        destination rows are re-encoded (_try_rewire), all against the
        same array/ELL objects, so device residency survives too.

        Returns False when the mirror was REBUILT: node-set changes,
        capacity overflow, or an oversized rewire.  Capacities are
        re-used when the new topology still fits, so kernel shapes — and
        therefore XLA compilations — are stable until a capacity bucket
        overflows.  The rebuild path never errors on a rewire the
        freelist could not absorb; it is the graceful fallback."""
        if ls.version == self.version:
            return True
        names = ls.node_names
        same_topology = names == self.node_names and len(
            ls.all_links
        ) * 2 == self.n_live
        if same_topology:
            # identical link OBJECTS?  Identity, not set equality:
            # Link.__eq__ keys on (node, iface) pairs only, so a link that
            # was removed and re-added as a new object would compare equal
            # while our edge_links still points at the retired object
            # (whose metric/up state no longer updates).
            current = {
                id(lp[0]) for lp in self.edge_links if lp is not None
            }
            same_topology = current == {id(link) for link in ls.all_links}
        if not same_topology:
            if self._try_rewire(ls):
                return True
            hint = self._sweep_hint
            rebuilt = CsrTopology.from_link_state(
                ls,
                node_capacity=(
                    self.node_capacity
                    if len(names) < self.node_capacity
                    else None
                ),
                edge_capacity=(
                    self.edge_capacity
                    if len(ls.all_links) * 2 <= self.edge_capacity
                    else None
                ),
            )
            self.__dict__.update(rebuilt.__dict__)
            # the relax depth is a property of the topology shape; keep
            # the learned hint across rebuilds
            self._sweep_hint = hint
            return False

        self._refresh_attributes(ls)
        self.version = ls.version
        if self._runner is not None:
            # re-pin the refreshed values (a stale staged runner would
            # read pre-refresh state); one upload per topology change,
            # amortized over every later dispatch
            self._runner.stage()
        return True

    def _refresh_attributes(self, ls: LinkState) -> None:
        """Re-read metric/up/overload from the shared link objects into
        the arrays, in place (retired slots stay padding)."""
        for e, lp in enumerate(self.edge_links):
            if lp is None:
                continue
            link, from_name = lp
            self.edge_metric[e] = link.metric_from_node(from_name)
            self.edge_up[e] = link.is_up()
        for name, i in self.node_id.items():
            self.node_overloaded[i] = ls.is_node_overloaded(name)

    def _try_rewire(self, ls: LinkState) -> bool:
        """Bounded in-place edge-set change — the OCS slot freelist.

        Retires the removed links' edge slots (styled as padding inside
        [:n_edges]), re-points recycled/appended slots at the added
        links, re-reads attributes, re-ranks out_slot and re-encodes
        only the affected ELL destination rows — all against the SAME
        numpy/ELL objects, so compiled kernels and device residency
        (keyed on object identity) survive.  Appends a RewireDelta to
        the bounded rewire log for the engine's masked-write rung.

        Returns False — leaving the caller to take the full-rebuild
        path, which never errors — on a node-set change, freelist +
        tail-capacity exhaustion, an affected ELL row outgrowing its
        bucket's K headroom, or an oversized delta.  A False return may
        leave the arrays partially patched: the rebuild replaces every
        field from `ls`, so no torn state survives it."""
        if ls.node_names != self.node_names:
            return False
        cur_slots: dict[int, list[int]] = {}
        cur_links: dict[int, Link] = {}
        for e, lp in enumerate(self.edge_links):
            if lp is None:
                continue
            cur_slots.setdefault(id(lp[0]), []).append(e)
            cur_links[id(lp[0])] = lp[0]
        new_links = {id(link): link for link in ls.all_links}
        retiring = sorted(
            s
            for lid, slots in cur_slots.items()
            if lid not in new_links
            for s in slots
        )
        added = sorted(
            link for lid, link in new_links.items() if lid not in cur_slots
        )
        if not retiring and not added:
            return False  # count drift without identity drift: rebuild
        pool = sorted(set(self._free_slots) | set(retiring))
        tail = self.edge_capacity - self.n_edges
        if 2 * len(added) > len(pool) + tail:
            return False  # capacity overflow: rebuild (may grow buckets)
        if len(retiring) + 2 * len(added) > self.REWIRE_MAX_SLOTS:
            return False  # oversized delta: the restage is cheaper

        pad_node = self.node_capacity - 1
        touched: list[int] = []
        affected_dst: set[int] = set()
        for s in retiring:
            affected_dst.add(int(self.edge_dst[s]))
            self.edge_src[s] = pad_node
            self.edge_dst[s] = pad_node
            self.edge_metric[s] = 1
            self.edge_up[s] = False
            self.edge_live[s] = False
            self.edge_links[s] = None
            touched.append(s)
        for link in added:
            for u_name in (link.n1, link.n2):
                v_name = link.other_node_name(u_name)
                metric = link.metric_from_node(u_name)
                assert metric >= 1, (
                    "edge metrics must be >= 1 (distance-ordered DAG "
                    "propagation and int32 distance math rely on "
                    "positive metrics)"
                )
                if pool:
                    s = pool.pop(0)
                else:
                    s = self.n_edges
                    self.n_edges += 1
                    self.edge_links.append(None)
                self.edge_src[s] = self.node_id[u_name]
                self.edge_dst[s] = self.node_id[v_name]
                self.edge_metric[s] = metric
                self.edge_up[s] = link.is_up()
                self.edge_live[s] = True
                self.edge_links[s] = (link, u_name)
                affected_dst.add(int(self.edge_dst[s]))
                touched.append(s)
        self._free_slots = pool
        self.n_live = int(self.edge_live[: self.n_edges].sum())

        # attribute flaps batched into the same version ride along, so
        # the delta's per-slot values and the ELL snapshots below are
        # read from post-refresh state
        self._refresh_attributes(ls)

        # re-encode the affected ELL destination rows in place (same
        # bucket arrays — residency identity survives); the relabeling
        # (new_of_old) is frozen at build time, so a node's row never
        # moves — only its contents change
        new_of_old = np.asarray(self.ell.new_of_old)
        row_lo = []
        lo = 0
        for b in self.ell.buckets:
            row_lo.append(lo)
            lo += b.nbr.shape[0]
        dst_v = self.edge_dst[: self.n_edges]
        live_v = self.edge_live[: self.n_edges]
        rows_patch = []
        for d in sorted(affected_dst):
            eids = np.flatnonzero((dst_v == d) & live_v)
            r = int(new_of_old[d])
            b_idx = bisect.bisect_right(row_lo, r) - 1
            bkt = self.ell.buckets[b_idx]
            k_cap = bkt.nbr.shape[1]
            if len(eids) > k_cap:
                return False  # in-degree outgrew the row's K headroom
            row_nbr = np.zeros(k_cap, dtype=np.int32)
            row_w = np.ones(k_cap, dtype=np.int32)
            row_eid = np.full(k_cap, -1, dtype=np.int32)
            row_ok = np.zeros(k_cap, dtype=bool)
            row_tok = np.zeros(k_cap, dtype=bool)
            k = len(eids)
            if k:
                row_nbr[:k] = new_of_old[self.edge_src[eids]]
                row_w[:k] = self.edge_metric[eids]
                row_eid[:k] = eids.astype(np.int32)
                row_ok[:k] = self.edge_up[eids]
                row_tok[:k] = ~self.node_overloaded[self.edge_src[eids]]
            rows_patch.append(
                (b_idx, r - row_lo[b_idx], row_nbr, row_w, row_eid,
                 row_ok, row_tok)
            )
        # feasibility proven — apply the row patches in place
        for b_idx, lr, rn, rw, re_, ro, rt in rows_patch:
            bkt = self.ell.buckets[b_idx]
            bkt.nbr[lr] = rn
            bkt.w[lr] = rw
            bkt.edge_id[lr] = re_
            bkt.ok[lr] = ro
            bkt.transit_ok[lr] = rt

        new_out, new_max = _build_out_slots(
            self.edge_src, self.edge_dst, self.n_edges, live=self.edge_live
        )
        out_changed = np.flatnonzero(new_out != self.out_slot).astype(
            np.int32
        )
        self.out_slot[:] = new_out
        self.max_out_slots = new_max

        # band structure is host-only (SpfRunner): rebuild it from the
        # live edges and let the runner re-materialize lazily
        from ..ops.banded import build_banded

        self.banded = build_banded(
            self.edge_src, self.edge_dst, self.n_edges, self.n_nodes
        )
        self._runner = None

        # a slot retired and recycled in the same rewire is touched
        # twice; the delta reads final array state, so dedupe (the
        # masked-write kernels require unique indices)
        slots_v = np.asarray(sorted(set(touched)), dtype=np.int32)
        self.rewire_seq += 1
        self._rewire_log.append(
            RewireDelta(
                seq=self.rewire_seq,
                version=ls.version,
                slots=slots_v,
                src=self.edge_src[slots_v].copy(),
                dst=self.edge_dst[slots_v].copy(),
                metric=self.edge_metric[slots_v].copy(),
                up=self.edge_up[slots_v].copy(),
                live=self.edge_live[slots_v].copy(),
                out_idx=out_changed,
                out_val=new_out[out_changed].copy(),
                ell_rows=rows_patch,
                n_edges=self.n_edges,
                max_out_slots=new_max,
                links_added=len(added),
                links_removed=len(retiring) // 2,
            )
        )
        del self._rewire_log[: -self.REWIRE_LOG_DEPTH]
        self.version = ls.version
        return True

    # -- SPF execution ------------------------------------------------------

    def run_batched_spf(
        self,
        sources: list[str],
        use_link_metric: bool = True,
        extra_edge_mask: Optional[np.ndarray] = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Run the device kernel (band-aware fixed-sweep relaxation);
        returns (dist [S, N*], dag [S, E_cap]) as numpy.  N* is n_nodes
        on the banded path and node_capacity on the ELL path — consumers
        index [: n_nodes] either way."""
        src_ids = np.asarray(
            [self.node_id[s] for s in sources], dtype=np.int32
        )
        return self.runner.forward(
            src_ids,
            use_link_metric=use_link_metric,
            extra_edge_mask=(
                None if extra_edge_mask is None else np.asarray(extra_edge_mask)
            ),
        )

    # -- result reconstruction (parity with the host oracle) ----------------

    def slot_neighbors(self, node: str) -> list[str]:
        """Sorted unique out-neighbor names of `node` — slot order of the
        bit-packed device first-hop masks (ids are assigned in sorted-name
        order, so id rank == name rank)."""
        return self._slot_neighbors(self._links_of, node)

    @staticmethod
    def _slot_neighbors(
        links_of: dict[str, list[Link]], node: str
    ) -> list[str]:
        return sorted(
            {link.other_node_name(node) for link in links_of.get(node, ())}
        )

    def to_spf_results(
        self,
        sources: list[str],
        dist: np.ndarray,
        dag: np.ndarray,
        nh_words: Optional[np.ndarray] = None,  # [S, N_cap, W] uint32
    ) -> dict[str, SpfResult]:
        """Convert kernel output into reference-shaped SpfResults: per node
        metric, tie-retaining path_links, and first-hop `next_hops` sets.

        With `nh_words` (ops.sssp.first_hops_ell output) the next-hop sets
        are decoded from the device bitmasks — O(reachable x set bits)
        host work.  Without it, falls back to host DAG propagation
        (O(S x N) — the round-1 bottleneck; kept for dist/dag-only
        callers)."""
        from ..ops.sssp import INF32

        inf = int(INF32)
        out: dict[str, SpfResult] = {}
        links_of = self._links_of  # hoisted: the property walks edge_links
        for row, src_name in enumerate(sources):
            d = dist[row]
            mask = dag[row]
            result: SpfResult = {}
            reachable = [
                i for i in range(self.n_nodes) if d[i] < inf
            ]
            for i in reachable:
                result[self.node_names[i]] = NodeSpfResult(int(d[i]))
            # path links from DAG edges, in host-Dijkstra append order
            for e in np.nonzero(mask[: self.n_edges])[0]:
                link, from_name = self.edge_links[e]
                v = self.node_names[int(self.edge_dst[e])]
                result[v].path_links.append((link, from_name))
            self._host_order_path_links(result)
            src_id = self.node_id[src_name]
            if nh_words is not None:
                slot_names = self._slot_neighbors(links_of, src_name)
                words = nh_words[row]
                for i in reachable:
                    if i == src_id:
                        continue
                    hops = result[self.node_names[i]].next_hops
                    for w in range(words.shape[1]):
                        bits = int(words[i, w])
                        base = 32 * w
                        while bits:
                            b = bits & -bits
                            hops.add(slot_names[base + b.bit_length() - 1])
                            bits ^= b
            else:
                # First hops by host propagation along the DAG in
                # increasing-distance order (metrics >= 1 makes this a
                # topological order).  A direct shortest edge src->v
                # contributes v itself (reference: addNextHop fires while
                # v's set is empty at src's pop and survives unless a
                # strictly shorter path resets it — i.e. iff src->v is a
                # DAG edge).
                order = sorted(
                    reachable, key=lambda i: (int(d[i]), self.node_names[i])
                )
                for i in order:
                    if i == src_id:
                        continue
                    name = self.node_names[i]
                    res = result[name]
                    for link, prev in res.path_links:
                        if prev == src_name:
                            res.next_hops.add(name)
                        else:
                            res.next_hops |= result[prev].next_hops
            out[src_name] = result
        return out

    @staticmethod
    def _host_order_path_links(result: SpfResult) -> None:
        """Order each node's path_links exactly as the host Dijkstra
        appends them — by (dist(prev), prev_name, link): run_spf pops the
        heap by (metric, node name) and iterates each node's links sorted
        (link_state.py run_spf).  trace_one_path's greedy link consumption
        is order-sensitive, so KSP parity with the host needs this."""
        for res in result.values():
            res.path_links.sort(
                key=lambda lp: (result[lp[1]].metric, lp[1], lp[0])
            )

    def in_edges(self) -> tuple[np.ndarray, np.ndarray]:
        """(order, start): the live edge ids sorted by destination, and
        for each node id i its in-edges at order[start[i]:start[i+1]].

        Built once per edge-array state.  An in-place rewire recycles
        retired slots and appends new edges, so the arrays are not kept
        sorted by destination; it bumps rewire_seq (and n_edges when it
        appends), which keys the rebuild.  Attribute refreshes leave the
        index as it is, and a full rebuild resets it."""
        key = (self.rewire_seq, self.n_edges)
        if self._in_edges is None or self._in_edges[0] != key:
            # retired slots (edge_links[e] is None) are not live
            ids = np.flatnonzero(self.edge_live[: self.n_edges])
            order = ids[np.argsort(self.edge_dst[ids], kind="stable")]
            start = np.searchsorted(
                self.edge_dst[order], np.arange(self.n_nodes + 1)
            )
            self._in_edges = (key, order, start)
        return self._in_edges[1], self._in_edges[2]

    def pair_edge_ids(self, bump=None) -> tuple[np.ndarray, np.ndarray]:
        """(pair_keys, ids): the live edge ids sorted by their node pair's
        key lo * node_capacity + hi (lo, hi = the smaller and the larger
        endpoint id), parallel links in id order.  The directed edges of
        every link between a and b are ids[i:j], where i and j are the
        left and right searchsorted of that pair's key in pair_keys.

        Built once per edge-array state, on in_edges()'s key and with its
        discipline; each build calls bump(PAIR_INDEX_BUILDS)."""
        key = (self.rewire_seq, self.n_edges)
        if self._pair_index is None or self._pair_index[0] != key:
            ids = np.flatnonzero(self.edge_live[: self.n_edges])
            src = self.edge_src[ids].astype(np.int64)
            dst = self.edge_dst[ids].astype(np.int64)
            pair = np.minimum(src, dst) * self.node_capacity + np.maximum(
                src, dst
            )
            order = np.argsort(pair, kind="stable")
            self._pair_index = (key, pair[order], ids[order])
            if bump is not None:
                bump(PAIR_INDEX_BUILDS)
        return self._pair_index[1], self._pair_index[2]

    def edges_of_links(self) -> dict:
        """Link -> [directed edge ids] (both directions; parallel links map
        to their own instances)."""
        out: dict = {}
        for e in range(self.n_edges):
            lp = self.edge_links[e]
            if lp is None:  # retired slot (edge freelist)
                continue
            out.setdefault(lp[0], []).append(e)
        return out

    def spf_from(
        self, sources: list[str], use_link_metric: bool = True
    ) -> dict[str, SpfResult]:
        """Full production pipeline: one device call (distances + SP-DAG +
        bit-packed first hops) -> reference-shaped SpfResults."""
        from ..ops import sssp as ops

        src_ids = np.asarray(
            [self.node_id[s] for s in sources], dtype=np.int32
        )
        n_words = max(1, -(-self._max_slots_of(sources) // 32))
        s = len(sources)
        args = (
            src_ids,
            self.ell,
            self.edge_src,
            self.edge_dst,
            self.edge_metric,
            self.edge_up,
            self.node_overloaded,
            self.out_slot,
            n_words,
        )
        # Fixed-sweep execution with an adaptive per-topology hint: a
        # data-dependent while_loop syncs host<->device per iteration on
        # latency-bound transports, so we run `sweep_hint` sweeps (fori) +
        # an in-program convergence verdict and double until it reads 1.
        # The hint tracks the topology's relax depth (weighted-path hop
        # count), which is stable across flaps.
        small = s * self.node_capacity <= (1 << 21)
        while True:
            n_sweeps = self._sweep_hint
            if small:
                # small control-plane query: ONE packed transfer.  This is
                # the host fallback of the degradation ladder — the exact
                # computation the engine's bucketed programs mirror — so
                # there is no engine front-end to route through here.
                packed = np.asarray(
                    # openr: disable=jit-unbucketed-dispatch
                    ops.spf_forward_full_packed(
                        *args,
                        use_link_metric=use_link_metric,
                        n_sweeps=n_sweeps,
                    )
                )
                converged = packed[-1] == 1
            else:
                # bulk batch: int32-widening the dag for packing would
                # dominate memory; take separate fetches instead.  Same
                # ladder-fallback rationale as the packed branch above.
                # openr: disable=jit-unbucketed-dispatch
                dist_j, dag_j, nh_j, ok_j = ops.spf_forward_full(
                    *args,
                    use_link_metric=use_link_metric,
                    n_sweeps=n_sweeps,
                )
                converged = bool(ok_j)
            if converged:
                break
            self._sweep_hint = n_sweeps * 2
        if small:
            n_dist = s * self.node_capacity
            n_dag = s * self.edge_capacity
            dist = packed[:n_dist].reshape(s, self.node_capacity)
            dag = packed[n_dist : n_dist + n_dag].reshape(
                s, self.edge_capacity
            ) != 0
            nh = (
                packed[n_dist + n_dag : -1]
                .view(np.uint32)
                .reshape(s, self.node_capacity, n_words)
            )
        else:
            dist = np.asarray(dist_j)
            dag = np.asarray(dag_j)
            nh = np.asarray(nh_j)
        return self.to_spf_results(sources, dist, dag, nh)

    def _max_slots_of(self, sources: list[str]) -> int:
        """Max distinct out-neighbors over the batch's sources — sizes the
        first-hop bitmask words for this call."""
        links_of = self._links_of
        best = 1
        for s in sources:
            n = len({l.other_node_name(s) for l in links_of.get(s, ())})
            if n > best:
                best = n
        return best

    @property
    def _links_of(self) -> dict[str, list[Link]]:
        links: dict[str, list[Link]] = {}
        for lp in self.edge_links:
            if lp is None:
                continue
            links.setdefault(lp[1], []).append(lp[0])
        return links

    @property
    def max_degree(self) -> int:
        deg: dict[str, set[str]] = {}
        for lp in self.edge_links:
            if lp is None:
                continue
            link, from_name = lp
            deg.setdefault(from_name, set()).add(link.other_node_name(from_name))
        return max((len(v) for v in deg.values()), default=0)


class RowPathView:
    """One (dist, dag) kernel row read as the SpfResult `trace_one_path`
    walks for KSP path extraction: metric and path_links, no first-hop
    sets.

    `name in view` is reachability; `view[name]` decodes that node's
    NodeSpfResult on first access and keeps it for the row.  Its
    path_links are the node's DAG in-edges in the order
    CsrTopology._host_order_path_links gives, (dist(prev), prev name,
    link): a total order, so `trace_one_path` takes the walk it takes on
    the host Dijkstra's result.  A k=2 trace reads the nodes on its way
    back from one destination, not the row's every node."""

    __slots__ = ("_csr", "_dist", "_dag", "_inf", "_nodes")

    def __init__(self, csr: CsrTopology, dist_row: np.ndarray, dag_row: np.ndarray):
        from ..ops.sssp import INF32

        self._csr = csr
        self._dist = dist_row
        self._dag = dag_row
        self._inf = int(INF32)
        self._nodes: dict[str, NodeSpfResult] = {}

    @property
    def decoded(self) -> int:
        """Nodes decoded so far."""
        return len(self._nodes)

    def _dist_of(self, name: str) -> Optional[int]:
        i = self._csr.node_id.get(name)
        if i is None:
            return None
        d = int(self._dist[i])
        return d if d < self._inf else None

    def __contains__(self, name: str) -> bool:
        return self._dist_of(name) is not None

    def __getitem__(self, name: str) -> NodeSpfResult:
        res = self._nodes.get(name)
        if res is not None:
            return res
        metric = self._dist_of(name)
        if metric is None:
            raise KeyError(name)
        csr = self._csr
        order, start = csr.in_edges()
        i = csr.node_id[name]
        eids = order[start[i] : start[i + 1]]
        links = [csr.edge_links[e] for e in eids[self._dag[eids]].tolist()]
        links.sort(key=lambda lp: (self._dist_of(lp[1]), lp[1], lp[0]))
        res = self._nodes[name] = NodeSpfResult(metric, links)
        return res
