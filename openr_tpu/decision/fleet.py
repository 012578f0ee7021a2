"""Fleet route view: the daemon consumer of the reduced all-sources product.

One reverse-SSSP device round (openr_tpu.ops.allsources) answers every
router's route build toward the destination set that route construction
actually reads — the prefix-advertising nodes plus every labeled node.
This is the in-daemon consumer of the round-4 flagship product; the
reference's equivalent consumer is the per-prefix route build
(openr/decision/Decision.cpp:615-793, createRouteForPrefix reads best-entry
node distances) and the any-node ctrl query
(openr/decision/Decision.cpp:1510-1530, getDecisionRouteDb).

Why the product suffices: the reverse distances dist[v, p] == dist(v -> p)
cover EVERY router v, so for any router `me` the route build has
- reachability:  dist(me -> advertiser) < INF
- best-metric:   min over advertisers of dist(me -> advertiser)
- LFA-free ECMP: link (me -l-> u) is a next hop toward p iff
                 metric(l) + dist(u -> p) == dist(me -> p)
                 (openr/decision/Decision.cpp:1296-1300), with the drain
                 exception (overloaded u only as the destination itself,
                 dist(u -> p) == 0) — all reads of the same [N, P] matrix.
The fused [N, P, W] bitmap is the device-side fleet-wide evaluation of the
same condition (ops.allsources.ecmp_bitmap_from_reverse_dist); the host
hooks in SpfSolver evaluate it per link so parallel links keep their
per-link metric semantics, and tests cross-check the two.

A view is a SNAPSHOT of one LinkState version: the runtime arrays are
copied at build time (the CSR mirror refreshes its arrays in place), and
the cache invalidates on version or destination-set change.
"""

from __future__ import annotations

import functools
import logging
import weakref
from typing import Optional

import numpy as np

from .link_state import LinkState

# mirrors ops.sssp.INF32 / ops.banded.INF16 (plain ints here so importing
# the decision layer does not pull jax; tests/test_fleet.py asserts both
# stay equal to the ops constants)
INF32 = 1 << 30
INF16 = 40000


def _row_i32(row: np.ndarray) -> np.ndarray:
    """Normalize a fetched distance row to the int32/INF32 contract —
    the device product runs raw uint16 (INF16 sentinel) when the banded
    kernel's small-distance mode engages (ops.banded raw_u16)."""
    if row.dtype == np.uint16:
        return np.where(row >= INF16, INF32, row.astype(np.int32))
    return row

log = logging.getLogger(__name__)


def _usable_edge_table(csr):
    """Canonical (directed-pair key, min metric) table of USABLE edges —
    the improvement-only gate's comparison unit.  Distances depend only
    on the min metric per usable directed (src, dst) pair (parallel
    links matter for next-hop slots, not distances)."""
    e = csr.n_edges
    up = np.asarray(csr.edge_up[:e], dtype=bool)
    src = np.asarray(csr.edge_src[:e], dtype=np.int64)[up]
    dst = np.asarray(csr.edge_dst[:e], dtype=np.int64)[up]
    met = np.asarray(csr.edge_metric[:e], dtype=np.int64)[up]
    key = (src << 32) | dst
    order = np.argsort(key, kind="stable")
    key, met = key[order], met[order]
    first = np.r_[True, key[1:] != key[:-1]]
    uniq = key[first]
    min_met = np.minimum.reduceat(met, np.flatnonzero(first))
    return uniq, min_met


def _improvement_only(
    old_keys, old_met, old_ov, new_keys, new_met, new_ov
) -> bool:
    """True iff the new graph can only have SHORTER-OR-EQUAL distances
    than the old one: every old usable directed pair is still usable
    with metric <= old, and no node gained the overload bit.  This is
    the warm-start proof obligation of ops.banded.spf_forward_banded —
    under it the previous product is an elementwise upper bound."""
    if np.any(new_ov & ~old_ov):
        return False
    pos = np.searchsorted(new_keys, old_keys)
    if np.any(pos >= len(new_keys)) or np.any(
        new_keys[np.minimum(pos, max(len(new_keys) - 1, 0))] != old_keys
    ):
        return False
    return bool(np.all(new_met[pos] <= old_met))


def _in_sorted(keys: np.ndarray, q: np.ndarray) -> np.ndarray:
    """Vectorized membership of q in the sorted key array."""
    if len(keys) == 0:
        return np.zeros(q.shape, dtype=bool)
    pos = np.searchsorted(keys, q)
    pos_c = np.minimum(pos, len(keys) - 1)
    return (pos < len(keys)) & (keys[pos_c] == q)


def _worsened_masks(prev: "FleetRouteView", new_keys, new_met, new_ov):
    """Per-reverse-slot masks of WORSENED forward edges, in the layout of
    the previous view's reverse runner (bg.resid slots + band
    positions) — the seed of the affected-set propagation
    (ops.banded.affected_mask).

    Worsened means the edge can only LENGTHEN paths that used it:
    - old usable directed pair now unusable (link down / all parallel
      links down),
    - old pair still usable but its min metric increased,
    - transit through a newly-overloaded node (drain): every reverse
      edge SOURCED at that node — conservatively including the
      destination-row exception, which only over-marks.
    Edges that improved or appeared are NOT worsened: the old product
    stays an upper bound wherever no worsened edge is on every old
    shortest path, even when improvements happen in the same delta
    (improvements only loosen the bound, and the relax fixes looseness;
    the verification certifies exactness either way)."""
    old_keys, old_met = prev._edge_keys, prev._edge_met
    present = _in_sorted(new_keys, old_keys)
    pos = np.minimum(
        np.searchsorted(new_keys, old_keys), max(len(new_keys) - 1, 0)
    )
    worse = ~present
    if len(new_keys):
        worse |= present & (new_met[pos] > old_met)
    bad_keys = old_keys[worse]  # sorted (subset of sorted old_keys)
    newly_ov = new_ov & ~prev._overloaded
    bg = prev._runner.bg
    n = bg.n_nodes
    rn = np.asarray(bg.resid_nbr)
    re_ = np.asarray(bg.resid_eid)
    # reverse edge u -> v is forward edge v -> u: forward key (v, u)
    v_ids = np.arange(n, dtype=np.int64)
    qk = (v_ids[:, None] << 32) | rn.astype(np.int64)
    worsened_resid = (re_ >= 0) & (
        _in_sorted(bad_keys, qk) | newly_ov[rn]
    )
    be = np.asarray(bg.band_eid)
    rows = []
    for b, c in enumerate(bg.offsets):
        u = (v_ids - c) % n
        qk = (v_ids << 32) | u
        rows.append(
            (be[b] >= 0) & (_in_sorted(bad_keys, qk) | newly_ov[u])
        )
    return worsened_resid, np.stack(rows)


def _affected_init(prev: "FleetRouteView", new: "FleetRouteView"):
    """Device init for a worsening-direction warm start: the previous
    distances with every possibly-affected entry re-set to INF, or None
    when the affected-set propagation could not certify its fixpoint
    (the caller must cold-start).

    Safety argument (the worsening mirror of _improvement_only): an
    entry is re-relaxed from INF whenever ANY old tight chain into it
    crosses a worsened edge (affected_mask, certified fixpoint), so
    every kept entry has an old shortest path that survives un-worsened
    — its old value is still an elementwise UPPER bound in the new
    graph — and the warm relax plus verification then reproduce the
    cold fixed point bit-for-bit (ops.banded.spf_forward_banded)."""
    import jax.numpy as jnp

    from ..ops.banded import affected_mask

    runner = prev._runner
    if runner is None or runner.bg is None or prev._dist_dev is None:
        return None
    worsened_resid, worsened_band = _worsened_masks(
        prev, new._edge_keys, new._edge_met, new._overloaded
    )
    small = prev._dist_dev.dtype == np.uint16
    _, _, r_met, r_up, r_ov = runner.call_arrays()
    aff, done = affected_mask(
        prev._dist_dev,
        runner.bg,
        r_up,
        r_met,
        r_ov,
        jnp.asarray(worsened_resid),
        jnp.asarray(worsened_band),
        small_dist=bool(small),
        max_iters=128,
    )
    # explicit single-scalar fetch: the certification verdict decides
    # warm-start vs cold rebuild on the host
    import jax

    if not jax.device_get(done):
        return None
    inf = jnp.uint16(INF16) if small else jnp.int32(INF32)
    return jnp.where(aff, inf, prev._dist_dev[: runner.bg.n_nodes])


def _reverse_runner(csr, hint: Optional[int] = None):
    """SpfRunner over the REVERSED directed edges of a CsrTopology
    snapshot (same construction as benchmarks.synthetic.reversed_topology,
    but from the daemon's mirror).  `hint` seeds the learned fixed-sweep
    count — the relax depth is a property of the topology shape, so
    re-learning it by doubling on every rebuild would pay failed
    full-P-source dispatches per link flap (DeviceSpfBackend._hint_by_shape
    discipline)."""
    from ..ops.banded import SpfRunner, build_banded
    from ..ops.sssp import build_ell

    # retired freelist slots (csr rewires) are padding inside
    # [:n_edges]; the reversed snapshot renumbers edges into its own
    # dense space anyway, so compact them away here
    live = getattr(csr, "edge_live", None)
    if live is None:
        ids = np.arange(csr.n_edges)
    else:
        ids = np.flatnonzero(live[: csr.n_edges])
    e = len(ids)
    src = csr.edge_dst[ids].copy()
    dst = csr.edge_src[ids].copy()
    met = csr.edge_metric[ids].copy()
    up = csr.edge_up[ids].copy()
    order = np.lexsort((src, dst))
    pad_node = csr.node_capacity - 1
    edge_src = np.full(csr.edge_capacity, pad_node, dtype=np.int32)
    edge_dst = np.full(csr.edge_capacity, pad_node, dtype=np.int32)
    edge_metric = np.ones(csr.edge_capacity, dtype=np.int32)
    edge_up = np.zeros(csr.edge_capacity, dtype=bool)
    edge_src[:e] = src[order]
    edge_dst[:e] = dst[order]
    edge_metric[:e] = met[order]
    edge_up[:e] = up[order]
    node_overloaded = csr.node_overloaded.copy()
    ell = build_ell(
        edge_src, edge_dst, edge_metric, edge_up, node_overloaded, e
    )
    banded = build_banded(edge_src, edge_dst, e, csr.n_nodes)
    runner = SpfRunner(
        ell,
        banded,
        edge_src,
        edge_dst,
        edge_metric,
        edge_up,
        node_overloaded,
        e,
    )
    if hint is not None:
        runner.hint = hint
    # snapshot arrays are immutable for the view's lifetime: pin them
    # device-resident so repeat computes/queries skip the re-upload
    runner.stage()
    return runner


class FleetRouteView:
    """Snapshot answering dist/ECMP queries for every (router, dest) pair.

    `dest_names` must cover every node route construction asks distances
    to: prefix advertisers + labeled nodes (fleet_destinations)."""

    def __init__(self, csr, dest_names: list[str], engine=None) -> None:
        self.csr = csr
        self.version = csr.version
        # device-residency engine (openr_tpu.device): when present, the
        # fleet product dispatches through its front-end (chaos fault
        # hook + device.engine.* dispatch accounting)
        self._engine = engine
        self.dest_names = list(dest_names)
        self.p_index = {name: i for i, name in enumerate(self.dest_names)}
        self._node_id = dict(csr.node_id)
        # runtime-state snapshot for the host-side per-link checks
        self._overloaded = csr.node_overloaded.copy()
        # canonical usable-edge table for the warm-start improvement gate
        # (the next view compares against it; ~10ms host work at 800k)
        self._edge_keys, self._edge_met = _usable_edge_table(csr)
        self._dist_dev = None  # jax [N*, P] — row per router (native
        #   kernel layout; a router's fetch is one contiguous row)
        self._bitmap_dev = None  # jax [N, P, W]
        self._out = None  # ops.allsources.OutEll
        self._rows: dict[int, np.ndarray] = {}  # node id -> [P] int32
        self.converged = False
        self.cold_fallback = False  # warm gate failed; cache retried cold
        self.warm = False  # computed from a previous view's distances
        # None | "improve" | "worsen" — which warm gate admitted the seed
        self.warm_mode: Optional[str] = None
        self.sweep_hint: Optional[int] = None
        # True when the blocked node-sharded rung served this view: its
        # [N, P] int32 product is NOT a valid warm/delta seed for the
        # banded relax (dtype/shape contract differs), so the cache
        # skips seeding from it
        self.node_sharded = False
        self._runner = None  # retained for the NEXT view's worsening
        #   warm start: affected-set propagation runs over THIS view's
        #   reverse graph and distances (_affected_init)

    # -- device round --------------------------------------------------------

    def compute(
        self,
        hint_seed: Optional[int] = None,
        init_from: Optional["FleetRouteView"] = None,
        warm_seed: Optional[int] = None,
        down_from: Optional["FleetRouteView"] = None,
    ) -> None:
        """One device ROUND — the P-source reverse relax with the ECMP
        bitmap folded into its final verification supersweep
        (reduced_all_sources' fused progressive fast path; the product
        is read once and convergence is certified on-device).
        `hint_seed` carries the previous view's learned COLD sweep
        count across topology versions (same-shape seeding, legacy
        fixed-sweep paths only).

        `init_from` warm-starts the relax from a previous view's device
        distances.  The CALLER (FleetViewCache.view) must have proven
        the improvement-only gate (_improvement_only) plus node/dest
        universe equality — an un-gated init can silently fix-point
        below the true distances (ops.banded.spf_forward_banded).
        `down_from` is the WORSENING-direction counterpart: the same
        universe equality, but the change removed/worsened edges — the
        seed is the previous distances with the certified affected set
        re-set to INF (_affected_init); when the certification fails
        the run silently cold-starts.  `warm_seed` is the sweep seed
        used ONLY when a warm path actually engages; whether it does
        depends on the runner's bandedness, which is known only after
        the runner is built here (the ELL fallback ignores dist0 and
        must keep the cold seed).  Callers read `self.warm` /
        `self.warm_mode` afterwards to route hint harvesting and
        counters."""
        from ..ops import allsources as asrc

        dest_ids = np.asarray(
            [self._node_id[d] for d in self.dest_names], dtype=np.int32
        )
        self._out = asrc.build_out_ell(
            self.csr.edge_src,
            self.csr.edge_dst,
            self.csr.n_edges,
            self.csr.n_nodes,
            out_slot=self.csr.out_slot,
        )
        # third rung: node-axis sharded blocked APSP (parallel.blocked)
        # when N outgrows the single-chip [N, P] ceiling (or the env
        # forces it).  Any failure — mesh-shape mismatch, tile/device
        # mismatch, an injected chaos fault mid-run — falls through to
        # the dest-sharded fused product below, which is the bit-exact
        # fallback.
        blocked = (
            getattr(self._engine, "blocked", None)
            if self._engine is not None
            else None
        )
        if blocked is not None and blocked.should_engage(self.csr.n_nodes):
            try:
                dist, bitmap, ok = blocked.fleet_product(
                    self.csr, dest_ids, self._out
                )
            except Exception:
                blocked._bump("mesh.blocked.fallbacks")
                log.warning(
                    "fleet: blocked-APSP rung failed; falling back to "
                    "the dest-sharded fused product",
                    exc_info=True,
                )
            else:
                # `ok` is host-side by the rung's contract (the closure
                # is exact after T rounds; no convergence certificate
                # to fetch)
                assert ok
                self._dist_dev = dist
                self._bitmap_dev = bitmap
                self.converged = True
                self.warm = False
                self.warm_mode = None
                self.sweep_hint = None
                self._runner = None
                self.node_sharded = True
                return
        runner = _reverse_runner(self.csr, hint=hint_seed)
        init = None
        self.warm_mode = None
        if runner.bg is not None:
            # the ELL fallback ignores dist0 (cold run): claiming warm
            # would mislabel the view AND poison _warm_hints with a cold
            # sweep count
            if init_from is not None:
                init = init_from._dist_dev
                self.warm_mode = "improve"
            elif down_from is not None:
                init = _affected_init(down_from, self)
                if init is not None:
                    self.warm_mode = "worsen"
        if init is not None and warm_seed is not None:
            runner.hint = warm_seed
        maps = (
            asrc.build_epilogue_maps(runner.bg, self._out)
            if runner.bg is not None
            else None
        )
        # engine front-end (openr_tpu.device): fault-hook + dispatch
        # accounting around the fused product; the direct call remains
        # the engine-less fallback path
        product = (
            functools.partial(
                self._engine.dispatch,
                "fleet_product",
                asrc.reduced_all_sources,
            )
            if self._engine is not None
            else asrc.reduced_all_sources
        )
        dist, bitmap, ok = product(
            dest_ids,
            runner,
            self._out,
            self.csr.edge_metric,
            self.csr.edge_up,
            self.csr.node_overloaded,
            init_dist=init,
            maps=maps,
        )
        # `ok` is a host bool by reduced_all_sources' contract (fetched
        # inside, fused with the block-counter read)
        if not ok and init is not None:
            # the warm relax exhausted its block budget without the
            # on-device certificate: the seed bought nothing — pay the
            # cold run rather than serve an uncertified product
            init = None
            self.warm_mode = None
            if hint_seed is not None:
                runner.hint = hint_seed
            dist, bitmap, ok = product(
                dest_ids,
                runner,
                self._out,
                self.csr.edge_metric,
                self.csr.edge_up,
                self.csr.node_overloaded,
                maps=maps,
            )
        # host bool per the same contract
        assert ok, "fleet reverse SSSP did not reach its fixed point"
        self._dist_dev = dist
        self._bitmap_dev = bitmap
        self.converged = True
        self.warm = init is not None
        self.sweep_hint = runner.hint
        self._runner = runner

    # -- host queries --------------------------------------------------------

    def covers(self, node: str) -> bool:
        return node in self._node_id

    def is_dest(self, node: str) -> bool:
        return node in self.p_index

    def _row(self, node: str) -> np.ndarray:
        """dist(node -> every dest), [P] int32; fetched lazily and cached
        (one device row fetch per new node — a ctrl query touches only
        the queried router and its neighbors)."""
        import jax

        i = self._node_id[node]
        hit = self._rows.get(i)
        if hit is None:
            hit = _row_i32(jax.device_get(self._dist_dev[i]))
            self._rows[i] = hit
        return hit

    def prefetch_rows(self, nodes: list[str]) -> None:
        """Fetch many routers' rows in one device gather (fleet dumps)."""
        import jax
        import jax.numpy as jnp

        ids = [self._node_id[n] for n in nodes if n in self._node_id]
        missing = [i for i in ids if i not in self._rows]
        if not missing:
            return
        rows = _row_i32(
            jax.device_get(
                jnp.take(
                    self._dist_dev, jnp.asarray(missing, jnp.int32), axis=0
                )
            )
        )
        for k, i in enumerate(missing):
            self._rows[i] = rows[k]

    def dist(self, node: str, dest: str) -> int:
        """dist(node -> dest); INF32 when unreachable."""
        d = self._row(node)[self.p_index[dest]]
        return int(d)

    def reachable(self, node: str, dest: str) -> bool:
        return self.dist(node, dest) < INF32

    def is_overloaded_id(self, node: str) -> bool:
        return bool(self._overloaded[self._node_id[node]])

    def next_hop_neighbors(self, node: str, dest: str) -> set[str]:
        """Decode the device bitmap row: slot-named ECMP next-hop
        neighbors of `node` toward `dest` (unique neighbors; parallel
        links share a slot).  Used by tests/dumps to cross-check the
        host-side per-link evaluation."""
        import jax

        i = self._node_id[node]
        p = self.p_index[dest]
        words = jax.device_get(self._bitmap_dev[i, p])
        slot_names = self.csr.slot_neighbors(node)
        out: set[str] = set()
        for w in range(words.shape[0]):
            bits = int(words[w])
            base = 32 * w
            while bits:
                b = bits & -bits
                out.add(slot_names[base + b.bit_length() - 1])
                bits ^= b
        return out


def fleet_destinations(ls: LinkState, prefix_state) -> list[str]:
    """The destination set route construction reads distances to, for one
    area: prefix-advertising nodes (reachability filter + unicast ECMP,
    Decision.cpp:445-613) + labeled nodes (MPLS node-label routes,
    Decision.cpp:655-745).  Sorted for a deterministic cache key."""
    dests: set[str] = set()
    for entries in prefix_state.prefixes.values():
        for node, _area in entries:
            if ls.has_node(node):
                dests.add(node)
    for node, adj_db in ls.get_adjacency_databases().items():
        if adj_db.node_label != 0 and ls.has_node(node):
            dests.add(node)
    return sorted(dests)


class FleetViewCache:
    """Per-LinkState cached FleetRouteView, invalidated on topology
    version or destination-set change.  Weakly keyed like
    DeviceSpfBackend's mirrors (ids recycle after GC).

    `delta` opts in to the incremental delta rung (decision.delta +
    ops.delta through the engine's delta_dispatch): a rebuild over the
    same universe first tries to fold the whole pending event batch into
    the previous device product at frontier-proportional cost, falling
    back to the legacy warm/cold paths below on any gate failure.
    Default OFF (None reads OPENR_FLEET_DELTA): the rung re-labels
    warm_mode and shifts counters, so existing deployments and the
    warm-path tests keep their exact behavior unless asked."""

    def __init__(
        self,
        delta: Optional[bool] = None,
        bump=None,
        delta_min_p: int = 32,
        delta_parity: Optional[bool] = None,
    ) -> None:
        import os

        if delta is None:
            delta = os.environ.get("OPENR_FLEET_DELTA", "0") == "1"
        self._delta = None
        if delta:
            from .delta import DeltaProductUpdater

            self._delta = DeltaProductUpdater(
                bump=bump, min_p=delta_min_p, parity=delta_parity
            )
        self._views: "weakref.WeakKeyDictionary[LinkState, FleetRouteView]" = (
            weakref.WeakKeyDictionary()
        )
        # learned reverse-relax sweep hints keyed by topology shape
        # (node/edge counts — the DeviceSpfBackend._hint_key discipline):
        # a rebuilt view of a same-shaped topology starts from the learned
        # count instead of re-learning it by doubling
        self._hints: dict[tuple[int, int], int] = {}
        # warm (previous-product-seeded) rebuilds converge in far fewer
        # sweeps than cold ones; learning them into _hints would poison
        # every later cold rebuild, so they get their own store
        self._warm_hints: dict[tuple[int, int], int] = {}

    def is_warm(self, ls: LinkState, dest_names: list[str]) -> bool:
        """True when a cached view already answers this (version, dests) —
        i.e. using the fleet path costs zero device work."""
        cached = self._views.get(ls)
        return (
            cached is not None
            and cached.version == ls.version
            and cached.dest_names == list(dest_names)
        )

    def view(
        self, ls: LinkState, dest_names: list[str], csr=None, engine=None
    ) -> Optional[FleetRouteView]:
        """Computed view for this (version, dests); None when empty.

        A rebuild WARM-STARTS from the previous view's device distances
        in BOTH change directions over the same node/dest universe:
        improvement-only changes (link up, metric decrease, overload
        clear) seed the full previous product — the upper-bound
        condition ops.banded.spf_forward_banded requires — while
        worsening/mixed changes (link down, metric increase, drain)
        seed the previous product with the certified affected set
        re-set to INF (_affected_init), the mirror-image upper bound.
        Either way reconvergence pays a few relax sweeps instead of the
        full cold count; only universe changes and uncertifiable
        affected sets still cold-start."""
        if not dest_names:
            return None
        if self.is_warm(ls, dest_names):
            return self._views[ls]
        if csr is None:
            from .csr import CsrTopology

            csr = CsrTopology.from_link_state(ls)
        elif csr.version != ls.version:
            csr.refresh(ls)
        prev = self._views.get(ls)
        view = FleetRouteView(csr, dest_names, engine=engine)
        # incremental rung first: fold the whole pending event batch
        # into the previous device product at frontier-proportional
        # cost; any gate failure falls through to the legacy warm/cold
        # paths below, which are the bit-exact fallback
        if (
            self._delta is not None
            and engine is not None
            and (prev is None or not prev.node_sharded)
            and self._delta.eligible(prev)
            and self._delta.update(prev, view, engine)
        ):
            self._views[ls] = view
            return view
        key = (csr.n_nodes, csr.n_edges)
        init_from = None
        down_from = None
        if (
            prev is not None
            and prev.converged
            and not prev.node_sharded
            and prev._dist_dev is not None
            and prev.dest_names == view.dest_names
            and prev._node_id == view._node_id
            and prev._overloaded.shape == view._overloaded.shape
        ):
            if _improvement_only(
                prev._edge_keys,
                prev._edge_met,
                prev._overloaded,
                view._edge_keys,
                view._edge_met,
                view._overloaded,
            ):
                init_from = prev
            elif prev._runner is not None and prev._runner.bg is not None:
                down_from = prev
        # cold seed always flows in; the warm seed applies only if the
        # warm path engages (compute() decides — ELL fallbacks stay
        # cold), and harvesting routes by what actually ran
        try:
            view.compute(
                hint_seed=self._hints.get(key),
                init_from=init_from,
                warm_seed=self._warm_hints.get(key, 4),
                down_from=down_from,
            )
        except Exception:
            if init_from is None and down_from is None:
                raise  # cold run failed: nothing softer to retry with
            # warm-start gate failure (bad seed, uncertifiable affected
            # set, device error during the seeded relax): retry COLD on a
            # fresh view — the caller reads cold_fallback for counters
            log.warning("fleet: warm-started rebuild failed; retrying cold")
            view = FleetRouteView(csr, dest_names, engine=engine)
            view.compute(hint_seed=self._hints.get(key))
            view.cold_fallback = True
        if view.sweep_hint is not None:
            store = self._warm_hints if view.warm else self._hints
            # max-merge, like DeviceSpfBackend._harvest_hint
            store[key] = max(store.get(key, 0), view.sweep_hint)
        self._views[ls] = view
        return view
