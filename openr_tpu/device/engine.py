"""Persistent device-residency engine for small-batch SPF dispatch.

The measured dispatch policy loses every single-event reconvergence to
the host because each device call re-stages the graph and re-enters the
jit cache (VERDICT "What's weak" §3).  This engine removes both taxes:

- **Residency**: one `_Resident` per CsrTopology mirror holds the ELL
  tables and edge/node attribute arrays on the device.  Attribute flaps
  (link up/down, metric, drain) are applied *on device* by scatter-free
  masked writes against host shadow copies — an adjacency flap never
  re-uploads the graph.  Only an edge-set/node-set rebuild (a new
  `csr.ell` object) forces a full restage.
- **Shape-bucketed program cache**: a query for S sources pads up the
  `S_BUCKETS` ladder and dispatches a persistently compiled program
  keyed by (topology bucket, S bucket, word count, sweep count, dtype
  mode, metric mode).  Programs are AOT-compiled
  (`jax.jit(...).lower(...).compile()`) so LRU eviction actually frees
  the executable, and the per-query distance scratch is donated
  (`donate_argnums`) back to the runtime.
- **Accounting**: every byte that crosses host->device and every
  staging/compile/dispatch interval is recorded under `device.engine.*`
  and exported through `OpenrCtrlHandler._all_counters` / the fb303
  shim.

Failure discipline: any exception thrown here rides the existing
degradation ladder (SpfSolver catches and falls back to the host
oracle); the chaos harness injects faults through `fault_hook`.
"""

from __future__ import annotations

import functools
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import trace as _trace
from ..ops import sssp as ops

# source-batch padding ladder; above the last rung, next power of two
S_BUCKETS = (1, 8, 64, 512)

ENGINE_COUNTER_KEYS = (
    "device.engine.compiles",
    "device.engine.bucket_hits",
    "device.engine.bucket_misses",
    "device.engine.evictions",
    "device.engine.bytes_staged",
    "device.engine.incremental_updates",
    "device.engine.full_restages",
    "device.engine.queries",
    "device.engine.dispatches",
    "device.engine.stage_us",
    "device.engine.compile_us",
    "device.engine.dispatch_us",
    "device.engine.epoch_invalidations",
    "device.engine.delta_dispatches",
    "device.engine.delta_dispatch_us",
    "device.engine.delta_bucket_hits",
    "device.engine.delta_bucket_misses",
    "device.engine.delta_overflow_fallbacks",
    "device.engine.rewires",
    "device.engine.rewire_dispatches",
    "device.engine.rewire_slots",
    "device.engine.rewire_rows",
    "device.engine.rewire_bytes_staged",
    "device.engine.rewire_us",
    "device.engine.rewire_fallbacks",
)

# affected-column padding ladder for the delta rung: a frontier of
# n_cols columns dispatches at the smallest rung >= n_cols so storms of
# similar size share one compiled program
DELTA_P_BUCKETS = (8, 16, 32, 64, 128, 256, 512)


class EpochMismatchError(RuntimeError):
    """The caller pinned a topology epoch (`expect_epoch`) that no longer
    matches the CsrTopology — a flap landed between coalescing and
    dispatch.  The serving layer catches this and recomputes against the
    fresh topology instead of serving stale routes."""

    def __init__(self, expected: int, actual: int) -> None:
        super().__init__(
            f"topology epoch moved: expected {expected}, now {actual}"
        )
        self.expected = expected
        self.actual = actual


def _s_bucket(s: int) -> int:
    for b in S_BUCKETS:
        if s <= b:
            return b
    b = S_BUCKETS[-1]
    while b < s:
        b *= 2
    return b


def _nbytes(*arrays) -> int:
    return sum(int(a.size) * int(a.dtype.itemsize) for a in arrays)


@functools.partial(jax.jit, static_argnames=("n_cap",))
def _dist0_T_device(sources, new_of_old, n_cap):
    # device-built initial distances: the only per-query upload stays the
    # [S] source-id vector
    return ops.make_dist0_T(sources, new_of_old, n_cap)


@functools.partial(jax.jit, donate_argnums=(0,))
def _masked_write_i32(arr, idx, vals):
    """arr[idx] = vals without a scatter (one scatter knocks the TPU
    runtime off its fast dispatch path; see ops.sssp.make_dist0_T).
    `idx` is padded with -1 (never matches), indices are unique."""
    hit = jnp.arange(arr.shape[0], dtype=jnp.int32)[:, None] == idx[None, :]
    picked = (hit * vals[None, :]).sum(axis=1)
    return jnp.where(hit.any(axis=1), picked.astype(arr.dtype), arr)


@functools.partial(jax.jit, donate_argnums=(0,))
def _masked_write_bool(arr, idx, vals):
    hit = jnp.arange(arr.shape[0], dtype=jnp.int32)[:, None] == idx[None, :]
    picked = (hit & vals[None, :]).any(axis=1)
    return jnp.where(hit.any(axis=1), picked, arr)


@functools.partial(jax.jit, donate_argnums=(0,))
def _masked_write_rows_i32(arr, row_idx, rows):
    """arr[row_idx, :] = rows without a scatter.  `arr` is [N, K],
    `row_idx` is [R] padded with -1 (never matches), `rows` is [R, K].
    Same fast-dispatch discipline as the element masked writes — the
    rewire rung patches whole re-encoded ELL destination rows."""
    hit = jnp.arange(arr.shape[0], dtype=jnp.int32)[:, None] == row_idx[None, :]
    picked = (hit[:, :, None] * rows[None, :, :]).sum(axis=1)
    return jnp.where(hit.any(axis=1)[:, None], picked.astype(arr.dtype), arr)


@functools.partial(jax.jit, donate_argnums=(0,))
def _masked_write_rows_bool(arr, row_idx, rows):
    hit = jnp.arange(arr.shape[0], dtype=jnp.int32)[:, None] == row_idx[None, :]
    picked = (hit[:, :, None] & rows[None, :, :]).any(axis=1)
    return jnp.where(hit.any(axis=1)[:, None], picked, arr)


def _pad_updates(idx: np.ndarray, vals: np.ndarray, pad_val):
    """Pad (idx, vals) to a small power-of-two K so the masked-write
    programs bucket by update count instead of retracing per flap."""
    k = 8
    while k < len(idx):
        k *= 2
    pad = k - len(idx)
    if pad:
        idx = np.concatenate([idx, np.full(pad, -1, dtype=np.int32)])
        vals = np.concatenate([vals, np.full(pad, pad_val, dtype=vals.dtype)])
    return idx, vals


def _pad_rows(row_idx: np.ndarray, *row_arrays):
    """Row-update analogue of `_pad_updates`: pad the [R] index vector
    with -1 and each [R, K] payload with zero rows up to a small
    power-of-two R so the row-write programs bucket by row count."""
    k = 8
    while k < len(row_idx):
        k *= 2
    pad = k - len(row_idx)
    if pad:
        row_idx = np.concatenate(
            [row_idx, np.full(pad, -1, dtype=np.int32)]
        )
        row_arrays = tuple(
            np.concatenate(
                [a, np.zeros((pad,) + a.shape[1:], dtype=a.dtype)]
            )
            for a in row_arrays
        )
    return (row_idx,) + row_arrays


def _forward_body(
    small: bool, use_link_metric: bool, n_sweeps: int, n_words: int
):
    """Program body for one (S bucket, mode) cell — mirrors
    ops.sssp.spf_forward_full(_packed) but takes the donated distance
    scratch as its first argument so the runtime reuses its pages."""

    # a stable program name (the trace's `jit_spf_forward_resident`) and
    # one named scope per phase, carried on the ops' metadata
    def spf_forward_resident(
        dist0_T,  # [N_cap, S_bucket] int32 — DONATED
        sources,  # [S_bucket] int32
        ell,
        edge_src,
        edge_dst,
        edge_metric,
        edge_up,
        node_overloaded,
        out_slot,
    ):
        with jax.named_scope("relax"):
            dist_T, dist_ok = ops.batched_sssp_ell(
                dist0_T,
                ell,
                unit_metric=not use_link_metric,
                edge_up=edge_up,
                node_overloaded=node_overloaded,
                edge_metric=edge_metric,
                n_sweeps=n_sweeps,
            )
        with jax.named_scope("sp_dag"):
            dist_old_T = ops.ell_dist_to_old_T(dist_T, ell)
            metric = (
                edge_metric if use_link_metric else jnp.ones_like(edge_metric)
            )
            allowed_T = ops.make_relax_allowed_T(
                sources, edge_src, edge_up, node_overloaded
            )
            d_u = jnp.take(dist_old_T, edge_src, axis=0)
            d_v = jnp.take(dist_old_T, edge_dst, axis=0)
            dag_T = allowed_T & (d_u < ops.INF32) & (
                d_u + metric[:, None] == d_v
            )
        with jax.named_scope("first_hops"):
            nh, nh_ok = ops.first_hops_ell(
                ell, dag_T, out_slot, sources, edge_src, n_words,
                n_sweeps=n_sweeps,
            )
        ok = dist_ok & nh_ok
        if not small:
            # dist stays in the donated [N_cap, S] layout: the output aval
            # must equal the donated input's for XLA to alias the buffer
            # (a transposed return silently drops the donation); the host
            # transposes the fetched view for free after device_get
            return dist_old_T, dag_T.T, nh, ok
        # small control-plane query: ONE packed device->host transfer
        return jnp.concatenate(
            [
                dist_old_T.T.ravel(),
                dag_T.T.ravel().astype(jnp.int32),
                jax.lax.bitcast_convert_type(nh, jnp.int32).ravel(),
                ok.astype(jnp.int32)[None],
            ]
        )

    return spf_forward_resident


@dataclass
class _Resident:
    """Device-resident mirror of one CsrTopology + host shadows for
    diffing.  `ell_host` pins the host ELL object: identity change means
    csr.refresh() rebuilt the topology and residency must restage."""

    topo_key: tuple
    ell_host: Any
    version: int
    # device arrays
    ell: Any
    edge_src: Any
    edge_dst: Any
    edge_metric: Any
    edge_up: Any
    node_overloaded: Any
    out_slot: Any
    # host shadows of the three mutable attribute arrays
    shadow_metric: np.ndarray = field(repr=False, default=None)
    shadow_up: np.ndarray = field(repr=False, default=None)
    shadow_overloaded: np.ndarray = field(repr=False, default=None)
    sweep_hint: int = 16
    # last CsrTopology.rewire_seq applied to the device mirror; a gap
    # against csr.rewire_seq routes sync() through the rewire rung
    rewire_seq: int = 0


class DeviceResidencyEngine:
    """Owns device residency, the bucketed program cache and the
    `device.engine.*` accounting.  One instance serves every area's
    CsrTopology mirror (residents key on mirror identity)."""

    def __init__(
        self,
        max_programs: int = 16,
        s_buckets: tuple = S_BUCKETS,
        small_threshold: int = 1 << 21,
    ) -> None:
        self.max_programs = max_programs
        self.s_buckets = tuple(s_buckets)
        # S_bucket * node_capacity at or below this dispatches the packed
        # single-transfer program shape; the program auditor forces it to 0
        # to exercise the full (donation-aliased) shape on tiny topologies
        self.small_threshold = small_threshold
        self.counters: dict[str, int] = {k: 0 for k in ENGINE_COUNTER_KEYS}
        # (topo_key, s_bucket, n_words, n_sweeps, small, use_link_metric)
        #   -> AOT-compiled executable; OrderedDict as LRU
        self._programs: "OrderedDict[tuple, Any]" = OrderedDict()
        # key -> (program body fn, arg ShapeDtypeStructs, donate_argnums):
        # enough for the program auditor to re-trace every ladder cell it
        # saw compiled, without holding example arrays alive
        self._program_specs: dict[tuple, tuple] = {}
        # id(csr) -> _Resident (csr mirrors are long-lived per area)
        self._residents: dict[int, _Resident] = {}
        # delta-rung bucket cells already traced (hit/miss accounting)
        self._delta_buckets_seen: set = set()
        # chaos seam: called with an op name at every engine entry point
        self.fault_hook: Optional[Callable[[str], None]] = None
        # third dispatch rung (delta < fused full < blocked): node-axis
        # sharded blocked APSP (parallel.blocked).  Eagerly constructed
        # so its pre-seeded mesh.blocked.* counters dump before the
        # first dispatch; the device mesh itself stays lazy.  It reads
        # THIS engine's fault_hook, so chaos faults armed here fire
        # inside the blocked rounds too.
        from ..parallel.blocked import BlockedApspEngine

        self.blocked = BlockedApspEngine(parent=self)
        # per-query attribution: bytes staged and wall time of the last query
        self.last_query_bytes = 0
        self.last_query_us = 0

    # -- counters -----------------------------------------------------------

    def get_counters(self) -> dict[str, int]:
        return dict(self.counters)

    def _bump(self, name: str, delta: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + delta

    # -- residency ----------------------------------------------------------

    def has_residency(self, csr) -> bool:
        """True when `csr`'s graph is resident (attribute drift is fine —
        the next sync applies it incrementally, which is cheap; only a
        topology rebuild forces a restage)."""
        res = self._residents.get(id(csr))
        return res is not None and res.ell_host is csr.ell

    def is_warm(self, csr) -> bool:
        """True when `csr`'s graph is resident and current — the measured
        dispatch policy flips small-S queries to the device only then."""
        res = self._residents.get(id(csr))
        return (
            res is not None
            and res.ell_host is csr.ell
            and res.version == csr.version
        )

    def sync(self, csr) -> _Resident:
        """Bring `csr`'s device residency to csr.version.

        Full restage only when the ELL object changed (topology
        rebuild); bounded edge-set rewires replay the CsrTopology rewire
        log through masked slot/row writes; attribute-only refreshes
        diff the host shadows and apply masked writes on device."""
        if self.fault_hook is not None:
            self.fault_hook("sync")
        t0 = time.perf_counter()
        res = self._residents.get(id(csr))
        if res is None or res.ell_host is not csr.ell:
            res = self._restage(csr)
        else:
            if getattr(csr, "rewire_seq", 0) != res.rewire_seq:
                try:
                    self._rewire_sync(res, csr)
                    tr = _trace.TRACE
                    if tr is not None:
                        tr.annotate("engine.rung", "rewire")
                except Exception:
                    # any rewire failure (log gap, fault injection, ...)
                    # demotes to the restage rung — never an error
                    self._bump("device.engine.rewire_fallbacks")
                    res = self._restage(csr)
            if res.version != csr.version:
                self._incremental(res, csr)
                tr = _trace.TRACE
                if tr is not None:
                    tr.annotate("engine.rung", "incremental")
        self._bump(
            "device.engine.stage_us",
            int((time.perf_counter() - t0) * 1e6),
        )
        return res

    def _restage(self, csr) -> _Resident:
        tr = _trace.TRACE
        if tr is not None:
            tr.annotate("engine.rung", "restage")
        host_arrays = (
            csr.edge_src,
            csr.edge_dst,
            csr.edge_metric,
            csr.edge_up,
            csr.node_overloaded,
            csr.out_slot,
        )
        ell_leaves = jax.tree_util.tree_leaves(csr.ell)
        staged = _nbytes(*host_arrays) + _nbytes(
            *(np.asarray(leaf) for leaf in ell_leaves)
        )
        res = _Resident(
            topo_key=(csr.node_capacity, csr.edge_capacity),
            ell_host=csr.ell,
            version=csr.version,
            ell=jax.device_put(csr.ell),
            edge_src=jax.device_put(csr.edge_src),
            edge_dst=jax.device_put(csr.edge_dst),
            edge_metric=jax.device_put(csr.edge_metric),
            edge_up=jax.device_put(csr.edge_up),
            node_overloaded=jax.device_put(csr.node_overloaded),
            out_slot=jax.device_put(csr.out_slot),
            shadow_metric=csr.edge_metric.copy(),
            shadow_up=csr.edge_up.copy(),
            shadow_overloaded=csr.node_overloaded.copy(),
            sweep_hint=csr._sweep_hint,
            rewire_seq=getattr(csr, "rewire_seq", 0),
        )
        self._residents[id(csr)] = res
        self._bump("device.engine.full_restages")
        self._bump("device.engine.bytes_staged", staged)
        return res

    def _incremental(self, res: _Resident, csr) -> None:
        """Apply attribute deltas (metric writes / up masks / overload
        flips) on device.  Upload cost is O(changed entries), padded to a
        small power-of-two bucket — never the graph."""
        staged = 0
        for attr, shadow, host, write in (
            ("edge_metric", res.shadow_metric, csr.edge_metric,
             _masked_write_i32),
            ("edge_up", res.shadow_up, csr.edge_up, _masked_write_bool),
            ("node_overloaded", res.shadow_overloaded, csr.node_overloaded,
             _masked_write_bool),
        ):
            changed = np.flatnonzero(shadow != host)
            if changed.size == 0:
                continue
            idx = changed.astype(np.int32)
            vals = host[changed]
            idx, vals = _pad_updates(
                idx, vals, pad_val=vals.dtype.type(0)
            )
            # explicit H2D staging: the masked-write programs must never
            # see raw host arrays (the transfer-guard sanitizer disallows
            # implicit transfers on every engine dispatch path)
            idx_dev, vals_dev = jax.device_put((idx, vals))
            setattr(res, attr, write(getattr(res, attr), idx_dev, vals_dev))
            staged += _nbytes(idx, vals)
            shadow[changed] = host[changed]
        res.version = csr.version
        self._bump("device.engine.incremental_updates")
        if staged:
            self._bump("device.engine.bytes_staged", staged)

    def _rewire_sync(self, res: _Resident, csr) -> None:
        """Replay the pending tail of csr's rewire log against the
        resident: masked writes for the rewritten edge slots plus
        donated row writes for every re-encoded ELL destination row.
        Upload cost is O(touched slots + touched rows) — never the
        graph, so a bounded OCS rewire keeps full_restages == 1.

        Raises on any inconsistency (log gap after eviction, injected
        fault); sync() demotes that to a restage."""
        t0 = time.perf_counter()
        if self.fault_hook is not None:
            self.fault_hook("rewire")
        pending = [d for d in csr._rewire_log if d.seq > res.rewire_seq]
        if (
            not pending
            or pending[0].seq != res.rewire_seq + 1
            or pending[-1].seq != csr.rewire_seq
            or any(
                b.seq != a.seq + 1 for a, b in zip(pending, pending[1:])
            )
        ):
            raise RuntimeError(
                f"rewire chain gap: resident at seq {res.rewire_seq}, "
                f"log covers {[d.seq for d in pending]}"
            )
        staged = n_slots = n_rows = 0
        for delta in pending:
            staged += self._apply_rewire(res, delta)
            n_slots += len(delta.slots)
            n_rows += len(delta.ell_rows)
            self._bump("device.engine.rewires")
        res.rewire_seq = csr.rewire_seq
        # the touched slots are current in the shadows now; when nothing
        # else drifted the resident is fully at csr.version and the
        # attribute-diff rung can be skipped outright
        if (
            np.array_equal(res.shadow_metric, csr.edge_metric)
            and np.array_equal(res.shadow_up, csr.edge_up)
            and np.array_equal(res.shadow_overloaded, csr.node_overloaded)
        ):
            res.version = csr.version
        self._bump("device.engine.rewire_dispatches")
        self._bump("device.engine.rewire_slots", n_slots)
        self._bump("device.engine.rewire_rows", n_rows)
        self._bump("device.engine.rewire_bytes_staged", staged)
        self._bump("device.engine.bytes_staged", staged)
        self._bump(
            "device.engine.rewire_us",
            int((time.perf_counter() - t0) * 1e6),
        )

    def _apply_rewire(self, res: _Resident, delta) -> int:
        """Apply one RewireDelta to the resident mirror; returns bytes
        uploaded.  Slot payloads ride the element masked writes, ELL
        rows ride the donated row writes (grouped per bucket so each
        [N_b, K_b] cell compiles once)."""
        staged = 0
        for attr, idx, vals, write, shadow in (
            ("edge_src", delta.slots, delta.src, _masked_write_i32, None),
            ("edge_dst", delta.slots, delta.dst, _masked_write_i32, None),
            ("edge_metric", delta.slots, delta.metric, _masked_write_i32,
             res.shadow_metric),
            ("edge_up", delta.slots, delta.up, _masked_write_bool,
             res.shadow_up),
            ("out_slot", delta.out_idx, delta.out_val, _masked_write_i32,
             None),
        ):
            if len(idx) == 0:
                continue
            pi, pv = _pad_updates(
                idx.astype(np.int32), vals, pad_val=vals.dtype.type(0)
            )
            # explicit H2D staging — same transfer-guard discipline as
            # the attribute rung
            pi_dev, pv_dev = jax.device_put((pi, pv))
            setattr(res, attr, write(getattr(res, attr), pi_dev, pv_dev))
            staged += _nbytes(pi, pv)
            if shadow is not None:
                shadow[idx] = vals
        by_bucket: dict[int, list] = {}
        for row in delta.ell_rows:
            by_bucket.setdefault(row[0], []).append(row)
        if not by_bucket:
            return staged
        buckets = list(res.ell.buckets)
        for b_idx, rows in by_bucket.items():
            bkt = buckets[b_idx]
            row_idx = np.asarray([r[1] for r in rows], dtype=np.int32)
            nbr = np.stack([r[2] for r in rows])
            w = np.stack([r[3] for r in rows])
            eid = np.stack([r[4] for r in rows])
            ok = np.stack([r[5] for r in rows])
            tok = np.stack([r[6] for r in rows])
            row_idx, nbr, w, eid, ok, tok = _pad_rows(
                row_idx, nbr, w, eid, ok, tok
            )
            idx_dev, nbr_dev, w_dev, eid_dev, ok_dev, tok_dev = (
                jax.device_put((row_idx, nbr, w, eid, ok, tok))
            )
            buckets[b_idx] = bkt._replace(
                nbr=_masked_write_rows_i32(bkt.nbr, idx_dev, nbr_dev),
                w=_masked_write_rows_i32(bkt.w, idx_dev, w_dev),
                edge_id=_masked_write_rows_i32(bkt.edge_id, idx_dev, eid_dev),
                ok=_masked_write_rows_bool(bkt.ok, idx_dev, ok_dev),
                transit_ok=_masked_write_rows_bool(
                    bkt.transit_ok, idx_dev, tok_dev
                ),
            )
            staged += _nbytes(row_idx, nbr, w, eid, ok, tok)
        res.ell = res.ell._replace(buckets=tuple(buckets))
        return staged

    def drop(self, csr) -> None:
        """Forget `csr`'s residency (mirror retired)."""
        self._residents.pop(id(csr), None)

    # -- snapshot seams (openr_tpu/snapshot) --------------------------------

    def export_resident(self, csr) -> dict:
        """Host-side image of `csr`'s residency for EngineSnapshot.take:
        sync first (the checkpoint is always at the mirror's current
        version), then one batched explicit device_get per surface —
        the snapshot layer never touches _Resident internals."""
        res = self.sync(csr)
        names = (
            "edge_src",
            "edge_dst",
            "edge_metric",
            "edge_up",
            "node_overloaded",
            "out_slot",
        )
        fetched = jax.device_get(tuple(getattr(res, n) for n in names))
        leaves = jax.device_get(jax.tree_util.tree_leaves(res.ell))
        return {
            "topo_key": res.topo_key,
            "version": res.version,
            "rewire_seq": res.rewire_seq,
            "sweep_hint": res.sweep_hint,
            "arrays": {
                n: np.asarray(a) for n, a in zip(names, fetched)
            },
            "ell_leaves": [np.asarray(x) for x in leaves],
        }

    def install_resident(
        self,
        csr,
        state: dict,
        *,
        version: Optional[int] = None,
        rewire_seq: Optional[int] = None,
    ) -> _Resident:
        """Install a host-side resident image (export_resident shape) as
        `csr`'s device residency.  The shadows come from the image, so a
        following sync() reconciles any attribute drift between the
        checkpoint and `csr` through the ordinary incremental rung.
        `version`/`rewire_seq` override the image's position when the
        caller proved `csr`'s content already matches (the snapshot
        content-equality rung)."""
        arr = state["arrays"]
        leaves = [np.asarray(x) for x in state["ell_leaves"]]
        treedef = jax.tree_util.tree_structure(csr.ell)
        ell = jax.tree_util.tree_unflatten(
            treedef, [jax.device_put(x) for x in leaves]
        )
        staged = _nbytes(*arr.values()) + _nbytes(*leaves)
        res = _Resident(
            topo_key=tuple(state["topo_key"]),
            ell_host=csr.ell,
            version=int(
                state["version"] if version is None else version
            ),
            ell=ell,
            edge_src=jax.device_put(arr["edge_src"]),
            edge_dst=jax.device_put(arr["edge_dst"]),
            edge_metric=jax.device_put(arr["edge_metric"]),
            edge_up=jax.device_put(arr["edge_up"]),
            node_overloaded=jax.device_put(arr["node_overloaded"]),
            out_slot=jax.device_put(arr["out_slot"]),
            shadow_metric=np.asarray(arr["edge_metric"]).copy(),
            shadow_up=np.asarray(arr["edge_up"]).copy(),
            shadow_overloaded=np.asarray(arr["node_overloaded"]).copy(),
            sweep_hint=int(state.get("sweep_hint", 16)),
            rewire_seq=int(
                state["rewire_seq"] if rewire_seq is None else rewire_seq
            ),
        )
        self._residents[id(csr)] = res
        self._bump("device.engine.bytes_staged", staged)
        return res

    def prewarm(self, csr, keys) -> int:
        """AOT-compile manifest ladder keys against `csr`'s resident
        shapes (snapshot warm-start).  Lowering takes ShapeDtypeStructs,
        so no example arrays are materialized — the XLA compile is the
        cold-start cost being moved off the serving path.  Keys for a
        different topology, or already cached, are skipped.  Returns how
        many programs were actually compiled."""
        res = self._residents.get(id(csr))
        if res is None or res.ell_host is not csr.ell:
            return 0

        def sds(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)

        warmed = 0
        for key in keys:
            topo, s_bucket, n_words, n_sweeps, small, use_lm = key
            key = (
                tuple(topo),
                int(s_bucket),
                int(n_words),
                int(n_sweeps),
                bool(small),
                bool(use_lm),
            )
            if key in self._programs or key[0] != res.topo_key:
                continue
            n_cap = res.topo_key[0]
            args = (
                jax.ShapeDtypeStruct((n_cap, key[1]), jnp.int32),
                jax.ShapeDtypeStruct((key[1],), jnp.int32),
                jax.tree_util.tree_map(sds, res.ell),
                sds(res.edge_src),
                sds(res.edge_dst),
                sds(res.edge_metric),
                sds(res.edge_up),
                sds(res.node_overloaded),
                sds(res.out_slot),
            )
            self._program(key, args)
            warmed += 1
        return warmed

    # -- program cache ------------------------------------------------------

    def cached_program_keys(self) -> list[tuple]:
        return list(self._programs.keys())

    def _program(self, key: tuple, example_args: tuple):
        cached = self._programs.get(key)
        if cached is not None:
            self._programs.move_to_end(key)
            self._bump("device.engine.bucket_hits")
            return cached
        self._bump("device.engine.bucket_misses")
        t0 = time.perf_counter()
        _topo, _sb, n_words, n_sweeps, small, use_link_metric = key
        fn = _forward_body(small, use_link_metric, n_sweeps, n_words)
        # The packed (small) shape concatenates everything into one 1-D
        # int32 vector, so no output can alias the [N_cap, S] scratch —
        # requesting donation there would be silently dropped.  The full
        # shape returns dist in the donated layout and is aliased.
        donate = () if small else (0,)
        # AOT: lower+compile now so the jit cache never owns the
        # executable — LRU eviction below genuinely frees it
        compiled = (
            jax.jit(fn, donate_argnums=donate)
            .lower(*example_args)
            .compile()
        )
        self._program_specs[key] = (
            fn,
            jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                example_args,
            ),
            donate,
        )
        self._bump("device.engine.compiles")
        self._bump(
            "device.engine.compile_us",
            int((time.perf_counter() - t0) * 1e6),
        )
        self._programs[key] = compiled
        while len(self._programs) > self.max_programs:
            self._programs.popitem(last=False)
            self._bump("device.engine.evictions")
        return compiled

    # -- queries ------------------------------------------------------------

    def spf_results(
        self,
        csr,
        sources: list,
        use_link_metric: bool = True,
        expect_epoch: Optional[int] = None,
    ):
        """Full production pipeline through residency: distances + SP-DAG
        + bit-packed first hops -> reference-shaped SpfResults.  Same
        contract as CsrTopology.spf_from, minus the per-call staging.

        `expect_epoch` pins the csr.version the caller coalesced against:
        if the topology moved since, the query raises EpochMismatchError
        *before* any device work, so batched callers never receive routes
        computed over a topology older than the one they observed."""
        if self.fault_hook is not None:
            self.fault_hook("spf")
        if expect_epoch is not None and int(csr.version) != int(expect_epoch):
            self._bump("device.engine.epoch_invalidations")
            raise EpochMismatchError(int(expect_epoch), int(csr.version))
        if not sources:
            return {}
        tr = _trace.TRACE
        if tr is not None:
            # rung taken by a serving dispatch: the warm path is "spf";
            # sync() upgrades it to restage/rewire/incremental when the
            # residency actually moved under this query
            tr.annotate("engine.rung", "spf")
        t_query = time.perf_counter()
        bytes_before = self.counters["device.engine.bytes_staged"]
        res = self.sync(csr)

        src_ids = np.asarray(
            [csr.node_id[s] for s in sources], dtype=np.int32
        )
        s = len(sources)
        s_bucket = _s_bucket(s)
        if s_bucket > s:
            # pad with the first source: pad rows compute real (discarded)
            # results, so the convergence verdict stays meaningful
            src_ids = np.concatenate(
                [src_ids, np.full(s_bucket - s, src_ids[0], np.int32)]
            )
        # topology-wide word count (not per-batch): keeps the program key
        # stable across source sets; unset high words decode to no bits
        n_words = max(1, -(-csr.max_out_slots // 32))
        n_cap = csr.node_capacity
        small = s_bucket * n_cap <= self.small_threshold

        t0 = time.perf_counter()
        while True:
            n_sweeps = res.sweep_hint
            key = (
                res.topo_key,
                s_bucket,
                n_words,
                n_sweeps,
                small,
                use_link_metric,
            )
            src_dev = jax.device_put(src_ids)
            self._bump("device.engine.bytes_staged", _nbytes(src_ids))
            dist0_T = _dist0_T_device(
                src_dev, res.ell.new_of_old, n_cap
            )
            args = (
                dist0_T,
                src_dev,
                res.ell,
                res.edge_src,
                res.edge_dst,
                res.edge_metric,
                res.edge_up,
                res.node_overloaded,
                res.out_slot,
            )
            compiled = self._program(key, args)
            out = compiled(*args)
            # every fetch below is an explicit device_get: the engine's
            # dispatch paths run under the transfer-guard sanitizer, which
            # disallows implicit host round-trips
            if small:
                packed = jax.device_get(out)
                converged = packed[-1] == 1
            else:
                dist_j, dag_j, nh_j, ok_j = out
                converged = bool(jax.device_get(ok_j))
            if converged:
                break
            res.sweep_hint = n_sweeps * 2
            # share the learned relax depth with the host-staged path
            csr._sweep_hint = res.sweep_hint
        if small:
            n_dist = s_bucket * n_cap
            n_dag = s_bucket * csr.edge_capacity
            dist = packed[:n_dist].reshape(s_bucket, n_cap)
            dag = (
                packed[n_dist : n_dist + n_dag].reshape(
                    s_bucket, csr.edge_capacity
                )
                != 0
            )
            nh = (
                packed[n_dist + n_dag : -1]
                .view(np.uint32)
                .reshape(s_bucket, n_cap, n_words)
            )
        else:
            # one batched fetch; dist comes back in the donated [N_cap, S]
            # layout (see _forward_body) and is transposed host-side
            dist_T, dag, nh = jax.device_get((dist_j, dag_j, nh_j))
            dist = dist_T.T
        self._bump(
            "device.engine.dispatch_us",
            int((time.perf_counter() - t0) * 1e6),
        )
        self._bump("device.engine.queries")
        self.last_query_bytes = (
            self.counters["device.engine.bytes_staged"] - bytes_before
        )
        self.last_query_us = int((time.perf_counter() - t_query) * 1e6)
        return csr.to_spf_results(sources, dist[:s], dag[:s], nh[:s])

    def dispatch(self, op: str, fn: Callable, *args, **kwargs):
        """Generic dispatch front-end for device work that is not an SPF
        query (fleet product, KSP re-runs): routes through the chaos
        fault hook and the dispatch accounting without changing the
        callee's contract."""
        if self.fault_hook is not None:
            self.fault_hook(op)
        t0 = time.perf_counter()
        try:
            return fn(*args, **kwargs)
        finally:
            self._bump("device.engine.dispatches")
            self._bump(
                "device.engine.dispatch_us",
                int((time.perf_counter() - t0) * 1e6),
            )

    # -- delta rung ----------------------------------------------------------

    def delta_bucket(self, n_cols: int, p: int) -> Optional[int]:
        """Padded slab width for an affected frontier of `n_cols` columns
        out of a `p`-wide product, or None when the frontier bound is
        exceeded (bucket >= p, or the frontier covers more than half the
        product — at that point the full fused product is cheaper and is
        the bit-exact fallback the caller must take)."""
        if n_cols <= 0:
            return None
        if 2 * n_cols > p:
            self._bump("device.engine.delta_overflow_fallbacks")
            return None
        for b in DELTA_P_BUCKETS:
            if n_cols <= b:
                if b >= p:
                    self._bump("device.engine.delta_overflow_fallbacks")
                    return None
                return b
        self._bump("device.engine.delta_overflow_fallbacks")
        return None

    def delta_register(self, nbytes: int) -> None:
        """Account the one full product upload a delta sequence starts
        from — the acceptance invariant is full_restages == 1 across a
        whole storm, everything after rides the donated delta slabs."""
        self._bump("device.engine.full_restages")
        self._bump("device.engine.bytes_staged", int(nbytes))

    def delta_dispatch(
        self,
        op: str,
        fn: Callable,
        *args,
        csr=None,
        expect_epoch: Optional[int] = None,
        bucket_key: Optional[tuple] = None,
        **kwargs,
    ):
        """Dispatch front-end for the incremental delta rung.

        Same chaos-hook + timing contract as `dispatch`, plus: an epoch
        pin (`expect_epoch` against `csr.version`, checked BEFORE device
        work so the serving coalescer's retry loop composes — a flap
        between coalescing and dispatch re-coalesces instead of relaxing
        a stale frontier) and bucket-ladder accounting (`bucket_key`
        identifies the compiled-program cell; first sighting is a miss =
        a compile, repeats are hits)."""
        if self.fault_hook is not None:
            self.fault_hook("delta_" + op)
        if (
            expect_epoch is not None
            and csr is not None
            and int(csr.version) != int(expect_epoch)
        ):
            self._bump("device.engine.epoch_invalidations")
            raise EpochMismatchError(int(expect_epoch), int(csr.version))
        if bucket_key is not None:
            if bucket_key in self._delta_buckets_seen:
                self._bump("device.engine.delta_bucket_hits")
            else:
                self._delta_buckets_seen.add(bucket_key)
                self._bump("device.engine.delta_bucket_misses")
        tr = _trace.TRACE
        if tr is not None:
            tr.annotate("engine.rung", "delta")
        t0 = time.perf_counter()
        try:
            return fn(*args, **kwargs)
        finally:
            self._bump("device.engine.delta_dispatches")
            self._bump(
                "device.engine.delta_dispatch_us",
                int((time.perf_counter() - t0) * 1e6),
            )
