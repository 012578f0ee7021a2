"""Batched single-source shortest paths on TPU.

This is the compute core replacing the reference's per-source Dijkstra
(openr/decision/LinkState.cpp:809-878 `runSpf`).  Instead of a priority queue
(inherently sequential, pointer-chasing — hostile to XLA), we use batched
frontier relaxation (Bellman-Ford iterated to fixed point):

    dist[s, v] <- min(dist[s, v], min over edges (u,v): dist[s, u] + w(u, v))

vmapped over a batch dimension `s`.  The batch rows are *independent problem
variants*: different source nodes (all-sources SPF), different link-exclusion
masks (k-shortest-path runs, SRLG what-if failure simulation), or both.
Each iteration is a dense gather + segment-min — ideal XLA/TPU work; the
fixed-point loop runs at most `graph diameter` iterations (lax.while_loop,
no host round-trips).

Semantics matched against the oracle (LinkState.run_spf):
- drained (overloaded) nodes are reachable but offer no transit: edges out of
  an overloaded node are masked unless that node is the row's source
  (reference: LinkState.cpp:829-836)
- down links never relax (reference: `!link->isUp()` skip)
- ECMP ties survive: the SP-DAG mask marks *every* edge e=(u,v) with
  dist[u] + w == dist[v], reproducing the reference's `>=` relax tie
  retention (LinkState.cpp:855-869)
- first-hop sets (`nextHops` in the reference) come from propagating
  first-hop membership along the SP-DAG to a fixed point

Distances are int32; INF32 (2^30) marks unreachable.  Metrics must be
positive and small enough that no path exceeds 2^30 (the reference uses
uint64 but real metrics are bounded by config; we document the constraint).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

INF32 = jnp.int32(1 << 30)

# uint16 distance mode, shared by the ELL and banded kernels: dist in
# [0, INF16], weights clamped to WBIG16 so INF16 + WBIG16 < 2^16 and the
# relax adds never wrap.  pick_small_dist (ops.banded) gates entry; the
# saturation verdict below certifies no true distance overflowed.
INF16 = jnp.uint32(40000).astype(jnp.uint16)
WBIG16 = jnp.uint32(20000).astype(jnp.uint16)


def clamp_metric_u16(metric: jax.Array) -> jax.Array:
    """Clamp BEFORE the cast: an oversized metric must saturate to the
    band infinity, never wrap (a racing in-place metric refresh must stay
    safe; the int32 retry path restores exactness)."""
    return jnp.minimum(metric, jnp.int32(WBIG16)).astype(jnp.uint16)


def u16_saturation_verdict(dist16: jax.Array, converged: jax.Array) -> jax.Array:
    """AND the convergence verdict with the saturation guard: with every
    weight < WBIG16, any true distance that would overflow INF16 forces
    SOME entry into the finite band [WBIG16, INF16) first, so a clean
    margin certifies no distance saturated."""
    fin_max = jnp.max(jnp.where(dist16 < INF16, dist16, jnp.uint16(0)))
    return converged & (fin_max < WBIG16)


def u16_dist_to_i32(dist16: jax.Array) -> jax.Array:
    """uint16/INF16 domain -> the int32/INF32 output contract."""
    return jnp.where(dist16 >= INF16, INF32, dist16.astype(jnp.int32))


def sp_dag_mask16_from_T(
    dist16_old_T: jax.Array,  # [N_cap, S] uint16 — ORIGINAL node ids
    edge_src: jax.Array,
    edge_dst: jax.Array,
    edge_metric: jax.Array,  # [E] int32 (clamped here)
    allowed_T: jax.Array,  # [E, S]
) -> jax.Array:
    """SP-DAG membership evaluated in the uint16 domain: the [E, S]
    gathers are the extraction's dominant cost at large S, and they move
    half the bytes here.  Valid because finite d + clamped metric < 2^16
    and saturated entries are excluded by the d_u < INF16 guard."""
    m16 = clamp_metric_u16(edge_metric)
    d_u = jnp.take(dist16_old_T, edge_src, axis=0)  # [E, S]
    d_v = jnp.take(dist16_old_T, edge_dst, axis=0)
    return (allowed_T & (d_u < INF16) & (d_u + m16[:, None] == d_v)).T


@jax.jit
def batched_sssp(
    dist0: jax.Array,  # [S, N] int32 — 0 at each row's source(s), INF32 elsewhere
    edge_src: jax.Array,  # [E] int32
    edge_dst: jax.Array,  # [E] int32
    edge_metric: jax.Array,  # [E] int32 (>0)
    relax_allowed: jax.Array,  # [S, E] bool — may this row relax along e?
) -> jax.Array:
    """Fixed-point frontier relaxation.  Returns dist [S, N] int32."""
    n_nodes = dist0.shape[1]

    def relax(dist):
        d_u = jnp.take(dist, edge_src, axis=1)  # [S, E]
        cand = jnp.where(
            relax_allowed & (d_u < INF32),
            d_u + edge_metric[None, :],
            INF32,
        )
        new = jax.vmap(
            lambda c: jax.ops.segment_min(
                c, edge_dst, num_segments=n_nodes, indices_are_sorted=True
            )
        )(cand)
        return jnp.minimum(dist, new)

    def cond(state):
        _, changed, it = state
        return changed & (it < n_nodes)  # path edge-count is bounded by N-1

    def body(state):
        dist, _, it = state
        new = relax(dist)
        return new, jnp.any(new != dist), it + 1

    dist, _, _ = jax.lax.while_loop(cond, body, (dist0, jnp.bool_(True), 0))
    return dist


def make_dist0(sources: jax.Array, n_nodes: int) -> jax.Array:
    """dist0 rows for per-row single sources.  sources: [S] int32."""
    s = sources.shape[0]
    dist0 = jnp.full((s, n_nodes), INF32, dtype=jnp.int32)
    return dist0.at[jnp.arange(s), sources].set(0)


def make_relax_allowed(
    sources: jax.Array,  # [S] int32 — row sources (for the drain exception)
    edge_src: jax.Array,  # [E]
    edge_up: jax.Array,  # [E] bool — link isUp (holds + overload + padding)
    node_overloaded: jax.Array,  # [N] bool
    extra_edge_mask: jax.Array | None = None,  # [S, E] or [E] bool, False=exclude
) -> jax.Array:
    """Row-wise relax permission combining link state, drained-node
    semantics, and per-row exclusions (KSP / what-if)."""
    transit_ok = ~node_overloaded[edge_src]  # [E]
    # a row's own source may relax its out-edges even when overloaded
    allowed = edge_up[None, :] & (
        transit_ok[None, :] | (edge_src[None, :] == sources[:, None])
    )
    if extra_edge_mask is not None:
        if extra_edge_mask.ndim == 1:
            extra_edge_mask = extra_edge_mask[None, :]
        allowed = allowed & extra_edge_mask
    return allowed


@jax.jit
def sp_dag_mask(
    dist: jax.Array,  # [S, N] int32
    edge_src: jax.Array,
    edge_dst: jax.Array,
    edge_metric: jax.Array,
    relax_allowed: jax.Array,  # [S, E]
) -> jax.Array:
    """Shortest-path DAG membership: edge e=(u,v) is on some shortest path
    from row s's source iff dist[s,u] + w(e) == dist[s,v] (and e was
    relaxable).  This reproduces the reference's tie-retaining `pathLinks`
    (every equal-cost in-edge is kept)."""
    d_u = jnp.take(dist, edge_src, axis=1)
    d_v = jnp.take(dist, edge_dst, axis=1)
    return relax_allowed & (d_u < INF32) & (d_u + edge_metric[None, :] == d_v)


# ---------------------------------------------------------------------------
# Degree-bucketed ELL formulation (the production kernel)
# ---------------------------------------------------------------------------
#
# The edge-list kernel above relaxes with a vmapped segment-min, which XLA
# lowers to scatter-min — serialized, slow on TPU (~ms per iteration even on
# a 1k-node grid).  The production kernel instead stores the graph as padded
# in-neighbor tables ("ELL" sparse format), so one relax iteration is K row
# gathers + elementwise mins — pure dense vector work, no scatters:
#
#     dist_T[v, s] <- min_k  dist_T[nbr[v, k], s] + w[v, k]
#
# Distances live TRANSPOSED ([N, S]) so the gather is a row gather
# (contiguous S-length rows — the HBM-friendly access pattern).
#
# Real topologies have skewed degree distributions (a fat-tree fabric switch
# has 100+ in-edges while racks have ~8), so one global K wastes
# N * (K_max - deg) work.  Nodes are therefore RELABELED by descending
# in-degree and partitioned into contiguous buckets of equal padded K
# (power-of-two): per-iteration work is sum_b R_b * K_b ~= 2E instead of
# N * K_max.  The permutation is internal to the ELL world; results are
# gathered back to original ids at the boundary.
#
# Drained-node semantics without per-row masks: the reference lets a row's
# *own source* relax its out-edges even when overloaded
# (LinkState.cpp:829-836).  Since all metrics are >= 1, `dist[s, u] == 0`
# identifies u as row s's source, so the exception is data-dependent and
# row-independent:  relax allowed iff  up & (~overloaded[u] | d_u == 0).
# This keeps the common path free of any [S, E] mask materialization.


class EllBucket(NamedTuple):
    """Contiguous run of (relabeled) nodes sharing padded in-degree K."""

    nbr: jax.Array  # [R, K] int32 — in-neighbor NEW ids (pad: 0, ok=False)
    w: jax.Array  # [R, K] int32 — edge metric (pad: 1)
    edge_id: jax.Array  # [R, K] int32 — original directed edge id; -1 pad
    ok: jax.Array  # [R, K] bool — slot holds a real, up edge
    transit_ok: jax.Array  # [R, K] bool — in-neighbor is not overloaded


class EllGraph(NamedTuple):
    buckets: tuple  # tuple[EllBucket, ...] — rows cover [0, N_cap) in order
    new_of_old: jax.Array  # [N_cap] int32 — old node id -> relabeled id
    old_of_new: jax.Array  # [N_cap] int32 — relabeled id -> old node id


def build_ell(
    edge_src: np.ndarray,
    edge_dst: np.ndarray,
    edge_metric: np.ndarray,
    edge_up: np.ndarray,
    node_overloaded: np.ndarray,
    n_edges: int,
    k_floor: int = 4,
) -> EllGraph:
    """Host-side ELL construction from the padded directed-edge arrays
    (vectorized numpy — runs on every topology rebuild, so no Python
    per-edge loops).

    Buckets have power-of-two K >= in-degree (capacity headroom lets
    incremental updates edit slots in place without reshaping).  The
    baked ok/transit_ok tables snapshot edge_up/node_overloaded at build
    time; the production forward passes re-derive both from the runtime
    arrays (see `batched_sssp_ell`), so link/overload flips do NOT require
    an ELL rebuild — only edge-set changes do."""
    n_cap = len(node_overloaded)
    src = np.asarray(edge_src[:n_edges], dtype=np.int64)
    dst = np.asarray(edge_dst[:n_edges], dtype=np.int64)
    deg = np.bincount(dst, minlength=n_cap)

    # stable sort by descending degree -> equal-K runs are contiguous
    old_of_new = np.argsort(-deg, kind="stable").astype(np.int32)
    new_of_old = np.empty_like(old_of_new)
    new_of_old[old_of_new] = np.arange(n_cap, dtype=np.int32)

    # padded K per node: power of two >= max(deg, k_floor)
    deg_sorted = deg[old_of_new]
    exp = np.ceil(np.log2(np.maximum(deg_sorted, 1))).astype(np.int64)
    k_node = np.maximum(np.int64(1) << exp, k_floor)

    # slot index of each edge within its destination's in-edge list.
    # Edge arrays are sorted by (dst, src) so in-edges per dst are
    # contiguous; slot = position within the run, ordered by edge id.
    starts = np.concatenate([[0], np.cumsum(deg)[:-1]])
    slot = np.arange(n_edges, dtype=np.int64) - starts[dst]

    new_dst = new_of_old[dst].astype(np.int64)  # row in permuted space
    buckets: list[EllBucket] = []
    lo = 0
    while lo < n_cap:
        k = int(k_node[lo])
        # contiguous run of equal K (k_node is non-increasing)
        hi = int(np.searchsorted(-k_node, -k, side="right"))
        r = hi - lo
        nbr = np.zeros((r, k), dtype=np.int32)
        w = np.ones((r, k), dtype=np.int32)
        eid = np.full((r, k), -1, dtype=np.int32)
        ok = np.zeros((r, k), dtype=bool)
        t_ok = np.zeros((r, k), dtype=bool)
        in_bucket = (new_dst >= lo) & (new_dst < hi)
        rows = new_dst[in_bucket] - lo
        cols = slot[in_bucket]
        es = np.flatnonzero(in_bucket)
        nbr[rows, cols] = new_of_old[src[es]]
        w[rows, cols] = edge_metric[es]
        eid[rows, cols] = es
        ok[rows, cols] = edge_up[es]
        t_ok[rows, cols] = ~node_overloaded[src[es]]
        buckets.append(EllBucket(nbr, w, eid, ok, t_ok))
        lo = hi

    return EllGraph(tuple(buckets), new_of_old, old_of_new)


def make_dist0_T(
    sources: jax.Array,
    new_of_old: jax.Array,
    n_cap: int,
    small_dist: bool = False,
) -> jax.Array:
    """Transposed-permuted dist0: [N_cap, S] with 0 at each column's source.

    Built as a dense compare, NOT a scatter: scatter ops knock the TPU
    runtime off its fast dispatch path (measured: one scatter in a session
    adds a flat ~100ms penalty to every subsequent kernel launch), so the
    production path must be scatter-free end to end."""
    rows = jnp.take(new_of_old, sources)  # [S]
    is_src = jnp.arange(n_cap, dtype=jnp.int32)[:, None] == rows[None, :]
    if small_dist:
        return jnp.where(is_src, jnp.uint16(0), INF16)
    return jnp.where(is_src, jnp.int32(0), INF32)


@functools.partial(
    jax.jit, static_argnames=("unit_metric", "check_every", "n_sweeps")
)
def batched_sssp_ell(
    dist0_T: jax.Array,  # [N_cap, S] int32 (permuted node rows)
    ell: EllGraph,
    row_allowed_T: Optional[jax.Array] = None,  # [E_cap, S] bool, or None
    unit_metric: bool = False,
    check_every: int = 1,
    edge_up: Optional[jax.Array] = None,  # [E_cap] bool (runtime state)
    node_overloaded: Optional[jax.Array] = None,  # [N_cap] bool, OLD ids
    edge_metric: Optional[jax.Array] = None,  # [E_cap] int32 (runtime state)
    n_sweeps: Optional[int] = None,
):
    """Fixed-point ELL relaxation; returns dist_T [N_cap, S] (permuted).

    With `n_sweeps` (static): runs exactly that many relax sweeps in a
    `fori_loop` plus one verification sweep, returning
    `(dist_T, converged)` — NO data-dependent loop.  A `while_loop` with a
    convergence cond forces a host sync per iteration, so production
    callers run fixed sweeps sized by an adaptive per-topology
    hint and double on a False verdict (csr.CsrTopology.spf_from).
    Without `n_sweeps`: converges via while_loop and returns dist_T only.

    When `edge_up` / `node_overloaded` / `edge_metric` are given, slot
    permissions and weights are derived from them at call time (per-bucket
    [R, K] gathers via edge_id — negligible), so link flaps, drain flips
    and metric changes never require an ELL rebuild and can never disagree
    with the tables.  Without them the build-time snapshots baked into
    `ell` apply.

    `row_allowed_T` adds per-(row, edge) exclusions (KSP link masking, SRLG
    what-if) on top of the up/transit conditions.
    `check_every` batches the convergence reduction over that many relax
    sweeps (saves two [N, S] passes per skipped check on large problems).

    Distances run in the dtype of `dist0_T`: uint16 (INF16 sentinel,
    weights clamped to WBIG16 so adds never wrap — round-5, same
    discipline as ops.banded) halves every gather's bytes; callers gate
    on pick_small_dist and verify the saturation guard.
    """
    n_cap = dist0_T.shape[0]
    small = dist0_T.dtype == jnp.uint16
    inf = INF16 if small else INF32

    # loop-invariant slot permissions, possibly runtime-derived
    overloaded_new = (
        None
        if node_overloaded is None
        else jnp.take(node_overloaded, ell.old_of_new)
    )
    slot_ok: list = []
    slot_transit: list = []
    slot_w: list = []
    slot_allowed: list = []
    for bk in ell.buckets:
        if edge_up is None:
            ok = bk.ok
        else:
            ok = (bk.edge_id >= 0) & jnp.take(
                edge_up, jnp.maximum(bk.edge_id, 0)
            )
        if overloaded_new is None:
            transit = bk.transit_ok
        else:
            transit = ~jnp.take(overloaded_new, bk.nbr)
        w = (
            bk.w
            if edge_metric is None
            else jnp.take(edge_metric, jnp.maximum(bk.edge_id, 0))
        )
        if small:
            w = clamp_metric_u16(w)
        slot_ok.append(ok)
        slot_transit.append(transit)
        slot_w.append(w)
        if row_allowed_T is None:
            slot_allowed.append(None)
        else:
            # HOISTED: the per-row exclusion mask is loop-invariant, so
            # gather it into slot space ONCE ([R, K, S] per bucket)
            # instead of per sweep — per-index gather cost dominates the
            # sweep on TPU, and this halves the masked sweep's gathers
            r, k = bk.nbr.shape
            ej = bk.edge_id
            sa = (ej >= 0)[:, :, None] & jnp.take(
                row_allowed_T, jnp.maximum(ej, 0).reshape(-1), axis=0
            ).reshape(r, k, -1)
            slot_allowed.append(sa)

    def relax(dist_T):
        parts = []
        lo = 0
        for b, bk in enumerate(ell.buckets):
            r, k = bk.nbr.shape
            acc = jax.lax.slice_in_dim(dist_T, lo, lo + r, axis=0)
            # static unroll over slots: each step is one [R, S] row gather
            # plus elementwise min — XLA fuses the whole sweep; a fori_loop
            # with dynamic slot indexing defeats that fusion (~1000x slower
            # measured on v5e)
            for j in range(k):
                d_u = jnp.take(dist_T, bk.nbr[:, j], axis=0)  # [R, S]
                allow = slot_ok[b][:, j][:, None] & (
                    slot_transit[b][:, j][:, None] | (d_u == 0)
                )
                if slot_allowed[b] is not None:
                    allow &= slot_allowed[b][:, j]
                metric_j = (
                    (jnp.uint16(1) if small else jnp.int32(1))
                    if unit_metric
                    else slot_w[b][:, j][:, None]
                )
                cand = jnp.where(allow & (d_u < inf), d_u + metric_j, inf)
                acc = jnp.minimum(acc, cand)
            parts.append(acc)
            lo += r
        assert lo == n_cap
        return jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]

    if n_sweeps is not None:
        dist_T = jax.lax.fori_loop(
            0, n_sweeps, lambda i, d: relax(d), dist0_T
        )
        verify = relax(dist_T)
        return verify, jnp.all(verify == dist_T)

    def cond(state):
        _, changed, it = state
        return changed & (it < n_cap)

    def body(state):
        dist_T, _, it = state
        new = dist_T
        for _ in range(check_every):
            new = relax(new)
        return new, jnp.any(new != dist_T), it + check_every

    dist_T, _, _ = jax.lax.while_loop(
        cond, body, (dist0_T, jnp.bool_(True), 0)
    )
    return dist_T


def ell_dist_to_old_T(dist_T: jax.Array, ell: EllGraph) -> jax.Array:
    """Permuted [N_cap, S] -> original-id [N_cap, S] (still transposed —
    callers that need [S, N] transpose at their boundary)."""
    return jnp.take(dist_T, ell.new_of_old, axis=0)


def make_relax_allowed_T(
    sources: jax.Array,  # [S]
    edge_src: jax.Array,  # [E]
    edge_up: jax.Array,  # [E]
    node_overloaded: jax.Array,  # [N]
    extra_edge_mask_T: jax.Array | None = None,  # [E, S] or [E]
) -> jax.Array:
    """Edge-major ([E, S]) variant of `make_relax_allowed` — the layout the
    transposed DAG/relax kernels consume without a transpose."""
    transit_ok = ~node_overloaded[edge_src]  # [E]
    allowed = edge_up[:, None] & (
        transit_ok[:, None] | (edge_src[:, None] == sources[None, :])
    )
    if extra_edge_mask_T is not None:
        if extra_edge_mask_T.ndim == 1:
            extra_edge_mask_T = extra_edge_mask_T[:, None]
        allowed = allowed & extra_edge_mask_T
    return allowed


@jax.jit
def sp_dag_mask_from_T(
    dist_old_T: jax.Array,  # [N_cap, S] int32 — ORIGINAL node ids
    edge_src: jax.Array,
    edge_dst: jax.Array,
    edge_metric: jax.Array,
    allowed_T: jax.Array,  # [E, S]
) -> jax.Array:
    """`sp_dag_mask` computed in edge-major space (row gathers only —
    the [S, N] column gather of the untransposed form is pathologically
    slow on TPU); returns dag [S, E]."""
    d_u = jnp.take(dist_old_T, edge_src, axis=0)  # [E, S]
    d_v = jnp.take(dist_old_T, edge_dst, axis=0)
    dag_T = allowed_T & (d_u < INF32) & (d_u + edge_metric[:, None] == d_v)
    return dag_T.T


@functools.partial(jax.jit, static_argnames=("use_link_metric",))
def spf_forward_ell(
    sources: jax.Array,  # [S] int32 (original ids)
    ell: EllGraph,
    edge_src: jax.Array,
    edge_dst: jax.Array,
    edge_metric: jax.Array,
    edge_up: jax.Array,
    node_overloaded: jax.Array,
    use_link_metric: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Production forward pass: ELL distances + edge-space SP-DAG.

    Same contract as `spf_forward` (dist [S, N_cap] original ids,
    dag [S, E_cap]) but relaxation runs on the bucketed ELL tables."""
    n_cap = node_overloaded.shape[0]
    dist_T = batched_sssp_ell(
        make_dist0_T(sources, ell.new_of_old, n_cap),
        ell,
        unit_metric=not use_link_metric,
        edge_up=edge_up,
        node_overloaded=node_overloaded,
        edge_metric=edge_metric,
    )
    dist_old_T = ell_dist_to_old_T(dist_T, ell)  # [N_cap, S]
    metric = edge_metric if use_link_metric else jnp.ones_like(edge_metric)
    allowed_T = make_relax_allowed_T(sources, edge_src, edge_up, node_overloaded)
    dag = sp_dag_mask_from_T(dist_old_T, edge_src, edge_dst, metric, allowed_T)
    return dist_old_T.T, dag


@functools.partial(
    jax.jit, static_argnames=("use_link_metric", "want_dag")
)
def spf_forward_ell_masked(
    sources: jax.Array,
    ell: EllGraph,
    edge_src: jax.Array,
    edge_dst: jax.Array,
    edge_metric: jax.Array,
    edge_up: jax.Array,
    node_overloaded: jax.Array,
    extra_edge_mask: jax.Array,  # [S, E_cap] or [E_cap] bool, False = exclude
    use_link_metric: bool = True,
    want_dag: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """ELL forward with per-row edge exclusions (KSP re-runs, SRLG
    what-if).  The [S, E] mask is materialized — callers batch many
    variants, so S is the what-if dimension here.  With want_dag=False
    only distances are computed/returned (dist, None) — the what-if
    reachability analysis never reads the DAG."""
    n_cap = node_overloaded.shape[0]
    extra_T = (
        extra_edge_mask.T if extra_edge_mask.ndim == 2 else extra_edge_mask
    )
    allowed_T = make_relax_allowed_T(
        sources, edge_src, edge_up, node_overloaded, extra_T
    )
    dist_T = batched_sssp_ell(
        make_dist0_T(sources, ell.new_of_old, n_cap),
        ell,
        row_allowed_T=allowed_T,
        unit_metric=not use_link_metric,
        edge_up=edge_up,
        node_overloaded=node_overloaded,
        edge_metric=edge_metric,
    )
    dist_old_T = ell_dist_to_old_T(dist_T, ell)
    if not want_dag:
        return dist_old_T.T, None
    metric = edge_metric if use_link_metric else jnp.ones_like(edge_metric)
    dag = sp_dag_mask_from_T(dist_old_T, edge_src, edge_dst, metric, allowed_T)
    return dist_old_T.T, dag


@functools.partial(
    jax.jit, static_argnames=("n_words", "check_every", "n_sweeps")
)
def first_hops_ell(
    ell: EllGraph,
    dag_T: jax.Array,  # [E_cap, S] bool — edge-major SP-DAG (original edge ids)
    out_slot: jax.Array,  # [E_cap] int32 — slot of edge among its source
    #   node's sorted unique out-neighbors; -1 for padding
    sources: jax.Array,  # [S] int32 — original node ids
    edge_src: jax.Array,  # [E_cap] int32 — original node ids
    n_words: int,  # ceil(max_slots / 32)
    check_every: int = 1,
    n_sweeps: Optional[int] = None,
):
    """First-hop sets propagated along the SP-DAG, bit-packed.

    With static `n_sweeps`: fixed fori_loop + one verification sweep,
    returning (nh, converged) — same host-sync rationale as
    `batched_sssp_ell`.  Without: while_loop to fixed point, returns nh.

    Returns nh [S, N_cap, n_words] uint32 (ORIGINAL node ids): bit b of
    word w is set for (s, v) iff slot (32w + b) — an out-neighbor of row
    s's source — begins some shortest path to v.  Device replacement for
    the reference's per-node nextHops accumulation (runSpf addNextHops,
    LinkState.cpp:855-869); the host only decodes bits afterwards.

    Gather-only (no scatters): propagation gathers predecessor masks
    through the ELL in-edge tables; an edge leaving the row's own source
    contributes its own out-slot bit instead of the predecessor mask."""
    n_cap = ell.new_of_old.shape[0]
    s_dim = sources.shape[0]

    # per-edge initial contribution: if the edge leaves the row's source,
    # its out-slot bit, else 0
    is_src_edge = edge_src[:, None] == sources[None, :]  # [E_cap, S]

    # HOISTED loop invariants (the dag, source membership and slot-bit
    # tables never change across sweeps): gathering them per sweep used to
    # triple the sweep's gather count, and per-index gather cost dominates
    # on TPU.  Precompute per (bucket, slot):
    #   src_contrib [R, S, W] — OR-term contributed by source-leaving
    #     dag edges (constant across sweeps)
    #   use_pred    [R, K, S] — dag edges that forward the predecessor mask
    src_contrib: list = []
    use_pred: list = []
    for bk in ell.buckets:
        r, k = bk.nbr.shape
        ej_all = jnp.maximum(bk.edge_id, 0)  # [R, K]
        on_dag = jnp.take(dag_T, ej_all.reshape(-1), axis=0).reshape(
            r, k, -1
        ) & (bk.edge_id >= 0)[:, :, None]  # [R, K, S]
        from_src = jnp.take(
            is_src_edge, ej_all.reshape(-1), axis=0
        ).reshape(r, k, -1)  # [R, K, S]
        slot = jnp.take(out_slot, ej_all)  # [R, K]
        bit = jnp.where(
            slot >= 0,
            jnp.uint32(1) << (jnp.maximum(slot, 0) % 32).astype(jnp.uint32),
            jnp.uint32(0),
        )
        src_words = jnp.where(
            (jnp.maximum(slot, 0) // 32)[:, :, None]
            == jnp.arange(n_words)[None, None, :],
            bit[:, :, None],
            jnp.uint32(0),
        )  # [R, K, W]
        # OR over slots of the constant source contributions
        sc = jnp.zeros((r, on_dag.shape[2], n_words), dtype=jnp.uint32)
        for j in range(k):
            sc = sc | jnp.where(
                (on_dag[:, j] & from_src[:, j])[:, :, None],
                src_words[:, j][:, None, :],
                jnp.uint32(0),
            )
        src_contrib.append(sc)  # [R, S, W]
        use_pred.append(on_dag & ~from_src)  # [R, K, S]

    def relax(nh_T):
        # nh_T: [N_cap, S, W] uint32, permuted rows
        parts = []
        lo = 0
        for b, bk in enumerate(ell.buckets):
            r, k = bk.nbr.shape
            acc = jax.lax.slice_in_dim(nh_T, lo, lo + r, axis=0)
            acc = acc | src_contrib[b]
            for j in range(k):
                pred = jnp.take(nh_T, bk.nbr[:, j], axis=0)  # [R, S, W]
                acc = acc | jnp.where(
                    use_pred[b][:, j][:, :, None], pred, jnp.uint32(0)
                )
            parts.append(acc)
            lo += r
        assert lo == n_cap
        return jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]

    nh0 = jnp.zeros((n_cap, s_dim, n_words), dtype=jnp.uint32)

    def to_original(nh_T):
        # permute rows back to original ids, reorder to [S, N, W]
        return jnp.take(nh_T, ell.new_of_old, axis=0).transpose(1, 0, 2)

    if n_sweeps is not None:
        nh_T = jax.lax.fori_loop(0, n_sweeps, lambda i, x: relax(x), nh0)
        verify = relax(nh_T)
        return to_original(verify), jnp.all(verify == nh_T)

    def cond(state):
        _, changed, it = state
        return changed & (it < n_cap)

    def body(state):
        nh_T, _, it = state
        new = nh_T
        for _ in range(check_every):
            new = relax(new)
        return new, jnp.any(new != nh_T), it + check_every

    nh_T, _, _ = jax.lax.while_loop(cond, body, (nh0, jnp.bool_(True), 0))
    return to_original(nh_T)


@functools.partial(
    jax.jit,
    static_argnames=("use_link_metric", "n_words", "check_every", "n_sweeps"),
)
def spf_forward_full(
    sources: jax.Array,  # [S] int32
    ell: EllGraph,
    edge_src: jax.Array,
    edge_dst: jax.Array,
    edge_metric: jax.Array,
    edge_up: jax.Array,
    node_overloaded: jax.Array,
    out_slot: jax.Array,  # [E_cap] int32
    n_words: int,
    use_link_metric: bool = True,
    check_every: int = 1,
    n_sweeps: Optional[int] = None,
):
    """Distances + SP-DAG + bit-packed first-hop sets in ONE device call —
    the full production forward for route building.

    With static `n_sweeps`: both fixed-point loops run fixed sweeps and
    the call returns (dist, dag, nh, converged) with a single combined
    convergence verdict (see batched_sssp_ell's host-sync rationale)."""
    n_cap = node_overloaded.shape[0]
    dist_out = batched_sssp_ell(
        make_dist0_T(sources, ell.new_of_old, n_cap),
        ell,
        unit_metric=not use_link_metric,
        check_every=check_every,
        edge_up=edge_up,
        node_overloaded=node_overloaded,
        edge_metric=edge_metric,
        n_sweeps=n_sweeps,
    )
    if n_sweeps is not None:
        dist_T, dist_ok = dist_out
    else:
        dist_T, dist_ok = dist_out, None
    dist_old_T = ell_dist_to_old_T(dist_T, ell)
    metric = edge_metric if use_link_metric else jnp.ones_like(edge_metric)
    allowed_T = make_relax_allowed_T(sources, edge_src, edge_up, node_overloaded)
    d_u = jnp.take(dist_old_T, edge_src, axis=0)
    d_v = jnp.take(dist_old_T, edge_dst, axis=0)
    dag_T = allowed_T & (d_u < INF32) & (d_u + metric[:, None] == d_v)
    nh_out = first_hops_ell(
        ell,
        dag_T,
        out_slot,
        sources,
        edge_src,
        n_words,
        check_every=check_every,
        n_sweeps=n_sweeps,
    )
    if n_sweeps is not None:
        nh, nh_ok = nh_out
        return dist_old_T.T, dag_T.T, nh, dist_ok & nh_ok
    return dist_old_T.T, dag_T.T, nh_out


@functools.partial(
    jax.jit,
    static_argnames=("use_link_metric", "n_words", "check_every", "n_sweeps"),
)
def spf_forward_full_packed(
    sources: jax.Array,
    ell: EllGraph,
    edge_src: jax.Array,
    edge_dst: jax.Array,
    edge_metric: jax.Array,
    edge_up: jax.Array,
    node_overloaded: jax.Array,
    out_slot: jax.Array,
    n_words: int,
    use_link_metric: bool = True,
    check_every: int = 1,
    n_sweeps: Optional[int] = None,
) -> jax.Array:
    """`spf_forward_full` with (dist, dag, nh[, converged]) flattened into
    ONE int32 buffer, so the host needs a single device->host transfer.
    Matters for small-S control-plane queries where per-transfer latency
    dominates; callers unpack by known sizes.  With `n_sweeps`, the final element is the convergence verdict
    (1 = fixed point reached)."""
    out = spf_forward_full(
        sources,
        ell,
        edge_src,
        edge_dst,
        edge_metric,
        edge_up,
        node_overloaded,
        out_slot,
        n_words,
        use_link_metric=use_link_metric,
        check_every=check_every,
        n_sweeps=n_sweeps,
    )
    dist, dag, nh = out[0], out[1], out[2]
    parts = [
        dist.ravel(),
        dag.ravel().astype(jnp.int32),
        jax.lax.bitcast_convert_type(nh, jnp.int32).ravel(),
    ]
    if n_sweeps is not None:
        parts.append(out[3].astype(jnp.int32)[None])
    return jnp.concatenate(parts)


@functools.partial(
    jax.jit,
    static_argnames=(
        "use_link_metric",
        "n_sweeps",
        "want_dag",
        "small_dist",
        "raw_u16",
        "transpose",
    ),
)
def spf_forward_ell_sweeps(
    sources: jax.Array,
    ell: EllGraph,
    edge_src: jax.Array,
    edge_dst: jax.Array,
    edge_metric: jax.Array,
    edge_up: jax.Array,
    node_overloaded: jax.Array,
    n_sweeps: int,
    use_link_metric: bool = True,
    extra_edge_mask: Optional[jax.Array] = None,
    want_dag: bool = True,
    small_dist: bool = False,
    raw_u16: bool = False,
    transpose: bool = True,
):
    """Fixed-sweep ELL forward: (dist [S, N_cap], dag, converged) — the
    production execution discipline (no data-dependent while_loop, which
    costs a host sync per iteration on latency-bound transports) exposed
    for dist+dag callers: bench rows and batch KSP/what-if runs on
    topologies without band structure (see ops.banded for the rest).

    ``small_dist`` runs the relax AND the DAG extraction in uint16
    (half the gather bytes; callers gate on pick_small_dist); the
    in-kernel saturation guard certifies no distance overflowed exactly
    as in ops.banded.  ``raw_u16`` additionally returns the raw uint16
    distances (INF16 sentinel) when want_dag=False — consumers key on
    dtype."""
    # static-arg guard (trace time): the dag path returns [S, N_cap]
    # unconditionally (see ops.banded.spf_forward_banded)
    assert transpose or not want_dag, (
        "transpose=False requires want_dag=False"
    )
    n_cap = node_overloaded.shape[0]
    extra_T = None
    if extra_edge_mask is not None:
        extra_T = (
            extra_edge_mask.T
            if extra_edge_mask.ndim == 2
            else extra_edge_mask[:, None]
        )
    allowed_T = make_relax_allowed_T(
        sources, edge_src, edge_up, node_overloaded, extra_T
    )
    dist_T, converged = batched_sssp_ell(
        make_dist0_T(sources, ell.new_of_old, n_cap, small_dist=small_dist),
        ell,
        row_allowed_T=allowed_T if extra_edge_mask is not None else None,
        unit_metric=not use_link_metric,
        edge_up=edge_up,
        node_overloaded=node_overloaded,
        edge_metric=edge_metric,
        n_sweeps=n_sweeps,
    )
    dist_old_T = ell_dist_to_old_T(dist_T, ell)
    dist16_old_T = None
    if small_dist:
        converged = u16_saturation_verdict(dist_old_T, converged)
        dist16_old_T = dist_old_T
        if raw_u16 and not want_dag:
            return (
                (dist_old_T.T if transpose else dist_old_T),
                None,
                converged,
            )
        dist_old_T = u16_dist_to_i32(dist_old_T)
    if not want_dag:
        return (dist_old_T.T if transpose else dist_old_T), None, converged
    metric = edge_metric if use_link_metric else jnp.ones_like(edge_metric)
    if dist16_old_T is not None:
        dag = sp_dag_mask16_from_T(
            dist16_old_T, edge_src, edge_dst, metric, allowed_T
        )
        return dist_old_T.T, dag, converged
    dag = sp_dag_mask_from_T(dist_old_T, edge_src, edge_dst, metric, allowed_T)
    return dist_old_T.T, dag, converged


@functools.partial(jax.jit, static_argnames=("use_link_metric",))
def spf_forward(
    sources: jax.Array,  # [S] int32
    edge_src: jax.Array,
    edge_dst: jax.Array,
    edge_metric: jax.Array,
    edge_up: jax.Array,
    node_overloaded: jax.Array,
    use_link_metric: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """One-call forward: distances + SP-DAG for a batch of sources.
    This is the flagship jittable step (see __graft_entry__)."""
    metric = edge_metric if use_link_metric else jnp.ones_like(edge_metric)
    n_nodes = node_overloaded.shape[0]
    allowed = make_relax_allowed(sources, edge_src, edge_up, node_overloaded)
    dist = batched_sssp(make_dist0(sources, n_nodes), edge_src, edge_dst, metric, allowed)
    dag = sp_dag_mask(dist, edge_src, edge_dst, metric, allowed)
    return dist, dag
