"""Blocked min-plus APSP over a ("batch", "row", "col") device mesh.

The dest-sharded fleet product (`parallel.mesh.fleet_product_sharded`)
splits the destination axis P, so the node count N is still capped by a
single chip's HBM: every device holds the full [N, P] distance state and
the whole graph mirror.  This module removes that ceiling by sharding
the NODE axis both ways — the classic three-phase blocked
Floyd-Warshall, following the 3-D-tensor accelerator formulation
(PAPERS.md, arxiv 2310.03983), expressed as jitted per-phase kernels
with explicit `NamedSharding`s so XLA inserts the row/col broadcasts.

Layout (the load-bearing trick): the padded Np x Np distance matrix is
held as a 4-D tile tensor

    dist [S, T, B, T, B]    P("batch", None, "row", None, "col")

node g -> (tile t = g // B, lane l = g % B).  The TILE dims stay
UNsharded and the intra-tile LANE dims shard over the mesh, so the
per-round panel extraction `dist[:, k]` / `dist[:, :, :, k]` is a
dynamic-slice on an unsharded dim — purely local, no matter that k is a
traced scalar.  The only collectives are then exactly the textbook
panel broadcasts: the row panel all-gathers its lane dim over "row",
the col panel over "col", and the B x B diagonal tile replicates — per
round O(B * Np) bytes against O(Np^2 / (R*C)) local compute.  The
leading S axis composes with the existing what-if batch: variants stay
embarrassingly parallel over "batch" while N shards both ways.

Per k-round (T = Np / B rounds), with `closed` the masked FW closure of
the diagonal tile:

    phase 1 (diag):   closed = FW(dist[k][k])          replicated
    phase 2 (panels): row' = min(row, closed (*) row)  P(-,-,-,"col")
                      col' = min(col, col (*) closed)  P(-,-,"row",-)
    phase 3 (outer):  dist[k] <- row'; dist[:,:,k] <- col'
                      dist = min(dist, col' (*) row')  rank-B update

where (*) is the min-plus product MASKED at the intermediate: a
contribution through lane m of tile k is dropped (INF) when node m is
overloaded.  That mask IS the fleet drain rule — an overloaded node
relays nothing but remains a valid endpoint (for positive metrics the
relax-kernel exception "blocked as transit unless its distance is 0"
is exactly "excluded as an intermediate") — so the blocked product is
bit-exact against `ops.allsources.reduced_all_sources` after the
int32 normalization.  The panel write-back in phase 3 is REQUIRED
under the mask: the plain-FW shortcut of folding panels into the outer
update assumes the unmasked zero-diagonal argument and silently loses
panel improvements when lanes of tile k are overloaded.

Arithmetic is saturating uint32 min-plus: INF is 1 << 30 (== the int32
INF32 sentinel), finite + finite <= 2^31 never wraps in uint32, and
`min(a + b, INF)` re-saturates — no floats anywhere, per the program
dtype rule.

Lookahead pipelining (the SUMMA/Cannon trick): for multi-round
closures the per-round loop runs `blocked_round_pipelined`, a fused
root that performs round k's write-back + rank-B outer update AND
round k+1's diagonal closure + panel updates in the same program.  The
k+1 panels are derived from the round-k panels restricted to the k+1
slices (integer min-plus is exact, so the restriction is bit-identical
to slicing the full outer update), which makes the k+1 panel
all-gathers data-independent of the round-k outer fori_loop — the
scheduler is then free to run the collectives under the compute.
`parallel.hlo_async` proves that independence from the lowered
module's def-use chains and materializes the async
all-gather-start/done spans.  `OPENR_BLOCKED_PIPELINE=0` forces the
bulk-synchronous loop; any pipelining failure demotes to it
(`mesh.blocked.pipeline_fallbacks`).
"""

from __future__ import annotations

import functools
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import trace as _trace

from ..ops import allsources as asrc

# saturation sentinel: uint32 mirror of the int32 INF32 = 1 << 30 used
# across the decision plane, so the extract is a pure dtype cast
INF32 = 1 << 30
_INFU = np.uint32(INF32)

#: exported through the ctrl handler's `mesh` surface; pre-seeded in
#: __init__ so every key dumps before the first dispatch
BLOCKED_COUNTER_KEYS = (
    "mesh.blocked.products",
    "mesh.blocked.rounds",
    "mesh.blocked.tile_updates",
    "mesh.blocked.panel_broadcasts",
    "mesh.blocked.bytes_exchanged",
    "mesh.blocked.diag_us",
    "mesh.blocked.panel_us",
    "mesh.blocked.outer_us",
    "mesh.blocked.extract_us",
    "mesh.blocked.fallbacks",
    "mesh.blocked.pipeline_rounds_overlapped",
    "mesh.blocked.pipeline_prefetch_issues",
    "mesh.blocked.pipeline_fallbacks",
    "mesh.blocked.pipeline_overlap_frac_est",
)


def make_blocked_mesh(
    devices=None,
    batch: int = 1,
    rows: int | None = None,
    cols: int | None = None,
) -> Mesh:
    """Build the ("batch", "row", "col") mesh over the given (or all)
    devices.  Omitted row/col sizes are factored from the device count
    (squarest split); indivisible requests raise ValueError with the
    numbers spelled out — mesh-shape mismatch is the documented
    graceful-fallback trigger, not an assert."""
    devices = list(devices) if devices is not None else jax.devices()
    n = len(devices)
    if batch <= 0 or n % batch:
        raise ValueError(
            f"blocked mesh: {n} devices do not divide into a batch axis "
            f"of {batch} (need batch * rows * cols == {n})"
        )
    per = n // batch
    if rows is None and cols is None:
        r = max(1, int(math.isqrt(per)))
        while per % r:
            r -= 1
        rows, cols = r, per // r
    elif rows is None:
        if cols <= 0 or per % cols:
            raise ValueError(
                f"blocked mesh: {per} devices per batch slice "
                f"({n} devices / batch={batch}) do not divide into "
                f"cols={cols}"
            )
        rows = per // cols
    elif cols is None:
        if rows <= 0 or per % rows:
            raise ValueError(
                f"blocked mesh: {per} devices per batch slice "
                f"({n} devices / batch={batch}) do not divide into "
                f"rows={rows}"
            )
        cols = per // rows
    if rows <= 0 or cols <= 0 or rows * cols != per:
        raise ValueError(
            f"blocked mesh: rows={rows} x cols={cols} != {per} devices "
            f"per batch slice ({n} devices / batch={batch})"
        )
    dev = np.asarray(devices).reshape(batch, rows, cols)
    return Mesh(dev, ("batch", "row", "col"))


def _sat_minplus(a, b):
    """Saturating uint32 min-plus accumulation term: a + b re-clamped to
    the INF sentinel (a, b <= INF = 2^30, so the uint32 add never
    wraps)."""
    return jnp.minimum(a + b, _INFU)


def _ov_lanes(node_overloaded, k, b: int):
    """[B] bool — drain mask for the lanes of tile k (node g = k*B + l
    blocked as an intermediate when overloaded)."""
    return lax.dynamic_slice_in_dim(node_overloaded, k * b, b)


@functools.partial(jax.jit, static_argnames=("mesh",))
def blocked_diag(dist, node_overloaded, k, *, mesh: Mesh):
    """Phase 1: masked FW closure of the k-th diagonal tile.

    dist [S, T, B, T, B] stays resident; the [S, B, B] tile replicates
    (the only phase-1 exchange).  B sequential rank-1 relaxations —
    work is O(B^3), duplicated on every device by design (cheaper than
    round-tripping a tile that every device needs anyway)."""
    s_repl = NamedSharding(mesh, P("batch"))
    b = dist.shape[2]
    tile = lax.dynamic_index_in_dim(
        lax.dynamic_index_in_dim(dist, k, axis=1, keepdims=False),
        k,
        axis=2,
        keepdims=False,
    )  # [S, B, B]
    tile = lax.with_sharding_constraint(tile, s_repl)
    ov = _ov_lanes(node_overloaded, k, b)

    def body(m, d):
        ov_m = lax.dynamic_index_in_dim(ov, m, axis=0, keepdims=False)
        col_m = lax.dynamic_index_in_dim(d, m, axis=2, keepdims=False)
        row_m = lax.dynamic_index_in_dim(d, m, axis=1, keepdims=False)
        cand = _sat_minplus(col_m[:, :, None], row_m[:, None, :])
        cand = jnp.where(ov_m, _INFU, cand)
        return jnp.minimum(d, cand)

    closed = lax.fori_loop(0, b, body, tile)
    return lax.with_sharding_constraint(closed, s_repl)


@functools.partial(jax.jit, static_argnames=("mesh",))
def blocked_panels(dist, closed, node_overloaded, k, *, mesh: Mesh):
    """Phase 2: update the k-th row and column panels through the closed
    diagonal tile.  The extraction is local (tile dims are unsharded);
    the sharding constraints below are the two panel BROADCASTS — the
    row panel's lane dim all-gathers over "row", the col panel's over
    "col" — after which each min-plus contraction is collective-free."""
    s_row_p = NamedSharding(mesh, P("batch", None, None, "col"))
    s_col_p = NamedSharding(mesh, P("batch", None, "row", None))
    b = dist.shape[2]
    row = lax.dynamic_index_in_dim(dist, k, axis=1, keepdims=False)
    row = lax.with_sharding_constraint(row, s_row_p)  # [S, B, T, B]
    col = lax.dynamic_index_in_dim(dist, k, axis=3, keepdims=False)
    col = lax.with_sharding_constraint(col, s_col_p)  # [S, T, B, B]
    ov = _ov_lanes(node_overloaded, k, b)

    def row_body(m, r):
        ov_m = lax.dynamic_index_in_dim(ov, m, axis=0, keepdims=False)
        c = lax.dynamic_index_in_dim(closed, m, axis=2, keepdims=False)
        rm = lax.dynamic_index_in_dim(row, m, axis=1, keepdims=False)
        cand = _sat_minplus(c[:, :, None, None], rm[:, None, :, :])
        return jnp.minimum(r, jnp.where(ov_m, _INFU, cand))

    def col_body(m, c_acc):
        ov_m = lax.dynamic_index_in_dim(ov, m, axis=0, keepdims=False)
        cm = lax.dynamic_index_in_dim(col, m, axis=3, keepdims=False)
        r = lax.dynamic_index_in_dim(closed, m, axis=1, keepdims=False)
        cand = _sat_minplus(cm[:, :, :, None], r[:, None, None, :])
        return jnp.minimum(c_acc, jnp.where(ov_m, _INFU, cand))

    row_p = lax.fori_loop(0, b, row_body, row)
    col_p = lax.fori_loop(0, b, col_body, col)
    return (
        lax.with_sharding_constraint(row_p, s_row_p),
        lax.with_sharding_constraint(col_p, s_col_p),
    )


@functools.partial(
    jax.jit, static_argnames=("mesh",), donate_argnums=(0,)
)
def blocked_outer(dist, row_p, col_p, node_overloaded, k, *, mesh: Mesh):
    """Phase 3: write the updated panels back, then the rank-B outer
    min-plus update over the whole matrix.  The write-back must come
    first: under the drain mask the outer product does NOT subsume the
    panel positions (the zero-diagonal shortcut of unmasked blocked FW
    breaks when lanes of tile k are overloaded).  Both panels agree on
    the diagonal tile (= closed), so the write order is immaterial."""
    s_dist = NamedSharding(mesh, P("batch", None, "row", None, "col"))
    b = dist.shape[2]
    dist = lax.dynamic_update_index_in_dim(
        dist, lax.with_sharding_constraint(row_p, NamedSharding(
            mesh, P("batch", "row", None, "col"))), k, axis=1
    )
    dist = lax.dynamic_update_index_in_dim(
        dist, lax.with_sharding_constraint(col_p, NamedSharding(
            mesh, P("batch", None, "row", "col"))), k, axis=3
    )
    ov = _ov_lanes(node_overloaded, k, b)

    def body(m, d):
        ov_m = lax.dynamic_index_in_dim(ov, m, axis=0, keepdims=False)
        cm = lax.dynamic_index_in_dim(col_p, m, axis=3, keepdims=False)
        rm = lax.dynamic_index_in_dim(row_p, m, axis=1, keepdims=False)
        cand = _sat_minplus(
            cm[:, :, :, None, None], rm[:, None, None, :, :]
        )
        return jnp.minimum(d, jnp.where(ov_m, _INFU, cand))

    dist = lax.fori_loop(0, b, body, dist)
    return lax.with_sharding_constraint(dist, s_dist)


def _lookahead(nrow, ncol, row_p, col_p, node_overloaded, k, k_next, *, mesh):
    """Round-(k+1) panel prefetch from the round-k panels.

    nrow [S, B, T, B] / ncol [S, T, B, B] are the k+1 panel slices with
    round k's WRITE-BACK already applied (sliced by
    `blocked_round_pipelined` from the written-back matrix).
    Three steps, each bit-exact against slicing the bulk-synchronous
    result:

      1. round k's rank-B outer update RESTRICTED to the k+1 slices —
         integer min-plus is exact and order-free, so restricting the
         update to a slab equals slicing the full update;
      2. phase 1 of round k+1: masked FW closure of the next diagonal
         tile (its replication constraint is a collective);
      3. phase 2 of round k+1: panel updates through the closed tile —
         the s_row_p/s_col_p constraints here are THE panel
         all-gathers the pipeline hides under round k's outer loop.

    Nothing in this chain reads the full-matrix outer update, so the
    collectives it issues are provably independent of the round-k
    compute (parallel.hlo_async verifies that from the lowered HLO)."""
    s_repl = NamedSharding(mesh, P("batch"))
    s_row_p = NamedSharding(mesh, P("batch", None, None, "col"))
    s_col_p = NamedSharding(mesh, P("batch", None, "row", None))
    b = row_p.shape[1]
    ov = _ov_lanes(node_overloaded, k, b)
    # the round-k panel blocks facing tile k+1
    colblk = lax.dynamic_index_in_dim(
        col_p, k_next, axis=1, keepdims=False
    )  # [S, B, B]
    rowblk = lax.dynamic_index_in_dim(
        row_p, k_next, axis=2, keepdims=False
    )  # [S, B, B]

    def nrow_body(m, r):
        ov_m = lax.dynamic_index_in_dim(ov, m, axis=0, keepdims=False)
        cm = lax.dynamic_index_in_dim(colblk, m, axis=2, keepdims=False)
        rm = lax.dynamic_index_in_dim(row_p, m, axis=1, keepdims=False)
        cand = _sat_minplus(cm[:, :, None, None], rm[:, None, :, :])
        return jnp.minimum(r, jnp.where(ov_m, _INFU, cand))

    def ncol_body(m, c_acc):
        ov_m = lax.dynamic_index_in_dim(ov, m, axis=0, keepdims=False)
        cm = lax.dynamic_index_in_dim(col_p, m, axis=3, keepdims=False)
        rm = lax.dynamic_index_in_dim(rowblk, m, axis=1, keepdims=False)
        cand = _sat_minplus(cm[:, :, :, None], rm[:, None, None, :])
        return jnp.minimum(c_acc, jnp.where(ov_m, _INFU, cand))

    nrow = lax.fori_loop(0, b, nrow_body, nrow)
    ncol = lax.fori_loop(0, b, ncol_body, ncol)

    # phase 1 of round k+1 on the post-outer diagonal tile
    ov_n = _ov_lanes(node_overloaded, k_next, b)
    tile = lax.dynamic_index_in_dim(nrow, k_next, axis=2, keepdims=False)
    tile = lax.with_sharding_constraint(tile, s_repl)

    def diag_body(m, d):
        ov_m = lax.dynamic_index_in_dim(ov_n, m, axis=0, keepdims=False)
        col_m = lax.dynamic_index_in_dim(d, m, axis=2, keepdims=False)
        row_m = lax.dynamic_index_in_dim(d, m, axis=1, keepdims=False)
        cand = _sat_minplus(col_m[:, :, None], row_m[:, None, :])
        return jnp.minimum(d, jnp.where(ov_m, _INFU, cand))

    closed = lax.fori_loop(0, b, diag_body, tile)
    closed = lax.with_sharding_constraint(closed, s_repl)

    # phase 2 of round k+1 — the constraints below are the panel
    # broadcasts being prefetched
    nrow = lax.with_sharding_constraint(nrow, s_row_p)
    ncol = lax.with_sharding_constraint(ncol, s_col_p)

    def row_body(m, r):
        ov_m = lax.dynamic_index_in_dim(ov_n, m, axis=0, keepdims=False)
        c = lax.dynamic_index_in_dim(closed, m, axis=2, keepdims=False)
        rm = lax.dynamic_index_in_dim(nrow, m, axis=1, keepdims=False)
        cand = _sat_minplus(c[:, :, None, None], rm[:, None, :, :])
        return jnp.minimum(r, jnp.where(ov_m, _INFU, cand))

    def col_body(m, c_acc):
        ov_m = lax.dynamic_index_in_dim(ov_n, m, axis=0, keepdims=False)
        cm = lax.dynamic_index_in_dim(ncol, m, axis=3, keepdims=False)
        r = lax.dynamic_index_in_dim(closed, m, axis=1, keepdims=False)
        cand = _sat_minplus(cm[:, :, :, None], r[:, None, None, :])
        return jnp.minimum(c_acc, jnp.where(ov_m, _INFU, cand))

    nrow_p = lax.fori_loop(0, b, row_body, nrow)
    ncol_p = lax.fori_loop(0, b, col_body, ncol)
    return (
        lax.with_sharding_constraint(nrow_p, s_row_p),
        lax.with_sharding_constraint(ncol_p, s_col_p),
    )


@functools.partial(
    jax.jit, static_argnames=("mesh",), donate_argnums=(0,)
)
def blocked_round_pipelined(dist, row_p, col_p, node_overloaded, k, *, mesh: Mesh):
    """One software-pipelined round: round k's write-back + full rank-B
    outer update, fused with the round-(k+1) panel prefetch.

    The k+1 chain (`_lookahead`) is sliced from the written-back matrix
    BEFORE the outer fori_loop consumes it, so its diagonal replication
    and panel all-gathers have no data dependence on the outer update —
    the scheduler overlaps them (thunk-runtime dataflow on CPU, async
    start/done pairs on TPU; `parallel.hlo_async` materializes the
    spans from the lowered module as evidence).  dist is donated and
    aliases output 0, exactly like `blocked_outer`.  Returns
    (dist', row_p', col_p') — the double-buffered panel carry for the
    next round."""
    s_dist = NamedSharding(mesh, P("batch", None, "row", None, "col"))
    b = dist.shape[2]
    k_next = k + 1
    dist = lax.dynamic_update_index_in_dim(
        dist, lax.with_sharding_constraint(row_p, NamedSharding(
            mesh, P("batch", "row", None, "col"))), k, axis=1
    )
    dist = lax.dynamic_update_index_in_dim(
        dist, lax.with_sharding_constraint(col_p, NamedSharding(
            mesh, P("batch", None, "row", "col"))), k, axis=3
    )
    # k+1 panel slices of the written-back matrix (write-back already
    # covers the round-k corrections the lookahead needs)
    nrow = lax.dynamic_index_in_dim(dist, k_next, axis=1, keepdims=False)
    ncol = lax.dynamic_index_in_dim(dist, k_next, axis=3, keepdims=False)
    nrow_p, ncol_p = _lookahead(
        nrow, ncol, row_p, col_p, node_overloaded, k, k_next, mesh=mesh
    )
    ov = _ov_lanes(node_overloaded, k, b)

    def body(m, d):
        ov_m = lax.dynamic_index_in_dim(ov, m, axis=0, keepdims=False)
        cm = lax.dynamic_index_in_dim(col_p, m, axis=3, keepdims=False)
        rm = lax.dynamic_index_in_dim(row_p, m, axis=1, keepdims=False)
        cand = _sat_minplus(
            cm[:, :, :, None, None], rm[:, None, None, :, :]
        )
        return jnp.minimum(d, jnp.where(ov_m, _INFU, cand))

    dist = lax.fori_loop(0, b, body, dist)
    return lax.with_sharding_constraint(dist, s_dist), nrow_p, ncol_p


@functools.partial(jax.jit, static_argnames=("n", "mesh"))
def blocked_extract(dist, tile_id, lane_id, *, n: int, mesh: Mesh):
    """[N, P] int32 destination columns of the S=0 slice: drev[v, p] =
    dist(v -> dest_p), replicated for the host/bitmap consumers.  The
    saturating domain guarantees unreachable == exactly INF32, so the
    cast is bit-exact against the fused product's normalization."""
    sub = dist[0][:, :, tile_id, lane_id]  # [T, B, P]
    t, b, p_dim = sub.shape
    flat = sub.reshape(t * b, p_dim)[:n]
    return lax.with_sharding_constraint(
        flat.astype(jnp.int32), NamedSharding(mesh, P())
    )


@functools.partial(jax.jit, static_argnames=("n_words",))
def _blocked_bitmap(
    drev, out, edge_metric, edge_up, node_overloaded, *, n_words: int
):
    """ECMP bitmap over the blocked product's int32 [N, P] columns —
    the SAME gather-only condition as the fused path
    (ops.allsources.ecmp_bitmap_from_reverse_dist keys on dtype)."""
    return asrc.ecmp_bitmap_from_reverse_dist(
        drev, out, edge_metric, edge_up, node_overloaded, n_words
    )


class BlockedApspEngine:
    """Owns the blocked-APSP mesh, tiling policy and the
    `mesh.blocked.*` accounting — the third dispatch rung behind
    `DeviceResidencyEngine` (delta < fused full < blocked).

    Engagement: `should_engage(n)` — `OPENR_NODE_SHARD=1` forces the
    rung on, `=0` forces it off, otherwise it engages above
    `node_shard_threshold` (the single-chip [N, P]+graph HBM ceiling).
    Mesh shape comes from `OPENR_BLOCKED_MESH` ("RxC" or "BxRxC") or is
    factored from the device count; an indivisible request raises
    ValueError, which the fleet rung converts into a graceful fallback
    to the dest-sharded product (`mesh.blocked.fallbacks`).

    Phase timing counters are dispatch-enqueue attributed (no per-phase
    device sync — a sync per phase would serialize the very pipeline
    being measured); the final extract blocks, so `extract_us` absorbs
    the tail of the device queue."""

    def __init__(
        self,
        parent=None,
        tile: int | None = None,
        node_shard_threshold: int = 1 << 15,
        mesh: Mesh | None = None,
    ) -> None:
        self.counters: dict[str, int] = {k: 0 for k in BLOCKED_COUNTER_KEYS}
        self._parent = parent  # DeviceResidencyEngine (fault_hook owner)
        self.tile = tile
        self.node_shard_threshold = node_shard_threshold
        self._mesh = mesh
        # chaos seam for engine-less use; with a parent, the parent's
        # hook (armed by ChaosSpfBackend) takes precedence so injected
        # faults land mid-run through the same gate as every dispatch
        self.fault_hook = None
        # pinned pipeline override ("0" off / "1" on); None consults
        # OPENR_BLOCKED_PIPELINE — the program auditor pins this
        # attribute instead of env-forcing
        self.pipeline_mode: str | None = None

    # -- counters -----------------------------------------------------------

    def get_counters(self) -> dict[str, int]:
        return dict(self.counters)

    def _bump(self, name: str, delta: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + delta

    def _hook(self, op: str) -> None:
        hook = self._parent.fault_hook if self._parent is not None else None
        if hook is None:
            hook = self.fault_hook
        if hook is not None:
            hook(op)

    # -- policy -------------------------------------------------------------

    def should_engage(self, n_nodes: int) -> bool:
        force = os.environ.get("OPENR_NODE_SHARD")
        if force == "1":
            return True
        if force == "0":
            return False
        return n_nodes > self.node_shard_threshold

    def mesh(self) -> Mesh:
        if self._mesh is None:
            spec = os.environ.get("OPENR_BLOCKED_MESH", "")
            if spec:
                try:
                    dims = [int(x) for x in spec.lower().split("x")]
                except ValueError:
                    raise ValueError(
                        f"OPENR_BLOCKED_MESH={spec!r}: expected 'RxC' or "
                        f"'BxRxC' integers"
                    ) from None
                if len(dims) == 2:
                    self._mesh = make_blocked_mesh(
                        rows=dims[0], cols=dims[1]
                    )
                elif len(dims) == 3:
                    self._mesh = make_blocked_mesh(
                        batch=dims[0], rows=dims[1], cols=dims[2]
                    )
                else:
                    raise ValueError(
                        f"OPENR_BLOCKED_MESH={spec!r}: expected 2 or 3 "
                        f"'x'-separated sizes, got {len(dims)}"
                    )
            else:
                self._mesh = make_blocked_mesh()
        return self._mesh

    def pipeline_enabled(self, t: int) -> bool:
        """Lookahead pipelining is the default for multi-round
        closures; `OPENR_BLOCKED_PIPELINE=0` (or a pinned
        `pipeline_mode="0"`) forces the bulk-synchronous loop.  A
        single-round closure has nothing to prefetch."""
        if t < 2:
            return False
        mode = self.pipeline_mode
        if mode is None:
            mode = os.environ.get("OPENR_BLOCKED_PIPELINE", "")
        return str(mode) != "0"

    def tile_for(self, n_nodes: int, rows: int, cols: int) -> int:
        """Tile size B: lane dims shard over the mesh, so B must be a
        multiple of lcm(rows, cols); env/ctor overrides are validated
        against that (another graceful-fallback trigger)."""
        base = math.lcm(rows, cols)
        b = self.tile
        if b is None:
            b = int(os.environ.get("OPENR_BLOCKED_TILE", "0")) or None
        if b is None:
            b = base
            while b < 16 and b < max(n_nodes, 1):
                b *= 2
        if b <= 0 or b % base:
            raise ValueError(
                f"blocked tile {b} is not a positive multiple of "
                f"lcm(rows={rows}, cols={cols}) = {base}"
            )
        return b

    # -- staging ------------------------------------------------------------

    @staticmethod
    def dense_dist0(
        n_nodes: int,
        n_pad: int,
        edge_src,
        edge_dst,
        edge_metric,
        edge_up,
        n_edges: int,
    ) -> np.ndarray:
        """[Np, Np] uint32 adjacency in the saturating min-plus domain:
        0 diagonal, min metric over parallel usable edges, INF
        elsewhere.  Padding nodes are isolated (0 self, INF off-diag)
        and never perturb real entries."""
        d0 = np.full((n_pad, n_pad), _INFU, dtype=np.uint32)
        np.fill_diagonal(d0, 0)
        src = np.asarray(edge_src[:n_edges], dtype=np.int64)
        dst = np.asarray(edge_dst[:n_edges], dtype=np.int64)
        met = np.asarray(edge_metric[:n_edges], dtype=np.int64)
        up = np.asarray(edge_up[:n_edges], dtype=bool)
        use = (
            up
            & (src >= 0)
            & (dst >= 0)
            & (src < n_nodes)
            & (dst < n_nodes)
            & (src != dst)
        )
        np.minimum.at(
            d0,
            (src[use], dst[use]),
            np.minimum(met[use], int(_INFU)).astype(np.uint32),
        )
        return d0

    # -- execution ----------------------------------------------------------

    def run_apsp(self, dist0: np.ndarray, node_overloaded: np.ndarray):
        """Run the full blocked closure of dist0 [S, Np, Np] uint32 with
        the [Np] drain mask; returns the device-resident tile tensor
        [S, T, B, T, B] and the (mesh, B) actually used.

        Multi-round closures take the software-pipelined loop by
        default; ANY failure there (chaos fault mid-pipeline, OOM,
        lowering error) bumps `mesh.blocked.pipeline_fallbacks` and
        re-runs the bulk-synchronous loop from the host staging copy —
        safe even though the pipelined rounds donate dist."""
        tr = _trace.TRACE
        if tr is not None:
            tr.annotate("engine.rung", "blocked")
        mesh = self.mesh()
        rows = mesh.shape["row"]
        cols = mesh.shape["col"]
        s, n_pad, _ = dist0.shape
        b = self.tile_for(n_pad, rows, cols)
        if n_pad % b:
            raise ValueError(
                f"blocked APSP: padded node count {n_pad} is not a "
                f"multiple of tile {b}"
            )
        t = n_pad // b
        s_dist = NamedSharding(mesh, P("batch", None, "row", None, "col"))
        ov = jax.device_put(
            np.asarray(node_overloaded, dtype=bool),
            NamedSharding(mesh, P()),
        )
        # modeled exchange per round: each panel's [S, B, Np] lane dim
        # replicates to the (R-1)/(C-1) non-owner rows/cols, the diag
        # tile to everyone
        round_bytes = 4 * s * (
            b * n_pad * (rows - 1) // max(rows, 1)
            + b * n_pad * (cols - 1) // max(cols, 1)
            + b * b
        )
        if self.pipeline_enabled(t):
            dist = jax.device_put(dist0.reshape(s, t, b, t, b), s_dist)
            try:
                return (
                    self._rounds_pipelined(dist, ov, t, mesh, round_bytes),
                    b,
                )
            except Exception:
                # the pipelined rounds donate dist, so the device copy
                # may be gone — demote to bulk from the host staging
                self._bump("mesh.blocked.pipeline_fallbacks")
        dist = jax.device_put(dist0.reshape(s, t, b, t, b), s_dist)
        return (
            self._rounds_bulk(dist, ov, t, mesh, round_bytes),
            b,
        )

    def _rounds_bulk(self, dist, ov, t, mesh, round_bytes):
        """The bulk-synchronous round loop: every round serializes
        diag closure -> panel broadcasts -> outer update."""
        for k in range(t):
            self._hook("blocked_round")
            kk = jnp.int32(k)
            t0 = time.monotonic_ns()
            closed = blocked_diag(dist, ov, kk, mesh=mesh)
            t1 = time.monotonic_ns()
            row_p, col_p = blocked_panels(dist, closed, ov, kk, mesh=mesh)
            t2 = time.monotonic_ns()
            dist = blocked_outer(dist, row_p, col_p, ov, kk, mesh=mesh)
            t3 = time.monotonic_ns()
            self._bump("mesh.blocked.tile_updates")
            self._bump("mesh.blocked.panel_broadcasts", 2)
            self._bump("mesh.blocked.bytes_exchanged", round_bytes)
            self._bump("mesh.blocked.diag_us", (t1 - t0) // 1000)
            self._bump("mesh.blocked.panel_us", (t2 - t1) // 1000)
            self._bump("mesh.blocked.outer_us", (t3 - t2) // 1000)
        self._bump("mesh.blocked.rounds", t)
        return dist

    def _rounds_pipelined(self, dist, ov, t, mesh, round_bytes):
        """The software-pipelined round loop (t >= 2): the panels are
        double-buffered — each round consumes panels[k] and produces
        panels[k+1] while the round-k outer update runs, so the panel
        all-gathers hide under compute.  The prologue computes
        panels[0] the bulk way (nothing to overlap them with yet); the
        epilogue round has no next panel to prefetch and runs the plain
        outer step."""
        multi = mesh.devices.size > 1
        k0 = jnp.int32(0)
        t0 = time.monotonic_ns()
        closed = blocked_diag(dist, ov, k0, mesh=mesh)
        t1 = time.monotonic_ns()
        row_p, col_p = blocked_panels(dist, closed, ov, k0, mesh=mesh)
        t2 = time.monotonic_ns()
        self._bump("mesh.blocked.diag_us", (t1 - t0) // 1000)
        self._bump("mesh.blocked.panel_us", (t2 - t1) // 1000)
        for k in range(t - 1):
            self._hook("blocked_round")
            kk = jnp.int32(k)
            t2 = time.monotonic_ns()
            dist, nrow_p, ncol_p = blocked_round_pipelined(
                dist, row_p, col_p, ov, kk, mesh=mesh
            )
            t3 = time.monotonic_ns()
            row_p, col_p = nrow_p, ncol_p
            self._bump("mesh.blocked.tile_updates")
            self._bump("mesh.blocked.panel_broadcasts", 2)
            self._bump("mesh.blocked.bytes_exchanged", round_bytes)
            self._bump("mesh.blocked.outer_us", (t3 - t2) // 1000)
            self._bump("mesh.blocked.pipeline_prefetch_issues")
            if multi:
                # only a multi-device mesh has collectives to hide; on
                # the degenerate 1-device mesh the prefetch is pure
                # compute reordering
                self._bump("mesh.blocked.pipeline_rounds_overlapped")
        # epilogue: the final round's panels were prefetched by the
        # previous round — only the outer update remains
        self._hook("blocked_round")
        kk = jnp.int32(t - 1)
        t2 = time.monotonic_ns()
        dist = blocked_outer(dist, row_p, col_p, ov, kk, mesh=mesh)
        t3 = time.monotonic_ns()
        self._bump("mesh.blocked.tile_updates")
        self._bump("mesh.blocked.panel_broadcasts", 2)
        self._bump("mesh.blocked.bytes_exchanged", round_bytes)
        self._bump("mesh.blocked.outer_us", (t3 - t2) // 1000)
        self._bump("mesh.blocked.rounds", t)
        # gauge: modeled fraction of rounds whose collectives overlap
        # compute (prologue gathers and the 1-device mesh overlap none)
        self.counters["mesh.blocked.pipeline_overlap_frac_est"] = (
            100 * (t - 1) // t if multi else 0
        )
        return dist

    def fleet_product(self, csr, dest_ids: np.ndarray, out):
        """The fleet-product face of the rung: forward-graph blocked
        APSP, destination-column extract, ECMP bitmap.  Returns
        (dist [N, P] int32, bitmap [N, P, W] uint32, True) matching the
        `reduced_all_sources` contract shape the fleet view stores."""
        self._hook("blocked_product")
        n = int(csr.n_nodes)
        mesh = self.mesh()
        b = self.tile_for(n, mesh.shape["row"], mesh.shape["col"])
        n_pad = -(-n // b) * b
        d0 = self.dense_dist0(
            n,
            n_pad,
            csr.edge_src,
            csr.edge_dst,
            csr.edge_metric,
            csr.edge_up,
            int(csr.n_edges),
        )
        ov_pad = np.zeros(n_pad, dtype=bool)
        ov_pad[:n] = np.asarray(csr.node_overloaded[:n], dtype=bool)
        dist, b = self.run_apsp(d0[None], ov_pad)
        t0 = time.monotonic_ns()
        dest = np.asarray(dest_ids, dtype=np.int32)
        drev = blocked_extract(
            dist,
            jnp.asarray(dest // b, dtype=jnp.int32),
            jnp.asarray(dest % b, dtype=jnp.int32),
            n=n,
            mesh=mesh,
        )
        bitmap = _blocked_bitmap(
            drev,
            out,
            jnp.asarray(csr.edge_metric),
            jnp.asarray(csr.edge_up),
            jnp.asarray(csr.node_overloaded),
            n_words=out.n_words,
        )
        # one deliberate sync: the product is complete here and the
        # enqueue-attributed phase timers need a closing edge
        jax.block_until_ready(bitmap)
        self._bump(
            "mesh.blocked.extract_us",
            (time.monotonic_ns() - t0) // 1000,
        )
        self._bump("mesh.blocked.products")
        return drev, bitmap, True
