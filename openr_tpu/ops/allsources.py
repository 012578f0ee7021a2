"""Reduced-output all-sources SPF: the product route building consumes.

The literal all-sources [N, N] distance matrix at 100k nodes is 40 GB —
un-materializable on one chip, and nobody reads it: the reference's
buildRouteDb consumes, per router, only the distances/next-hops toward
the P prefix-originating nodes (openr/decision/Decision.cpp:615-793
createRouteForPrefix reads best-entry node distances; getNextHopsThrift's
LFA-free ECMP keeps neighbor u for destination t iff
metric(v,u) + dist(u,t) == dist(v,t), Decision.cpp:1296-1300).

So the whole-fleet product is all-sources-to-P-destinations, and on the
reversed graph that is ONE P-source SSSP:

    dist(v -> p)  ==  reverse-SSSP from p over reversed edges, read at v.

Drain semantics survive reversal exactly: the kernel blocks relaxation
through an overloaded predecessor unless its distance is 0 (ops.sssp /
ops.banded).  On the reversed graph the d==0 exception lands on the
original DESTINATION p (whose original in-edges are always usable), and
an overloaded original source v is reached by a final reverse hop whose
predecessor is v's neighbor — never blocked — while overloaded
intermediates still block as reverse-edge tails.  A case-by-case check
of (source, intermediate, destination) overload shows equality with the
forward rule; tests/test_banded.py (TestReducedAllSources) asserts it against the oracle.

The fused consumer pass then emits, per (router v, destination p), the
bit-packed ECMP next-hop set straight from the reverse distances —
gathers over a per-node out-neighbor table, no scatters — so the entire
fleet-wide route-building input is ONE device call returning
[N, P] int32 distances + [N, P, W] uint32 next-hop bitmaps.

The fast path goes further: the reverse in-edges of router v are
exactly v's forward out-edges, so the ECMP condition
``metric(v,u) + dist(u,p) == dist(v,p)`` is precisely "this reverse
relax candidate is tight".  The fused program
(_fused_progressive_banded) therefore computes the bitmap INSIDE the
final verification pass of the banded kernel — each [N, P] gather is
read once and feeds both the convergence verdict (min) and the bitmap
(compare + OR into precomputed slot bits), replacing the round-5
standalone bitmap pass that re-gathered the whole product.  The relax
itself runs the progressive while-loop (ops.banded), so one dispatch
covers relax + verify + bitmap and stops at the actual fixed point.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

from .sssp import INF16, INF32, clamp_metric_u16, u16_saturation_verdict


class OutEll(NamedTuple):
    """Per-node out-edge table in original node order (host-built)."""

    nbr: jax.Array  # [N, K] int32 — out-neighbor node id (pad 0)
    eid: jax.Array  # [N, K] int32 — directed edge id; -1 pad
    slot: jax.Array  # [N, K] int32 — rank among the node's sorted unique
    #   out-neighbors (parallel links share a slot); -1 pad
    n_words: int  # ceil(max_slots / 32) — static


def build_out_ell(
    edge_src: np.ndarray,
    edge_dst: np.ndarray,
    n_edges: int,
    n_nodes: int,
    out_slot: Optional[np.ndarray] = None,
) -> OutEll:
    """Vectorized out-edge table build.  `out_slot` (per-edge slot ids,
    csr._build_out_slots layout) is recomputed here when not supplied.

    Retired freelist slots (csr rewires) sit inside [:n_edges] styled as
    padding — endpoints at the pad node >= n_nodes — and are dropped
    here so they never index the [N]-sized tables."""
    src = np.asarray(edge_src[:n_edges], dtype=np.int64)
    dst = np.asarray(edge_dst[:n_edges], dtype=np.int64)
    ids = np.flatnonzero((src < n_nodes) & (dst < n_nodes))
    src, dst = src[ids], dst[ids]
    if out_slot is None:
        from ..decision.csr import _build_out_slots

        live = np.zeros(n_edges, dtype=bool)
        live[ids] = True
        out_slot, _ = _build_out_slots(
            np.asarray(edge_src), np.asarray(edge_dst), n_edges, live=live
        )
    e_slot = np.asarray(out_slot[:n_edges])[ids]
    deg = np.bincount(src, minlength=n_nodes)
    k = int(deg.max()) if ids.size else 1
    k_pad = 1
    while k_pad < max(k, 1):
        k_pad *= 2
    order = np.argsort(src, kind="stable")
    e_sorted = order
    s_sorted = src[order]
    starts = np.searchsorted(s_sorted, np.arange(n_nodes))
    pos = np.arange(len(order)) - starts[s_sorted]
    nbr = np.zeros((n_nodes, k_pad), dtype=np.int32)
    eid = np.full((n_nodes, k_pad), -1, dtype=np.int32)
    slot = np.full((n_nodes, k_pad), -1, dtype=np.int32)
    nbr[s_sorted, pos] = dst[e_sorted].astype(np.int32)
    eid[s_sorted, pos] = ids[e_sorted].astype(np.int32)
    slot[s_sorted, pos] = e_slot[e_sorted]
    max_slots = int(e_slot.max()) + 1 if ids.size else 1
    return OutEll(
        nbr=jnp.asarray(nbr),
        eid=jnp.asarray(eid),
        slot=jnp.asarray(slot),
        n_words=max(1, -(-max_slots // 32)),
    )


class EpilogueMaps(NamedTuple):
    """Reverse-slot -> forward-out-slot tables for the fused
    verify+bitmap epilogue.  Reverse in-edges of v are exactly v's
    forward out-edges: the reverse residual slot (v, k) with neighbor u
    and the reverse band edge (v-c)%N -> v each correspond to one
    forward out-edge of v, whose ECMP bit position is the rank of that
    neighbor among v's sorted unique out-neighbors (OutEll.slot).
    Host-built once per topology snapshot."""

    resid_slot: jax.Array  # [N, K] int32 — forward out-slot; -1 pad
    band_slot: jax.Array  # [B, N] int32 — forward out-slot; -1 no edge


def build_epilogue_maps(bg, out: OutEll) -> EpilogueMaps:
    """Map every reverse-graph relax slot (ops.banded.BandedGraph over
    the REVERSED edges) to the forward out-slot bit it certifies.
    Parallel forward links share a slot, and their reverse counterparts
    occupy distinct residual slots (build_banded demotes band
    duplicates), so every candidate lands on the right bit and the
    min-metric parallel link is the one whose equality fires."""
    nbr = np.asarray(out.nbr)
    eid = np.asarray(out.eid)
    slot = np.asarray(out.slot)
    n = bg.n_nodes

    def rank(u_row: np.ndarray, valid: np.ndarray) -> np.ndarray:
        """Forward out-slot of edge v -> u_row[v]; -1 where invalid."""
        m = (nbr[:n] == u_row[:, None]) & (eid[:n] >= 0)
        s = np.where(m, slot[:n], -1).max(axis=1)
        return np.where(valid, s, -1).astype(np.int32)

    rn = np.asarray(bg.resid_nbr)
    re_ = np.asarray(bg.resid_eid)
    resid_slot = np.stack(
        [rank(rn[:, k], re_[:, k] >= 0) for k in range(rn.shape[1])],
        axis=1,
    )
    ids = np.arange(n, dtype=np.int64)
    be = np.asarray(bg.band_eid)
    band_slot = np.stack(
        [
            rank(((ids - c) % n).astype(np.int32), be[b] >= 0)
            for b, c in enumerate(bg.offsets)
        ]
    )
    return EpilogueMaps(
        resid_slot=jnp.asarray(resid_slot), band_slot=jnp.asarray(band_slot)
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "check_every",
        "max_blocks",
        "depth",
        "resid_rounds",
        "small_dist",
        "chord_mode",
        "n_words",
    ),
)
def _fused_progressive_banded(
    dest_ids,
    bg,
    r_edge_up,  # REVERSED-graph runtime arrays (the runner's)
    r_edge_metric,
    node_overloaded,
    resid_slot,  # EpilogueMaps
    band_slot,
    init_dist,  # [N*, P] warm-start upper bound or None
    check_every: int,
    max_blocks: int,
    depth: int,
    resid_rounds: int,
    small_dist: bool,
    chord_mode: bool,
    n_words: int,
):
    """Relax + verify + ECMP bitmap as ONE compiled program, with the
    bitmap folded into the verification pass: the progressive while-loop
    (ops.banded) runs supersweep blocks to the fixed point, then a
    single Jacobi epilogue re-evaluates every exact relax candidate ONCE
    and uses it for BOTH the convergence verdict (min, v == d) and the
    ECMP bit (cand == d, finite) — the [N, P] product is read once, not
    re-gathered by a standalone bitmap pass.

    Correctness of the bit rule: for the forward out-edge v->u the
    reference condition metric(v,u) + dist(u,p) == dist(v,p)
    (Decision.cpp:1296-1300) is exactly "the reverse candidate through u
    is tight".  The candidate already encodes link-up and the drain
    exception (overloaded u allowed only at d(u,p) == 0), and the
    d < inf guard keeps unreachable rows bitless — a saturated cand can
    alias the INF sentinel, so equality alone is not enough.  Bits are
    meaningful only when ``converged`` is True (callers re-run
    otherwise, exactly like the distances)."""
    from .banded import _RelaxOps, make_dist0_orig

    n = bg.n_nodes
    d0 = make_dist0_orig(dest_ids, n, small_dist=small_dist)
    if init_dist is not None:
        init = init_dist[:n]
        if small_dist and init.dtype != jnp.uint16:
            init = jnp.minimum(init, INF16).astype(jnp.uint16)
        elif not small_dist and init.dtype != jnp.int32:
            init = jnp.where(
                init >= INF16, jnp.int32(INF32), init.astype(jnp.int32)
            )
        # re-pin sources to 0; elsewhere keep the caller's bound
        d0 = jnp.minimum(d0, init)
    ops = _RelaxOps(
        bg,
        r_edge_up,
        r_edge_metric,
        node_overloaded[:n],
        0 if chord_mode else depth,
        resid_rounds,
        None,
        small_dist,
        chord_mode,
        d0.dtype,
    )

    def body(state):
        d, _, i = state
        for _ in range(check_every - 1):
            d = ops.supersweep(d)
        v = ops.supersweep(d)
        return v, jnp.all(v == d), i + jnp.int32(1)

    def cond(state):
        _, conv, i = state
        return jnp.logical_and(~conv, i < max_blocks)

    d, _, blocks = jax.lax.while_loop(
        cond, body, (d0, jnp.bool_(False), jnp.int32(0))
    )

    # fused verify+bitmap epilogue (authoritative exact check: the
    # while-loop's own certificate is implied by v == d below)
    p_dim = d.shape[1]
    fin = d < ops.inf
    v = d

    def bit_of(slot_row):
        return jnp.where(
            slot_row >= 0,
            jnp.uint32(1)
            << (jnp.maximum(slot_row, 0) % 32).astype(jnp.uint32),
            jnp.uint32(0),
        )

    # one (candidate, forward-slot-row) pair per reverse edge group;
    # thunked so only one [N, P] candidate is live at a time
    groups = [
        (functools.partial(ops.resid_cand, d, k), resid_slot[:, k])
        for k in range(ops.n_resid)
    ] + [
        (functools.partial(ops.band0_cand, d, b), band_slot[b])
        for b in range(ops.n_bands)
    ]
    if n_words == 1:
        bitmap2d = jnp.zeros((n, p_dim), dtype=jnp.uint32)
        for mk_cand, srow in groups:
            cand = mk_cand()
            on = fin & (cand == d)
            bitmap2d = bitmap2d | jnp.where(
                on, bit_of(srow)[:, None], jnp.uint32(0)
            )
            v = jnp.minimum(v, cand)
        bitmap = bitmap2d[:, :, None]
    else:
        bitmap = jnp.zeros((n, p_dim, n_words), dtype=jnp.uint32)
        for mk_cand, srow in groups:
            cand = mk_cand()
            on = fin & (cand == d)
            word_sel = (jnp.maximum(srow, 0) // 32)[:, None] == jnp.arange(
                n_words
            )[None, :]  # [N, W]
            bitmap = bitmap | jnp.where(
                on[:, :, None] & word_sel[:, None, :],
                bit_of(srow)[:, None, None],
                jnp.uint32(0),
            )
            v = jnp.minimum(v, cand)
    converged = jnp.all(v == d)
    if small_dist:
        converged = u16_saturation_verdict(d, converged)
    # blocks: executed while-loop blocks — blocks*check_every supersweeps
    # ran, so that count is a PROVEN-sufficient fixed-sweep budget for
    # this (topology, dest-set) shape; callers teach the runner's hint
    # from it so fixed-sweep consumers (sharded product, masked variants)
    # inherit the progressive run's auto-tuning
    return d, bitmap, converged, blocks


@functools.partial(jax.jit, static_argnames=("n_words",))
def ecmp_bitmap_from_reverse_dist(
    drev: jax.Array,  # [N*, P] — reverse-SSSP distances (drev[v, p] =
    #   dist(v->p)); N* is n_nodes (banded kernel) or node_capacity (ELL
    #   fallback).  Native kernel layout — no transpose on either side
    #   (round-5: the [P, N] orientation cost two 200MB-scale transposes
    #   per product round)
    out: OutEll,
    edge_metric: jax.Array,  # [E_cap] int32
    edge_up: jax.Array,  # [E_cap] bool
    node_overloaded: jax.Array,  # [N_cap] bool
    n_words: int,
) -> jax.Array:
    """[N, P, W] uint32: bit s of (v, p) set iff out-slot s of router v
    is an ECMP next-hop toward destination p — the reference's LFA-free
    condition metric(v,u) + dist(u,p) == dist(v,p)
    (openr/decision/Decision.cpp:1296-1300), evaluated fleet-wide from
    reverse distances.  Gather-only.

    Drain: the reference draws ECMP neighbors from the source's
    drain-respecting SPF tree (nextHopNodes is keyed by
    shortestPathsFromHere nextHops, Decision.cpp:1182-1260), so an
    overloaded neighbor u is a valid next-hop ONLY as the destination
    itself — the same own-source/destination exception the relax kernels
    encode, here as d(u,p) == 0."""
    n, k_pad = out.nbr.shape
    p_dim = drev.shape[1]
    d_self = drev[:n]  # [N, P]
    # uint16 domain (raw banded distances, INF16 sentinel): the gathers
    # move half the bytes.  Safe because finite d < INF16=40000 and
    # clamped metric <= WBIG16=20000 never wrap in uint16, and a finite
    # d_nbr with a usable edge implies a finite d_self (so the
    # d_nbr + w == d_self compare never matches a saturated self).
    u16 = drev.dtype == jnp.uint16
    inf = INF16 if u16 else INF32

    def slot_bits(k):
        """(on [N, P] bool, slot [N]): out-slot k of every router is an
        ECMP hop toward each destination."""
        eidk = lax.dynamic_index_in_dim(out.eid, k, axis=1, keepdims=False)
        ok = (eidk >= 0) & jnp.take(edge_up, jnp.maximum(eidk, 0))
        w = jnp.take(edge_metric, jnp.maximum(eidk, 0))  # [N]
        if u16:
            w = clamp_metric_u16(w)
        nbr = lax.dynamic_index_in_dim(out.nbr, k, axis=1, keepdims=False)
        d_nbr = jnp.take(drev, nbr, axis=0)  # [N, P]
        nbr_ov = jnp.take(node_overloaded, nbr)  # [N]
        on = (
            ok[:, None]
            & (d_nbr < inf)
            & (d_nbr + w[:, None] == d_self)
            & (~nbr_ov[:, None] | (d_nbr == 0))
        )
        slot = lax.dynamic_index_in_dim(out.slot, k, axis=1, keepdims=False)
        bit = jnp.where(
            slot >= 0,
            jnp.uint32(1) << (jnp.maximum(slot, 0) % 32).astype(jnp.uint32),
            jnp.uint32(0),
        )  # [N]
        return on, slot, bit

    # a loop over the out-slots, not a static unroll: unrolled, XLA keeps
    # every slot's [N, P] gather live at once (12.9 GB of temporaries and
    # a two-minute v5e compile at fat-tree 10k, 124 slots x P = 10,080)
    if n_words == 1:
        # single-word fast path (any topology with <=32 unique
        # out-neighbors per node): a flat uint32 OR chain, no [N, P, W]
        # broadcast scaffolding per slot
        def body1(k, bitmap2d):
            on, _, bit = slot_bits(k)
            return bitmap2d | jnp.where(on, bit[:, None], jnp.uint32(0))

        bitmap2d = lax.fori_loop(
            0, k_pad, body1, jnp.zeros((n, p_dim), dtype=jnp.uint32)
        )
        return bitmap2d[:, :, None]

    def body(k, bitmap):
        on, slot, bit = slot_bits(k)
        word_sel = (jnp.maximum(slot, 0) // 32)[:, None] == jnp.arange(
            n_words
        )[None, :]  # [N, W]
        return bitmap | jnp.where(
            on[:, :, None] & word_sel[:, None, :],
            bit[:, None, None],
            jnp.uint32(0),
        )

    return lax.fori_loop(
        0, k_pad, body, jnp.zeros((n, p_dim, n_words), dtype=jnp.uint32)
    )


def reduced_all_sources(
    dest_ids,
    reverse_runner,
    out: OutEll,
    edge_metric,
    edge_up,
    node_overloaded,
    n_sweeps: Optional[int] = None,
    fused: Optional[bool] = None,
    init_dist=None,
    maps: Optional[EpilogueMaps] = None,
    check_every: int = 4,
    max_blocks: int = 64,
):
    """Fleet-wide route-building input in one device round:
    (dist [N*, P] jax — dist[v, p] = dist(v -> p), nh_bitmap
    [N, P, W] uint32 jax, converged bool).  dist is raw uint16 with the
    INF16 sentinel when the banded kernel's small-distance mode engages
    (half the bitmap-gather bytes), int32/INF32 otherwise — consumers
    key on dtype (decision.fleet._row_i32).  The [N*, P] orientation is
    the relax kernel's NATIVE layout (round-5: the former [P, N*]
    contract paid two 200MB-scale transposes per product round), and it
    is also what consumers want — a router's row fetch is contiguous.

    `reverse_runner` is an ops.banded.SpfRunner over the REVERSED edge
    arrays (benchmarks.synthetic.reversed_topology / csr mirror).  With
    `n_sweeps` the call is non-adaptive (the caller asserts
    convergence).  Adaptive mode doubles the runner's hint on a False
    verdict — then REFINES the hint back down by bounded binary probes,
    exactly like SpfRunner.forward: a doubling overshoot would otherwise
    tax every later product round with up to 2x surplus supersweeps.

    The DEFAULT path on banded topologies (`fused=None`) is the fused
    PROGRESSIVE program (_fused_progressive_banded): relax, verify and
    bitmap in one dispatch, the relax early-exiting on-device at the
    actual fixed point (lax.while_loop over supersweep blocks of
    `check_every`) and the bitmap folded into the verification pass so
    the [N, P] product is read once.  This reverses the round-5 call:
    that fusion merely concatenated the relax with a SECOND full bitmap
    gather pass, which XLA scheduled worse than two pipelined
    dispatches; with the bitmap riding the verification gathers there
    is no second pass left to schedule, and the fixed-sweep hint (and
    its overshoot) disappears entirely.  `fused=False` forces the
    legacy two-dispatch path; `fused=True` with `n_sweeps` runs the
    legacy fixed-sweep fused program.

    `init_dist` ([N*, P], either distance dtype) warm-starts the relax
    from a caller-PROVEN elementwise upper bound — the previous product
    of the same (node universe, dest set) after gated topology changes
    (see ops.banded.spf_forward_banded for the safety argument and
    decision.fleet for both gate directions).  A converged warm round
    equals the cold one exactly.  Banded path only (the ELL fallback
    cold-starts).

    `maps` (build_epilogue_maps) feeds the fused epilogue; built here
    on first need when not supplied — callers that rebuild repeatedly
    should build it once per topology snapshot."""
    import numpy as _np

    if fused and n_sweeps is not None and init_dist is not None:
        # the legacy fixed-sweep fused program has no dist0 input
        raise ValueError("fused=True with n_sweeps does not support init_dist")

    dest_ids = jnp.asarray(_np.asarray(dest_ids, dtype=_np.int32))

    if (
        fused is not False
        and n_sweeps is None
        and reverse_runner.bg is not None
    ):
        # fast path: one progressive fused program, no sweep hint
        if maps is None:
            maps = build_epilogue_maps(reverse_runner.bg, out)
        _, _, r_met, r_up, r_ov = reverse_runner.call_arrays()

        def run_prog(small: bool):
            return _fused_progressive_banded(
                dest_ids,
                reverse_runner.bg,
                r_up,
                r_met,
                r_ov,
                maps.resid_slot,
                maps.band_slot,
                init_dist,
                check_every=check_every,
                max_blocks=max_blocks,
                depth=reverse_runner.depth,
                resid_rounds=reverse_runner.resid_rounds,
                small_dist=small,
                chord_mode=reverse_runner.chord_mode,
                n_words=out.n_words,
            )

        small = reverse_runner.small_dist
        dist, bitmap, ok, blocks = run_prog(small)
        # One explicit fetch for the convergence certificate + block count:
        # the retry/hint decisions below are host control flow, and reading
        # the two scalars piecemeal (bool(ok), bool(ok), int(blocks)) would
        # block the dispatch thread up to three times per round.
        ok_h, blocks_h = jax.device_get((ok, blocks))
        if small and not ok_h:
            # saturation presents as non-convergence: latch uint16 off
            # (the SpfRunner.adapt discipline) and retry once in int32
            reverse_runner.small_allowed = False
            dist, bitmap, ok, blocks = run_prog(False)
            ok_h, blocks_h = jax.device_get((ok, blocks))
        if ok_h and init_dist is None:
            # teach the fixed-sweep hint from the cold progressive run
            # (warm runs converge in delta-sized counts — not a valid
            # cold budget, so they never write it)
            reverse_runner.hint = max(1, int(blocks_h) * check_every)
        return dist, bitmap, bool(ok_h)

    def run(sweeps: int, want_bitmap: bool):
        # the one-program fusion exists on the banded path only; the ELL
        # fallback computes the bitmap separately AFTER convergence, so
        # failed adaptive attempts never pay a discarded bitmap pass
        if want_bitmap and fused and reverse_runner.bg is not None:
            return _fused_product(
                dest_ids,
                reverse_runner,
                out,
                edge_metric,
                edge_up,
                node_overloaded,
                sweeps,
            )
        # raw uint16 distances when the banded kernel runs small: the
        # bitmap pass gathers half the bytes (ecmp_bitmap keys on dtype)
        dist, _, ok = reverse_runner.run_once(
            dest_ids,
            sweeps,
            want_dag=False,
            raw_u16=True,
            transpose=False,
            dist0=init_dist,
        )
        return dist, None, ok

    if n_sweeps is not None:
        dist, bitmap, ok = run(n_sweeps, want_bitmap=True)
    else:
        # shared adaptation machinery (double / saturation-fallback /
        # capped refine-down): SpfRunner.adapt
        def attempt(sweeps: int):
            r = run(sweeps, want_bitmap=True)
            # adapt() decides double/refine from the convergence verdict;
            # one scalar sync per attempt is the price of adaptive sweep
            # control  # openr: disable=jit-dispatch-sync
            return r, bool(r[2])

        dist, bitmap, ok = reverse_runner.adapt(
            "hint",
            attempt=attempt,
            # same adaptive-control verdict  # openr: disable=jit-dispatch-sync
            probe=lambda s: bool(run(s, want_bitmap=False)[2]),
            eff_small=lambda: reverse_runner.small_dist,
        )
    if bitmap is None:
        bitmap = ecmp_bitmap_from_reverse_dist(
            dist, out, edge_metric, edge_up, node_overloaded, out.n_words
        )
    # Contract: the certificate is a HOST bool on every return path (the
    # fused path above fetches it with device_get), so callers can branch
    # on it without paying another sync.  On the adaptive path the scalar
    # was already realized by attempt(); this bool() is a cached read.
    return dist, bitmap, bool(ok)


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_supersweeps",
        "depth",
        "resid_rounds",
        "small_dist",
        "n_words",
        "chord_mode",
    ),
)
def _fused_product_banded(
    dest_ids,
    bg,
    r_edge_src,
    r_edge_dst,
    r_edge_metric,
    r_edge_up,
    node_overloaded,
    out: OutEll,
    f_edge_metric,
    f_edge_up,
    n_supersweeps: int,
    depth: int,
    resid_rounds: int,
    small_dist: bool,
    n_words: int,
    chord_mode: bool = False,
):
    """Reverse relax + fleet ECMP bitmaps as ONE compiled program (banded
    path).  Bitmaps are computed unconditionally; on a failed convergence
    verdict the caller re-runs, wasting only the cheap bitmap pass."""
    from .banded import spf_forward_banded

    # native [N, S] == the [N*, P] drev layout, transpose-free on both
    # sides (raw uint16 when small — the bitmap pass gathers half bytes)
    dist, _, ok = spf_forward_banded(
        dest_ids,
        bg,
        r_edge_src,
        r_edge_dst,
        r_edge_metric,
        r_edge_up,
        node_overloaded,
        n_supersweeps=n_supersweeps,
        depth=depth,
        resid_rounds=resid_rounds,
        small_dist=small_dist,
        want_dag=False,
        chord_mode=chord_mode,
        raw_u16=True,
        transpose=False,
    )
    bitmap = ecmp_bitmap_from_reverse_dist(
        dist, out, f_edge_metric, f_edge_up, node_overloaded, n_words
    )
    return dist, bitmap, ok


def _fused_product(
    dest_ids,
    reverse_runner,
    out: OutEll,
    f_edge_metric,
    f_edge_up,
    node_overloaded,
    n_sweeps: int,
):
    """One-dispatch reduced product (banded path only; callers fall back
    to run_once + a post-convergence bitmap pass on ELL topologies)."""
    assert reverse_runner.bg is not None
    r_src, r_dst, r_metric, r_up, r_ov = reverse_runner.call_arrays()
    return _fused_product_banded(
        dest_ids,
        reverse_runner.bg,
        r_src,
        r_dst,
        r_metric,
        r_up,
        r_ov,
        out,
        jnp.asarray(f_edge_metric),
        jnp.asarray(f_edge_up),
        n_supersweeps=n_sweeps,
        depth=reverse_runner.depth,
        resid_rounds=reverse_runner.resid_rounds,
        small_dist=reverse_runner.small_dist,
        n_words=out.n_words,
        chord_mode=reverse_runner.chord_mode,
    )
