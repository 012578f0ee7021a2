"""Hand-tiled Pallas kernels for the saturating min-plus inner loops.

Every dispatch rung — fused full product, delta frontier relax, blocked
outer phase — bottoms out in the same saturating integer min-plus
contraction that XLA compiles generically.  This module hand-tiles the
two hottest bodies (PAPER.md names Pallas as the compute substrate; the
blocked-outer tiling follows the 3-D tensor Floyd-Warshall formulation
of arxiv 2310.03983, PAPERS.md):

1. `fused_epilogue_pallas` — the fused verify+bitmap epilogue of
   `ops.allsources._fused_progressive_banded`.  The lax body walks the
   relax groups (residual gathers + band rolls) re-reading the [N, P]
   product once per group output; the kernel instead holds one
   [N, 128] column tile of the product in VMEM and, per tile, unrolls
   ALL groups — min-plus candidate, ECMP-bitmap hit test, and
   fixed-point min — so the product crosses HBM once per output, not
   once per group.  Every group is normalized to one uniform row
   quadruple (gather index, weight, overloaded-predecessor, forward
   out-slot): a residual slot k contributes `bg.resid_nbr[:, k]`, a
   band of offset c contributes the roll written as the gather
   `(v - c) mod N`, which makes the band and residual relaxes the SAME
   kernel statement.  Interpreter only: the per-group row gather has no
   Mosaic lowering, so the default policy keeps it off on the TPU
   (EPILOGUE_COMPILED_REFUSAL) until it is rewritten or deleted.

2. `blocked_outer_pallas` — phase 3 of the blocked APSP rung
   (`parallel.blocked.blocked_outer`): the rank-B outer update
   `d[i, j] = min(d[i, j], min_m(col[i, m] + row[m, j]))` over
   [128, 128] VMEM blocks with the col/row panels streamed in 128-wide
   chunks of the m axis.  The drain mask is folded into the row panel
   in the kernel (`row[m, :] = INF` where lane m is overloaded) —
   bit-exact because `min(c + INF, INF) == INF` in the saturating
   domain (operands <= 2^30).  Compiles for v5e
   (tests/test_tpu_compile.py) but is off by default
   (OUTER_DEFAULT_REFUSAL): no default tile policy hands it the
   128-multiple tiles Mosaic needs.

Dispatch contract (`run_with_fallback`): a kernel that is off, or
that the compiler would refuse, is a counted skip
(`device.engine.pallas_skips`) decided before dispatch (the blocked
rung decides its tile conformance once per closure, `outer_conformance`).  A compiled
launch never demotes: a failure there raises.  Only interpret mode, the
CPU correctness tool, demotes to the XLA thunk on failure (the chaos
seam's contract, `device.engine.pallas_fallbacks`).  Tier-1 proves
bit-exactness against the lax kernels with `interpret=True` on CPU.

Bit-exactness argument, epilogue: padding rows/columns carry the INF
sentinel and padded group rows carry wbig weights, so padded candidates
are exactly INF — they set no bits (the `d < inf` guard is False) and
leave the fixed-point min at d, hence the verdict reduction over the
padded block equals the reduction over the live region.  The kernel
evaluates the identical where-expression as `_RelaxOps.resid_cand` /
`band0_cand` (weights pass through int32 exactly; wdt -> int32 -> wdt
round-trips are lossless for clamped metrics), and integer min is
exact and order-free, so bitmap and verdict match the lax epilogue
bit for bit.
"""

from __future__ import annotations

import functools
import logging
import os

import jax
import jax.numpy as jnp
from jax import lax

from .sssp import INF16, INF32

from jax.experimental import pallas as pl

log = logging.getLogger(__name__)

# saturation constants as plain ints (kernel closures; values mirror
# ops.sssp INF16/WBIG16 and ops.banded WBIG / parallel.blocked INF32)
_INF16 = int(INF16)  # 40000
_WBIG16 = 20000  # ops.sssp.WBIG16
_INF32 = int(INF32)  # 1 << 30
_WBIG32 = 1 << 28  # ops.banded.WBIG


# -- policy -------------------------------------------------------------------

# Neither kernel is on the TPU default ("auto" resolves to "off" on every
# backend); each is opt-in with OPENR_PALLAS=1/compiled.
#
# The fused epilogue's per-group row gather (`jnp.take(d, idx, axis=0)`)
# has no Mosaic lowering, and at fat-tree-10k widths its [N_pad, 128]
# distance + bitmap tiles alone overrun the VMEM budget, so even an
# explicit compiled request is refused before dispatch as a counted skip.
EPILOGUE_COMPILED_REFUSAL = (
    "fused epilogue: the per-group row gather has no Mosaic lowering"
)
# The blocked outer kernel compiles for v5e and ran bit-exact on the chip
# when forced, but no default path hands it the 128-multiple tiles Mosaic
# needs: the blocked rung picks B=16 on a one-device mesh, and sharded
# meshes keep the collective-aware XLA kernel.
OUTER_DEFAULT_REFUSAL = (
    "blocked outer: no default tile policy yields 128-multiple tiles"
)


def pallas_mode(env: str | None = None) -> str:
    """Resolve the OPENR_PALLAS knob to "off" | "interpret" | "compiled".

    Default (unset / "auto"): off on every backend (EPILOGUE_COMPILED_
    REFUSAL, OUTER_DEFAULT_REFUSAL); the interpreter is a correctness
    tool, not a fast path, so it never engages implicitly either.
    "1"/"on" forces the kernels on (compiled on TPU, interpreter
    elsewhere); "0"/"off" forces them off; "interpret"/"compiled" pin
    the execution mode explicitly (tests and the program auditor use
    "interpret" on CPU)."""
    v = (env if env is not None else os.environ.get("OPENR_PALLAS", "")) or ""
    v = v.strip().lower()
    if v in ("0", "off"):
        return "off"
    if v == "interpret":
        return "interpret"
    if v == "compiled":
        return "compiled"
    if v in ("1", "on"):
        return "compiled" if jax.default_backend() == "tpu" else "interpret"
    if v not in ("", "auto"):
        log.warning("OPENR_PALLAS=%r not understood; treating as auto", v)
    return "off"


def count_skip(counters, kind: str, reason: str) -> None:
    """Account one kernel launch not taken (`pallas_skips`), with why."""
    counters["device.engine.pallas_skips"] = (
        counters.get("device.engine.pallas_skips", 0) + 1
    )
    log.debug("pallas %s kernel skipped: %s", kind, reason)


def run_with_fallback(
    kind: str,
    pallas_thunk,
    xla_thunk,
    *,
    counters=None,
    fault_hook=None,
    mode: str | None = None,
):
    """Run `pallas_thunk(interpret: bool)`, or `xla_thunk()` when the
    kernel is off or refused.

    `kind` is "product" (fused epilogue) or "outer" (blocked rank-B
    update) and selects the success counter.  `counters`/`fault_hook`
    are the owning engine's seams (`DeviceResidencyEngine.run_pallas`
    binds them); engine-less callers get policy-only behavior with no
    accounting.  `mode` overrides the env policy (tests and the program
    auditor pass "interpret" instead of mutating the environment).

    Off, and a compiled epilogue (EPILOGUE_COMPILED_REFUSAL), are a
    counted skip decided before dispatch.  A compiled launch that fails
    raises — compiled kernels never demote silently, so a device failure
    shows as one.  Only the interpreter (a CPU correctness tool) keeps
    the demotion path: there any failure, the armed `engine:pallas`
    chaos fault included, re-runs `xla_thunk` and bumps
    `pallas_fallbacks`."""
    eff = mode if mode is not None else pallas_mode()
    if counters is None:
        counters = {}  # engine-less caller: policy only, no accounting
    if eff == "off":
        count_skip(counters, kind, "off")
        return xla_thunk()
    if eff == "compiled" and kind == "product":
        count_skip(counters, kind, EPILOGUE_COMPILED_REFUSAL)
        return xla_thunk()
    if eff == "interpret":
        try:
            if fault_hook is not None:
                fault_hook("pallas")
            out = pallas_thunk(True)
        except Exception:
            counters["device.engine.pallas_fallbacks"] = (
                counters.get("device.engine.pallas_fallbacks", 0) + 1
            )
            log.warning(
                "pallas %s kernel demoted to the XLA path", kind,
                exc_info=True,
            )
            return xla_thunk()
    else:
        if fault_hook is not None:
            fault_hook("pallas")
        out = pallas_thunk(False)
    if kind == "product":
        counters["device.engine.pallas_products"] = (
            counters.get("device.engine.pallas_products", 0) + 1
        )
    else:
        counters["device.engine.pallas_outer_updates"] = (
            counters.get("device.engine.pallas_outer_updates", 0) + 1
        )
    return out


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# -- kernel 1: fused verify+bitmap epilogue -----------------------------------


def _epilogue_kernel(
    idx_ref,
    w_ref,
    ov_ref,
    slot_ref,
    d_ref,
    bitmap_ref,
    vmin_ref,
    *,
    n_groups: int,
    n_words: int,
    inf: int,
    wbig: int,
):
    """One [Np, 128] product tile: unroll every relax group over the
    resident tile — candidate, bitmap hit, fixed-point min — in VMEM."""
    d = d_ref[...]  # [Np, TP] ddt
    inf_c = jnp.asarray(inf, d.dtype)
    fin = d < inf_c
    vmin = d
    words = [jnp.zeros(d.shape, jnp.uint32) for _ in range(n_words)]
    for g in range(n_groups):
        idxg = idx_ref[g, :]  # [Np] int32 — gather row per node
        wg = w_ref[g, :]  # [Np] int32 — clamped weight (wbig = unusable)
        ovg = ov_ref[g, :]  # [Np] int32 0/1 — predecessor overloaded
        sg = slot_ref[g, :]  # [Np] int32 — forward out-slot (-1 = none)
        du = jnp.take(d, idxg, axis=0)  # [Np, TP]
        allow = (wg < wbig)[:, None] & ((ovg == 0)[:, None] | (du == 0))
        cand = jnp.where(
            allow & (du < inf_c), du + wg.astype(d.dtype)[:, None], inf_c
        )
        on = fin & (cand == d)
        bit = jnp.where(
            sg >= 0,
            jnp.uint32(1) << (jnp.maximum(sg, 0) % 32).astype(jnp.uint32),
            jnp.uint32(0),
        )
        if n_words == 1:
            words[0] = words[0] | jnp.where(on, bit[:, None], jnp.uint32(0))
        else:
            wsel = jnp.maximum(sg, 0) // 32
            for wi in range(n_words):
                words[wi] = words[wi] | jnp.where(
                    on & (wsel == wi)[:, None], bit[:, None], jnp.uint32(0)
                )
        vmin = jnp.minimum(vmin, cand)
    bitmap_ref[...] = jnp.stack(words, axis=0)
    vmin_ref[...] = vmin


@functools.partial(
    jax.jit, static_argnames=("n_groups", "n_words", "interpret")
)
def fused_epilogue_pallas(
    d,  # [Np, Pp] ddt — product, padded to (mult 128, mult 128) with INF
    idx,  # [Gp, Np] int32 — gather row; pad rows/cols are neutral (0)
    w,  # [Gp, Np] int32 — clamped weight; pad = wbig (masks the edge)
    ov,  # [Gp, Np] int32 — 0/1 predecessor-overloaded; pad 0
    slot,  # [Gp, Np] int32 — forward out-slot bit position; pad -1
    *,
    n_groups: int,
    n_words: int,
    interpret: bool,
):
    """Pallas launch for the fused epilogue: grid over 128-wide product
    column tiles, group tables resident per instance.  Returns
    (bitmap [W, Np, Pp] uint32, vmin [Np, Pp] ddt); the caller slices
    off the padding and reduces `all(vmin == d)` for the verdict."""
    np_pad, pp = d.shape
    gp = idx.shape[0]
    small = d.dtype == jnp.uint16
    inf = _INF16 if small else _INF32
    wbig = _WBIG16 if small else _WBIG32
    tp = 128
    if not interpret:
        raise ValueError(EPILOGUE_COMPILED_REFUSAL)
    kernel = functools.partial(
        _epilogue_kernel,
        n_groups=n_groups,
        n_words=n_words,
        inf=inf,
        wbig=wbig,
    )
    tab = pl.BlockSpec((gp, np_pad), lambda j: (0, 0))
    bitmap, vmin = pl.pallas_call(
        kernel,
        grid=(pp // tp,),
        in_specs=[
            tab,  # idx
            tab,  # w
            tab,  # ov
            tab,  # slot
            pl.BlockSpec((np_pad, tp), lambda j: (0, j)),  # d
        ],
        out_specs=[
            pl.BlockSpec((n_words, np_pad, tp), lambda j: (0, 0, j)),
            pl.BlockSpec((np_pad, tp), lambda j: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_words, np_pad, pp), jnp.uint32),
            jax.ShapeDtypeStruct((np_pad, pp), d.dtype),
        ],
        interpret=interpret,
    )(idx, w, ov, slot, d)
    return bitmap, vmin


def _pad2(a, rows: int, cols: int, fill: int):
    return jnp.pad(
        a,
        ((0, rows - a.shape[0]), (0, cols - a.shape[1])),
        constant_values=fill,
    )


def fused_epilogue(ops, bg, d, resid_slot, band_slot, n_words, *, interpret):
    """Traced front half of kernel 1 (called INSIDE the
    `_fused_progressive_banded` jit when its `pallas` static is set):
    normalize every relax group to the uniform (idx, w, ov, slot) row
    form, pad to Mosaic-conformant tiles, launch, and strip the padding.
    Returns (bitmap [N, P, W] uint32, converged bool) matching the lax
    epilogue exactly (the small-dist saturation verdict stays with the
    caller, as in the lax path)."""
    if getattr(ops, "resid_excl", None) is not None:
        # per-row exclusion masks belong to the masked what-if variants,
        # which never reach this epilogue; refuse rather than mis-fuse
        raise ValueError("pallas epilogue does not support row exclusions")
    n, p = d.shape
    idx_rows, w_rows, ov_rows, slot_rows = [], [], [], []
    for k in range(ops.n_resid):
        idx_rows.append(bg.resid_nbr[:, k])
        w_rows.append(ops.rw[:, k].astype(jnp.int32))
        ov_rows.append(ops.rov[:, k].astype(jnp.int32))
        slot_rows.append(resid_slot[:, k])
    ids = jnp.arange(n, dtype=jnp.int32)
    for b, c in enumerate(bg.offsets):
        w0, ovb, _ = ops.band_tabs[b]
        # roll(d, c)[v] == d[(v - c) mod N]: the band relax as a gather
        idx_rows.append(jnp.remainder(ids - jnp.int32(c), jnp.int32(n)))
        w_rows.append(w0[:, 0].astype(jnp.int32))
        ov_rows.append(ovb[:, 0].astype(jnp.int32))
        slot_rows.append(band_slot[b])
    g = len(idx_rows)
    small = d.dtype == jnp.uint16
    inf = _INF16 if small else _INF32
    wbig = _WBIG16 if small else _WBIG32
    gp = _round_up(g, 8)  # int32 sublane tile
    np_pad = _round_up(n, 128)  # lane tile for the [Gp, Np] tables AND
    #   sublane multiple for both distance dtypes
    pp = _round_up(p, 128)
    idx = _pad2(jnp.stack(idx_rows), gp, np_pad, 0)
    w = _pad2(jnp.stack(w_rows), gp, np_pad, wbig)
    ovt = _pad2(jnp.stack(ov_rows), gp, np_pad, 0)
    slot = _pad2(jnp.stack(slot_rows), gp, np_pad, -1)
    dpad = jnp.pad(
        d, ((0, np_pad - n), (0, pp - p)), constant_values=inf
    )
    bitmap, vmin = fused_epilogue_pallas(
        dpad,
        idx,
        w,
        ovt,
        slot,
        n_groups=g,
        n_words=n_words,
        interpret=interpret,
    )
    # padded candidates are exactly INF == dpad there, so the verdict
    # over the padded block equals the verdict over the live region
    return (
        bitmap[:, :n, :p].transpose(1, 2, 0),
        jnp.all(vmin == dpad),
    )


# -- kernel 2: blocked rank-B outer update ------------------------------------


def outer_conformance(s: int, t: int, b: int) -> str | None:
    """Why the compiled outer kernel cannot take tiles (S, T, B), or None.

    Mosaic needs 128-multiple lane dims for the [ti, tj] / [ti, 128]
    blocks (ti = 128, the m axis chunked by 128).  The blocks are fixed
    at that size whatever S, T and B are (about 0.6 MiB of VMEM
    double-buffered), so the tile is the only refusal.  Decided once per
    closure by the blocked rung (`BlockedApspEngine.run_apsp`, a counted
    skip), and asserted again by a direct compiled launch."""
    if b % 128:
        return (
            f"blocked outer: tile B={b} is not a multiple of 128 "
            f"(Mosaic lane tiling)"
        )
    return None


def _outer_kernel(d_ref, c_ref, r_ref, ov_ref, o_ref, *, kb: int):
    """One [ti, tj] distance tile, one kb-wide chunk of the rank-B
    update (grid axis 3 walks the chunks and revisits the output tile).

    Mosaic has no dynamic lane slice, so the m loop is unrolled over the
    chunk: column m of the col block is a static lane slice, row m of
    the row block a static sublane slice of its ref.  The drain table
    runs m over sublanes (`ov_ref[m, :]` all equal), so lifting row m to
    INF where lane m is overloaded is a sublane read too.  Everything is
    int32 (Mosaic has no unsigned min): operands lie in [0, 2^30], and
    `r + min(c, INF - r)` is the saturating `min(c + r, INF)` without an
    intermediate that could wrap."""

    @pl.when(pl.program_id(3) == 0)
    def _():
        o_ref[...] = d_ref[...]

    infu = jnp.int32(_INF32)
    c = c_ref[0]  # [ti, kb], m over lanes
    acc = o_ref[0]
    for m in range(kb):
        rm = r_ref[0, m : m + 1, :]  # [1, tj]
        rm = jnp.where(ov_ref[m : m + 1, 0:1] != 0, infu, rm)
        acc = jnp.minimum(acc, rm + jnp.minimum(c[:, m : m + 1], infu - rm))
    o_ref[0] = acc


@functools.partial(
    jax.jit, static_argnames=("interpret",), donate_argnums=(0,)
)
def blocked_outer_pallas(
    dist, row_p, col_p, node_overloaded, k, *, interpret: bool
):
    """Pallas phase 3 of the blocked APSP round
    (`parallel.blocked.blocked_outer`, single-device meshes only): panel
    write-back in XLA, then the rank-B outer update as a tiled kernel
    over the [Np, Np] view of the tile tensor.

    The drain mask folds into the kernel (`_outer_kernel` lifts row m of
    the row-panel block to INF where lane m of tile k is overloaded):
    bit-exact against the per-m `where(ov_m, INF, cand)` of the XLA
    kernel because `min(c + INF, INF) == INF`.  Integer min is exact and
    order-free, so the chunked m accumulation matches XLA's bit for bit.
    The uint32 tensors enter and leave as int32 bitcasts (free; values
    never exceed 2^30).

    Donation note: `dist` is donated (matching `blocked_outer`); the
    conformance gate below raises at trace time, before any buffer is
    consumed."""
    s, t, b = dist.shape[0], dist.shape[1], dist.shape[2]
    np_ = t * b
    if not interpret:
        reason = outer_conformance(s, t, b)
        if reason is not None:
            raise ValueError(reason)
    dist = lax.dynamic_update_index_in_dim(dist, row_p, k, axis=1)
    dist = lax.dynamic_update_index_in_dim(dist, col_p, k, axis=3)
    ov = lax.dynamic_slice_in_dim(node_overloaded, k * b, b)  # [B] bool
    i32 = functools.partial(lax.bitcast_convert_type, new_dtype=jnp.int32)
    rm = i32(row_p.reshape(s, b, np_))
    cm = i32(col_p.reshape(s, np_, b))
    d2 = i32(dist.reshape(s, np_, np_))  # tile dims contiguous: free view
    # [B, 128] drain table, m over sublanes (every lane of row m equal)
    ovt = jnp.broadcast_to(ov.astype(jnp.int32)[:, None], (b, 128))
    ti = 128 if np_ % 128 == 0 else b
    kb = 128 if b % 128 == 0 else b
    out = pl.pallas_call(
        functools.partial(_outer_kernel, kb=kb),
        grid=(s, np_ // ti, np_ // ti, b // kb),
        in_specs=[
            pl.BlockSpec((1, ti, ti), lambda si, i, j, q: (si, i, j)),
            pl.BlockSpec((1, ti, kb), lambda si, i, j, q: (si, i, q)),
            pl.BlockSpec((1, kb, ti), lambda si, i, j, q: (si, q, j)),
            pl.BlockSpec((kb, 128), lambda si, i, j, q: (q, 0)),
        ],
        out_specs=pl.BlockSpec((1, ti, ti), lambda si, i, j, q: (si, i, j)),
        out_shape=jax.ShapeDtypeStruct((s, np_, np_), jnp.int32),
        input_output_aliases={0: 0},
        interpret=interpret,
    )(d2, cm, rm, ovt)
    return lax.bitcast_convert_type(out, jnp.uint32).reshape(s, t, b, t, b)
