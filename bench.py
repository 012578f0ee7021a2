"""Benchmark of record: batched all-sources SPF at the BASELINE.md scale
points, measured against a native C++ Dijkstra baseline.

Configs (BASELINE.json):
  #1 grid 1024 (32x32, unit metric)      — all-sources, continuity metric
  #2 fat-tree ~10k switches (4-plane)    — all-sources, THE HEADLINE
  #3 WAN 100k small-world, dual metrics  — router-view SPF (self+neighbors,
     the per-router production question) + a 1024-source tile for the
     all-sources scaling story

The baseline is an in-repo native binary-heap Dijkstra (benchmarks/cpp/
spf_baseline.cpp, g++ -O3) with the reference's runSpf semantics
(openr/decision/LinkState.cpp:809-878), run sequentially per source exactly
as the reference computes per-source SPF.  It is conformance-checked
bit-exact against the TPU kernel before timing.  For the 10k all-sources
row the C++ time is measured on a 64-source sample and scaled linearly
(per-source cost is constant); noted in details.

The TPU kernel additionally extracts the full tie-retaining shortest-path
DAG (ECMP structure) in the same measured call — work the C++ baseline does
not even attempt.

Timing: min over reps after warmup; full per-rep samples land in
bench_details.json.

One process per chip: all device rows run in a CHILD process
(`--device-child`), the only process that touches the TPU.  It exits
non-zero when JAX finds no TPU (no CPU rows are ever timed under device
names), and appends each completed row to a JSONL side file, flushed per
row.  The parent pins its own JAX to the CPU for the host rows, enforces
a per-row progress timeout, kills a stalled child, merges whatever
landed, and respawns the child (skipping finished rows), so a hung
device costs at most one row per attempt.

Prints ONE JSON line (headline), writes bench_details.json with all rows.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from typing import Optional

import numpy as np

DETAILS_PATH = "bench_details.json"
DEVICE_ROWS_PATH = "bench_device_rows.jsonl"
# the caller's JAX_PLATFORMS (None = unset), recorded by main() before it
# pins this parent process to the CPU; the device child gets it back
_CALLER_JAX_PLATFORMS: list = [None]
# per-row progress timeout for the child: covers device init (~15s),
# topology build (100k WAN ~60s) and first-compile (~40s) with slack
ROW_TIMEOUT_S = float(os.environ.get("OPENR_BENCH_ROW_TIMEOUT_S", "900"))
DEVICE_ATTEMPTS = int(os.environ.get("OPENR_BENCH_DEVICE_ATTEMPTS", "4"))
RETRY_SLEEP_S = float(os.environ.get("OPENR_BENCH_RETRY_SLEEP_S", "60"))
# global wall budget for the WHOLE bench run (0 = uncapped).  When the
# driver runs this under its own timeout, set the cap slightly below it:
# the bench then sheds remaining rows (each marked as shed) and still
# prints the headline JSON — instead of being killed mid-row (rc 124).
BUDGET_S = float(os.environ.get("OPENR_BENCH_BUDGET_S", "0"))
_START = time.monotonic()


def _budget_left() -> float:
    if BUDGET_S <= 0:
        return float("inf")
    return BUDGET_S - (time.monotonic() - _START)


def _shed_marker(section: str) -> dict:
    """Pre-check shed row: emitted INSTEAD OF starting a compile-heavy
    section when the remaining wall budget cannot cover it — the row
    dies cleanly in the artifact rather than the whole run dying at
    rc=124 mid-compile (BENCH_r05)."""
    return {
        "error": (
            f"skipped: wall budget exhausted before {section} "
            f"(shed marker, OPENR_BENCH_BUDGET_S)"
        )
    }


def _child_env(**extra: str) -> dict:
    """Environment for a child process: the global budget var is
    rewritten to the REMAINING budget so the child's own shed
    pre-checks measure from the right clock (a child restarts
    time.monotonic() accounting from its own import)."""
    env = {**os.environ, **extra}
    if BUDGET_S > 0:
        env["OPENR_BENCH_BUDGET_S"] = str(max(_budget_left(), 1.0))
    return env


def _device_child_env() -> dict:
    env = _child_env()
    if _CALLER_JAX_PLATFORMS[0] is None:
        env.pop("JAX_PLATFORMS", None)
    else:
        env["JAX_PLATFORMS"] = _CALLER_JAX_PLATFORMS[0]
    return env


def _attach_bw(row: dict, bytes_moved: Optional[float], wall_ms) -> dict:
    """Record the utilization lens on a device row: estimated HBM bytes
    moved by one timed call and the achieved fraction of peak BW
    (benchmarks.util.achieved_bw_frac).  Estimates are traffic models
    (dist matrix passes + outputs), not profiler counts — named *_est."""
    from benchmarks.util import achieved_bw_frac

    row["bytes_moved_est"] = int(bytes_moved) if bytes_moved else None
    row["achieved_bw_frac"] = achieved_bw_frac(bytes_moved, wall_ms)
    return row


def _flush_details(details: dict) -> None:
    """Incremental flush so a crash/wedge mid-run never loses prior rows."""
    tmp = DETAILS_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(details, f, indent=1)
    os.replace(tmp, DETAILS_PATH)


def _time_device(fn, reps: int, warmup: int = 2) -> list[float]:
    """Per-rep wall ms of `fn` after `warmup` calls, each ending in
    block_until_ready."""
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn())
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        out.append((time.perf_counter() - t0) * 1e3)
    return out


def bench_all_sources(topo, sources, reps, cpp_sample=None):
    """Returns dict row: kernel ms (dist + SP-DAG), C++ baseline ms.

    Runs the PRODUCTION fixed-sweep path (ops.banded.SpfRunner): the
    band-aware kernel where the topology has circulant structure (grid,
    WAN ring) and the bucketed ELL elsewhere (fat-tree), at the learned
    per-topology sweep hint with the in-dispatch convergence verdict —
    no data-dependent while_loop, whose per-iteration host sync would
    dominate these rows."""
    import jax

    from benchmarks import cpp_baseline

    sources = np.asarray(sources, dtype=np.int32)
    runner = topo.runner

    # warmup learns the sweep hint + compiles; then timed runs execute at
    # the fixed hint and the verdict is asserted after timing
    runner.forward(sources)
    hint = runner.hint

    # rotate the source batch per timed rep: identical inputs re-run
    # could be served from a transport-level result cache (observed
    # anomalous ~0ms walls on repeat-identical dispatches), which would
    # fake the wall number; a rolled batch is cost-equivalent fresh work
    rep_counter = [0]
    # shifts must stay below the batch length or a wrapped roll would
    # re-dispatch a byte-identical input (replay-guard degeneracy);
    # a single-source batch has no distinct rolls — modulo-1 keeps the
    # shift harmlessly constant instead of dividing by zero
    max_calls = max(1, len(sources) - 1)

    def run():
        rep_counter[0] = rep_counter[0] % max_calls + 1
        return runner.run_once(np.roll(sources, rep_counter[0]), hint)

    # parity check (small sample) before timing
    sample = np.asarray(sources[:: max(1, len(sources) // 8)][:8], np.int32)
    _, cdist = cpp_baseline.spf_all_sources(
        topo.n_nodes,
        topo.edge_src[: topo.n_edges],
        topo.edge_dst[: topo.n_edges],
        topo.edge_metric[: topo.n_edges],
        topo.edge_up[: topo.n_edges],
        topo.node_overloaded[: topo.n_nodes],
        sample,
        want_dist=True,
    )
    dist, _ = runner.forward(sample)
    np.testing.assert_array_equal(dist[:, : topo.n_nodes], cdist)

    times = _time_device(run, reps)
    _, _, ok = run()
    assert bool(ok), "timed runs did not reach the fixed point"

    # C++ baseline timing
    cpp_sources = sources
    scale = 1.0
    if cpp_sample is not None and cpp_sample < len(sources):
        cpp_sources = sources[:: len(sources) // cpp_sample][:cpp_sample]
        scale = len(sources) / len(cpp_sources)
    cpp_secs, _ = cpp_baseline.spf_all_sources(
        topo.n_nodes,
        topo.edge_src[: topo.n_edges],
        topo.edge_dst[: topo.n_edges],
        topo.edge_metric[: topo.n_edges],
        topo.edge_up[: topo.n_edges],
        topo.node_overloaded[: topo.n_nodes],
        np.asarray(cpp_sources, dtype=np.int32),
    )
    # traffic model: the [S, N] distance matrix is read+written once per
    # relax supersweep plus one verification pass; the SP-DAG adds one
    # output write of the edge-mask words
    itemsize = 2 if getattr(runner, "small_dist", False) else 4
    dist_bytes = len(sources) * topo.n_nodes * itemsize
    bytes_moved = dist_bytes * 2 * (hint + 1)
    return _attach_bw(
        {
            "topology": topo.name,
            "n_nodes": topo.n_nodes,
            "n_directed_edges": topo.n_edges,
            "n_sources": len(sources),
            "device_ms_min": round(min(times), 3),
            "device_ms_all": [round(t, 2) for t in times],
            "cpp_baseline_ms": round(cpp_secs * 1e3 * scale, 3),
            "cpp_sources_measured": len(cpp_sources),
            "cpp_scaled": scale != 1.0,
        },
        bytes_moved,
        min(times),
    )


def _pctl(xs, p: float) -> float:
    return float(np.percentile(np.asarray(xs, dtype=np.float64), p))


def bench_allsrc_full_wan100k(topo, n_prefixes: int = 1024) -> dict:
    """The 100k-node all-sources product, REDUCED-OUTPUT formulation
    (round-4): route building never reads an [N, N] matrix — per router
    it reads distances + ECMP next-hops toward the P prefix-originating
    nodes (reference: createRouteForPrefix / getNextHopsThrift,
    Decision.cpp:615-793, 1296-1300).  All-sources-to-P-destinations is
    ONE P-source SSSP on the reversed graph, and the next-hop bitmaps
    for ALL 100k routers follow from the reverse distances in a fused
    gather-only pass (ops.allsources) — so the fleet-wide route-building
    input is a single device round, not ceil(N/1024)=98 tiled dispatches
    of an output nobody consumes (r3: 197.7 s end-to-end).

    Output ([N,P] dist + [N,P,W] uint32 bitmaps, ~600 MB at
    P=1024) stays on device; each router's route build reads its own
    row, exactly as the per-tile distances did before."""
    import jax

    from benchmarks.synthetic import reversed_topology
    from openr_tpu.ops import allsources as asrc

    n = topo.n_nodes
    rev = reversed_topology(topo)
    rng = np.random.default_rng(7)
    dests = np.sort(
        rng.choice(n, size=n_prefixes, replace=False).astype(np.int32)
    )
    import jax.numpy as _jnp

    out = asrc.build_out_ell(
        topo.edge_src, topo.edge_dst, topo.n_edges, n
    )
    runner = rev.runner
    # device-resident forward arrays for the bitmap pass (the reverse
    # runner's own arrays are staged by Topology.runner), so no timed
    # dispatch re-uploads them
    fwd_metric = _jnp.asarray(topo.edge_metric)
    fwd_up = _jnp.asarray(topo.edge_up)
    fwd_ov = _jnp.asarray(topo.node_overloaded)

    # warm + compile the FUSED PROGRESSIVE program (the production
    # default since round 6): relax supersweeps early-exit at the actual
    # fixed point via an on-device while_loop over supersweep blocks,
    # and the ECMP bitmap is folded into the final verification pass so
    # the [N, P] product is read once — no separate bitmap dispatch
    maps = asrc.build_epilogue_maps(runner.bg, out)
    dist, bitmap, ok = asrc.reduced_all_sources(
        dests, runner, out, fwd_metric, fwd_up, fwd_ov, maps=maps
    )
    assert bool(ok)
    # minimal fixed-sweep count that converges (attribution probes only;
    # the timed path runs the progressive program, which needs no hint)
    hint = None
    for s in (4, 6, 8, 12, 16, 24, 32, 48, 64):
        _, _, okp = runner.run_once(
            dests, s, want_dag=False, raw_u16=True, transpose=False
        )
        if bool(okp):
            hint = s
            break
    assert hint is not None

    # spot parity: reverse distances == forward oracle rows
    from benchmarks import cpp_baseline

    sample_v = rng.choice(n, size=4, replace=False).astype(np.int32)
    _, cdist = cpp_baseline.spf_all_sources(
        n,
        topo.edge_src[: topo.n_edges],
        topo.edge_dst[: topo.n_edges],
        topo.edge_metric[: topo.n_edges],
        topo.edge_up[: topo.n_edges],
        topo.node_overloaded[:n],
        sample_v,
        want_dist=True,
    )
    from openr_tpu.decision.fleet import _row_i32

    # raw uint16 product -> the int32/INF32 oracle domain ([N*, P]
    # native layout: row v = dist(v -> every dest))
    dist_np = _row_i32(np.asarray(dist))
    for i, v in enumerate(sample_v):
        np.testing.assert_array_equal(dist_np[v], cdist[i, dests])

    rep_counter = [0]

    def run_reduced():
        # roll the destination rows per rep (transport replay guard —
        # see bench_all_sources)
        rep_counter[0] += 1
        dist, bitmap, ok = asrc.reduced_all_sources(
            np.roll(dests, rep_counter[0]),
            runner,
            out,
            fwd_metric,
            fwd_up,
            fwd_ov,
            maps=maps,
        )
        jax.block_until_ready((dist, bitmap))
        return ok

    times = _time_device(run_reduced, reps=6, warmup=0)
    assert bool(run_reduced())
    end_to_end_ms = min(times)

    # gap attribution (r3 next #2): where does the distance to the 50 ms
    # target go?  A true zero-work dispatch doesn't exist (even
    # n_supersweeps=1 runs one relax + the verification sweep), so
    # derive per-sweep cost from the (1, hint) pair and attribute:
    #   per_sweep     = (t(hint) - t(1)) / (hint - 1)
    #   dispatch tax  = t(1) - 2*per_sweep   (1 relax + 1 verify sweep)
    #   relax total   = (hint + 1) * per_sweep
    #   bitmap pass   = end-to-end minus the epilogue-free progressive run
    # every attribution sample gets a DISTINCT input (rolled dests /
    # rolled distance rows): repeat-identical dispatches can be served
    # from a transport result cache, which once produced physically
    # impossible per-sweep numbers here
    attr_counter = [0]

    def _min_t(make_call):
        def fn():
            attr_counter[0] += 1
            return make_call(attr_counter[0])

        return min(_time_device(fn, reps=3, warmup=1))

    t_one = _min_t(
        lambda i: runner.run_once(
            np.roll(dests, i), 1, want_dag=False, raw_u16=True,
            transpose=False,
        )
    )
    t_kernel = _min_t(
        lambda i: runner.run_once(
            np.roll(dests, i), hint, want_dag=False, raw_u16=True,
            transpose=False,
        )
    )
    per_sweep = max(t_kernel - t_one, 0.0) / max(hint - 1, 1)
    t_tax = max(t_one - 2 * per_sweep, 0.0)
    # progressive relax WITHOUT the fused bitmap epilogue: the difference
    # vs end-to-end is the true marginal of the in-relax bitmap pass
    # (round-5's separate ecmp_bitmap_from_reverse_dist dispatch no
    # longer exists on the production path)
    t_relax_prog = _min_t(
        lambda i: runner.run_once(
            np.roll(dests, i), None, want_dag=False, raw_u16=True,
            transpose=False, progressive=True,
        )
    )
    t_bitmap = end_to_end_ms - t_relax_prog
    # traffic model: each relax supersweep streams the [N, P] state
    # twice (read + write), the fused verify/epilogue pass reads it
    # once more, and the epilogue writes the [N, P, W] uint32 bitmaps
    itemsize = 2 if getattr(runner, "small_dist", False) else 4
    dist_bytes = n * n_prefixes * itemsize
    bytes_moved = (
        dist_bytes * (2 * hint + 1) + n * n_prefixes * out.n_words * 4
    )
    return _attach_bw(
        {
            "topology": topo.name,
            "n_nodes": n,
            "n_prefix_destinations": n_prefixes,
            "nh_bitmap_words": out.n_words,
            "end_to_end_ms": round(end_to_end_ms, 1),
            "end_to_end_ms_all": [round(t, 1) for t in times],
            "gap_attribution_ms": {
                "dispatch_tax_est": round(t_tax, 1),
                "relax_sweeps_total": round(per_sweep * (hint + 1), 1),
                "nh_bitmap_pass_marginal": round(max(t_bitmap, 0), 1),
                "per_supersweep": round(per_sweep, 2),
                "n_supersweeps": hint,
                "in_dispatch_est": round(max(end_to_end_ms - t_tax, 0), 1),
            },
            "progressive": {"check_every": 4, "max_blocks": 64},
            "fused_epilogue": True,
            "north_star_target_ms": 50.0,
            "note": (
                "round-6 production path: fused progressive program — "
                "on-device while_loop over supersweep blocks early-exits "
                "at the certified fixed point, and the fleet-wide ECMP "
                "bitmap is folded into the final verification pass (no "
                "separate bitmap dispatch). The [N,N] product remains "
                "un-materializable (40 GB) and unconsumed by route "
                "building; outputs stay on device for per-router builds."
            ),
        },
        bytes_moved,
        end_to_end_ms,
    )


def bench_fleet_warm_wan100k(topo, n_prefixes: int = 1024) -> dict:
    """Warm-started fleet rebuild, BOTH gate directions (round-6).
    Improvement-only (flap recovery — a downed ring link comes back up):
    the previous product is an elementwise upper bound, so the relax
    seeds from it directly.  Worsening (the link goes DOWN): the
    affected set — every entry some old tight chain reaches across the
    worsened edge — is re-initialized to INF and the rest of the
    previous product kept (ops.banded.affected_mask, certified
    fixpoint; gates in decision.fleet).  Reports cold vs warm end-to-end
    for the SAME final topology in each direction; warm == cold
    distances are asserted bit-exact before timing.  The reference has
    no equivalent: its SPF memo is invalidated wholesale on any
    topology change (openr/decision/LinkState.cpp:714-719)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.synthetic import reversed_topology
    from openr_tpu.ops import allsources as asrc
    from openr_tpu.ops.banded import SpfRunner

    n = topo.n_nodes
    rev = reversed_topology(topo)
    rng = np.random.default_rng(7)
    dests = np.sort(
        rng.choice(n, size=n_prefixes, replace=False).astype(np.int32)
    )
    out = asrc.build_out_ell(topo.edge_src, topo.edge_dst, topo.n_edges, n)
    fwd_metric = jnp.asarray(topo.edge_metric)
    fwd_up = jnp.asarray(topo.edge_up)
    fwd_ov = jnp.asarray(topo.node_overloaded)

    # "down" topology: one ring link down (both directions), in BOTH the
    # reverse runner (relax) and the forward masks (fused bitmap pass)
    down_up = rev.edge_up.copy()
    down_eids = np.flatnonzero(
        ((rev.edge_src[: rev.n_edges] == 0) & (rev.edge_dst[: rev.n_edges] == 1))
        | ((rev.edge_src[: rev.n_edges] == 1) & (rev.edge_dst[: rev.n_edges] == 0))
    )
    down_up[down_eids] = False
    runner_down = SpfRunner(
        rev.ell, rev.banded, rev.edge_src, rev.edge_dst, rev.edge_metric,
        down_up, rev.node_overloaded, rev.n_edges,
    )
    runner_down.stage()
    fwd_down = np.asarray(topo.edge_up).copy()
    fwd_down_eids = np.flatnonzero(
        ((topo.edge_src[: topo.n_edges] == 0) & (topo.edge_dst[: topo.n_edges] == 1))
        | ((topo.edge_src[: topo.n_edges] == 1) & (topo.edge_dst[: topo.n_edges] == 0))
    )
    fwd_down[fwd_down_eids] = False
    fwd_up_down = jnp.asarray(fwd_down)

    runner = rev.runner
    maps = asrc.build_epilogue_maps(runner.bg, out)

    dist_before, _, ok = asrc.reduced_all_sources(
        dests, runner_down, out, fwd_metric, fwd_up_down, fwd_ov, maps=maps
    )
    assert bool(ok)
    # pristine cold product (the link-UP "after" state)
    dist_cold, _, ok = asrc.reduced_all_sources(
        dests, runner, out, fwd_metric, fwd_up, fwd_ov, maps=maps
    )
    assert bool(ok)

    # -- link UP (flap recovery, improvement-only): warm from the downed
    # product on the pristine graph; exactness vs the cold fixed point
    dist_w, _, okw = asrc.reduced_all_sources(
        dests, runner, out, fwd_metric, fwd_up, fwd_ov,
        init_dist=dist_before, maps=maps,
    )
    assert bool(okw)
    assert bool(jnp.all(dist_w == dist_cold))

    # -- link DOWN (worsening): affected-set re-init from the pristine
    # product (decision.fleet._affected_init discipline): propagate the
    # worsened-edge seed along OLD tight reverse chains to a certified
    # fixpoint, re-set affected entries to INF, keep the rest
    from openr_tpu.ops.banded import INF16, INF32, affected_mask

    bg = runner.bg
    nb = bg.n_nodes
    rn = np.asarray(bg.resid_nbr)
    re_ = np.asarray(bg.resid_eid)
    v_ids = np.arange(nb, dtype=np.int64)
    # reverse edge u -> v is forward edge v -> u: the downed forward
    # pairs (0,1) and (1,0) mark reverse slots (v=0,u=1) and (v=1,u=0)
    wr = (re_ >= 0) & (
        ((v_ids[:, None] == 0) & (rn == 1))
        | ((v_ids[:, None] == 1) & (rn == 0))
    )
    be = np.asarray(bg.band_eid)
    rows = []
    for b, c in enumerate(bg.offsets):
        u = (v_ids - c) % nb
        rows.append(
            (be[b] >= 0)
            & (((v_ids == 0) & (u == 1)) | ((v_ids == 1) & (u == 0)))
        )
    wb = np.stack(rows)
    _, _, r_met, r_up, r_ov = runner.call_arrays()
    small = dist_cold.dtype == np.uint16
    aff, done = affected_mask(
        dist_cold, bg, r_up, r_met, r_ov,
        jnp.asarray(wr), jnp.asarray(wb),
        small_dist=bool(small), max_iters=128,
    )
    assert bool(done), "affected-set propagation must certify its fixpoint"
    inf = jnp.uint16(INF16) if small else jnp.int32(INF32)
    init_down = jnp.where(aff, inf, dist_cold[:nb])
    affected_frac = float(jnp.mean(aff.astype(jnp.float32)))
    dist_wd, _, okd = asrc.reduced_all_sources(
        dests, runner_down, out, fwd_metric, fwd_up_down, fwd_ov,
        init_dist=init_down, maps=maps,
    )
    assert bool(okd)
    # exactness: warm-down fixed point == the cold downed product
    assert bool(jnp.all(dist_wd == dist_before))

    # relax-only sweep counts (reporting + the bw traffic model): the
    # timed path is progressive and never sees a fixed count
    def _probe_sweeps(rnr, ladder, dist0=None):
        for s in ladder:
            _, _, okp = rnr.run_once(
                dests, s, want_dag=False, raw_u16=True, transpose=False,
                dist0=dist0,
            )
            if bool(okp):
                return s
        return None

    ladder = (1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64)
    cold_sweeps = _probe_sweeps(runner, ladder[3:])
    warm_sweeps = _probe_sweeps(runner, ladder, dist0=dist_before)
    cold_down_sweeps = _probe_sweeps(runner_down, ladder[3:])
    warm_down_sweeps = _probe_sweeps(runner_down, ladder, dist0=init_down)

    # timing: distinct pre-staged (dests, init) pairs per rep (transport
    # replay guard); init columns roll WITH the dest roll so each warm
    # rep is the same question under a permuted dest order
    # reps+warmup+1 distinct pairs per timing fn: a wrapped cycle would
    # re-dispatch byte-identical inputs inside the timed window (replay
    # guard degeneracy)
    staged = [
        (
            np.roll(dests, i),
            jnp.roll(dist_before, i, axis=1),
            jnp.roll(init_down, i, axis=1),
        )
        for i in range(1, 9)
    ]
    jax.block_until_ready([s[1] for s in staged] + [s[2] for s in staged])
    rep = [0]

    def _run(rnr, up_mask, init_col):
        d = staged[rep[0] % len(staged)]
        init = None if init_col is None else d[init_col]
        rep[0] += 1
        dist, bm, ok = asrc.reduced_all_sources(
            d[0], rnr, out, fwd_metric, up_mask, fwd_ov,
            init_dist=init, maps=maps,
        )
        jax.block_until_ready((dist, bm))
        return ok

    run_warm = lambda: _run(runner, fwd_up, 1)           # noqa: E731
    run_cold = lambda: _run(runner, fwd_up, None)        # noqa: E731
    run_warm_down = lambda: _run(runner_down, fwd_up_down, 2)  # noqa: E731
    run_cold_down = lambda: _run(runner_down, fwd_up_down, None)  # noqa: E731

    warm_times = _time_device(run_warm, reps=5, warmup=1)
    assert bool(run_warm())
    cold_times = _time_device(run_cold, reps=5, warmup=1)
    assert bool(run_cold())
    warm_down_times = _time_device(run_warm_down, reps=5, warmup=1)
    assert bool(run_warm_down())
    cold_down_times = _time_device(run_cold_down, reps=5, warmup=1)
    assert bool(run_cold_down())
    itemsize = 2 if small else 4
    dist_bytes = n * n_prefixes * itemsize
    bytes_cold = (
        dist_bytes * (2 * (cold_sweeps or 0) + 1)
        + n * n_prefixes * out.n_words * 4
    )
    return _attach_bw(
        {
            "topology": topo.name,
            "n_nodes": n,
            "n_prefix_destinations": n_prefixes,
            "scenario": "ring link 0-1 flap: DOWN (worsening) + recovery",
            "warm_sweeps": warm_sweeps,
            "cold_sweeps": cold_sweeps,
            "warm_ms_min": round(min(warm_times), 1),
            "warm_ms_all": [round(t, 1) for t in warm_times],
            "cold_ms_min": round(min(cold_times), 1),
            "cold_ms_all": [round(t, 1) for t in cold_times],
            "warm_down_sweeps": warm_down_sweeps,
            "cold_down_sweeps": cold_down_sweeps,
            "warm_down_ms_min": round(min(warm_down_times), 1),
            "warm_down_ms_all": [round(t, 1) for t in warm_down_times],
            "cold_down_ms_min": round(min(cold_down_times), 1),
            "cold_down_ms_all": [round(t, 1) for t in cold_down_times],
            "affected_frac": round(affected_frac, 6),
            "note": (
                "round-6 warm starts, BOTH directions: improvement-only "
                "changes seed the relax from the previous product "
                "(upper-bound init); link-DOWN/worsening changes re-init "
                "only the certified affected set to INF and keep the "
                "rest (ops.banded.affected_mask).  Warm == cold "
                "asserted bit-exact above before timing, each direction."
            ),
        },
        bytes_cold if cold_sweeps else None,
        min(cold_times),
    )


def bench_flap_storm_wan100k(
    topo,
    n_prefixes: int = 1024,
    events: int = 1000,
    chunks: int = 4,
    seed: int = 7,
) -> dict:
    """Incremental delta dataflow under a seeded 1k-event flap storm
    (round-8 tentpole).  Four high-metric (backup-grade) +1 ring links
    flap between their base metric and 90; each chunk of 250 coalesced
    events becomes ONE frontier certification + ONE frontier-bucketed
    relax (ops.delta) against the resident product — never a full
    restage.  Headline: events_per_dispatch, ms_per_event, and
    delta_work_ratio (delta relax sweeps*columns vs the full-width cold
    product's), with every intermediate product asserted bit-exact
    against a cold host-oracle rebuild of that chunk's topology state.

    The flappy links are HIGH-metric on purpose: a live low-metric edge
    is the SPT parent of its endpoint for ~1/degree of ALL destination
    columns (probed: 822/1024 here), so storms on primary links
    correctly overflow the frontier bound and take the bit-exact full
    fallback; backup links at the metric ceiling are tight almost
    nowhere (probed: 29/1024 for all four worsened at once), which is
    the regime the delta rung turns into ~P/32-width work."""
    import jax
    import jax.numpy as jnp

    from benchmarks.synthetic import reversed_topology
    from openr_tpu.device.engine import DeviceResidencyEngine
    from openr_tpu.ops import allsources as asrc
    from openr_tpu.ops import delta as dops
    from openr_tpu.ops.banded import SpfRunner

    n = topo.n_nodes
    e = topo.n_edges
    rev = reversed_topology(topo)
    rng = np.random.default_rng(seed)
    dests = np.sort(
        rng.choice(n, size=n_prefixes, replace=False).astype(np.int32)
    )
    out = asrc.build_out_ell(topo.edge_src, topo.edge_dst, topo.n_edges, n)
    runner = rev.runner
    maps = asrc.build_epilogue_maps(runner.bg, out)
    fwd_up = jnp.asarray(topo.edge_up)
    fwd_ov = jnp.asarray(topo.node_overloaded)

    # flappy set: 4 spread +1 ring directed edges already at the metric
    # ceiling (10) — operationally, flap storms live on backup links
    fsrc, fdst, fmet = topo.edge_src[:e], topo.edge_dst[:e], topo.edge_metric[:e]
    ring10 = np.flatnonzero((fdst == (fsrc + 1) % n) & (fmet == 10))
    flappy = [int(ring10[i * len(ring10) // 4]) for i in range(4)]
    rsrc, rdst = rev.edge_src[:e], rev.edge_dst[:e]
    rev_eid = {}
    for fe in flappy:
        m = np.flatnonzero((rsrc == fdst[fe]) & (rdst == fsrc[fe]))
        assert len(m) == 1
        rev_eid[fe] = int(m[0])

    bg = runner.bg
    re_ = np.asarray(bg.resid_eid)
    be = np.asarray(bg.band_eid)
    _, _, _, r_up, r_ov = runner.call_arrays()

    # initial (pristine) cold product: the one-and-only full upload
    dist, bitmap, ok = asrc.reduced_all_sources(
        dests, runner, out, jnp.asarray(topo.edge_metric), fwd_up, fwd_ov,
        maps=maps,
    )
    jax.block_until_ready((dist, bitmap))
    assert bool(ok)
    small = dist.dtype == jnp.uint16
    dist0_h = np.asarray(dist)
    bm0_h = np.asarray(bitmap)
    engine = DeviceResidencyEngine()
    engine.delta_register(dist.nbytes + bitmap.nbytes)

    # denominator of delta_work_ratio: sweeps the full-width cold
    # product needs (probe the runner's ladder once, pristine state)
    cold_sweeps = None
    for s in (8, 12, 16, 24, 32, 48):
        _, _, okp = runner.run_once(
            dests, s, want_dag=False, raw_u16=True, transpose=False
        )
        if bool(okp):
            cold_sweeps = s
            break
    assert cold_sweeps is not None

    # seeded storm event stream, replayed identically by every pass
    ev_rng = np.random.default_rng(seed + 1)
    per_chunk = events // chunks
    chunk_targets = []
    metric_now = {fe: int(fmet[fe]) for fe in flappy}
    for _c in range(chunks):
        for _ in range(per_chunk):
            fe = flappy[int(ev_rng.integers(len(flappy)))]
            metric_now[fe] = (
                90 if int(ev_rng.integers(2)) else int(fmet[fe])
            )
        chunk_targets.append(dict(metric_now))

    def run_storm(dist, bitmap, col_roll, verify):
        """One full replay of the storm against (donated) dist/bitmap.
        Returns (dist, bitmap, per-chunk stats, per-chunk ms)."""
        r_met = np.asarray(rev.edge_metric).copy()
        f_met = np.asarray(topo.edge_metric).copy()
        d_roll = np.roll(dests, col_roll)
        stats, times = [], []
        for c in range(chunks):
            r_new, f_new = r_met.copy(), f_met.copy()
            for fe, m in chunk_targets[c].items():
                r_new[rev_eid[fe]] = m
                f_new[fe] = m
            worse = np.flatnonzero(r_new > r_met)
            better = np.flatnonzero(r_new < r_met)
            w_resid = (re_ >= 0) & np.isin(re_, worse)
            w_band = (be >= 0) & np.isin(be, worse)
            i_resid = (re_ >= 0) & np.isin(re_, better)
            i_band = (be >= 0) & np.isin(be, better)
            t0 = time.perf_counter()
            aff, col_mask, done = engine.delta_dispatch(
                "frontier",
                dops.delta_frontier,
                dist,
                bg,
                r_up,
                jnp.asarray(r_met),
                r_ov,
                jnp.asarray(w_resid),
                jnp.asarray(w_band),
                bg,
                r_up,
                jnp.asarray(r_new),
                r_ov,
                jnp.asarray(i_resid),
                jnp.asarray(i_band),
                small_dist=bool(small),
                max_iters=128,
            )
            done_h, col_mask_h = jax.device_get((done, col_mask))
            assert bool(done_h), "frontier must certify its fixpoint"
            col_idx = np.flatnonzero(col_mask_h).astype(np.int32)
            blocks_h, pb = 0, 0
            if len(col_idx):
                pb = engine.delta_bucket(len(col_idx), n_prefixes)
                assert pb is not None, (
                    f"chunk {c}: frontier {len(col_idx)} cols overflowed "
                    "the bucket ladder — the storm design regressed"
                )
                col_pad = np.full(pb, col_idx[0], dtype=np.int32)
                col_pad[: len(col_idx)] = col_idx
                dist, bitmap, conv, blocks = engine.delta_dispatch(
                    "relax",
                    dops.delta_relax,
                    dist,
                    bitmap,
                    aff,
                    jnp.asarray(col_pad),
                    jnp.asarray(d_roll),
                    bg,
                    r_up,
                    jnp.asarray(r_new),
                    r_ov,
                    maps.resid_slot,
                    maps.band_slot,
                    depth=runner.depth,
                    resid_rounds=runner.resid_rounds,
                    small_dist=bool(small),
                    chord_mode=runner.chord_mode,
                    n_words=out.n_words,
                    bucket_key=("relax", (n, e, n_prefixes), pb,
                                out.n_words, bool(small)),
                )
                conv_h, blocks_h = jax.device_get((conv, blocks))
                assert bool(conv_h), "delta relax must converge on device"
                blocks_h = int(blocks_h)
            jax.block_until_ready(dist)
            times.append((time.perf_counter() - t0) * 1e3)
            stats.append({"cols": int(len(col_idx)), "pb": int(pb),
                          "blocks": blocks_h})
            r_met, f_met = r_new, f_new
            if verify:
                oracle_runner = SpfRunner(
                    rev.ell, rev.banded, rev.edge_src, rev.edge_dst,
                    r_met, rev.edge_up, rev.node_overloaded, rev.n_edges,
                )
                oracle_runner.stage()
                dist_o, bm_o, ok_o = asrc.reduced_all_sources(
                    d_roll, oracle_runner, out, jnp.asarray(f_met),
                    fwd_up, fwd_ov, maps=maps,
                )
                assert bool(ok_o)
                assert bool(jnp.all(dist == dist_o)), (
                    f"chunk {c}: delta product diverged from host oracle"
                )
                assert bool(jnp.all(bitmap == bm_o)), (
                    f"chunk {c}: delta bitmap diverged from host oracle"
                )
                del dist_o, bm_o, oracle_runner
        return dist, bitmap, stats, times

    # pass A: live storm, every intermediate product verified bit-exact
    # against a cold oracle of that chunk's topology (compiles included
    # in its chunk times)
    dist, bitmap, stats, times_a = run_storm(dist, bitmap, 0, verify=True)
    # pass B: warm replay from a rolled pristine product (distinct bytes
    # per dispatch; same programs) — the steady-state timing
    dist_b = jax.device_put(np.roll(dist0_h, 1, axis=1))
    bm_b = jax.device_put(np.roll(bm0_h, 1, axis=1))
    jax.block_until_ready((dist_b, bm_b))
    dist_b, bm_b, _, times_b = run_storm(dist_b, bm_b, 1, verify=False)
    del dist_b, bm_b

    dispatches = engine.counters["device.engine.delta_dispatches"] // 2
    assert dispatches <= 2 * chunks, "storm exceeded its dispatch budget"
    assert engine.counters["device.engine.full_restages"] == 1
    assert engine.counters["device.engine.delta_overflow_fallbacks"] == 0
    delta_sweep_cols = sum(s["blocks"] * 4 * s["pb"] for s in stats)
    work_ratio = delta_sweep_cols / (chunks * cold_sweeps * n_prefixes)
    assert work_ratio < 0.05, f"delta_work_ratio regressed: {work_ratio}"
    storm_ms = min(sum(times_a), sum(times_b))
    # traffic model for the storm's relax work: each relax block makes 4
    # sweeps over the pb-column slab (read+write), each chunk writes the
    # slab's bitmap once and the frontier pass reads the full dist once
    itemsize = 2 if small else 4
    bytes_storm = (
        2 * delta_sweep_cols * n * itemsize
        + sum(s["pb"] for s in stats) * n * out.n_words * 4
        + chunks * n * n_prefixes * itemsize
    )
    return _attach_bw({
        "topology": topo.name,
        "n_nodes": n,
        "n_prefix_destinations": n_prefixes,
        "events": events,
        "chunks": chunks,
        "scenario": (
            "seeded 1k-event flap storm on 4 backup (metric-10) ring "
            "links, coalesced into one delta chain per 250-event chunk"
        ),
        "events_per_dispatch": round(events / dispatches, 1),
        "ms_per_event": round(storm_ms / events, 3),
        "delta_work_ratio": round(work_ratio, 5),
        "storm_ms_live": [round(t, 1) for t in times_a],
        "storm_ms_warm": [round(t, 1) for t in times_b],
        "frontier_cols": [s["cols"] for s in stats],
        "bucket_pb": [s["pb"] for s in stats],
        "relax_blocks": [s["blocks"] for s in stats],
        "cold_sweeps": cold_sweeps,
        "delta_dispatches": dispatches,
        "full_restages": engine.counters["device.engine.full_restages"],
        "overflow_fallbacks": engine.counters[
            "device.engine.delta_overflow_fallbacks"
        ],
        "note": (
            "every chunk's product asserted bit-exact against a cold "
            "host-oracle rebuild of that chunk's topology before the "
            "next chunk ran; full_restages stays 1 (the initial upload) "
            "and delta_work_ratio counts relax sweeps*columns vs the "
            "full-width cold product's.  ms_per_event is min over the "
            "live pass and a rolled-product warm replay (distinct bytes "
            "per dispatch, replay-guard discipline)."
        ),
    }, bytes_storm, storm_ms)


def bench_ocs_rewire_wan100k(
    n: int = 100_000,
    rounds: int = 16,
    swaps_per_round: int = 4,
    seed: int = 13,
) -> dict:
    """OCS reconfiguration economics at WAN scale (round-11 tentpole):
    rolling optical-circuit swaps against ONE resident graph through the
    CSR slot freelist + engine rewire rung.  The headline is the byte
    asymmetry — a bounded rewire stages a handful of masked-write rows
    (KBs) where a restage re-uploads the whole edge set (MBs) — plus
    rewire_us per dispatch.  full_restages must stay 1 (the initial
    upload): every circuit swap rides the rewire rung or the row fails.

    The topology mirrors OcsController's chorded WAN ring (ring +-1/+-2
    under deterministic asymmetric metrics, one chord per node) but at
    wan100k node count, driven through the real LinkState -> CsrTopology
    refresh path; only the swap endpoints' adjacency databases are
    re-pushed per round (LinkState preserves Link identity for untouched
    adjacencies).  Chord picks are rejection-sampled — the controller's
    exhaustive candidate scan is O(n^2) and only meant for test scale.

    Honors OPENR_BENCH_BUDGET_S: sheds remaining rounds (and the final
    cold bit-exact sweep) when the global wall budget runs low, and says
    so in the row."""
    import random

    from openr_tpu.chaos.ocs import _CHORD_DEG_CAP, OcsController
    from openr_tpu.decision.csr import CsrTopology
    from openr_tpu.device.engine import DeviceResidencyEngine

    ctl = OcsController(seed=seed, n=n, rounds=rounds, fault_round=-1)
    rng = random.Random(seed)
    chords = ctl._initial_chords()
    deg = {i: 1 for i in range(n)}  # perfect matching: one chord each

    t0 = time.perf_counter()
    ls = ctl._build_ls(chords, {})
    ls_build_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    csr = CsrTopology.from_link_state(ls)
    csr_build_s = time.perf_counter() - t0

    engine = DeviceResidencyEngine()
    t0 = time.perf_counter()
    engine.sync(csr)  # the one legitimate full staging
    stage_s = time.perf_counter() - t0
    restage_bytes = engine.get_counters()["device.engine.bytes_staged"]

    def pick_chord():
        # rejection-sample a fresh capacity-bounded non-ring chord
        while True:
            a, b = rng.randrange(n), rng.randrange(n)
            if a == b:
                continue
            a, b = min(a, b), max(a, b)
            d = b - a
            if d in (1, 2) or n - d in (1, 2):
                continue  # ring +-1/+-2 edge
            if (a, b) in chords:
                continue
            if (
                deg.get(a, 0) >= _CHORD_DEG_CAP
                or deg.get(b, 0) >= _CHORD_DEG_CAP
            ):
                continue
            return (a, b)

    def push_nodes(touched):
        for i in sorted(touched):
            ls.update_adjacency_database(ctl._node_db(i, chords, {}))

    shed_note = None
    round_ms = []
    done_rounds = 0
    for _r in range(rounds):
        if _budget_left() < 120:
            shed_note = (
                f"budget: shed {rounds - done_rounds} of {rounds} rounds"
            )
            break
        touched = set()
        for _ in range(swaps_per_round):
            victim = rng.choice(sorted(chords))
            chords.discard(victim)
            for v in victim:
                deg[v] -= 1
            fresh = pick_chord()
            chords.add(fresh)
            for v in fresh:
                deg[v] += 1
            touched.update(victim)
            touched.update(fresh)
        push_nodes(touched)
        t0 = time.perf_counter()
        rewired = csr.refresh(ls)
        assert rewired, "bounded swap fell off the rewire path"
        engine.sync(csr)
        round_ms.append((time.perf_counter() - t0) * 1e3)
        done_rounds += 1

    c = engine.get_counters()
    assert c["device.engine.full_restages"] == 1, c
    assert c["device.engine.rewire_fallbacks"] == 0, c
    assert c["device.engine.rewire_dispatches"] == done_rounds, c
    rewire_bytes = c["device.engine.rewire_bytes_staged"]
    per_rewire = rewire_bytes / max(done_rounds, 1)

    # acceptance spot-check: the incrementally-rewired resident must be
    # bit-exact vs a cold rebuild+restage of the final topology
    exact = None
    if _budget_left() > 180 and done_rounds:
        names = ls.node_names
        sources = [names[(seed * 977 + k * 40503) % n] for k in range(3)]
        got = engine.spf_results(csr, sources)
        cold = DeviceResidencyEngine()
        expect = cold.spf_results(CsrTopology.from_link_state(ls), sources)

        def view(result):
            return {
                k: (v.metric, frozenset(v.next_hops))
                for k, v in result.items()
            }

        exact = all(view(got[s]) == view(expect[s]) for s in sources)
        assert exact, "rewired resident diverged from cold rebuild"
    else:
        shed_note = (shed_note or "") + "; budget: skipped cold sweep"

    # utilization lens on the rewire rung itself: H2D bytes the masked
    # writes staged over the engine-side staging wall (rewire_us)
    rewire_ms = c["device.engine.rewire_us"] / 1e3
    return _attach_bw({
        "topology": f"wan{n // 1000}k-ocs-ring",
        "n_nodes": n,
        "rounds": done_rounds,
        "links_swapped": done_rounds * swaps_per_round,
        "scenario": (
            f"rolling OCS circuit swaps, {swaps_per_round} chords "
            "retired+programmed per round, one rewire dispatch per round"
        ),
        "rewire_dispatches": c["device.engine.rewire_dispatches"],
        "rewire_slots": c["device.engine.rewire_slots"],
        "rewire_rows": c["device.engine.rewire_rows"],
        "bytes_per_rewire": round(per_rewire),
        "full_restage_bytes": restage_bytes,
        "restage_vs_rewire_bytes": (
            round(restage_bytes / per_rewire, 1) if per_rewire else None
        ),
        "rewire_us_per_dispatch": round(
            c["device.engine.rewire_us"] / max(done_rounds, 1), 1
        ),
        "round_ms_p50": round(_pctl(round_ms, 50), 2) if round_ms else None,
        "round_ms_p95": round(_pctl(round_ms, 95), 2) if round_ms else None,
        "full_restages": c["device.engine.full_restages"],
        "rewire_fallbacks": c["device.engine.rewire_fallbacks"],
        "initial_stage_s": round(stage_s, 2),
        "ls_build_s": round(ls_build_s, 1),
        "csr_build_s": round(csr_build_s, 1),
        "cold_sweep_exact": exact,
        "note": (
            "restage_vs_rewire_bytes is the headline: H2D bytes a full "
            "re-upload costs per byte the masked-write rewire rung "
            "stages for one bounded circuit swap.  round_ms includes "
            "the host-side LinkState->CSR refresh (identity diff + slot "
            "freelist patch), not just device time; rewire_us is the "
            "engine-side staging alone (also the achieved_bw_frac wall)."
            + (f"  {shed_note}" if shed_note else "")
        ),
    }, rewire_bytes, rewire_ms)


def bench_pallas_vs_xla(reps: int = 5) -> dict:
    """The blocked rank-B outer Pallas kernel (opt-in: off under the
    auto policy, ops.pallas_kernels) against its XLA twin on identical inputs, with the roofline
    column.  Bytes prefer the compiled program's own cost_analysis()
    over the traffic model (bytes_source records which).  The fused
    epilogue kernel has no Mosaic lowering (ops.pallas_kernels) and is
    not timed."""
    import jax
    import jax.numpy as jnp

    from benchmarks.util import achieved_bw_frac, peak_bw_source
    from openr_tpu.ops import pallas_kernels as pk
    import openr_tpu.parallel.blocked as blk

    rng = np.random.default_rng(14)

    def _cost_bytes(lowerable, *args, **kwargs):
        """cost_analysis 'bytes accessed' of the compiled program, or
        None when the backend doesn't expose it."""
        try:
            ca = lowerable.lower(*args, **kwargs).compile().cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            v = ca.get("bytes accessed") if hasattr(ca, "get") else None
            return float(v) if v and v > 0 else None
        except Exception:
            return None

    # -- kernel 2: blocked rank-B outer update ----------------------------
    s, t, b = 1, 8, 128
    np_ = t * b
    k = 3
    dist_h = rng.integers(0, 1 << 20, size=(s, t, b, t, b)).astype(np.uint32)
    row_p = jnp.asarray(
        rng.integers(0, 1 << 20, size=(s, b, t, b)).astype(np.uint32)
    )
    col_p = jnp.asarray(
        rng.integers(0, 1 << 20, size=(s, t, b, b)).astype(np.uint32)
    )
    ov_n = jnp.asarray(rng.random(np_) < 0.05)
    mesh = blk.make_blocked_mesh(jax.devices()[:1])
    xla_outer = jax.jit(
        lambda dd, rp, cp, o, kk: blk.blocked_outer(
            dd, rp, cp, o, kk, mesh=mesh
        )
    )
    # blocked_outer_pallas donates dist: rotate pre-staged copies so no
    # rep re-submits a deleted buffer (and no rep dispatches twice on
    # identical bytes — replay-guard discipline)
    staged = [jax.device_put(dist_h) for _ in range(reps + 2)]
    jax.block_until_ready(staged)
    it = iter(staged)
    blk_pallas_ms = min(_time_device(
        lambda: pk.blocked_outer_pallas(
            next(it), row_p, col_p, ov_n, k, interpret=False
        ),
        reps=reps, warmup=1,
    ))
    dist0 = jax.device_put(dist_h)
    blk_xla_ms = min(_time_device(
        lambda: xla_outer(dist0, row_p, col_p, ov_n, k), reps=reps, warmup=1
    ))
    out_p = pk.blocked_outer_pallas(
        jax.device_put(dist_h), row_p, col_p, ov_n, k, interpret=False
    )
    assert bool(jnp.all(out_p == xla_outer(dist0, row_p, col_p, ov_n, k)))
    # traffic model: dist read+written once; each panel re-read per tile
    # row/column of the grid
    blk_tm = 2 * s * np_ * np_ * 4 + 2 * t * s * np_ * b * 4
    blk_bytes, blk_src = blk_tm, "traffic_model"
    cb = _cost_bytes(
        pk.blocked_outer_pallas,
        jax.ShapeDtypeStruct(dist_h.shape, jnp.uint32),
        row_p, col_p, ov_n, k, interpret=False,
    )
    if cb:
        blk_bytes, blk_src = cb, "cost_analysis"
    blk_xla_bytes = _cost_bytes(
        xla_outer, jax.ShapeDtypeStruct(dist_h.shape, jnp.uint32),
        row_p, col_p, ov_n, k,
    ) or blk_tm

    row = {
        "scenario": (
            "hand-tiled Pallas blocked outer update vs its generic-XLA "
            "twin, identical inputs, bit-exactness asserted"
        ),
        "backend": jax.default_backend(),
        "peak_bw_source": peak_bw_source(),
        "blocked_outer": {
            "tiles": [s, t, b],
            "pallas_ms": round(blk_pallas_ms, 3),
            "xla_ms": round(blk_xla_ms, 3),
            "speedup_vs_xla": round(blk_xla_ms / blk_pallas_ms, 2),
            "bytes_moved": int(blk_bytes),
            "bytes_source": blk_src,
            "achieved_bw_frac": achieved_bw_frac(blk_bytes, blk_pallas_ms),
            "xla_achieved_bw_frac": achieved_bw_frac(
                blk_xla_bytes, blk_xla_ms
            ),
        },
    }
    # headline utilization columns for the uniform device-row surface
    return _attach_bw(row, blk_bytes, blk_pallas_ms)


def bench_ksp_dual_metric_wan100k(topo, n_dests: int = 8) -> dict:
    """BASELINE config #3: dual-metric (IGP + TE) KSP at 100k nodes.
    Round-5 formulation: base SPF, ON-DEVICE path trace, and the masked
    k=2 edge-disjoint re-run batch for BOTH cost planes run as ONE fused
    dispatch (ops.ksp.fused_ksp2_banded) — round 4's 4-dispatch chain
    with host traces between paid the flat transport fee per hop and
    lost 3.1x on wall.  The C++ baseline runs the same (1 + D) Dijkstras
    per plane sequentially (sampled + scaled like the other 100k rows)."""
    import jax

    from benchmarks import cpp_baseline
    from openr_tpu.ops.ksp import FusedKsp2Runner
    from openr_tpu.ops.protection import build_reverse_edge_ids

    e = topo.n_edges
    rng = np.random.default_rng(17)
    te_metric = topo.edge_metric.copy()
    te_metric[:e] = rng.integers(1, 101, size=e).astype(np.int32)
    dests = rng.choice(
        np.arange(1, topo.n_nodes), size=n_dests, replace=False
    ).astype(np.int32)
    runner = topo.runner
    planes = [topo.edge_metric, te_metric]
    rev = np.asarray(
        build_reverse_edge_ids(topo.edge_src[:e], topo.edge_dst[:e])
    )
    fk = FusedKsp2Runner(runner, topo.edge_dst, e, topo.n_nodes, rev, planes)

    # warmup: learn base + masked hints through the adaptive fused path
    res = fk.run(0, dests, adaptive=True)

    # parity BEFORE timing: k1 vs the C++ oracle; k2 vs a host Dijkstra
    # run under the device's own exclusions; excluded edges must form a
    # shortest path (sum of metrics == k1)
    for p, metric in enumerate(planes):
        r = res[p]
        _, cd = cpp_baseline.spf_all_sources(
            topo.n_nodes,
            topo.edge_src[:e],
            topo.edge_dst[:e],
            metric[:e],
            topo.edge_up[:e],
            topo.node_overloaded[: topo.n_nodes],
            np.zeros(1, np.int32),
            want_dist=True,
        )
        np.testing.assert_array_equal(np.asarray(r.k1), cd[0, dests])
        excl = np.asarray(r.excl)
        for i in range(0, n_dests, max(1, n_dests // 2)):
            ee = excl[i]
            ee = ee[ee < e]
            assert metric[ee].sum() == cd[0, dests[i]], "trace not shortest"
            up = topo.edge_up.copy()
            up[ee] = False
            rv = rev[ee]
            up[rv[rv >= 0]] = False
            _, cd2 = cpp_baseline.spf_all_sources(
                topo.n_nodes,
                topo.edge_src[:e],
                topo.edge_dst[:e],
                metric[:e],
                up[:e],
                topo.node_overloaded[: topo.n_nodes],
                np.zeros(1, np.int32),
                want_dist=True,
            )
            assert int(np.asarray(r.k2)[i]) == int(cd2[0, dests[i]])

    def run_fused(rep: int) -> float:
        # replay guard: distinct destination order per rep
        t0 = time.perf_counter()
        out = fk.run(0, np.roll(dests, rep + 1), adaptive=False)
        jax.block_until_ready([r.k2 for r in out])
        elapsed = (time.perf_counter() - t0) * 1e3
        for r in out:
            assert bool(r.ok_base) and bool(r.ok_masked) and bool(r.trace_ok)
        return elapsed

    times = [run_fused(i) for i in range(3)]

    # C++ baseline: 1 base + 2 sampled masked Dijkstras per plane, masked
    # runs scaled to D
    cpp_ms = 0.0
    for metric in (topo.edge_metric, te_metric):
        secs, cdist = cpp_baseline.spf_all_sources(
            topo.n_nodes,
            topo.edge_src[:e],
            topo.edge_dst[:e],
            metric[:e],
            topo.edge_up[:e],
            topo.node_overloaded[: topo.n_nodes],
            np.zeros(1, dtype=np.int32),
            want_dist=True,
        )
        cpp_ms += secs * 1e3
        masked_secs = 0.0
        for _d in dests[:2]:
            # per-destination exclusions do not change Dijkstra's cost
            # profile; the sampled re-runs time the same full SPF the
            # reference's getKthPaths would re-run per destination
            secs2, _ = cpp_baseline.spf_all_sources(
                topo.n_nodes,
                topo.edge_src[:e],
                topo.edge_dst[:e],
                metric[:e],
                topo.edge_up[:e],
                topo.node_overloaded[: topo.n_nodes],
                np.asarray([0], np.int32),
            )
            masked_secs += secs2
        cpp_ms += masked_secs * 1e3 * (n_dests / 2)
    return {
        "topology": topo.name,
        "n_nodes": topo.n_nodes,
        "planes": 2,
        "ksp_destinations": n_dests,
        "device_ms_min": round(min(times), 3),
        "device_ms_all": [round(t, 1) for t in times],
        "cpp_baseline_ms": round(cpp_ms, 3),
        "cpp_scaled": True,
        "note": (
            "ONE fused dispatch for both planes: base SPF + on-device "
            "path trace + masked k=2 edge-disjoint batch "
            "(ops.ksp.fused_ksp2_banded); k1/k2 parity-checked against "
            "the C++ oracle under the device's own exclusions before "
            "timing"
        ),
    }


def bench_srlg_whatif(topo, n_variants: int, reps: int, cpp_sample: int) -> dict:
    """Config #4: batched SRLG what-if — n_variants single-link failure
    scenarios x 1 source on `topo`, ONE masked-ELL device call (the
    variant axis IS the batch axis).  The C++ baseline re-runs a full
    Dijkstra per scenario, which is what the reference would have to do
    (one Decision re-run per what-if, Decision.cpp:1866)."""
    from benchmarks import cpp_baseline
    from openr_tpu.ops import sssp as ops
    from openr_tpu.ops.protection import build_reverse_edge_ids

    e = topo.n_edges
    rng = np.random.default_rng(42)
    rev = np.asarray(
        build_reverse_edge_ids(topo.edge_src[:e], topo.edge_dst[:e])
    )
    fail = rng.integers(0, e, size=n_variants)
    mask = np.ones((n_variants, topo.edge_capacity), dtype=bool)
    rows = np.arange(n_variants)
    mask[rows, fail] = False
    rev_of_fail = rev[fail]
    valid = rev_of_fail >= 0
    mask[rows[valid], rev_of_fail[valid]] = False
    sources = np.zeros(n_variants, dtype=np.int32)  # router-view what-if

    import jax.numpy as _jnp

    runner = topo.runner
    # warmup learns the hint under the masked batch (distances only: the
    # what-if reachability analysis never reads the DAG)
    dist, _ = runner.forward(sources, extra_edge_mask=mask, want_dag=False)
    hint = runner.hint_masked

    # device-resident inputs for the timed runs: the scenario masks (tens
    # of MB at 10k variants) derive from topology state that already
    # lives on device in production — re-uploading them per dispatch
    # would time the host-to-device transfer, not the what-if kernel
    mask_res = _jnp.asarray(mask)
    src_res = _jnp.asarray(sources)
    # replay guard with ONE dispatch per timed rep: pre-stage a few
    # distinct variant orders OUTSIDE the timed window (an in-window
    # roll would add a dispatch + a full-mask copy to every rep)
    n_staged = min(9, n_variants - 1)
    assert n_staged >= 2, "need at least 2 distinct staged masks"
    staged_masks = [
        _jnp.roll(mask_res, i, axis=0) for i in range(1, n_staged + 1)
    ]
    import jax as _jax

    _jax.block_until_ready(staged_masks)
    rep_counter = [0]

    def run():
        rep_counter[0] += 1
        return runner.run_once(
            src_res,
            hint,
            extra_edge_mask=staged_masks[rep_counter[0] % n_staged],
            want_dag=False,
        )

    # parity on a sample of variants vs C++ with the link removed
    for v in range(0, n_variants, max(1, n_variants // 4))[:4]:
        up = topo.edge_up.copy()
        up[fail[v]] = False
        if rev_of_fail[v] >= 0:
            up[rev_of_fail[v]] = False
        _, cdist = cpp_baseline.spf_all_sources(
            topo.n_nodes,
            topo.edge_src[:e],
            topo.edge_dst[:e],
            topo.edge_metric[:e],
            up[:e],
            topo.node_overloaded[: topo.n_nodes],
            np.zeros(1, dtype=np.int32),
            want_dist=True,
        )
        np.testing.assert_array_equal(dist[v, : topo.n_nodes], cdist[0])

    times = _time_device(run, reps)

    _, _, ok = run()
    assert bool(ok), "timed SRLG runs did not reach the fixed point"

    # C++ baseline: one full SPF per scenario (sampled + scaled)
    sample = min(cpp_sample, n_variants)
    cpp_secs = 0.0
    for v in range(0, n_variants, n_variants // sample)[:sample]:
        up = topo.edge_up.copy()
        up[fail[v]] = False
        if rev_of_fail[v] >= 0:
            up[rev_of_fail[v]] = False
        secs, _ = cpp_baseline.spf_all_sources(
            topo.n_nodes,
            topo.edge_src[:e],
            topo.edge_dst[:e],
            topo.edge_metric[:e],
            up[:e],
            topo.node_overloaded[: topo.n_nodes],
            np.zeros(1, dtype=np.int32),
        )
        cpp_secs += secs
    scale = n_variants / sample
    return {
        "topology": topo.name,
        "n_variants": n_variants,
        "n_nodes": topo.n_nodes,
        "device_ms_min": round(min(times), 3),
        "device_ms_all": [round(t, 2) for t in times],
        "cpp_baseline_ms": round(cpp_secs * 1e3 * scale, 3),
        "cpp_variants_measured": sample,
        "cpp_scaled": True,
    }


def bench_tilfa(topo, source: int, reps: int) -> dict:
    """Config #5: TI-LFA backup-path computation at scale — per out-edge
    post-convergence SPF (+ SP-DAG) for one protected node, one batched
    device call over the failure dimension."""
    from benchmarks import cpp_baseline
    from openr_tpu.ops import protection as prot

    e = topo.n_edges
    out_edges = np.where(topo.edge_src[:e] == source)[0].astype(np.int32)
    rev = np.asarray(
        prot.build_reverse_edge_ids(topo.edge_src[:e], topo.edge_dst[:e])
    )
    rev_full = np.full(topo.edge_capacity, -1, dtype=np.int32)
    rev_full[:e] = rev

    import jax.numpy as _jnp

    runner = topo.runner
    # transport-replay guard: every timed rep protects a DIFFERENT node
    # of the same out-degree (a genuinely distinct TI-LFA question of
    # identical cost), pre-staged device-resident so the timed window
    # holds exactly one dispatch.  Repeat-identical dispatches can be
    # served from a transport result cache, faking the wall number.
    degree = len(out_edges)
    deg_all = np.bincount(topo.edge_src[:e], minlength=topo.n_nodes)
    candidates = np.flatnonzero(deg_all == degree)
    # even 2 distinct staged questions defeat repeat-identical replay;
    # 16 keeps every rep distinct on rich topologies
    n_staged = min(16, len(candidates))
    assert n_staged >= 2, "too few equal-degree sources to stage"
    staged = []
    for cand in candidates[:n_staged]:
        oe = np.where(topo.edge_src[:e] == cand)[0].astype(np.int32)
        staged.append(
            (
                _jnp.asarray(
                    np.full(degree, cand, dtype=np.int32)
                ),
                _jnp.asarray(
                    prot.build_edge_failure_masks(
                        oe, rev_full, topo.edge_capacity
                    )
                ),
            )
        )
    survives = staged[0][1]
    src_rows = staged[0][0]

    # warmup: learn hint via the production protection API (runner path)
    dist, _ = prot.ti_lfa_backups(
        np.int32(source),
        out_edges,
        topo.edge_src,
        topo.edge_dst,
        topo.edge_metric,
        topo.edge_up,
        topo.node_overloaded,
        rev_full,
        max_degree=len(out_edges),
        runner=runner,
    )
    hint = runner.hint_masked

    rep_counter = [0]

    def run():
        rep_counter[0] += 1
        srcs_i, mask_i = staged[rep_counter[0] % len(staged)]
        return runner.run_once(srcs_i, hint, extra_edge_mask=mask_i)

    # parity: each row vs C++ with that edge pair down
    for d in range(min(2, len(out_edges))):
        up = topo.edge_up.copy()
        up[out_edges[d]] = False
        if rev[out_edges[d]] >= 0:
            up[rev[out_edges[d]]] = False
        _, cdist = cpp_baseline.spf_all_sources(
            topo.n_nodes,
            topo.edge_src[:e],
            topo.edge_dst[:e],
            topo.edge_metric[:e],
            up[:e],
            topo.node_overloaded[: topo.n_nodes],
            np.asarray([source], dtype=np.int32),
            want_dist=True,
        )
        np.testing.assert_array_equal(dist[d, : topo.n_nodes], cdist[0])

    # every staged candidate must converge at the source-learned hint
    # BEFORE timing: the timed reps cycle through them, and an
    # unconverged candidate would time cheaper, unfinished work
    for srcs_i, mask_i in staged:
        _, _, ok_i = runner.run_once(
            srcs_i, hint, extra_edge_mask=mask_i
        )
        assert bool(ok_i), "staged TI-LFA candidate missed the hint"

    times = _time_device(run, reps)

    # C++ baseline: one full SPF per protected out-edge
    cpp_secs = 0.0
    for d in range(len(out_edges)):
        up = topo.edge_up.copy()
        up[out_edges[d]] = False
        if rev[out_edges[d]] >= 0:
            up[rev[out_edges[d]]] = False
        secs, _ = cpp_baseline.spf_all_sources(
            topo.n_nodes,
            topo.edge_src[:e],
            topo.edge_dst[:e],
            topo.edge_metric[:e],
            up[:e],
            topo.node_overloaded[: topo.n_nodes],
            np.asarray([source], dtype=np.int32),
        )
        cpp_secs += secs
    return {
        "topology": topo.name,
        "n_nodes": topo.n_nodes,
        "protected_out_edges": int(len(out_edges)),
        "device_ms_min": round(min(times), 3),
        "device_ms_all": [round(t, 2) for t in times],
        "cpp_baseline_ms": round(cpp_secs * 1e3, 3),
        "cpp_scaled": False,
    }


def bench_decision_cold_start(
    n_side: int = 10, reps: int = 3, dbs=None, name: Optional[str] = None
) -> dict:
    """Decision-module cold start: initial adj+prefix publications pushed
    into a LIVE Decision event base -> debounce -> full route build ->
    DecisionRouteUpdate emitted (reference: BM_DecisionGridInitialUpdate,
    DecisionBenchmark.cpp:19-33, which measures the accumulated
    DECISION_DEBOUNCE -> ROUTE_UPDATE perf-event span).  With `dbs`,
    benchmarks an arbitrary topology (fabric rows, BM_DecisionFabric)."""
    from openr_tpu.decision.decision import Decision
    from openr_tpu.runtime.queue import ReplicateQueue
    from openr_tpu.serializer import dumps
    from openr_tpu.types import (
        PrefixDatabase,
        PrefixEntry,
        Publication,
        Value,
        adj_key,
        prefix_key,
    )
    from openr_tpu.utils.topo import grid_topology

    if dbs is None:
        dbs = grid_topology(n_side)
        name = name or f"grid{n_side * n_side}"
    n_nodes = len(dbs)
    kv = {}
    for i, db in enumerate(dbs):
        kv[adj_key(db.this_node_name)] = Value(
            version=1, originator_id=db.this_node_name, value=dumps(db)
        )
        pdb = PrefixDatabase(
            this_node_name=db.this_node_name,
            prefix_entries=[PrefixEntry(prefix=f"fc00:{i:x}::/96")],
        )
        kv[
            prefix_key(
                db.this_node_name, pdb.prefix_entries[0].prefix, "0"
            )
        ] = Value(version=1, originator_id=db.this_node_name, value=dumps(pdb))

    times = []
    for _ in range(reps):
        kvq: ReplicateQueue = ReplicateQueue()
        routeq: ReplicateQueue = ReplicateQueue()
        reader = routeq.get_reader()
        decision = Decision(
            dbs[0].this_node_name,
            kvq.get_reader(),
            None,
            routeq,
            debounce_min_s=0.001,
            debounce_max_s=0.005,
        )
        decision.run()
        try:
            t0 = time.perf_counter()
            kvq.push(Publication(key_vals=dict(kv), area="0"))
            update = reader.get(timeout=60)
            elapsed = (time.perf_counter() - t0) * 1e3
            # routes for every other node's prefix
            assert (
                len(update.unicast_routes_to_update) == n_nodes - 1
            ), len(update.unicast_routes_to_update)
            times.append(elapsed)
        finally:
            kvq.close()
            routeq.close()
            decision.stop()
            decision.wait_until_stopped(5)
    return {
        "topology": name or f"grid{n_nodes}",
        "n_nodes": n_nodes,
        "cold_start_ms_min": round(min(times), 3),
        "cold_start_ms_all": [round(t, 2) for t in times],
    }


def bench_incremental_prefix_updates(
    n_prefixes: int = 100,
    reps: int = 50,
    dbs=None,
    name: str = "grid100",
    own_node: str = "node-0-0",
) -> dict:
    """Per-prefix incremental route update latency (reference:
    BM_DecisionGridPrefixUpdates,
    openr/decision/tests/DecisionBenchmark.cpp:63-76): one advertised
    prefix changes -> only that route recomputes (the reference's
    incremental path, Decision.cpp:1903-1912).  Defaults to the
    100-node grid; `dbs` benchmarks the larger scale points (grid10000,
    fattree10k — r4 verdict bench-grid residue)."""
    from openr_tpu.decision import LinkState
    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.decision.spf_solver import SpfSolver
    from openr_tpu.types import PrefixEntry, normalize_prefix
    from openr_tpu.utils.topo import grid_topology

    if dbs is None:
        dbs = grid_topology(10)  # 100 nodes
    ls = LinkState()
    for db in dbs:
        ls.update_adjacency_database(db)
    ps = PrefixState()
    # advertisers exclude the solver's own node: a self-originated best
    # entry correctly yields no route, which is not what this row measures
    nodes = [db.this_node_name for db in dbs if db.this_node_name != own_node]
    for i in range(n_prefixes):
        ps.update_prefix(
            nodes[i % len(nodes)], "0", PrefixEntry(prefix=f"fc00:{i:x}::/64")
        )
    solver = SpfSolver(own_node)
    solver.build_route_db({"0": ls}, ps)  # warm SPF memo

    times = []
    for r in range(reps):
        i = r % n_prefixes
        prefix = normalize_prefix(f"fc00:{i:x}::/64")
        node = nodes[(i + 7) % len(nodes)]  # re-home the prefix
        t0 = time.perf_counter()
        ps.update_prefix(node, "0", PrefixEntry(prefix=prefix))
        # incremental path: recompute just this prefix
        route = solver.create_route_for_prefix_or_get_static_route(
            {"0": ls}, ps, prefix
        )
        times.append((time.perf_counter() - t0) * 1e3)
        assert route is not None
    return {
        "topology": name,
        "n_nodes": len(dbs),
        "n_prefixes": n_prefixes,
        "per_prefix_ms_min": round(min(times), 4),
        "per_prefix_ms_all": [round(t, 3) for t in times],
    }


def bench_reconvergence(
    dbs,
    name: str,
    own_node: str,
    flap_node: str,
    n_prefixes: int = 128,
    host_reps: int = 8,
    device_reps: int = 20,
) -> dict:
    """End-to-end Decision reconvergence after an adjacency flap
    (reference: BM_DecisionGridAdjUpdates,
    openr/decision/tests/DecisionBenchmark.cpp:43-54): toggle one node's
    overload bit, then rebuild the full route DB through SpfSolver —
    host-Dijkstra backend vs device backend, identical outputs asserted."""
    from openr_tpu.decision import LinkState
    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.decision.spf_solver import DeviceSpfBackend, SpfSolver
    from openr_tpu.types import PrefixEntry

    ls = LinkState()
    for db in dbs:
        ls.update_adjacency_database(db)
    ps = PrefixState()
    step = max(1, len(dbs) // n_prefixes)
    advertised = 0
    for i in range(0, len(dbs), step):
        node = dbs[i].this_node_name
        if node == own_node:
            continue
        ps.update_prefix(node, "0", PrefixEntry(prefix=f"::{i:x}:0/112"))
        advertised += 1

    flap_db = next(d for d in dbs if d.this_node_name == flap_node)

    def run(solver):
        flap_db.is_overloaded = not flap_db.is_overloaded
        ls.update_adjacency_database(flap_db)
        return solver.build_route_db({"0": ls}, ps)

    host = SpfSolver(own_node)
    device = SpfSolver(
        own_node, spf_backend=DeviceSpfBackend(min_device_nodes=64, min_device_sources=1)
    )
    # warm both (compile device kernels, prime caches) + assert parity
    rdb_h = run(host)
    rdb_h2 = run(host)
    rdb_d = run(device)
    rdb_d2 = run(device)
    assert rdb_d.unicast_routes == rdb_h.unicast_routes or (
        rdb_d.unicast_routes == rdb_h2.unicast_routes
    )

    def ms(solver, reps):
        out = []
        for _ in range(reps):
            t0 = time.perf_counter()
            run(solver)
            out.append((time.perf_counter() - t0) * 1e3)
        return out

    # >=20 device reps: the claim is about the dispatch-latency
    # *distribution*, so p50/p95 matter here, not just min
    host_times = ms(host, reps=host_reps)
    engine = getattr(device.spf, "engine", None)
    snap = dict(engine.get_counters()) if engine is not None else {}
    device_times = ms(device, reps=device_reps)
    engine_cols = _engine_attribution(
        engine, snap, min(host_times), device_reps
    )
    return {
        "topology": name,
        "advertised_prefixes": advertised,
        "host_ms_min": round(min(host_times), 3),
        "host_ms_p50": round(_pctl(host_times, 50), 3),
        "host_ms_all": [round(t, 2) for t in host_times],
        "device_ms_min": round(min(device_times), 3),
        "device_ms_p50": round(_pctl(device_times, 50), 3),
        "device_ms_p95": round(_pctl(device_times, 95), 3),
        "device_ms_all": [round(t, 2) for t in device_times],
        "device_vs_host": round(min(host_times) / min(device_times), 2),
        **engine_cols,
        "note": (
            "measures the FORCED device path (min_device_sources=1); the "
            "shipped default policy routes these small-batch flows to the "
            "host below the measured batch crossover "
            "(DeviceSpfBackend docstring)"
        ),
    }


def _engine_attribution(engine, snap, host_ms_min, reps) -> dict:
    """device.engine.* counter deltas over the timed device reps, folded
    into the row: how much of the device wall is engine time (staging +
    dispatch), what was staged, and whether updates stayed incremental."""
    if engine is None:
        return {}
    now = engine.get_counters()
    delta = {k: now[k] - snap.get(k, 0) for k in now}
    engine_ms = (
        delta["device.engine.stage_us"] + delta["device.engine.dispatch_us"]
    ) / 1e3 / max(reps, 1)
    return {
        "engine_vs_host": (
            round(host_ms_min / engine_ms, 2) if engine_ms else None
        ),
        "engine_ms_per_rep": round(engine_ms, 3),
        "bytes_staged_per_rep": delta["device.engine.bytes_staged"]
        // max(reps, 1),
        "engine_counters_delta": {
            k.removeprefix("device.engine."): v
            for k, v in delta.items()
            if v
            and k
            in (
                "device.engine.queries",
                "device.engine.bucket_hits",
                "device.engine.bucket_misses",
                "device.engine.compiles",
                "device.engine.incremental_updates",
                "device.engine.full_restages",
            )
        },
    }


def bench_reconvergence_grid1024() -> dict:
    from openr_tpu.utils.topo import grid_topology

    return bench_reconvergence(
        grid_topology(32), "grid1024", "node-0-0", "node-16-16"
    )


def bench_reconvergence_fattree10k() -> dict:
    """Crossover evidence at production scale (r3 weak #3): the same
    end-to-end reconvergence pipeline on a ~10k-switch fabric, where the
    host Dijkstra pays ~10x the 1k-grid graph work per SPF while the
    device batch cost barely moves."""
    from openr_tpu.utils.topo import fabric_topology

    dbs = fabric_topology(96, planes=4, ssw_per_plane=24, rsw_per_pod=100)
    own = next(d.this_node_name for d in dbs if d.this_node_name.startswith("rsw"))
    flap = next(d.this_node_name for d in dbs if d.this_node_name.startswith("fsw"))
    return bench_reconvergence(
        dbs,
        f"fattree{len(dbs)}",
        own,
        flap,
        n_prefixes=128,
        host_reps=3,
        device_reps=8,
    )


def bench_reconvergence_fabric5000() -> dict:
    """The reference BM's largest fabric reconvergence point
    (BM_DecisionFabric 5000, DecisionBenchmark.cpp:78-86) on the same
    end-to-end flap pipeline as the grid1024/fattree10k rows."""
    from openr_tpu.utils.topo import fabric_topology

    dbs = fabric_topology(156, rsw_per_pod=28)  # 5008 switches
    own = next(
        d.this_node_name for d in dbs if d.this_node_name.startswith("rsw")
    )
    flap = next(
        d.this_node_name for d in dbs if d.this_node_name.startswith("fsw")
    )
    return bench_reconvergence(
        dbs,
        f"fabric{len(dbs)}",
        own,
        flap,
        n_prefixes=128,
        host_reps=3,
        device_reps=8,
    )


def bench_chaos_fuzz_smoke(n: int = 8, seed: int = 20260807) -> dict:
    """Throughput of the coverage-guided chaos fuzzer's inner loop
    (openr_tpu/chaos/fuzz.py): one small fixed-seed session, reporting
    runs/s and the coverage the search discovered beyond its seed
    timelines.  The row exists so a regression that slows the oracle
    bundle (each run replays the full dispatch ladder + fleet + kv
    fabric) or kills coverage growth shows up in the artifact, not just
    as a slower soak."""
    from openr_tpu.chaos.fuzz import FUZZ_COUNTERS, fuzz

    c0 = FUZZ_COUNTERS.get_counters()
    t0 = time.monotonic()
    # leave the harness its exit slack; the session sheds inside itself
    session = fuzz(n, seed=seed, budget_s=max(_budget_left() - 120, 30.0))
    wall = time.monotonic() - t0
    c1 = FUZZ_COUNTERS.get_counters()
    ran = len(session.results)
    hist = session.coverage_history
    return {
        "runs": ran,
        "shed": session.shed,
        "wall_s": round(wall, 3),
        "runs_per_s": round(ran / wall, 3) if wall > 0 else None,
        "coverage_tokens": hist[-1] if hist else 0,
        "coverage_from_search": (hist[-1] - hist[2]) if len(hist) > 3 else 0,
        "corpus_size": len(session.corpus),
        "oracle_failures": (
            c1["chaos.fuzz.oracle_failures"] - c0["chaos.fuzz.oracle_failures"]
        ),
        "note": f"fuzz(n={n}, seed={seed}); oracle bundle on every run",
    }


def bench_sched_explore_smoke(budget_s: float = 30.0, seed: int = 0) -> dict:
    """Throughput of the deterministic schedule explorer
    (openr_tpu/analysis/sched.py): one budgeted library sweep (exhaustive
    DPOR on the small scenarios, POS sampling on the rest), reporting
    schedules/s and the DPOR prune ratio on the exhaustive pair.  The
    row exists so a regression that slows the controlled scheduler's
    round trip (every step is a cross-thread handoff) or weakens the
    reduction (prune ratio collapsing toward 1x means DPOR degenerated
    to naive enumeration) shows up in the artifact."""
    from openr_tpu.analysis import sched

    t0 = time.monotonic()
    out = sched.tier1_smoke(
        total_budget_s=min(budget_s, max(_budget_left() - 120, 10.0)),
        seed=seed,
    )
    wall = time.monotonic() - t0
    schedules = sum(r["schedules"] for r in out["scenarios"].values())
    prunes = sum(r["prunes"] for r in out["scenarios"].values())
    # reduction evidence on the exhaustive scenarios: explored vs the
    # full interleaving count (explored + pruned sleep-set skips)
    dpor = {
        n: out["scenarios"][n]
        for n in sched.EXHAUSTIVE_SCENARIOS
        if n in out["scenarios"] and out["scenarios"][n]["complete"]
    }
    explored = sum(r["schedules"] for r in dpor.values())
    return {
        "scenarios": len(out["scenarios"]),
        "shed": out["shed"],
        "schedules": schedules,
        "prunes": prunes,
        "wall_s": round(wall, 3),
        "schedules_per_s": round(schedules / wall, 3) if wall > 0 else None,
        "dpor_certificates": sorted(dpor),
        "dpor_prune_ratio": (
            round((explored + sum(r["prunes"] for r in dpor.values()))
                  / explored, 2)
            if explored
            else None
        ),
        "failures": len(out["failures"]),
        "note": f"tier1_smoke(seed={seed}); unplanted library must be clean",
    }


def bench_ksp2(
    dbs,
    name: str,
    own_node: str,
    n_prefixes: int,
    host_reps: int = 4,
    device_reps: int = 4,
) -> dict:
    """KSP2_ED_ECMP route build (reference: BM_DecisionGridAdjUpdates
    KSP2 rows, DecisionBenchmark.cpp:48-54): k=1/k=2 edge-disjoint paths
    for every best node — host per-destination recursion vs ONE masked
    batched device run."""
    from openr_tpu.decision import LinkState
    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.decision.spf_solver import DeviceSpfBackend, SpfSolver
    from openr_tpu.types import (
        PrefixEntry,
        PrefixForwardingAlgorithm,
        PrefixForwardingType,
    )

    step = max(1, len(dbs) // n_prefixes)
    advertisers = [
        db.this_node_name
        for db in dbs[:: step]
        if db.this_node_name != own_node
    ][:n_prefixes]

    def fresh_state():
        ls = LinkState()
        for db in dbs:
            ls.update_adjacency_database(db)
        ps = PrefixState()
        for i, node in enumerate(advertisers):
            ps.update_prefix(
                node,
                "0",
                PrefixEntry(
                    prefix=f"fc00:{i:x}::/64",
                    forwarding_type=PrefixForwardingType.SR_MPLS,
                    forwarding_algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP,
                ),
            )
        return ls, ps

    def ms(backend, reps):
        out = []
        rdb = None
        for _ in range(reps):
            ls, ps = fresh_state()  # cold caches each rep (the honest cost)
            solver = SpfSolver(own_node, spf_backend=backend)
            t0 = time.perf_counter()
            rdb = solver.build_route_db({"0": ls}, ps)
            out.append((time.perf_counter() - t0) * 1e3)
        return out, rdb

    host_times, host_rdb = ms(None, host_reps)
    dev_backend = DeviceSpfBackend(min_device_nodes=64, min_device_sources=1)
    snap = (
        dict(dev_backend.engine.get_counters())
        if dev_backend.engine is not None
        else {}
    )
    device_times, device_rdb = ms(dev_backend, device_reps)
    # cold caches each rep -> a fresh CSR mirror each rep, so the engine
    # restages the graph per rep; bytes_staged_per_rep records that cold
    # staging cost (the warm rows live in bench_reconvergence)
    engine_cols = _engine_attribution(
        dev_backend.engine, snap, min(host_times), device_reps
    )
    assert host_rdb.unicast_routes == device_rdb.unicast_routes
    return {
        "topology": name,
        "ksp2_prefixes": len(advertisers),
        "host_ms_min": round(min(host_times), 3),
        "host_ms_all": [round(t, 2) for t in host_times],
        "device_ms_min": round(min(device_times), 3),
        "device_ms_all": [round(t, 2) for t in device_times],
        "device_vs_host": round(min(host_times) / min(device_times), 2),
        **engine_cols,
        "note": (
            "measures the FORCED device path (min_device_sources=1); the "
            "shipped default policy routes these small-batch flows to the "
            "host below the measured batch crossover "
            "(DeviceSpfBackend docstring)"
        ),
    }


def bench_ksp2_grid1024() -> dict:
    from openr_tpu.utils.topo import grid_topology

    return bench_ksp2(grid_topology(32), "grid1024", "node-0-0", 32)


def bench_ksp2_fattree10k() -> dict:
    """KSP2 crossover evidence at production scale (r3 weak #3).  Host
    KSP2 at 10k pays two full Dijkstras plus path tracing per prefix;
    the device batches every (prefix, k) re-run into one masked call."""
    from openr_tpu.utils.topo import fabric_topology

    dbs = fabric_topology(96, planes=4, ssw_per_plane=24, rsw_per_pod=100)
    own = next(
        d.this_node_name for d in dbs if d.this_node_name.startswith("rsw")
    )
    return bench_ksp2(
        dbs,
        f"fattree{len(dbs)}",
        own,
        n_prefixes=8,
        host_reps=1,
        device_reps=3,
    )


class _WanServingBackend:
    """Serving batch-backend contract straight over the synthetic
    wan arrays: run_paths returns {source: [N] distance row}.  Every
    dispatch pads its source batch to one fixed S bucket, so the
    whole run reuses a single compiled program (the engine ladder's
    S-bucket discipline — a fresh S shape is a fresh XLA compile at
    100k and would dominate the row).  Shared by the single-scheduler
    serving row and the replica-fleet row (every replica dispatches
    into the same compiled program, like K daemons on one device)."""

    def __init__(self, topo, s_pad: int) -> None:
        self.runner = topo.runner
        self.n_nodes = topo.n_nodes
        self.s_pad = s_pad
        self._epoch = 0

    def epoch(self, area: str) -> int:
        return self._epoch

    def run_paths(
        self, area, sources, use_link_metric=True, expect_epoch=0
    ) -> dict:
        from openr_tpu.device.engine import EpochMismatchError

        if int(expect_epoch) != self._epoch:
            raise EpochMismatchError(int(expect_epoch), self._epoch)
        srcs = [int(s) for s in sources]
        out: dict = {}
        for lo in range(0, len(srcs), self.s_pad):
            chunk = srcs[lo : lo + self.s_pad]
            padded = chunk + [chunk[0]] * (self.s_pad - len(chunk))
            dist, _ = self.runner.forward(
                np.asarray(padded, np.int32), want_dag=False
            )
            dist = np.asarray(dist)[:, : self.n_nodes]
            for i, s in enumerate(chunk):
                out[s] = dist[i].copy()
        return out


def bench_serving_load_wan100k(
    topo, clients: int = 6, qps_per_client: float = 30.0, duration_s: float = 3.0
) -> dict:
    """Open-loop query serving at wan100k through the QueryScheduler
    (admission -> epoch-keyed coalescing -> double-buffered dispatch):
    N clients submit single-source distance queries at a fixed cadence
    regardless of replies; coalesced batches ride ONE padded-S runner
    dispatch.  Reports sustained qps, per-query p50/p99 latency, mean
    batch occupancy, and the shed/overflow ledger — plus a bit-exact
    parity sample of batched replies against serial single-query
    dispatches of the same backend."""
    from openr_tpu.chaos.overload import OpenLoopLoadGen
    from openr_tpu.serving import QueryScheduler

    s_pad = 16
    backend = _WanServingBackend(topo, s_pad)
    # warm: compile the padded program + learn the sweep hint before the
    # clock starts (every later dispatch reuses it)
    backend.run_paths("0", list(range(s_pad)))

    # source population: node 0's router view plus a spread of chords
    nodes = [int(s) for s in _wan_router_sources(topo)]
    nodes += [int(x) for x in range(0, topo.n_nodes, topo.n_nodes // 64)]

    sched = QueryScheduler(backend, max_pending=8192, max_coalesce=s_pad)
    sched.run()
    try:
        gen = OpenLoopLoadGen(sched, nodes=nodes, seed=7, clients=clients)
        report = gen.run_paced(
            duration_s, qps_per_client, gather_timeout_s=300.0
        )

        # bit-exact parity: batched replies vs serial single-query
        # dispatches of the same backend (one source per dispatch)
        sample = nodes[:: max(1, len(nodes) // 6)][:6]
        futs = [(s, sched.submit("paths", sources=(s,))) for s in sample]
        parity_ok = True
        for s, fut in futs:
            got = fut.result(120).value[s]
            serial = backend.run_paths("0", [s])[s]
            parity_ok &= bool(np.array_equal(got, serial))

        counters = sched.get_counters()
    finally:
        sched.stop()

    return {
        "clients": clients,
        "offered_qps": round(clients * qps_per_client, 1),
        "duration_s": duration_s,
        "submitted": report.submitted,
        "replied": report.replied,
        "shed": report.shed,
        "errors": report.errors,
        "zero_silent_drops": report.accounted == report.submitted,
        "sustained_qps": round(report.qps, 1),
        "p50_us": report.pctl_us(50),
        "p99_us": report.pctl_us(99),
        "mean_batch_occupancy": round(report.mean_batch_occupancy, 2),
        "batches": counters["serving.batches"],
        "coalesced": counters["serving.coalesced"],
        "admission_overflows": sched.admission.stats()["overflows"],
        "parity_sample": len(sample),
        "parity_ok": parity_ok,
    }


def bench_trace_overhead_wan100k(
    topo, clients: int = 6, qps_per_client: float = 30.0, duration_s: float = 2.0
) -> dict:
    """Span-tracing overhead on the wan100k serving path: the SAME
    open-loop load twice — tracing unarmed (the shipped default: one
    module-attribute load per seam), then armed at 1-in-8 sampling —
    reporting the qps and p99 deltas.  The armed segment sheds whole
    under OPENR_BENCH_BUDGET_S (an overhead row with only a baseline is
    useless, so the baseline sheds too)."""
    from openr_tpu.chaos.overload import OpenLoopLoadGen
    from openr_tpu.obs import trace as _trace
    from openr_tpu.serving import QueryScheduler

    if _budget_left() < 3 * (3 * duration_s + 10):
        return _shed_marker("trace_overhead_wan100k")

    s_pad = 16
    backend = _WanServingBackend(topo, s_pad)
    backend.run_paths("0", list(range(s_pad)))
    nodes = [int(s) for s in _wan_router_sources(topo)]
    nodes += [int(x) for x in range(0, topo.n_nodes, topo.n_nodes // 64)]

    def segment() -> dict:
        sched = QueryScheduler(backend, max_pending=8192, max_coalesce=s_pad)
        sched.run()
        try:
            gen = OpenLoopLoadGen(sched, nodes=nodes, seed=7, clients=clients)
            report = gen.run_paced(
                duration_s, qps_per_client, gather_timeout_s=300.0
            )
            return {
                "sustained_qps": round(report.qps, 1),
                "p50_us": report.pctl_us(50),
                "p99_us": report.pctl_us(99),
                "replied": report.replied,
            }
        finally:
            sched.stop()

    was_armed = _trace.TRACE is not None
    _trace.disable()
    try:
        # throwaway warm segment: the first paced run pays dispatch-path
        # warm-up (program cache, thread spin-up) that would otherwise
        # land entirely in the unarmed baseline and bias the delta
        segment()
        off = segment()
        tr = _trace.enable(sample_every=8, ring=512)
        armed = segment()
        obs_counters = tr.get_counters()
    finally:
        if not was_armed:
            _trace.disable()

    qps_delta_pct = (
        round(100.0 * (off["sustained_qps"] - armed["sustained_qps"])
              / off["sustained_qps"], 2)
        if off["sustained_qps"] > 0
        else None
    )
    return {
        "clients": clients,
        "offered_qps": round(clients * qps_per_client, 1),
        "duration_s": duration_s,
        "sample_every": 8,
        "unarmed": off,
        "armed": armed,
        "qps_delta_pct": qps_delta_pct,
        "p99_delta_us": armed["p99_us"] - off["p99_us"],
        "traces_started": obs_counters["obs.traces_started"],
        "spans_total": obs_counters["obs.spans_total"],
    }


def bench_serving_fleet_wan100k(
    topo,
    clients: int = 6,
    qps_per_client: float = 30.0,
    duration_s: float = 2.0,
) -> dict:
    """Replica-fleet front door at wan100k: the SAME open-loop load as
    serving_load_wan100k, submitted through a ReplicaRouter over K
    QueryScheduler replicas sharing one compiled program.  Reports
    aggregate qps/p50/p99 at 1 vs 2 vs 4 replicas (the router-overhead
    and spread curve), then a mid-run replica-kill segment at K=2: one
    replica's scheduler stops while clients keep submitting, and the
    row records the p99 delta vs the undisturbed K=2 segment plus the
    zero-silent-drops ledger and the router's failover/retry counters.
    Honors OPENR_BENCH_BUDGET_S: later fleet sizes (and the kill
    segment) shed whole rather than being killed mid-segment."""
    import threading

    from openr_tpu.chaos.overload import OpenLoopLoadGen
    from openr_tpu.serving import (
        QueryScheduler,
        ReplicaRouter,
        SchedulerReplica,
    )

    s_pad = 16
    backend = _WanServingBackend(topo, s_pad)
    # warm: compile the padded program before any segment's clock starts
    backend.run_paths("0", list(range(s_pad)))

    nodes = [int(s) for s in _wan_router_sources(topo)]
    nodes += [int(x) for x in range(0, topo.n_nodes, topo.n_nodes // 64)]

    def fleet(k: int):
        scheds = [
            QueryScheduler(backend, max_pending=8192, max_coalesce=s_pad)
            for _ in range(k)
        ]
        for s in scheds:
            s.run()
        router = ReplicaRouter(
            [SchedulerReplica(f"rep-{i}", s) for i, s in enumerate(scheds)],
            hedge_after_s=0.05 if k > 1 else None,
        )
        return router, scheds

    def segment(k: int, kill_at_s: Optional[float] = None):
        router, scheds = fleet(k)
        killer = None
        try:
            gen = OpenLoopLoadGen(
                router, nodes=nodes, seed=7, clients=clients, sessions=True
            )
            if kill_at_s is not None:
                killer = threading.Timer(kill_at_s, scheds[-1].stop)
                killer.start()
            report = gen.run_paced(
                duration_s, qps_per_client, gather_timeout_s=300.0
            )
            counters = router.get_counters()
        finally:
            if killer is not None:
                killer.cancel()
            router.stop()
            for s in scheds:
                s.stop()
        return report, counters

    scaling: dict = {}
    for k in (1, 2, 4):
        if _budget_left() < 3 * duration_s + 10:
            scaling[str(k)] = None  # shed whole
            continue
        report, _counters = segment(k)
        scaling[str(k)] = {
            "submitted": report.submitted,
            "sustained_qps": round(report.qps, 1),
            "p50_us": report.pctl_us(50),
            "p99_us": report.pctl_us(99),
            "shed": report.shed,
            "errors": report.errors,
            "zero_silent_drops": report.accounted == report.submitted,
        }

    kill_segment = None
    base2 = scaling.get("2")
    if base2 is not None and _budget_left() >= 3 * duration_s + 10:
        report, counters = segment(2, kill_at_s=duration_s / 2)
        kill_segment = {
            "killed_at_s": round(duration_s / 2, 2),
            "submitted": report.submitted,
            "replied": report.replied,
            "shed": report.shed,
            "errors": report.errors,
            "zero_silent_drops": report.accounted == report.submitted,
            "p99_us": report.pctl_us(99),
            "p99_delta_us": report.pctl_us(99) - base2["p99_us"],
            "router_retries": counters["serving.router.retries"],
            "router_failovers": counters["serving.router.failovers"],
            "router_replica_deaths": counters[
                "serving.router.replica_deaths"
            ],
        }

    return {
        "clients": clients,
        "offered_qps": round(clients * qps_per_client, 1),
        "duration_s": duration_s,
        "replica_scaling": scaling,
        "replica_kill": kill_segment,
    }


def bench_fleet_scaleout_wan100k(
    topo,
    n: int = 100_000,
    seed: int = 13,
    clients: int = 6,
    qps_per_client: float = 30.0,
    duration_s: float = 2.0,
) -> dict:
    """Elastic scale-out economics (round-20 tentpole): what a joining
    replica pays before it serves its first query, cold vs
    snapshot-restored, plus the router's qps/p99 curve across live
    scale(1 -> 2 -> 4) membership transitions.

    Segment A builds the OCS chorded ring at wan scale, checkpoints the
    donor engine (EngineSnapshot, serialized blob) and brings the SAME
    fresh mirror up twice on fresh engines: once cold (the first served
    query pays restage + XLA compile + query) and once restored (the
    install rung + manifest prewarm run at bring-up, OFF the serving
    path, so the first served query pays only the query).  The headline
    is time-to-first-served-query: restore must beat cold, and the
    restored replica's answers must match the donor's bit-exact.

    Segment B reuses the serving-fleet open-loop harness but keeps ONE
    router alive across the whole run and grows membership in place via
    `add_replica` (the fleet join path): per-k qps/p50/p99 plus the
    exactly-closing dispatch ledger over the union of all segments —
    the join transition may not leak a single unaccounted dispatch.

    Honors OPENR_BENCH_BUDGET_S: each segment sheds whole, and says so
    in the row."""
    from openr_tpu.chaos.ocs import OcsController
    from openr_tpu.chaos.overload import OpenLoopLoadGen
    from openr_tpu.decision.csr import CsrTopology
    from openr_tpu.device.engine import DeviceResidencyEngine
    from openr_tpu.serving import (
        QueryScheduler,
        ReplicaRouter,
        SchedulerReplica,
    )
    from openr_tpu.serving.router import dispatch_ledger_closes
    from openr_tpu.snapshot import SNAPSHOT_COUNTERS, EngineSnapshot

    def view(result):
        return {
            k: (v.metric, frozenset(v.next_hops)) for k, v in result.items()
        }

    # -- segment A: cold vs snapshot-restored bring-up ----------------------
    snapshot_section: dict
    if _budget_left() < 300:
        snapshot_section = _shed_marker("fleet_scaleout_wan100k:snapshot")
    else:
        ctl = OcsController(seed=seed, n=n, rounds=1, fault_round=-1)
        ls = ctl._build_ls(ctl._initial_chords(), {})
        names = ls.node_names
        sources = [names[(seed * 977 + k * 40503) % n] for k in range(8)]

        donor_csr = CsrTopology.from_link_state(ls)
        donor = DeviceResidencyEngine()
        donor.sync(donor_csr)
        donor_view = {
            s: view(r) for s, r in donor.spf_results(donor_csr, sources).items()
        }  # compiles the serving ladder key the manifest will carry

        c0 = SNAPSHOT_COUNTERS.get_counters()
        t0 = time.perf_counter()
        blob = EngineSnapshot.take(donor, donor_csr).to_bytes()
        take_s = time.perf_counter() - t0

        # ONE fresh mirror, brought up twice on fresh engines: identical
        # starting state for both paths (cold runs first, so any global
        # caching would help cold, not the restore being measured)
        t0 = time.perf_counter()
        joiner_csr = CsrTopology.from_link_state(ls)
        mirror_build_s = time.perf_counter() - t0

        cold = DeviceResidencyEngine()
        t0 = time.perf_counter()
        cold_res = cold.spf_results(joiner_csr, sources)
        cold_first_query_s = time.perf_counter() - t0

        warm = DeviceResidencyEngine()
        t0 = time.perf_counter()
        mode = EngineSnapshot.from_bytes(blob).restore(warm, joiner_csr)
        bringup_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        warm_res = warm.spf_results(joiner_csr, sources)
        warm_first_query_s = time.perf_counter() - t0

        assert mode == "install", mode
        parity = all(
            view(warm_res[s]) == donor_view[s]
            and view(cold_res[s]) == donor_view[s]
            for s in sources
        )
        assert parity, "restored replica diverged from donor"
        assert warm_first_query_s < cold_first_query_s, (
            warm_first_query_s,
            cold_first_query_s,
        )
        c1 = SNAPSHOT_COUNTERS.get_counters()
        snapshot_section = {
            "n_nodes": n,
            "snapshot_bytes": len(blob),
            "take_s": round(take_s, 3),
            "mirror_build_s": round(mirror_build_s, 3),
            "restore_mode": mode,
            "restore_bringup_s": round(bringup_s, 3),
            "manifest_programs": c1["snapshot.manifest_programs"]
            - c0["snapshot.manifest_programs"],
            "prewarmed_programs": c1["snapshot.prewarmed_programs"]
            - c0["snapshot.prewarmed_programs"],
            "cold_first_query_s": round(cold_first_query_s, 3),
            "restored_first_query_s": round(warm_first_query_s, 3),
            "first_query_speedup": round(
                cold_first_query_s / max(warm_first_query_s, 1e-9), 1
            ),
            "restored_vs_donor_parity": parity,
        }

    # -- segment B: live 1 -> 2 -> 4 membership transitions -----------------
    transitions: dict = {}
    ledger = None
    if _budget_left() < 3 * (3 * duration_s + 10):
        transitions = _shed_marker("fleet_scaleout_wan100k:transitions")
    else:
        s_pad = 16
        backend = _WanServingBackend(topo, s_pad)
        backend.run_paths("0", list(range(s_pad)))  # warm the program
        nodes = [int(s) for s in _wan_router_sources(topo)]
        nodes += [int(x) for x in range(0, topo.n_nodes, topo.n_nodes // 64)]

        scheds = [
            QueryScheduler(backend, max_pending=8192, max_coalesce=s_pad)
            for _ in range(4)
        ]
        scheds[0].run()
        started = [scheds[0]]
        router = ReplicaRouter(
            [SchedulerReplica("rep-0", scheds[0])], hedge_after_s=None
        )
        total_submitted = 0
        try:
            for k in (1, 2, 4):
                while len(started) < k:
                    s = scheds[len(started)]
                    s.run()
                    router.add_replica(
                        SchedulerReplica(f"rep-{len(started)}", s)
                    )
                    started.append(s)
                if _budget_left() < 3 * duration_s + 10:
                    transitions[str(k)] = None  # shed whole
                    continue
                gen = OpenLoopLoadGen(
                    router, nodes=nodes, seed=7, clients=clients, sessions=True
                )
                report = gen.run_paced(
                    duration_s, qps_per_client, gather_timeout_s=300.0
                )
                total_submitted += report.submitted
                transitions[str(k)] = {
                    "submitted": report.submitted,
                    "sustained_qps": round(report.qps, 1),
                    "p50_us": report.pctl_us(50),
                    "p99_us": report.pctl_us(99),
                    "shed": report.shed,
                    "errors": report.errors,
                    "zero_silent_drops": report.accounted == report.submitted,
                }
            counters = router.get_counters()
        finally:
            router.stop()
            for s in started:
                s.stop()
        # ONE ledger over the union of segments: the two join
        # transitions happened under this router and must not have
        # leaked a single unaccounted dispatch
        ledger = {
            "submitted": total_submitted,
            "dispatches": counters["serving.router.dispatches"],
            "closes_exactly": dispatch_ledger_closes(
                counters, total_submitted
            ),
        }
        assert ledger["closes_exactly"], (counters, total_submitted)

    return {
        "snapshot_bringup": snapshot_section,
        "clients": clients,
        "offered_qps": round(clients * qps_per_client, 1),
        "duration_s": duration_s,
        "scale_transitions": transitions,
        "dispatch_ledger": ledger,
    }


def bench_te_wan100k(
    topo,
    n_sources: int = 512,
    n_dests: int = 4,
    steps: int = 12,
    round_trips: int = 3,
) -> dict:
    """Differentiable TE at wan100k: time-to-optimized-metrics for the
    gradient-descent optimizer (soft float32 descent + exact uint32
    validation gate, openr_tpu/te) on a seeded demand matrix, against a
    host hill-climb baseline given the SAME number of exact-solver
    evaluations.  Headline: optimizer wall seconds, exact objective
    before/after for both searches, and descent steps taken.  Honors
    OPENR_BENCH_BUDGET_S through the optimizer's budget hook (stages
    shed, never a mid-stage kill)."""
    from openr_tpu.te import TeOptimizer, TeProblem, hill_climb

    rng = np.random.RandomState(0)
    n = topo.n_nodes
    dests = np.linspace(0, n - 1, n_dests).astype(np.int32)
    sources = rng.choice(n, size=n_sources, replace=False)
    demand = np.zeros((topo.node_capacity, n_dests), dtype=np.float32)
    demand[sources] = rng.uniform(
        0.5, 2.0, size=(n_sources, n_dests)
    ).astype(np.float32)
    demand[dests, np.arange(n_dests)] = 0.0
    problem = TeProblem.from_topology(
        topo, dests, demand, metric_lo=1, metric_hi=16
    )

    def room() -> float:
        return _budget_left() - 120  # leave the harness its exit slack

    opt = TeOptimizer()
    t0 = time.perf_counter()
    res = opt.optimize(
        problem,
        steps=steps,
        round_trips=round_trips,
        n_sweeps=64,
        flow_sweeps=48,
        budget_left=room,
    )
    te_wall_s = time.perf_counter() - t0

    # host baseline: hill-climb spending the same exact-evaluation count
    # the optimizer's validation gate spent (its only search oracle)
    t0 = time.perf_counter()
    _hm, hill_obj, hill_evals = hill_climb(
        problem, rounds=res.round_trips, seed=1, budget_left=room
    )
    hill_wall_s = time.perf_counter() - t0

    return {
        "n_sources": n_sources,
        "n_dests": n_dests,
        "te_wall_s": round(te_wall_s, 2),
        "te_steps": res.steps,
        "te_round_trips": res.round_trips,
        "te_accepted": res.accepted,
        "exact_objective_before": round(res.objective_before, 4),
        "exact_objective_after": round(res.objective_after, 4),
        "te_improvement_frac": round(
            1.0 - res.objective_after / res.objective_before, 4
        )
        if res.objective_before
        else 0.0,
        "hill_wall_s": round(hill_wall_s, 2),
        "hill_evals": hill_evals,
        "hill_objective_after": round(hill_obj, 4),
        "te_beats_or_matches_hill": bool(
            res.objective_after <= hill_obj + 1e-9
        ),
        "counters": {
            k: v
            for k, v in opt.get_counters().items()
            if not k.endswith("_milli")
        },
    }


class _Topos:
    """Lazy shared topology cache for the device-row child."""

    def __init__(self) -> None:
        self._cache: dict = {}

    def __getattr__(self, name: str):
        if name not in self._cache:
            from benchmarks import synthetic

            if name == "grid":
                self._cache[name] = synthetic.grid(32)
            elif name == "fat_tree":
                self._cache[name] = synthetic.fat_tree()  # 10080, 4-plane
            elif name == "wan":
                self._cache[name] = synthetic.wan(100_000)
            else:
                raise AttributeError(name)
        return self._cache[name]


def _wan_router_sources(wan) -> np.ndarray:
    from benchmarks import synthetic

    # router-view: self + every neighbor (the per-router production SPF
    # set — LFA-free ECMP needs distances from each neighbor)
    return np.concatenate([[0], synthetic.neighbors_of(wan, 0)]).astype(
        np.int32
    )


# Device rows, headline first so a wedge loses the least important rows.
# Each entry: name -> fn(topos) returning the row dict.
DEVICE_ROWS = {
    "allsrc_spf_fattree10k": lambda t: bench_all_sources(
        t.fat_tree, np.arange(t.fat_tree.n_nodes), reps=5, cpp_sample=64
    ),
    "allsrc_spf_grid1024": lambda t: bench_all_sources(
        t.grid, np.arange(t.grid.n_nodes), reps=10
    ),
    "router_spf_wan100k": lambda t: bench_all_sources(
        t.wan, _wan_router_sources(t.wan), reps=5
    ),
    "allsrc_tile1024_wan100k": lambda t: bench_all_sources(
        t.wan, np.arange(1024, dtype=np.int32), reps=3, cpp_sample=32
    ),
    "allsrc_full_wan100k": lambda t: bench_allsrc_full_wan100k(t.wan),
    # the literal north-star shape: <50ms single-chip for the fleet-wide
    # route-building input at a production-plausible prefix count
    "allsrc_reduced_p128_wan100k": lambda t: bench_allsrc_full_wan100k(
        t.wan, n_prefixes=128
    ),
    # round-5 warm start: flap-recovery rebuild from the previous product
    "fleet_warm_rebuild_wan100k": lambda t: bench_fleet_warm_wan100k(t.wan),
    # round-8 incremental delta dataflow: 1k-event storm -> 8 dispatches
    "flap_storm_wan100k": lambda t: bench_flap_storm_wan100k(t.wan),
    # round-11 OCS circuit swaps: slot-freelist rewires vs full restage
    # byte economics on one resident graph (builds its own LinkState)
    "ocs_rewire_wan100k": lambda t: bench_ocs_rewire_wan100k(),
    # round-14 Pallas kernels vs their XLA twins, roofline column per
    # kernel (compiled on TPU; interpreter elsewhere, labeled)
    "pallas_vs_xla": lambda t: bench_pallas_vs_xla(),
    # BASELINE config #3: dual-metric KSP at 100k (r3 next #6)
    "ksp_dual_metric_wan100k": lambda t: bench_ksp_dual_metric_wan100k(
        t.wan
    ),
    "srlg_whatif_10kx1k": lambda t: bench_srlg_whatif(
        t.grid, n_variants=10_000, reps=5, cpp_sample=64
    ),
    "tilfa_wan100k": lambda t: bench_tilfa(t.wan, source=0, reps=5),
    "reconverge_flap_grid1024": lambda t: bench_reconvergence_grid1024(),
    "ksp2_grid1024": lambda t: bench_ksp2_grid1024(),
    # production-scale host/device crossover rows (r3 next #3)
    "reconverge_flap_fattree10k": lambda t: bench_reconvergence_fattree10k(),
    "ksp2_fattree10k": lambda t: bench_ksp2_fattree10k(),
    # the reference BM's largest fabric reconvergence point
    # (BM_DecisionFabric 5000, DecisionBenchmark.cpp:78-86; r4 verdict
    # bench-grid residue)
    "reconverge_flap_fabric5000": lambda t: bench_reconvergence_fabric5000(),
    # query-serving layer under open-loop load: sustained qps, p50/p99,
    # batch occupancy through admission/coalescing/double-buffering
    "serving_load_wan100k": lambda t: bench_serving_load_wan100k(t.wan),
    # replica-fleet front door: aggregate qps at 1/2/4 replicas through
    # the ReplicaRouter, plus a mid-run replica-kill segment (p99 delta,
    # zero-silent-drops ledger, failover counters)
    "serving_fleet_wan100k": lambda t: bench_serving_fleet_wan100k(t.wan),
    # round-20 elastic scale-out: cold vs snapshot-restored replica
    # bring-up (time-to-first-served-query, restored-vs-donor parity)
    # plus live 1->2->4 add_replica transitions under open-loop load
    # with the union dispatch ledger closing exactly
    "fleet_scaleout_wan100k": lambda t: bench_fleet_scaleout_wan100k(t.wan),
    # differentiable TE: gradient-descent metric optimization with the
    # exact-solver acceptance gate vs host hill-climb at equal exact
    # evaluations (openr_tpu/te; docs/OPERATIONS.md "TE runbook")
    "te_wan100k": lambda t: bench_te_wan100k(t.wan),
    # span-tracing overhead: the serving load row twice, unarmed vs
    # armed at 1-in-8 sampling (qps/p99 delta; docs/OPERATIONS.md
    # "Tracing runbook")
    "trace_overhead_wan100k": lambda t: bench_trace_overhead_wan100k(t.wan),
}

DEVICE_NOTES = [
    "device times include shortest-path-DAG extraction; the C++ "
    "baseline computes distances only",
    "min-over-reps after warmup; per-rep samples retained above; "
    "p50/p95 reported for the latency-sensitive rows",
    "every timed rep dispatches a DISTINCT pre-staged input (rolled "
    "batches / masks / equal-degree sources), so no rep re-runs a "
    "byte-identical dispatch",
    "achieved_bw_frac: bytes-moved-estimate / (wall x the device's "
    "peak HBM BW, benchmarks.util.PEAKS by device_kind) — the "
    "utilization lens "
    "on every device row; null where no traffic model exists for the "
    "row (bytes_moved_est null).  A memory-bound kernel near 1.0 is "
    "done; a small fraction says the wall is dispatch/latency, not "
    "bandwidth",
    "pallas_vs_xla times the blocked_outer kernel (the one Pallas "
    "kernel that compiles for the TPU; opt-in) with its own bytes_source — "
    "compiled-program cost_analysis when available, traffic model "
    "otherwise",
]


# the device child's exit code when JAX finds no TPU: the parent stops
# instead of retrying, and the bench exits non-zero
NO_TPU_RC = 3


def _device_child(rows_file: str, skip: set[str]) -> None:
    """Run device rows in order, appending one JSON line per finished row.
    Runs until done or killed by the parent's progress watchdog.  Exits
    NO_TPU_RC before any row when JAX's backend is not a TPU."""
    import jax

    from openr_tpu.utils.compile_cache import configure_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"[device-child] no TPU (JAX platform {dev.platform!r}); "
            "device rows are never timed on another backend",
            file=sys.stderr,
            flush=True,
        )
        sys.exit(NO_TPU_RC)
    configure_compile_cache()
    topos = _Topos()
    # a child killed mid-write leaves a torn line with no trailing
    # newline; terminate it so this attempt's first row isn't glued on
    if os.path.exists(rows_file) and os.path.getsize(rows_file):
        with open(rows_file, "rb") as f:
            f.seek(-1, os.SEEK_END)
            torn = f.read(1) != b"\n"
        if torn:
            with open(rows_file, "a") as f:
                f.write("\n")
    with open(rows_file, "a") as out:
        for name, fn in DEVICE_ROWS.items():
            if name in skip:
                continue
            if _budget_left() < 90:
                # pre-check BEFORE starting a compile-heavy row: a row
                # begun with seconds left gets killed mid-compile by
                # the parent watchdog (or the driver's rc=124 timeout)
                record = {"row": name, **_shed_marker(name)}
                out.write(json.dumps(record) + "\n")
                out.flush()
                os.fsync(out.fileno())
                continue
            # stderr: the bench contract is ONE JSON line on stdout
            print(f"[device-child] row {name} ...", file=sys.stderr, flush=True)
            t0 = time.perf_counter()
            try:
                record = {"row": name, "data": fn(topos)}
            except Exception as exc:  # a failing row must not kill the rest
                record = {"row": name, "error": f"{type(exc).__name__}: {exc}"}
            data = record.get("data")
            if isinstance(data, dict) and "achieved_bw_frac" not in data:
                # rows without a traffic model still carry the field
                # (null): every device row reports utilization uniformly
                data["bytes_moved_est"] = data.get("bytes_moved_est")
                data["achieved_bw_frac"] = None
            record["wall_s"] = round(time.perf_counter() - t0, 1)
            out.write(json.dumps(record) + "\n")
            out.flush()
            os.fsync(out.fileno())


def _read_device_rows(rows_file: str) -> dict:
    rows: dict = {}
    if not os.path.exists(rows_file):
        return rows
    with open(rows_file) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue  # torn final line from a killed child
            rows[rec["row"]] = rec
    return rows


_HEADLINE = {"emitted": False}


def _maybe_emit_headline(details: dict) -> None:
    """Print the bench contract's ONE stdout JSON line as soon as the
    headline row has data — not at process end.  A driver that kills
    this process at its own wall cap then still has the headline
    (rc:124 with parsed:null is the failure mode this buys out of).
    Idempotent; later calls are no-ops."""
    if _HEADLINE["emitted"]:
        return
    headline = details["rows"].get("allsrc_spf_fattree10k")
    if isinstance(headline, dict) and "device_ms_min" in headline:
        print(
            json.dumps(
                {
                    "metric": "allsrc_spf_fattree10k_ms",
                    "value": headline["device_ms_min"],
                    "unit": "ms",
                    "vs_baseline": round(
                        headline["cpp_baseline_ms"]
                        / headline["device_ms_min"],
                        2,
                    ),
                }
            ),
            flush=True,
        )
        _HEADLINE["emitted"] = True


def _run_device_rows(details: dict) -> None:
    """Parent-side orchestration: spawn the device child, watch the rows
    file for progress, kill on per-row stall, merge, retry with completed
    rows skipped.  Attempts are spread across the run (sleep between).
    Budget-aware: no new attempt starts (and the child is killed) once
    OPENR_BENCH_BUDGET_S is nearly spent.  Returns False when the child
    found no TPU."""
    if os.path.exists(DEVICE_ROWS_PATH):
        os.remove(DEVICE_ROWS_PATH)
    attempt_log: list[str] = []
    for attempt in range(DEVICE_ATTEMPTS):
        done = _read_device_rows(DEVICE_ROWS_PATH)
        # only successful rows are final; errored rows get retried in
        # later attempts (a transient device failure can raise instead
        # of hanging — both deserve the retry)
        succeeded = [n for n in done if "data" in done[n]]
        remaining = [n for n in DEVICE_ROWS if n not in succeeded]
        if not remaining:
            break
        if _budget_left() < 120:
            attempt_log.append(
                f"attempt {attempt + 1}: skipped, wall budget exhausted"
            )
            break
        if attempt:
            time.sleep(min(RETRY_SLEEP_S, max(0.0, _budget_left() - 120)))
        proc = subprocess.Popen(
            [
                sys.executable,
                os.path.abspath(__file__),
                "--device-child",
                "--rows-file",
                DEVICE_ROWS_PATH,
                "--skip",
                ",".join(succeeded),
            ],
            env=_device_child_env(),
        )
        last_size = -1
        last_progress = time.monotonic()
        while True:
            rc = proc.poll()
            size = (
                os.path.getsize(DEVICE_ROWS_PATH)
                if os.path.exists(DEVICE_ROWS_PATH)
                else 0
            )
            if size != last_size:
                last_size = size
                last_progress = time.monotonic()
                # merge incrementally: a later wedge keeps earlier rows
                for name, rec in _read_device_rows(DEVICE_ROWS_PATH).items():
                    details["rows"][name] = rec.get(
                        "data", {"error": rec.get("error")}
                    )
                _flush_details(details)
                _maybe_emit_headline(details)
            if rc is not None:
                if rc == NO_TPU_RC:
                    return False
                if rc != 0:
                    attempt_log.append(f"attempt {attempt + 1}: exit rc={rc}")
                break
            stalled = time.monotonic() - last_progress > ROW_TIMEOUT_S
            if stalled or _budget_left() <= 0:
                attempt_log.append(
                    f"attempt {attempt + 1}: "
                    + (
                        f"no row progress in {ROW_TIMEOUT_S:.0f}s"
                        if stalled
                        else "wall budget exhausted mid-row"
                    )
                    + "; killed child"
                )
                proc.kill()
                try:
                    proc.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    # D-state child: abandon it rather than block; it may
                    # still hold the chip, and no later phase of this run
                    # touches it (the parent's JAX is pinned to the CPU)
                    attempt_log.append(
                        f"attempt {attempt + 1}: child did not exit; "
                        "abandoned, no further device attempts"
                    )
                    details["device_attempt_log"] = attempt_log
                    return True
                break
            time.sleep(2)
    done = _read_device_rows(DEVICE_ROWS_PATH)
    for name, rec in done.items():
        details["rows"][name] = rec.get("data", {"error": rec.get("error")})
    missing = [n for n in DEVICE_ROWS if n not in done]
    if missing:
        details["device_rows_missing"] = missing
    if attempt_log:
        details["device_attempt_log"] = attempt_log
    _maybe_emit_headline(details)
    return True


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--device-child", action="store_true")
    parser.add_argument("--rows-file", default=DEVICE_ROWS_PATH)
    parser.add_argument("--skip", default="")
    args = parser.parse_args()
    if args.device_child:
        _device_child(
            args.rows_file, {s for s in args.skip.split(",") if s}
        )
        return

    # one process per chip: the device child is the only process that
    # touches the TPU.  Pin this parent's JAX (host rows may import it)
    # to the CPU before anything imports it, and hand the child the
    # caller's own platform setting back.
    _CALLER_JAX_PLATFORMS[0] = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    from openr_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()

    details: dict = {"rows": {}, "notes": list(DEVICE_NOTES)}

    # --- device rows FIRST: the headline row (allsrc_spf_fattree10k)
    # --- leads DEVICE_ROWS and its stdout JSON line is emitted the
    # --- moment it lands (_maybe_emit_headline) — under a tight wall
    # --- budget the host rows below are the ones sacrificed, never the
    # --- headline
    if not _run_device_rows(details):
        print(
            json.dumps(
                {
                    "metric": "allsrc_spf_fattree10k_ms",
                    "value": None,
                    "unit": "ms",
                    "error": "no TPU: JAX found no TPU in the device child",
                }
            )
        )
        sys.exit(1)
    _flush_details(details)

    # --- host-only rows: no device needed; each is skipped (not run
    # --- half-way) once the wall budget is nearly spent
    def _fabric_cold(pods: int, label: str, reps: int = 3):
        from openr_tpu.utils.topo import fabric_topology

        dbs = fabric_topology(pods, rsw_per_pod=28)
        return bench_decision_cold_start(reps=reps, dbs=dbs, name=label)

    def _incremental_grid10000():
        from openr_tpu.utils.topo import grid_topology

        return bench_incremental_prefix_updates(
            reps=20, dbs=grid_topology(100), name="grid10000"
        )

    def _incremental_fattree10k():
        from openr_tpu.utils.topo import fabric_topology

        dbs = fabric_topology(96, planes=4, ssw_per_plane=24, rsw_per_pod=100)
        own = next(
            d.this_node_name
            for d in dbs
            if d.this_node_name.startswith("rsw")
        )
        return bench_incremental_prefix_updates(
            reps=20, dbs=dbs, name=f"fattree{len(dbs)}", own_node=own
        )

    for name, fn in (
        ("incremental_prefix_grid100", bench_incremental_prefix_updates),
        # the larger reference scale points for the incremental path
        # (r4 verdict bench-grid residue)
        ("incremental_prefix_grid10000", _incremental_grid10000),
        ("incremental_prefix_fattree10k", _incremental_fattree10k),
        ("decision_cold_start_grid100", bench_decision_cold_start),
        # reference scale points (BM_DecisionGridInitialUpdate 1k grid,
        # BM_DecisionFabric 344/1000 switches, DecisionBenchmark.cpp:19-86)
        (
            "decision_cold_start_grid1024",
            lambda: bench_decision_cold_start(n_side=32, reps=2),
        ),
        (
            "decision_cold_start_fabric336",
            lambda: _fabric_cold(10, "fabric336"),
        ),
        (
            "decision_cold_start_fabric1008",
            lambda: _fabric_cold(31, "fabric1008"),
        ),
        # the reference BM's largest fabric point (BM_DecisionFabric 5000,
        # DecisionBenchmark.cpp:78-86): 156 pods x 32 + 16 ssw = 5008
        (
            "decision_cold_start_fabric5000",
            lambda: _fabric_cold(156, "fabric5008", reps=3),
        ),
        # the reference BM's largest grid; single rep (~3s measured after
        # the publication-parse fix — it was ~2.9s for 1k BEFORE it)
        # >=3 samples (r4 verdict: the single-sample rows)
        (
            "decision_cold_start_grid10000",
            lambda: bench_decision_cold_start(n_side=100, reps=3),
        ),
        # chaos-fuzzer inner-loop throughput (oracle bundle per run)
        ("chaos_fuzz_smoke", bench_chaos_fuzz_smoke),
        # schedule-explorer throughput + DPOR reduction evidence
        ("sched_explore_smoke", bench_sched_explore_smoke),
    ):
        if _budget_left() < 60:
            details["rows"][name] = _shed_marker(name)
            _flush_details(details)
            continue
        try:
            details["rows"][name] = fn()
        except Exception as exc:
            details["rows"][name] = {"error": f"{type(exc).__name__}: {exc}"}
        _flush_details(details)
    # virtual-mesh scaling evidence (r3 next #8): child process so the
    # 8-device CPU mesh env never touches this process's TPU platform
    if _budget_left() < 60:
        details["rows"]["virtual_mesh_scaling"] = _shed_marker(
            "virtual_mesh_scaling"
        )
    else:
        try:
            proc = subprocess.run(
                [sys.executable, "-m", "benchmarks.mesh_scaling"],
                capture_output=True,
                text=True,
                timeout=min(900.0, max(_budget_left(), 60.0)),
                env=_child_env(
                    JAX_PLATFORMS="cpu",
                    XLA_FLAGS="--xla_force_host_platform_device_count=8",
                ),
            )
            details["rows"]["virtual_mesh_scaling"] = json.loads(
                proc.stdout.strip().splitlines()[-1]
            )
        except Exception as exc:
            details["rows"]["virtual_mesh_scaling"] = {
                "error": f"{type(exc).__name__}: {exc}"
            }
    _flush_details(details)

    # run_all contains per-row failures; guard the whole call too so a
    # host-side regression can never sink the details file
    from benchmarks import host_subsystems

    if _budget_left() < 60:
        details["rows"]["host_subsystems"] = _shed_marker("host_subsystems")
    else:
        try:
            details["rows"]["host_subsystems"] = host_subsystems.run_all()
        except Exception as exc:
            details["rows"]["host_subsystems"] = {
                "error": f"{type(exc).__name__}: {exc}"
            }
    _flush_details(details)

    _maybe_emit_headline(details)
    if not _HEADLINE["emitted"]:
        headline = details["rows"].get("allsrc_spf_fattree10k")
        error = (
            headline.get("error")
            if isinstance(headline, dict)
            else "headline device row did not complete in any attempt window"
        )
        print(
            json.dumps(
                {
                    "metric": "allsrc_spf_fattree10k_ms",
                    "value": None,
                    "unit": "ms",
                    "vs_baseline": None,
                    "error": error,
                }
            )
        )


if __name__ == "__main__":
    main()
