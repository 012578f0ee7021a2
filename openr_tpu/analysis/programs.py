"""Program-level invariant auditor: jaxpr contracts for every jit root.

The AST families can only see source text.  This family — the ONLY one
that imports jax — proves properties of the *compiled programs*:

- it discovers every ``jax.jit`` root the AST pass knows about (the
  ``jit_paths`` set: ``ops/`` + ``decision/fleet.py``) plus the jit roots
  in ``device/engine.py`` and every bucket cell of the
  ``DeviceResidencyEngine`` AOT ladder;
- it runs a fixed set of deterministic CPU drivers (ring/grid fleets,
  residency-engine queries, KSP prefetch, protection what-ifs, direct
  kernel exercisers) with every root monkeypatched by a recording
  wrapper, so each root's *real production argument shapes* are captured
  without hand-maintaining spec tables;
- it re-traces each captured (root, spec) to a jaxpr and checks:

  ``program-donation``  every ``donate_argnums`` arg is actually aliased
                        by XLA.  jax matches donated inputs to outputs by
                        exact aval equality and silently DROPS the
                        donation otherwise (a warning at lowering is the
                        only trace) — the bug class that cost the engine
                        ladder its donation for a transposed return.
  ``program-dtype``     no float64 and no weak-type float promotion
                        anywhere in the jaxpr; the relax pipeline is
                        integer min-plus end to end, so floats are
                        allowed only for roots named in
                        ``program_float_allowed`` (loss kernels).
  ``program-callback``  no host callback / debug primitives — one
                        ``io_callback`` turns a resident program into a
                        per-sweep host round-trip.
  ``program-constants`` no closed-over constant above
                        ``program_const_max_bytes`` — embedded arrays
                        re-upload on every compile instead of living in
                        device residency.
  ``program-budget``    total jaxpr primitive count per program vs the
                        checked-in budget file
                        (``openr_tpu/analysis/program_budgets.json``) so
                        graph blowups fail loudly; regenerate with
                        ``--write-budgets`` after reviewing a growth.
  ``program-coverage``  a jit root no driver reached — keeps the driver
                        set honest as kernels are added.

Drivers force ``JAX_PLATFORMS=cpu`` tracing (no accelerator needed);
driver or trace failures raise :class:`AnalysisError` so the CLI exits 2
("broken analyzer"), never silently shrinking coverage.
"""

from __future__ import annotations

import ast
import functools
import importlib
import json
import os
import sys
import warnings
from pathlib import Path
from typing import Any, Callable, Iterator

from .core import (
    AnalysisConfig,
    AnalysisError,
    Reporter,
    SourceFile,
)

BUDGET_FILE = "openr_tpu/analysis/program_budgets.json"

#: extra files (beyond jit_paths) whose module-level jit roots are audited;
#: the residency engine's helper programs donate buffers and must stay
#: aliased just like the ladder cells
EXTRA_ROOT_FILES = ("openr_tpu/device/engine.py",)

#: at most this many distinct captured arg-specs are audited per root
MAX_SPECS_PER_ROOT = 4

_CALLBACK_PRIMITIVES = {
    "io_callback",
    "pure_callback",
    "python_callback",
    "callback",
    "debug_callback",
    "debug_print",
    "infeed",
    "outfeed",
}

_DONATION_WARNING = "Some donated buffers were not usable"


# ---------------------------------------------------------------------------
# Root discovery (AST, shared with the jit family)
# ---------------------------------------------------------------------------


def _root_files(
    files: list[SourceFile], config: AnalysisConfig, root: Path
) -> list[SourceFile]:
    """jit_paths + EXTRA_ROOT_FILES as SourceFiles, parsed from the tree
    regardless of what `targets` the caller passed (program rules always
    audit the whole tree)."""
    by_rel = {sf.rel: sf for sf in files}
    out: dict[str, SourceFile] = {}
    wanted: list[Path] = []
    for p in [*config.jit_paths, *EXTRA_ROOT_FILES]:
        wanted.append(root / p)
    from .core import walk_python_files

    for path in walk_python_files(wanted):
        try:
            rel = path.resolve().relative_to(root.resolve()).as_posix()
        except ValueError:
            rel = path.as_posix()
        if rel in by_rel:
            out[rel] = by_rel[rel]
            continue
        sf = SourceFile.parse(path, root)
        if sf is not None:
            out[rel] = sf
    return list(out.values())


def _discover_roots(root_files: list[SourceFile]):
    """(module, name) -> FuncRecord for every jitted def in the root set."""
    from .jit import _Index

    index = _Index(root_files)
    return {
        rec.key: rec
        for rec in index.funcs.values()
        if rec.is_jitted and not rec.module.startswith("tests")
    }


# ---------------------------------------------------------------------------
# Spec capture: monkeypatch roots, run drivers, record ShapeDtypeStructs
# ---------------------------------------------------------------------------


class _Recorder:
    """Records (args, kwargs) specs for every patched root invocation.

    Array-like leaves (device arrays, tracers, numpy arrays) become
    ShapeDtypeStructs; everything else (static ints/bools/strings/None)
    is kept verbatim so the spec replays through ``root.trace``."""

    def __init__(self) -> None:
        self.specs: dict[tuple[str, str], list[tuple]] = {}
        self._seen: set[tuple[tuple[str, str], str]] = set()

    def _to_spec(self, leaf):
        import jax

        aval = getattr(leaf, "aval", None)
        if aval is not None and hasattr(aval, "shape"):
            return jax.ShapeDtypeStruct(aval.shape, aval.dtype)
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            return jax.ShapeDtypeStruct(shape, dtype)
        return leaf

    def record(self, key: tuple[str, str], args: tuple, kwargs: dict) -> None:
        import jax

        spec = jax.tree_util.tree_map(self._to_spec, (args, dict(kwargs)))
        dedup = (key, str(jax.tree_util.tree_flatten(spec)))
        if dedup in self._seen:
            return
        if len(self.specs.get(key, ())) >= MAX_SPECS_PER_ROOT:
            return
        self._seen.add(dedup)
        self.specs.setdefault(key, []).append(spec)

    def wrap(self, key: tuple[str, str], orig: Callable) -> Callable:
        @functools.wraps(orig)
        def wrapper(*args, **kwargs):
            try:
                self.record(key, args, kwargs)
            except Exception:
                pass  # never let spec capture change driver behavior
            return orig(*args, **kwargs)

        wrapper.__openr_audit_orig__ = orig
        return wrapper


def _patch_roots(roots, recorder: _Recorder):
    """Install recording wrappers over every alias of every root across
    the imported openr_tpu modules.  Function-level ``from .x import f``
    re-resolves per call, but MODULE-level imports bind an alias in the
    importer's namespace — so every module attribute that *is* the root
    object gets patched, not just the defining module's.

    Returns an undo list of (module, attr, original)."""
    undo: list[tuple[Any, str, Any]] = []
    originals: dict[tuple[str, str], Any] = {}
    for (mod_name, fn_name), rec in roots.items():
        try:
            module = importlib.import_module(mod_name)
        except Exception as e:  # pragma: no cover - import errors are fatal
            raise AnalysisError(
                f"program auditor could not import {mod_name}: {e}"
            ) from e
        orig = getattr(module, fn_name, None)
        if orig is None or not callable(orig):
            continue
        originals[(mod_name, fn_name)] = orig
    # patch every alias (same object) in every loaded openr_tpu module
    for key, orig in originals.items():
        wrapper = recorder.wrap(key, orig)
        for mod in list(sys.modules.values()):
            name = getattr(mod, "__name__", "")
            if not name.startswith("openr_tpu"):
                continue
            for attr, val in list(vars(mod).items()):
                if val is orig:
                    undo.append((mod, attr, orig))
                    setattr(mod, attr, wrapper)
    return undo, originals


# ---------------------------------------------------------------------------
# Deterministic drivers
# ---------------------------------------------------------------------------


def _ring_link_state(n: int = 64, metric_fn=None, drop: dict | None = None):
    """64-node circulant ring (d = +-1, +-2): the smallest topology the
    banded kernel accepts, so the fleet warm paths actually engage (the
    ELL fallback ignores warm seeds and would hide those roots)."""
    from ..decision.link_state import LinkState
    from ..types import Adjacency, AdjacencyDatabase

    metric_fn = metric_fn or (lambda i, j: 20)
    drop = drop or {}

    def name(i: int) -> str:
        return f"r{i % n:03d}"

    ls = LinkState()
    for i in range(n):
        me = name(i)
        adjs = [
            Adjacency(
                other_node_name=name(i + d),
                if_name=f"{me}/{name(i + d)}",
                other_if_name=f"{name(i + d)}/{me}",
                metric=metric_fn(i, (i + d) % n),
                next_hop_v6=f"fe80::{i}:{d % 7}",
                next_hop_v4=f"10.0.{i}.{d % 7}",
            )
            for d in (1, -1, 2, -2)
            if d != drop.get(i)
        ]
        ls.update_adjacency_database(
            AdjacencyDatabase(
                this_node_name=me, adjacencies=adjs, node_label=1000 + i
            )
        )
    return ls


def _update_ring_node(
    ls, i: int, n: int = 64, metric_fn=None, drop=None, overloaded=False
):
    from ..types import Adjacency, AdjacencyDatabase

    metric_fn = metric_fn or (lambda i, j: 20)

    def name(j: int) -> str:
        return f"r{j % n:03d}"

    me = name(i)
    adjs = [
        Adjacency(
            other_node_name=name(i + d),
            if_name=f"{me}/{name(i + d)}",
            other_if_name=f"{name(i + d)}/{me}",
            metric=metric_fn(i, (i + d) % n),
            next_hop_v6=f"fe80::{i}:{d % 7}",
            next_hop_v4=f"10.0.{i}.{d % 7}",
        )
        for d in (1, -1, 2, -2)
        if d != drop
    ]
    ls.update_adjacency_database(
        AdjacencyDatabase(
            this_node_name=me,
            adjacencies=adjs,
            is_overloaded=overloaded,
            node_label=1000 + i,
        )
    )


def _drive_engine(state: dict) -> None:
    """Residency-engine ladder: small + full program shapes over two S
    buckets, plus an incremental masked-write sync.  The engines are kept
    in `state` so the ladder audit can read their _program_specs."""
    import numpy as np  # noqa: F401  (kept: drivers stay numpy-only)

    from ..decision.csr import CsrTopology
    from ..device.engine import DeviceResidencyEngine

    ls = _ring_link_state()
    engines = []
    for small_threshold in (1 << 21, 0):
        csr = CsrTopology.from_link_state(ls)
        eng = DeviceResidencyEngine(small_threshold=small_threshold)
        eng.spf_results(csr, ["r000"])  # S bucket 1
        eng.spf_results(csr, ["r001", "r002", "r003"])  # S bucket 8
        engines.append(eng)
    # attribute flaps -> incremental sync: a metric write (i32 masked
    # write) and an overload flip (bool masked write)
    _update_ring_node(ls, 5, metric_fn=lambda i, j: 35)
    csr.refresh(ls)
    eng.spf_results(csr, ["r004"])
    _update_ring_node(ls, 7, overloaded=True)
    csr.refresh(ls)
    eng.spf_results(csr, ["r006"])
    state["engines"] = engines


def _drive_rewire(state: dict) -> None:
    """Edge-set rewire rung: retire a ring link and then re-add it so the
    CSR slot freelist recycles the retired slots and the engine's
    masked-ROW writers (`_masked_write_rows_i32` / `_masked_write_rows_bool`
    for the changed ELL rows, plus the element writers for edge columns)
    record production arg shapes.  The asserts keep the driver honest: a
    demotion to restage would leave the row-writer roots spec-less and
    fail program-coverage with a much less actionable finding."""
    from ..decision.csr import CsrTopology
    from ..device.engine import DeviceResidencyEngine

    ls = _ring_link_state()
    csr = CsrTopology.from_link_state(ls)
    engine = DeviceResidencyEngine()
    engine.spf_results(csr, ["r000"])
    # link DOWN: bidirectional adjacency broken -> edge slots retire
    _update_ring_node(ls, 20, drop=1)
    assert csr.refresh(ls), "ring link drop must ride the rewire path"
    engine.spf_results(csr, ["r001"])
    # link back UP: the freelist hands the retired slots back
    _update_ring_node(ls, 20)
    assert csr.refresh(ls), "ring link re-add must ride the rewire path"
    engine.spf_results(csr, ["r002"])
    c = engine.get_counters()
    assert c["device.engine.full_restages"] == 1, c
    assert c["device.engine.rewires"] == 2, c
    assert c["device.engine.rewire_rows"] > 0, c


def _drive_fleet_ring(state: dict) -> None:
    """Fleet product on the banded ring: cold, warm-improve and warm-down
    rebuilds (the three reduced_all_sources entry modes)."""
    from ..decision.fleet import FleetViewCache

    dests = ["r000", "r031", "r063"]
    cache = FleetViewCache()
    ls = _ring_link_state()
    v1 = cache.view(ls, dests)
    assert v1 is not None and v1.converged
    state["fleet_view"] = v1
    # improvement-only change -> warm "improve" gate
    _update_ring_node(ls, 5, metric_fn=lambda i, j: 15)
    v2 = cache.view(ls, dests)
    assert v2 is not None and v2.converged
    # link DOWN -> certified affected-set warm start
    _update_ring_node(ls, 10, drop=1)
    v3 = cache.view(ls, dests)
    assert v3 is not None and v3.converged


def _drive_delta(state: dict) -> None:
    """Incremental delta rung (ops.delta): frontier certification +
    frontier-sized relax on a metric worsening, then an adjacency drop so
    the changed out-rows re-encode (delta_rows_bitmap) runs too.  The
    asserts keep the driver honest: a silent fallback to the full path
    would leave the delta roots spec-less and fail the audit later with
    a much less actionable finding."""
    from ..decision.fleet import FleetViewCache
    from ..device.engine import DeviceResidencyEngine

    ls = _ring_link_state()
    # full-width destination set: the frontier bound is relative to P
    # (2 * cols <= P), so a handful of columns cannot host a delta
    dests = [f"r{i:03d}" for i in range(64)]
    engine = DeviceResidencyEngine()
    cache = FleetViewCache(delta=True)
    v1 = cache.view(ls, dests, engine=engine)
    assert v1 is not None and v1.converged
    # metric worsening of ONE edge -> delta_frontier + delta_relax
    # (worsening a node's whole adjacency set drops every support of its
    # row and the full-width frontier correctly falls back instead)
    _update_ring_node(ls, 5, metric_fn=lambda i, j: 90 if j == 6 else 20)
    v2 = cache.view(ls, dests, engine=engine)
    assert v2 is not None and v2.converged and v2.warm_mode == "delta"
    # adjacency drop -> out-slot re-rank -> delta_rows_bitmap
    _update_ring_node(ls, 40, drop=1)
    v3 = cache.view(ls, dests, engine=engine)
    assert v3 is not None and v3.converged and v3.warm_mode == "delta"


def _drive_blocked(state: dict) -> None:
    """Node-axis sharding rung (parallel.blocked): force the blocked
    APSP through the fleet dispatch so all three phase kernels, the
    destination-column extract and the bitmap root record specs.  The
    threshold is dropped instead of env-forcing OPENR_NODE_SHARD so the
    audit run does not leak environment into other drivers; the asserts
    keep the driver honest — a silent fallback to the fused product
    would leave the blocked roots spec-less and fail the audit later
    with a much less actionable finding.

    Both pipeline settings run (pinned `pipeline_mode`, same no-leak
    discipline as the threshold): the default lookahead closure must
    record the fused `blocked_round_pipelined` root — donation has to
    survive the double-buffered panel carry — and the pinned-off run
    keeps the bulk-synchronous `blocked_outer` root audit-visible."""
    from ..decision.fleet import FleetViewCache
    from ..device.engine import DeviceResidencyEngine

    ls = _ring_link_state()
    engine = DeviceResidencyEngine()
    engine.blocked.node_shard_threshold = 0  # every N engages the rung
    cache = FleetViewCache()
    view = cache.view(ls, ["r000", "r031", "r063"], engine=engine)
    assert view is not None and view.converged and view.node_sharded
    assert engine.blocked.counters["mesh.blocked.products"] == 1
    assert engine.blocked.counters["mesh.blocked.fallbacks"] == 0
    # auto-on pipelining at n=64/tile=16 -> 4 rounds, 3 prefetches; a
    # demotion here would silently audit the wrong loop
    assert (
        engine.blocked.counters["mesh.blocked.pipeline_prefetch_issues"] > 0
    )
    assert engine.blocked.counters["mesh.blocked.pipeline_fallbacks"] == 0

    engine2 = DeviceResidencyEngine()
    engine2.blocked.node_shard_threshold = 0
    engine2.blocked.pipeline_mode = "0"  # pinned off: bulk loop
    view2 = FleetViewCache().view(ls, ["r000", "r031", "r063"], engine=engine2)
    assert view2 is not None and view2.converged and view2.node_sharded
    assert engine2.blocked.counters["mesh.blocked.products"] == 1
    assert (
        engine2.blocked.counters["mesh.blocked.pipeline_prefetch_issues"] == 0
    )


def _drive_fleet_grid_ell(state: dict) -> None:
    """Fleet product on a grid: no banded structure, so the ELL fallback
    and its fixed-sweep kernels run."""
    from ..decision.fleet import FleetViewCache
    from ..decision.link_state import LinkState
    from ..utils.topo import grid_topology

    ls = LinkState()
    for db in grid_topology(4):
        ls.update_adjacency_database(db)
    nodes = sorted(ls.node_names)
    cache = FleetViewCache()
    view = cache.view(ls, [nodes[0], nodes[-1]])
    assert view is not None and view.converged


def _drive_allsources_legacy(state: dict) -> None:
    """The non-default reduced_all_sources paths: adaptive two-dispatch
    (fused=False) and the fixed-sweep fused product."""
    import numpy as np

    from ..ops import allsources as asrc

    view = state["fleet_view"]
    csr = view.csr
    dest_ids = np.asarray(
        [view._node_id[d] for d in view.dest_names], dtype=np.int32
    )
    runner = view._runner
    for kw in ({"fused": False}, {"fused": True, "n_sweeps": 96}):
        dist, bitmap, ok = asrc.reduced_all_sources(
            dest_ids,
            runner,
            view._out,
            csr.edge_metric,
            csr.edge_up,
            csr.node_overloaded,
            **kw,
        )
        assert ok
    # standalone early-exit kernel (the fused product inlines its own
    # while-loop, so this root only runs via the runner's progressive mode)
    _dist, _dag, ok = runner.run_once(dest_ids, 8, progressive=True)
    assert bool(ok)


def _drive_ksp(state: dict) -> None:
    """2-shortest-paths: the device-backend prefetch (masked batched SPF)
    and the fused KSP2 runner.  The fused runner needs a spare padding
    edge (n_edges < E_cap), which the 64-ring's exactly-full edge table
    does not leave — a 65-ring pads up to the next capacity bucket."""
    import numpy as np

    from ..decision.fleet import FleetViewCache
    from ..decision.spf_solver import DeviceSpfBackend
    from ..ops.ksp import FusedKsp2Runner
    from ..ops.protection import build_reverse_edge_ids

    ls = _ring_link_state()
    backend = DeviceSpfBackend(min_device_nodes=1, min_device_sources=1)
    backend.prefetch_kth_paths(ls, "r000", ["r005", "r010"])

    ls65 = _ring_link_state(65)
    view = FleetViewCache().view(ls65, ["r000", "r031"])
    assert view is not None and view.converged
    csr = view.csr
    e = csr.n_edges
    rev = np.asarray(build_reverse_edge_ids(csr.edge_src[:e], csr.edge_dst[:e]))
    fk = FusedKsp2Runner(
        view._runner,
        csr.edge_dst,
        e,
        len(csr.node_names),
        rev,
        [csr.edge_metric],
    )
    res = fk.run(
        csr.node_id["r000"],
        np.asarray(
            [csr.node_id["r005"], csr.node_id["r010"]], dtype=np.int32
        ),
    )
    assert len(res) == 1


def _drive_protection(state: dict) -> None:
    """SRLG what-if + TI-LFA reports (protection kernels and the legacy
    batched_sssp/sp_dag_mask relax they reuse)."""
    from ..decision.link_state import LinkState
    from ..decision.protection_api import ti_lfa, what_if
    from ..utils.topo import ring_topology

    ls = LinkState()
    for db in ring_topology(4):
        ls.update_adjacency_database(db)
    rows = what_if(ls, [[("r0", "r1")]])
    assert rows and rows[0]["unknown_links"] == []
    report = ti_lfa(ls, "r0")
    assert report["node"] == "r0"


def _drive_forward_direct(state: dict) -> None:
    """Direct exercisers for forward kernels not on the default dispatch
    paths: the host-staged CSR fallback (packed + full) and the legacy
    one-call forwards."""
    import numpy as np

    from ..decision.csr import CsrTopology
    from ..ops import sssp as ops

    ls = _ring_link_state()
    csr = CsrTopology.from_link_state(ls)
    # host-staged degradation-ladder path (spf_forward_full_packed)
    csr.spf_from(["r000", "r007"])
    src = np.asarray([csr.node_id["r000"]], dtype=np.int32)
    n_words = max(1, -(-csr.max_out_slots // 32))
    # bulk (non-packed) host-staged shape.  These exercisers ARE the
    # audit harness: they dispatch kernels directly, on purpose, to put a
    # spec on roots no production path reaches.
    # openr: disable=jit-unbucketed-dispatch
    ops.spf_forward_full(
        src,
        csr.ell,
        csr.edge_src,
        csr.edge_dst,
        csr.edge_metric,
        csr.edge_up,
        csr.node_overloaded,
        csr.out_slot,
        n_words,
        n_sweeps=96,
    )
    # legacy one-call forwards (kept exported for conformance + mesh)
    # openr: disable=jit-unbucketed-dispatch
    ops.spf_forward(
        src,
        csr.edge_src,
        csr.edge_dst,
        csr.edge_metric,
        csr.edge_up,
        csr.node_overloaded,
    )
    # openr: disable=jit-unbucketed-dispatch
    ops.spf_forward_ell(
        src,
        csr.ell,
        csr.edge_src,
        csr.edge_dst,
        csr.edge_metric,
        csr.edge_up,
        csr.node_overloaded,
    )


def _drive_te(state: dict) -> None:
    """Differentiable-TE soft kernels (the tree's only float jit roots)
    plus one exact-gate round trip: soft distances must anneal toward
    the exact solver's, the descent step must move metrics, and the
    rounded candidate must score through the uint32 product."""
    import numpy as np

    from ..te import TeOptimizer, TeProblem
    from ..te import soft
    from ..te.exact import INF32

    # 8-node ring with one chord: small, asymmetric, cyclic
    n = 8
    links = np.array([[i, (i + 1) % n] for i in range(n)] + [[0, 4]])
    mets = np.vstack([np.tile([1, 1], (n, 1)), [[2, 2]]])
    from benchmarks.synthetic import Topology

    topo = Topology.from_links("te_audit", n, links, mets)
    dests = np.array([0, 3], dtype=np.int32)
    demand = np.zeros((topo.node_capacity, 2), dtype=np.float32)
    demand[1:n] = 1.0
    demand[3, 1] = 0.0
    problem = TeProblem.from_topology(topo, dests, demand, metric_hi=8)

    import jax.numpy as jnp

    args = (
        jnp.asarray(problem.edge_src),
        jnp.asarray(problem.edge_dst),
        jnp.asarray(problem.edge_metric, dtype=jnp.float32),
        jnp.asarray(problem.edge_up),
        jnp.asarray(problem.node_overloaded),
        jnp.asarray(problem.dest_ids),
    )
    # audit-harness direct dispatch, same rationale as _drive_forward_direct
    dist = np.asarray(
        # openr: disable=jit-unbucketed-dispatch
        soft.soft_sssp(*args, np.float32(0.05), n_sweeps=8)
    )
    opt = TeOptimizer()
    ev = opt._evaluator(problem)
    exact = ev.distances(problem.edge_metric)
    finite = exact[:n] < INF32
    assert np.abs(dist[:n][finite] - exact[:n][finite]).max() < 0.5

    # one descent step + exact gate through the optimizer front-end
    # (traces soft_objective_value, te_descent_step, and the te_exact
    # dispatch path)
    res = opt.optimize(
        problem, steps=2, round_trips=1, n_sweeps=8, flow_sweeps=8
    )
    assert res.metrics.dtype == np.int32
    assert opt.get_counters()["te.steps"] == 2
    # openr: disable=jit-unbucketed-dispatch
    _ = soft.soft_objective_value(
        jnp.asarray(problem.edge_metric, dtype=jnp.float32),
        args[0], args[1], args[3], args[4], args[5],
        jnp.asarray(problem.demand, dtype=jnp.float32),
        jnp.asarray(problem.capacity, dtype=jnp.float32),
        np.float32(0.1), np.float32(0.1), n_sweeps=8, flow_sweeps=8,
    )


def _drive_snapshot(state: dict) -> None:
    """Engine-snapshot restore rungs over the banded ring: take a
    checkpoint, drift the donor mirror (replay rung: the engine's
    incremental ladder runs under restore), then install the serialized
    artifact into a fresh engine over a content-identical fresh mirror
    (install rung + manifest prewarm — the AOT lowering path records
    its specs with no example arrays), and finally demote against a
    drifted foreign mirror (cold rung: the ordinary restage).  The
    asserts keep the driver honest about which rung each step took."""
    from ..decision.csr import CsrTopology
    from ..device.engine import DeviceResidencyEngine
    from ..snapshot import EngineSnapshot

    ls = _ring_link_state()
    csr = CsrTopology.from_link_state(ls)
    donor = DeviceResidencyEngine()
    donor.spf_results(csr, ["r000"])  # compile the manifest's ladder key
    snap = EngineSnapshot.take(donor, csr)
    blob = snap.to_bytes()
    # donor drift -> replay rung (masked-write incremental under restore)
    _update_ring_node(ls, 9, metric_fn=lambda i, j: 31)
    assert csr.refresh(ls), "attribute flap must stay in place"
    assert snap.restore(donor, csr) == "replay"
    donor.spf_results(csr, ["r001"])
    # fresh replica, content-identical mirror -> install rung + prewarm
    fresh_ls = _ring_link_state()
    _update_ring_node(fresh_ls, 9, metric_fn=lambda i, j: 31)
    fresh_csr = CsrTopology.from_link_state(fresh_ls)
    joiner = DeviceResidencyEngine()
    warm = EngineSnapshot.take(donor, csr)
    assert warm.restore(joiner, fresh_csr) == "install"
    joiner.spf_results(fresh_csr, ["r002"])
    # stale serialized artifact vs a drifted foreign mirror -> cold rung
    drifted_ls = _ring_link_state()
    _update_ring_node(drifted_ls, 3, metric_fn=lambda i, j: 29)
    drifted_csr = CsrTopology.from_link_state(drifted_ls)
    cold_eng = DeviceResidencyEngine()
    assert EngineSnapshot.from_bytes(blob).restore(cold_eng, drifted_csr) == (
        "cold"
    )
    cold_eng.spf_results(drifted_csr, ["r003"])


DRIVERS: tuple[tuple[str, Callable[[dict], None]], ...] = (
    ("engine", _drive_engine),
    ("rewire", _drive_rewire),
    ("fleet_ring", _drive_fleet_ring),
    ("delta", _drive_delta),
    ("blocked", _drive_blocked),
    ("fleet_grid_ell", _drive_fleet_grid_ell),
    ("allsources_legacy", _drive_allsources_legacy),
    ("ksp", _drive_ksp),
    ("protection", _drive_protection),
    ("forward_direct", _drive_forward_direct),
    ("te", _drive_te),
    ("snapshot", _drive_snapshot),
)


def _run_drivers(roots, recorder: _Recorder) -> dict:
    state: dict = {}
    undo, originals = _patch_roots(roots, recorder)
    try:
        for name, driver in DRIVERS:
            try:
                driver(state)
            except Exception as e:
                raise AnalysisError(
                    f"program auditor driver '{name}' failed: "
                    f"{type(e).__name__}: {e}"
                ) from e
    finally:
        for mod, attr, orig in undo:
            setattr(mod, attr, orig)
    state["originals"] = originals
    return state


# ---------------------------------------------------------------------------
# Jaxpr checks
# ---------------------------------------------------------------------------


def _params_jaxprs(value) -> Iterator:
    """Jaxprs held in one eqn param value (a Jaxpr, a ClosedJaxpr, or a
    tuple/list of them, as cond branches are)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    if isinstance(value, Jaxpr):
        yield value
    elif isinstance(value, ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _params_jaxprs(v)


def _all_jaxprs(jaxpr) -> Iterator:
    yield jaxpr
    for eqn in jaxpr.eqns:
        for value in eqn.params.values():
            for sub in _params_jaxprs(value):
                yield from _all_jaxprs(sub)


def _count_eqns(jaxpr) -> int:
    return sum(len(j.eqns) for j in _all_jaxprs(jaxpr))


def _iter_avals(jaxpr) -> Iterator:
    for j in _all_jaxprs(jaxpr):
        seen = set()
        for v in [
            *j.constvars,
            *j.invars,
            *j.outvars,
            *(v for e in j.eqns for v in [*e.invars, *e.outvars]),
        ]:
            if id(v) in seen:
                continue
            seen.add(id(v))
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "dtype"):
                yield aval


class _ProgramAudit:
    """Shared per-program checks; emission goes to the Reporter against a
    stable source location (the root's def line, or _forward_body for
    ladder cells)."""

    def __init__(
        self, reporter: Reporter, config: AnalysisConfig, root: Path
    ) -> None:
        self.reporter = reporter
        self.config = config
        self.root = root
        self.op_counts: dict[str, int] = {}
        self.primitive_counts: dict[str, dict[str, int]] = {}

    # -- donation -----------------------------------------------------------

    def check_donation(
        self, sf, node, label: str, fn, specs, donate: tuple
    ) -> None:
        import jax

        if not donate:
            return
        try:
            with warnings.catch_warnings(record=True) as caught:
                warnings.simplefilter("always")
                lowered = jax.jit(fn, donate_argnums=donate).lower(*specs)
                text = lowered.as_text()
        except Exception as e:
            raise AnalysisError(
                f"program auditor could not lower {label}: {e}"
            ) from e
        dropped = [
            str(w.message)
            for w in caught
            if _DONATION_WARNING in str(w.message)
        ]
        if dropped or "tf.aliasing_output" not in text:
            detail = dropped[0].splitlines()[0] if dropped else (
                "no input/output aliasing in the lowered module"
            )
        else:
            return
        self.reporter.emit(
            sf,
            "program-donation",
            node,
            f"{label}: donate_argnums={tuple(donate)} is declared but XLA "
            f"drops the donation ({detail}); make the donated input's aval "
            "match an output exactly (same shape AND dtype, no transpose) "
            "or remove the donation request",
        )

    # -- jaxpr body ---------------------------------------------------------

    def check_jaxpr(self, sf, node, label: str, fn_name: str, closed) -> None:
        import numpy as np

        jaxpr = closed.jaxpr
        # dtype discipline
        float_ok = fn_name in self.config.program_float_allowed
        flagged_dtypes: set[str] = set()
        for aval in _iter_avals(jaxpr):
            dt = np.dtype(aval.dtype)
            weak = bool(getattr(aval, "weak_type", False))
            bad = (
                dt == np.float64
                or (dt.kind == "f" and weak)
                or (dt.kind == "f" and not float_ok)
            )
            if bad and dt.name not in flagged_dtypes:
                flagged_dtypes.add(dt.name)
                kind = (
                    "float64"
                    if dt == np.float64
                    else f"weak-type {dt.name}"
                    if weak
                    else dt.name
                )
                self.reporter.emit(
                    sf,
                    "program-dtype",
                    node,
                    f"{label}: {kind} value inside the traced program; the "
                    "relax pipeline is integer min-plus end to end — chase "
                    "the promotion (a Python float constant or np.float64 "
                    "default) or whitelist the root in "
                    "program_float_allowed",
                )
        # host callbacks
        prim_counts: dict[str, int] = {}
        for j in _all_jaxprs(jaxpr):
            for eqn in j.eqns:
                pname = eqn.primitive.name
                prim_counts[pname] = prim_counts.get(pname, 0) + 1
                if pname in _CALLBACK_PRIMITIVES or "callback" in pname:
                    self.reporter.emit(
                        sf,
                        "program-callback",
                        node,
                        f"{label}: host callback primitive '{pname}' in "
                        "the compiled program — every invocation is a "
                        "device->host round-trip inside the graph",
                    )
        # large closed-over constants
        limit = self.config.program_const_max_bytes
        for const in closed.consts:
            nbytes = getattr(const, "nbytes", None)
            if nbytes is None:
                arr = np.asarray(const)
                nbytes = arr.nbytes
            if nbytes > limit:
                shape = getattr(const, "shape", ())
                dtype = getattr(const, "dtype", type(const).__name__)
                self.reporter.emit(
                    sf,
                    "program-constants",
                    node,
                    f"{label}: closed-over constant {dtype}{list(shape)} "
                    f"({nbytes} bytes > {limit}) is embedded in the "
                    "program and re-uploaded per compile; pass it as an "
                    "argument so it lives in device residency",
                )
        # op-count bookkeeping (max across specs of the same program name)
        n = _count_eqns(jaxpr)
        if n > self.op_counts.get(label, -1):
            self.op_counts[label] = n
            self.primitive_counts[label] = prim_counts


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def check(
    files: list[SourceFile],
    reporter: Reporter,
    config: AnalysisConfig,
    root: Path,
    write_budgets: bool = False,
) -> dict[str, int]:
    """Run the program auditor; returns the measured op counts (the CLI
    uses them for --write-budgets)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    try:
        import jax  # noqa: F401
    except Exception as e:  # pragma: no cover - jax is baked into the image
        raise AnalysisError(
            f"program rules need jax to trace programs: {e}"
        ) from e
    # roots reached only *while tracing* other roots (batched_sssp inside
    # spf_forward, ...) never run when the outer executable is already
    # cached — so a warm process (pytest after device tests) would lose
    # their specs and report phantom coverage gaps.  Start cold, always.
    jax.clear_caches()

    root_files = _root_files(files, config, root)
    roots = _discover_roots(root_files)
    if not roots:
        raise AnalysisError(
            "program auditor found no jit roots under "
            f"jit_paths={config.jit_paths}"
        )

    recorder = _Recorder()
    state = _run_drivers(roots, recorder)
    originals = state["originals"]

    audit = _ProgramAudit(reporter, config, root)

    # -- jit roots ----------------------------------------------------------
    for key, rec in sorted(roots.items()):
        mod_name, fn_name = key
        specs = recorder.specs.get(key)
        if not specs:
            if key in originals:
                reporter.emit(
                    rec.sf,
                    "program-coverage",
                    rec.node,
                    f"jit root {mod_name}.{fn_name} was never traced by "
                    "the program auditor's drivers; add a driver (or an "
                    "exerciser to _drive_forward_direct) in "
                    "openr_tpu/analysis/programs.py",
                )
            continue
        orig = originals[key]
        label = f"{mod_name}.{fn_name}"
        for args, kwargs in specs:
            try:
                traced = orig.trace(*args, **kwargs)
            except Exception as e:
                raise AnalysisError(
                    f"program auditor could not trace {label} with a "
                    f"captured spec: {type(e).__name__}: {e}"
                ) from e
            audit.check_jaxpr(rec.sf, rec.node, label, fn_name, traced.jaxpr)

    # -- residency-engine ladder cells --------------------------------------
    engine_sf, engine_node = _engine_location(root_files)
    for eng in state.get("engines", ()):
        for cell_key, (fn, specs, donate) in eng._program_specs.items():
            _topo, s_bucket, _n_words, _sweeps, small, use_metric = cell_key
            label = (
                "device.engine._forward_body"
                f"[s{s_bucket},{'packed' if small else 'full'},"
                f"{'metric' if use_metric else 'unit'}]"
            )
            audit.check_donation(
                engine_sf, engine_node, label, fn, specs, donate
            )
            try:
                traced = jax.jit(fn).trace(*specs)
            except Exception as e:
                raise AnalysisError(
                    f"program auditor could not trace ladder cell "
                    f"{label}: {e}"
                ) from e
            audit.check_jaxpr(
                engine_sf, engine_node, label, "_forward_body", traced.jaxpr
            )
        if not eng._program_specs:
            raise AnalysisError(
                "engine driver compiled no ladder programs; the audit "
                "would be vacuous"
            )

    # -- op-count budgets ---------------------------------------------------
    budget_path = root / BUDGET_FILE
    if write_budgets:
        budget_path.write_text(
            json.dumps(dict(sorted(audit.op_counts.items())), indent=2)
            + "\n",
            encoding="utf-8",
        )
    else:
        budgets = _load_budgets(budget_path)
        for label in sorted(audit.op_counts):
            count = audit.op_counts[label]
            sf, node = _budget_location(
                label, roots, engine_sf, engine_node
            )
            if label not in budgets:
                reporter.emit(
                    sf,
                    "program-budget",
                    node,
                    f"{label}: no op-count budget entry ({count} "
                    "primitives measured); run `python -m "
                    "openr_tpu.analysis --programs --write-budgets` and "
                    "commit the updated budget file",
                )
            elif count > budgets[label]:
                reporter.emit(
                    sf,
                    "program-budget",
                    node,
                    f"{label}: jaxpr grew to {count} primitives (budget "
                    f"{budgets[label]}); if the growth is intentional, "
                    "regenerate with --write-budgets and justify it in "
                    "the PR",
                )
    return audit.op_counts


def _load_budgets(path: Path) -> dict[str, int]:
    if not path.is_file():
        return {}
    try:
        data = json.loads(path.read_text(encoding="utf-8"))
    except (OSError, ValueError) as e:
        raise AnalysisError(f"unreadable budget file {path}: {e}") from e
    if not isinstance(data, dict):
        raise AnalysisError(f"budget file {path} must be a JSON object")
    return {str(k): int(v) for k, v in data.items()}


def _engine_location(root_files: list[SourceFile]):
    for sf in root_files:
        if sf.rel.endswith("device/engine.py"):
            for node in ast.walk(sf.tree):
                if (
                    isinstance(node, ast.FunctionDef)
                    and node.name == "_forward_body"
                ):
                    return sf, node
            return sf, (1, 0)
    raise AnalysisError("device/engine.py not found for the ladder audit")


def _budget_location(label, roots, engine_sf, engine_node):
    if label.startswith("device.engine."):
        return engine_sf, engine_node
    mod, _, fn = label.rpartition(".")
    rec = roots.get((mod, fn))
    if rec is not None:
        return rec.sf, rec.node
    return engine_sf, engine_node
