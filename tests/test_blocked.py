"""Blocked min-plus APSP (openr_tpu/parallel/blocked.py) on the virtual
8-device CPU mesh — the node-axis sharding rung.

Covers: per-phase unit parity against a numpy reference, full-closure
parity against scipy's host APSP and against the masked-FW drain oracle,
bit-exact agreement with the unblocked fused product (reduced_all_sources)
on ring / grid / fattree / wan-shaped topologies including the 1-device
degenerate mesh and odd-N padding, the fleet dispatch rung (threshold +
OPENR_NODE_SHARD engagement, graceful fallback on mesh-shape mismatch,
chaos partition mid-run), the make_mesh ValueError contract, the
software-pipelined loop (pipelined-vs-bulk bit-exactness on every
family, chaos fault mid-pipeline demoting to bulk, pipeline_* counter
semantics), and the compile-only async-span evidence that the
lookahead panel all-gathers legally bracket the outer-update while
(parallel.hlo_async on the lowered scheduled module).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from openr_tpu.decision.csr import CsrTopology
from openr_tpu.decision.fleet import FleetViewCache, _reverse_runner, _row_i32
from openr_tpu.decision.link_state import LinkState
from openr_tpu.device.engine import DeviceResidencyEngine
from openr_tpu.ops import allsources as asrc
from openr_tpu.parallel import blocked as blk
from openr_tpu.utils.topo import (
    fat_tree_topology,
    grid_topology,
    ring_topology,
)

INF = 1 << 30


@pytest.fixture(scope="module")
def eight_cpu_devices():
    devices = jax.devices("cpu")
    if len(devices) < 8:
        pytest.skip("needs xla_force_host_platform_device_count=8")
    return devices[:8]


def _overload(dbs, name):
    """Mark one node drained (is_overloaded) in a topo-builder output."""
    for db in dbs:
        if db.this_node_name == name:
            db.is_overloaded = True
            return dbs
    raise AssertionError(f"no node {name!r} in fixture")


def _csr(dbs) -> CsrTopology:
    ls = LinkState()
    for db in dbs:
        ls.update_adjacency_database(db)
    return CsrTopology.from_link_state(ls)


def _dense(csr) -> np.ndarray:
    """[N, N] int64 usable-edge adjacency (min over parallel edges)."""
    n = int(csr.n_nodes)
    d = np.full((n, n), INF, dtype=np.int64)
    np.fill_diagonal(d, 0)
    e = int(csr.n_edges)
    src = np.asarray(csr.edge_src[:e])
    dst = np.asarray(csr.edge_dst[:e])
    met = np.asarray(csr.edge_metric[:e], dtype=np.int64)
    up = np.asarray(csr.edge_up[:e], dtype=bool)
    for s, t, w, u in zip(src, dst, met, up):
        if u and 0 <= s < n and 0 <= t < n and s != t:
            d[s, t] = min(d[s, t], w)
    return d


def _masked_fw(d: np.ndarray, ov: np.ndarray) -> np.ndarray:
    """Host drain oracle: FW with overloaded nodes excluded as
    intermediates (endpoints stay valid) — the relax-kernel rule
    'blocked as transit unless its distance is 0' for positive metrics."""
    d = d.copy()
    for k in range(d.shape[0]):
        if ov[k]:
            continue
        d = np.minimum(d, np.minimum(d[:, k : k + 1] + d[k : k + 1, :], INF))
    return d


def _out_ell(topo):
    return asrc.build_out_ell(
        topo.edge_src,
        topo.edge_dst,
        int(topo.n_edges),
        int(topo.n_nodes),
        out_slot=getattr(topo, "out_slot", None),
    )


def _blocked_full(csr, mesh, tile) -> np.ndarray:
    """[N, N] int64 closure through the engine's staging + kernels."""
    eng = blk.BlockedApspEngine(tile=tile, mesh=mesh)
    n = int(csr.n_nodes)
    dist, _, ok = eng.fleet_product(
        csr, np.arange(n, dtype=np.int32), _out_ell(csr)
    )
    assert ok
    return np.asarray(jax.device_get(dist)).astype(np.int64)


def _fused_product(topo, dest_ids):
    """(dist [N, P] int32-normalized, bitmap [N, P, W]) via the unblocked
    dest-sharded fused product — the bit-exact reference the rung must
    match.  `topo` is a CsrTopology or a benchmarks.synthetic.Topology
    (same array contract)."""
    from benchmarks import synthetic

    if isinstance(topo, CsrTopology):
        runner = _reverse_runner(topo)
    else:
        runner = synthetic.reversed_topology(topo).runner
    out = _out_ell(topo)
    maps = (
        asrc.build_epilogue_maps(runner.bg, out)
        if runner.bg is not None
        else None
    )
    dist, bitmap, ok = asrc.reduced_all_sources(
        np.asarray(dest_ids, dtype=np.int32),
        runner,
        out,
        topo.edge_metric,
        topo.edge_up,
        topo.node_overloaded,
        maps=maps,
    )
    assert ok
    n = int(topo.n_nodes)
    dist = _row_i32(np.asarray(jax.device_get(dist)))[:n]
    bitmap = np.asarray(jax.device_get(bitmap))[:n]
    return dist, bitmap


def _blocked_product(topo, dest_ids, mesh, tile=None):
    eng = blk.BlockedApspEngine(tile=tile, mesh=mesh)
    dist, bitmap, ok = eng.fleet_product(
        topo, np.asarray(dest_ids, dtype=np.int32), _out_ell(topo)
    )
    assert ok
    return (
        np.asarray(jax.device_get(dist)),
        np.asarray(jax.device_get(bitmap)),
        eng,
    )


class TestMeshValidation:
    def test_make_mesh_indivisible_raises_valueerror(self, eight_cpu_devices):
        from openr_tpu.parallel.mesh import make_mesh

        with pytest.raises(ValueError, match=r"8 devices.*batch axis of\s*3"):
            make_mesh(eight_cpu_devices, batch_axis=3)
        with pytest.raises(ValueError):
            make_mesh(eight_cpu_devices, batch_axis=0)
        # divisible request still builds
        mesh = make_mesh(eight_cpu_devices, batch_axis=4)
        assert dict(mesh.shape) == {"batch": 4, "node": 2}

    def test_make_blocked_mesh_shapes_and_errors(self, eight_cpu_devices):
        mesh = blk.make_blocked_mesh(eight_cpu_devices)
        assert dict(mesh.shape) == {"batch": 1, "row": 2, "col": 4}
        mesh2 = blk.make_blocked_mesh(eight_cpu_devices, batch=2)
        assert dict(mesh2.shape) == {"batch": 2, "row": 2, "col": 2}
        with pytest.raises(ValueError, match=r"rows=7 x cols=3 != 8"):
            blk.make_blocked_mesh(eight_cpu_devices, rows=7, cols=3)
        with pytest.raises(ValueError, match=r"batch axis\s*of 3"):
            blk.make_blocked_mesh(eight_cpu_devices, batch=3)
        with pytest.raises(ValueError, match=r"cols=5"):
            blk.make_blocked_mesh(eight_cpu_devices, cols=5)

    def test_tile_must_divide_by_mesh_lanes(self, eight_cpu_devices):
        eng = blk.BlockedApspEngine(
            tile=6, mesh=blk.make_blocked_mesh(eight_cpu_devices)
        )
        with pytest.raises(ValueError, match=r"lcm\(rows=2, cols=4\)"):
            eng.tile_for(64, 2, 4)


class TestPhaseUnits:
    """Each phase kernel against a literal numpy transcription of one
    blocked-FW round, drain mask included."""

    @pytest.mark.parametrize(
        "s,t,b,ks,seed,hi,inf_share,drain_share",
        [
            (1, 3, 4, (1,), 5, 60, 0.3, 0.2),
            # wider tiles, two batch slices, every round k, drain mask
            (2, 3, 16, (0, 1, 2), 0, 1 << 20, 0.1, 0.2),
            # no drain mask
            (1, 4, 8, (2,), 3, 1 << 20, 0.1, 0.0),
        ],
        ids=["t3_b4", "s2_b16_all_k_drained", "t4_b8_no_mask"],
    )
    def test_three_phases_match_numpy_round(
        self, eight_cpu_devices, s, t, b, ks, seed, hi, inf_share, drain_share
    ):
        rng = np.random.default_rng(seed)
        n = t * b
        d_all = rng.integers(1, hi, size=(s, n, n)).astype(np.int64)
        d_all[rng.random((s, n, n)) < inf_share] = INF
        for d in d_all:
            np.fill_diagonal(d, 0)
        ov = rng.random(n) < drain_share
        mesh = blk.make_blocked_mesh(eight_cpu_devices)
        dist4 = jnp.asarray(d_all.astype(np.uint32).reshape(s, t, b, t, b))
        ovd = jnp.asarray(ov)
        for k in ks:
            kk = jnp.int32(k)
            closed = blk.blocked_diag(dist4, ovd, kk, mesh=mesh)
            row_p, col_p = blk.blocked_panels(
                dist4, closed, ovd, kk, mesh=mesh
            )
            dist_new = blk.blocked_outer(
                jnp.array(dist4), row_p, col_p, ovd, kk, mesh=mesh
            )
            got_diag, got_row, got_col, got_d = (
                np.asarray(jax.device_get(x)).astype(np.int64)
                for x in (closed, row_p, col_p, dist_new)
            )
            for si in range(s):
                diag, row_ref, col_ref, out = _numpy_round(
                    d_all[si], ov, k, b
                )
                assert np.array_equal(got_diag[si], diag), (si, k)
                assert np.array_equal(got_row[si].reshape(b, n), row_ref)
                assert np.array_equal(got_col[si].reshape(n, b), col_ref)
                assert np.array_equal(got_d[si].reshape(n, n), out), (si, k)


def _numpy_round(d, ov, k, b):
    """One blocked-FW round k of the [n, n] matrix d, transcribed
    literally: (closed diagonal tile, row panel, col panel, matrix after
    the panel write-back and the masked rank-B outer update)."""
    sl = slice(k * b, (k + 1) * b)

    # phase 1: masked closure of the diagonal tile
    diag = d[sl, sl].copy()
    for m in range(b):
        if ov[k * b + m]:
            continue
        diag = np.minimum(
            diag, np.minimum(diag[:, m : m + 1] + diag[m : m + 1, :], INF)
        )

    # phase 2: panel updates through the closed tile (contractions read
    # the ORIGINAL panels — `closed` is transitively closed, so one
    # application suffices)
    row = d[sl, :].copy()
    col = d[:, sl].copy()
    row_ref, col_ref = row.copy(), col.copy()
    for m in range(b):
        if ov[k * b + m]:
            continue
        row_ref = np.minimum(
            row_ref, np.minimum(diag[:, m : m + 1] + row[m : m + 1, :], INF)
        )
        col_ref = np.minimum(
            col_ref, np.minimum(col[:, m : m + 1] + diag[m : m + 1, :], INF)
        )

    # phase 3: panel write-back + masked rank-B outer update
    ref = d.copy()
    ref[sl, :] = row_ref
    ref[:, sl] = col_ref
    out = ref.copy()
    for m in range(b):
        if ov[k * b + m]:
            continue
        g = k * b + m
        out = np.minimum(
            out, np.minimum(ref[:, g : g + 1] + ref[g : g + 1, :], INF)
        )
    return diag, row_ref, col_ref, out


class TestClosureParity:
    """Full blocked closure vs scipy's host APSP and the drain oracle."""

    def test_seeded_random_graph_matches_scipy(self, eight_cpu_devices):
        import scipy.sparse as sp
        import scipy.sparse.csgraph as csg

        rng = np.random.default_rng(0)
        n = 23  # odd: exercises the padding path (tile 4 -> Np = 24)
        mask = rng.random((n, n)) < 0.25
        np.fill_diagonal(mask, False)
        src, dst = np.nonzero(mask)
        met = rng.integers(1, 50, size=len(src)).astype(np.int32)
        eng = blk.BlockedApspEngine(
            tile=4, mesh=blk.make_blocked_mesh(eight_cpu_devices)
        )
        n_pad = 24
        d0 = eng.dense_dist0(
            n, n_pad, src, dst, met, np.ones(len(src), bool), len(src)
        )
        dist, b = eng.run_apsp(d0[None], np.zeros(n_pad, bool))
        ids = np.arange(n, dtype=np.int32)
        got = np.asarray(
            jax.device_get(
                blk.blocked_extract(
                    dist, ids // b, ids % b, n=n, mesh=eng.mesh()
                )
            )
        ).astype(np.int64)
        g = sp.csr_matrix((met.astype(np.float64), (src, dst)), shape=(n, n))
        ref = csg.shortest_path(g, method="D", directed=True)
        ref = np.where(np.isinf(ref), INF, ref).astype(np.int64)
        assert np.array_equal(got, ref)

    @pytest.mark.parametrize(
        "dbs_fn",
        [
            lambda: ring_topology(17),  # odd N again, via the link state
            lambda: grid_topology(4),
            lambda: fat_tree_topology(2),
        ],
        ids=["ring17", "grid4x4", "fattree"],
    )
    def test_topologies_match_host_oracle(self, eight_cpu_devices, dbs_fn):
        csr = _csr(dbs_fn())
        got = _blocked_full(
            csr, blk.make_blocked_mesh(eight_cpu_devices), tile=4
        )
        n = int(csr.n_nodes)
        ov = np.asarray(csr.node_overloaded[:n], dtype=bool)
        ref = _masked_fw(_dense(csr), ov)
        assert np.array_equal(got, ref)

    def test_drain_semantics_match_oracle(self, eight_cpu_devices):
        """An overloaded node drops out as an intermediate but stays a
        valid endpoint — the grid center going into drain must reroute
        every through-path and keep its own rows/columns finite."""
        csr = _csr(_overload(grid_topology(4), "node-1-1"))
        n = int(csr.n_nodes)
        ov = np.asarray(csr.node_overloaded[:n], dtype=bool)
        assert ov.any(), "fixture lost its overloaded node"
        got = _blocked_full(
            csr, blk.make_blocked_mesh(eight_cpu_devices), tile=4
        )
        ref = _masked_fw(_dense(csr), ov)
        assert np.array_equal(got, ref)
        i = int(np.nonzero(ov)[0][0])
        assert got[i, i] == 0 and (got[i] < INF).sum() > 1


class TestFusedProductParity:
    """Bit-exact agreement with the unblocked fused product (dist after
    the int32 normalization, bitmap verbatim), including the 1-device
    degenerate mesh."""

    @pytest.mark.parametrize(
        "dbs_fn",
        [
            lambda: ring_topology(17),
            lambda: grid_topology(4),
            lambda: fat_tree_topology(2),
            lambda: _overload(grid_topology(4), "node-1-1"),
        ],
        ids=["ring17", "grid4x4", "fattree", "grid-drained"],
    )
    def test_matches_fused_product(self, eight_cpu_devices, dbs_fn):
        csr = _csr(dbs_fn())
        n = int(csr.n_nodes)
        dests = np.asarray(sorted({0, n // 3, n - 1}), dtype=np.int32)
        ref_dist, ref_bitmap = _fused_product(csr, dests)
        got_dist, got_bitmap, _ = _blocked_product(
            csr, dests, blk.make_blocked_mesh(eight_cpu_devices)
        )
        assert np.array_equal(got_dist, ref_dist)
        assert np.array_equal(got_bitmap, ref_bitmap)

    def test_wan_shaped_and_degenerate_mesh(self, eight_cpu_devices):
        """wan-shaped (ring + chords) topology from benchmarks.synthetic:
        the 8-device blocked product, the 1-device degenerate mesh and
        the fused product must all agree bit-exactly."""
        from benchmarks import synthetic

        topo = synthetic.wan(96, chords=2, seed=3)
        rng = np.random.default_rng(4)
        dests = np.sort(
            rng.choice(topo.n_nodes, size=8, replace=False).astype(np.int32)
        )
        ref_dist, ref_bitmap = _fused_product(topo, dests)
        d8, b8, _ = _blocked_product(
            topo, dests, blk.make_blocked_mesh(eight_cpu_devices)
        )
        d1, b1, _ = _blocked_product(
            topo, dests, blk.make_blocked_mesh(eight_cpu_devices[:1])
        )
        assert np.array_equal(d8, ref_dist)
        assert np.array_equal(b8, ref_bitmap)
        assert np.array_equal(d1, d8)
        assert np.array_equal(b1, b8)

    def test_batch_axis_composes(self, eight_cpu_devices):
        """S=2 identical variants over a 2x2x2 mesh: the batch axis must
        stay independent — both slices equal the host closure."""
        csr = _csr(ring_topology(12))
        n = int(csr.n_nodes)
        eng = blk.BlockedApspEngine(
            tile=4, mesh=blk.make_blocked_mesh(eight_cpu_devices, batch=2)
        )
        d0 = eng.dense_dist0(
            n,
            n,
            csr.edge_src,
            csr.edge_dst,
            csr.edge_metric,
            csr.edge_up,
            int(csr.n_edges),
        )
        dist, _ = eng.run_apsp(np.stack([d0, d0]), np.zeros(n, bool))
        full = np.asarray(jax.device_get(dist)).astype(np.int64)
        flat0 = full[0].reshape(n, n)
        flat1 = full[1].reshape(n, n)
        ref = _masked_fw(_dense(csr), np.zeros(n, bool))
        assert np.array_equal(flat0, ref)
        assert np.array_equal(flat0, flat1)


class TestDispatchRung:
    """fleet.py / DeviceResidencyEngine select the blocked rung by
    threshold or OPENR_NODE_SHARD, fall back gracefully, and keep the
    mesh.blocked.* registry pre-seeded."""

    def _ls(self):
        ls = LinkState()
        for db in grid_topology(4):
            ls.update_adjacency_database(db)
        return ls

    def test_counters_preseeded_before_first_dispatch(self):
        eng = DeviceResidencyEngine()
        counters = eng.blocked.get_counters()
        assert set(blk.BLOCKED_COUNTER_KEYS) <= set(counters)
        assert all(v == 0 for v in counters.values())

    def test_threshold_and_env_engagement(self, monkeypatch):
        monkeypatch.delenv("OPENR_NODE_SHARD", raising=False)
        eng = DeviceResidencyEngine()
        assert not eng.blocked.should_engage(64)  # default ceiling 2^15
        eng.blocked.node_shard_threshold = 0
        assert eng.blocked.should_engage(64)
        monkeypatch.setenv("OPENR_NODE_SHARD", "0")
        assert not eng.blocked.should_engage(64)  # forced off
        monkeypatch.setenv("OPENR_NODE_SHARD", "1")
        eng.blocked.node_shard_threshold = 1 << 15
        assert eng.blocked.should_engage(64)  # forced on

    def test_rung_serves_view_and_matches_fused(self, monkeypatch):
        monkeypatch.delenv("OPENR_NODE_SHARD", raising=False)
        monkeypatch.delenv("OPENR_BLOCKED_MESH", raising=False)
        ls = self._ls()
        nodes = sorted(ls.node_names)
        dests = [nodes[0], nodes[5], nodes[-1]]
        engine = DeviceResidencyEngine()
        engine.blocked.node_shard_threshold = 0
        vb = FleetViewCache().view(ls, dests, engine=engine)
        assert vb.converged and vb.node_sharded
        assert engine.blocked.counters["mesh.blocked.products"] == 1
        assert engine.blocked.counters["mesh.blocked.rounds"] > 0
        assert engine.blocked.counters["mesh.blocked.fallbacks"] == 0
        vf = FleetViewCache().view(self._ls(), dests)
        assert vf.converged and not vf.node_sharded
        for node in nodes:
            assert np.array_equal(vb._row(node), vf._row(node))
        assert np.array_equal(
            np.asarray(jax.device_get(vb._bitmap_dev)),
            np.asarray(jax.device_get(vf._bitmap_dev)),
        )

    def test_mesh_mismatch_falls_back_gracefully(self, monkeypatch):
        monkeypatch.delenv("OPENR_NODE_SHARD", raising=False)
        monkeypatch.setenv("OPENR_BLOCKED_MESH", "7x3")  # != 8 devices
        ls = self._ls()
        nodes = sorted(ls.node_names)
        dests = [nodes[0], nodes[-1]]
        engine = DeviceResidencyEngine()
        engine.blocked.node_shard_threshold = 0
        view = FleetViewCache().view(ls, dests, engine=engine)
        assert view.converged and not view.node_sharded
        assert engine.blocked.counters["mesh.blocked.fallbacks"] == 1
        monkeypatch.delenv("OPENR_BLOCKED_MESH")
        vf = FleetViewCache().view(self._ls(), dests)
        for node in nodes:
            assert np.array_equal(view._row(node), vf._row(node))

    def test_chaos_partition_mid_run_falls_back(self, monkeypatch):
        """Partition-during-blocked-run seam: a chaos fault injected at
        the per-round gate (engine:blocked_round) aborts the blocked
        closure mid-flight; the fleet rung must absorb it — fallback
        counter bumped, view served bit-exactly by the fused product."""
        from types import SimpleNamespace

        from openr_tpu.chaos.chaos import ChaosSpfBackend

        monkeypatch.delenv("OPENR_NODE_SHARD", raising=False)
        monkeypatch.delenv("OPENR_BLOCKED_MESH", raising=False)
        ls = self._ls()
        nodes = sorted(ls.node_names)
        dests = [nodes[0], nodes[-1]]
        engine = DeviceResidencyEngine()
        engine.blocked.node_shard_threshold = 0
        chaos = ChaosSpfBackend(
            SimpleNamespace(engine=engine),
            seed=7,
            fail_prob=1.0,
            fail_ops={"engine:blocked_round"},
        )
        view = FleetViewCache().view(ls, dests, engine=engine)
        assert view.converged and not view.node_sharded
        assert engine.blocked.counters["mesh.blocked.fallbacks"] == 1
        spf_stream = chaos.log.streams().get("spf", [])
        assert any("engine:blocked_round:fail" in e for e in spf_stream)
        chaos.disarm()
        vf = FleetViewCache().view(self._ls(), dests)
        for node in nodes:
            assert np.array_equal(view._row(node), vf._row(node))

    def test_blocked_rung_parity_on_fattree(self):
        """Fat-trees are never banded: on a single-device mesh the
        engine-routed blocked rung must serve the same view, distances
        and ECMP bitmap, as the unblocked fused product."""

        def fat_tree_ls():
            ls = LinkState()
            for db in fat_tree_topology(4):
                ls.update_adjacency_database(db)
            return ls

        ls = fat_tree_ls()
        nodes = sorted(ls.node_names)
        dests = [nodes[0], nodes[3], nodes[-1]]
        engine = DeviceResidencyEngine()
        engine.blocked.node_shard_threshold = 0
        engine.blocked._mesh = blk.make_blocked_mesh(jax.devices("cpu")[:1])
        vb = FleetViewCache().view(ls, dests, engine=engine)
        assert vb.converged and vb.node_sharded
        assert engine.blocked.counters["mesh.blocked.fallbacks"] == 0
        vf = FleetViewCache().view(fat_tree_ls(), dests)
        assert vf.converged and not vf.node_sharded
        for node in nodes:
            assert np.array_equal(vb._row(node), vf._row(node))
        assert np.array_equal(
            np.asarray(jax.device_get(vb._bitmap_dev)),
            np.asarray(jax.device_get(vf._bitmap_dev)),
        )


def _blocked_product_mode(topo, dest_ids, mesh, tile, pipeline_mode):
    """_blocked_product with the pipeline override pinned on the engine
    (the same no-env-leak discipline the program auditor uses)."""
    eng = blk.BlockedApspEngine(tile=tile, mesh=mesh)
    eng.pipeline_mode = pipeline_mode
    dist, bitmap, ok = eng.fleet_product(
        topo, np.asarray(dest_ids, dtype=np.int32), _out_ell(topo)
    )
    assert ok
    return (
        np.asarray(jax.device_get(dist)),
        np.asarray(jax.device_get(bitmap)),
        eng,
    )


class TestPipelinedParity:
    """The software-pipelined loop (auto-on default for multi-round
    closures) against the bulk-synchronous loop: bit-exact on every
    topology family, correct pipeline_* counter semantics, 1-device
    degenerate mesh parity, chaos fault mid-pipeline demoting to bulk
    with `mesh.blocked.pipeline_fallbacks` accounted."""

    @pytest.mark.parametrize(
        "dbs_fn",
        [
            lambda: ring_topology(17),  # odd N: drags the padding tail
            lambda: grid_topology(4),
            lambda: fat_tree_topology(2),
            lambda: _overload(grid_topology(4), "node-1-1"),
        ],
        ids=["ring17", "grid4x4", "fattree", "grid-drained"],
    )
    def test_pipelined_matches_bulk(self, eight_cpu_devices, dbs_fn):
        csr = _csr(dbs_fn())
        n = int(csr.n_nodes)
        dests = np.asarray(sorted({0, n // 3, n - 1}), dtype=np.int32)
        mesh = blk.make_blocked_mesh(eight_cpu_devices)
        # tile 4 forces a genuinely multi-round closure
        d_bulk, b_bulk, e_bulk = _blocked_product_mode(
            csr, dests, mesh, 4, "0"
        )
        d_pipe, b_pipe, e_pipe = _blocked_product_mode(
            csr, dests, mesh, 4, "1"
        )
        assert np.array_equal(d_pipe, d_bulk)
        assert np.array_equal(b_pipe, b_bulk)
        t = e_pipe.counters["mesh.blocked.rounds"]
        assert t >= 2
        assert e_pipe.counters["mesh.blocked.pipeline_prefetch_issues"] == t - 1
        assert (
            e_pipe.counters["mesh.blocked.pipeline_rounds_overlapped"] == t - 1
        )
        assert e_pipe.counters["mesh.blocked.pipeline_overlap_frac_est"] > 0
        assert e_pipe.counters["mesh.blocked.pipeline_fallbacks"] == 0
        # the bulk engine never touches the pipeline family
        for key in blk.BLOCKED_COUNTER_KEYS:
            if "pipeline" in key:
                assert e_bulk.counters[key] == 0, key

    def test_wan_and_degenerate_mesh_parity(self, eight_cpu_devices):
        """wan-shaped family plus the 1-device degenerate mesh: the
        pipelined prefetch on one device is pure compute reordering —
        still bit-exact, and the overlap counters must say so."""
        from benchmarks import synthetic

        topo = synthetic.wan(96, chords=2, seed=3)
        rng = np.random.default_rng(4)
        dests = np.sort(
            rng.choice(topo.n_nodes, size=8, replace=False).astype(np.int32)
        )
        mesh8 = blk.make_blocked_mesh(eight_cpu_devices)
        d_bulk, b_bulk, _ = _blocked_product_mode(topo, dests, mesh8, 8, "0")
        d_pipe, b_pipe, _ = _blocked_product_mode(topo, dests, mesh8, 8, "1")
        assert np.array_equal(d_pipe, d_bulk)
        assert np.array_equal(b_pipe, b_bulk)
        mesh1 = blk.make_blocked_mesh(eight_cpu_devices[:1])
        d1, b1, e1 = _blocked_product_mode(topo, dests, mesh1, 8, "1")
        assert np.array_equal(d1, d_bulk)
        assert np.array_equal(b1, b_bulk)
        t = e1.counters["mesh.blocked.rounds"]
        assert e1.counters["mesh.blocked.pipeline_prefetch_issues"] == t - 1
        assert e1.counters["mesh.blocked.pipeline_rounds_overlapped"] == 0
        assert e1.counters["mesh.blocked.pipeline_overlap_frac_est"] == 0

    def test_env_knob_forces_bulk(self, eight_cpu_devices, monkeypatch):
        """OPENR_BLOCKED_PIPELINE=0 forces the bulk loop; unset or any
        other value keeps the pipelined default for t >= 2."""
        eng = blk.BlockedApspEngine(
            tile=4, mesh=blk.make_blocked_mesh(eight_cpu_devices)
        )
        monkeypatch.delenv("OPENR_BLOCKED_PIPELINE", raising=False)
        assert eng.pipeline_enabled(2)
        assert not eng.pipeline_enabled(1)  # nothing to prefetch
        monkeypatch.setenv("OPENR_BLOCKED_PIPELINE", "0")
        assert not eng.pipeline_enabled(4)
        monkeypatch.setenv("OPENR_BLOCKED_PIPELINE", "1")
        assert eng.pipeline_enabled(4)
        # the pinned override outranks the env (auditor discipline)
        eng.pipeline_mode = "0"
        assert not eng.pipeline_enabled(4)

    def test_chaos_fault_mid_pipeline_demotes_to_bulk(self, monkeypatch):
        """A chaos fault at the per-round gate lands inside the
        pipelined loop first: the rung must account the demotion
        (`pipeline_fallbacks`), retry bulk-synchronously, and — with
        the fault still armed — surface the failure to the fleet rung,
        which serves the view via the fused product as before."""
        from types import SimpleNamespace

        from openr_tpu.chaos.chaos import ChaosSpfBackend

        monkeypatch.delenv("OPENR_NODE_SHARD", raising=False)
        monkeypatch.delenv("OPENR_BLOCKED_MESH", raising=False)
        monkeypatch.delenv("OPENR_BLOCKED_PIPELINE", raising=False)
        ls = LinkState()
        for db in grid_topology(4):
            ls.update_adjacency_database(db)
        nodes = sorted(ls.node_names)
        dests = [nodes[0], nodes[-1]]
        engine = DeviceResidencyEngine()
        engine.blocked.node_shard_threshold = 0
        engine.blocked.tile = 4  # multi-round closure -> pipeline engages
        chaos = ChaosSpfBackend(
            SimpleNamespace(engine=engine),
            seed=7,
            fail_prob=1.0,
            fail_ops={"engine:blocked_round"},
        )
        view = FleetViewCache().view(ls, dests, engine=engine)
        assert view.converged and not view.node_sharded
        assert engine.blocked.counters["mesh.blocked.pipeline_fallbacks"] == 1
        assert engine.blocked.counters["mesh.blocked.fallbacks"] == 1
        spf_stream = chaos.log.streams().get("spf", [])
        assert any("engine:blocked_round:fail" in e for e in spf_stream)
        chaos.disarm()
        ls2 = LinkState()
        for db in grid_topology(4):
            ls2.update_adjacency_database(db)
        vf = FleetViewCache().view(ls2, dests)
        for node in nodes:
            assert np.array_equal(view._row(node), vf._row(node))

    def test_transient_fault_recovers_on_bulk_retry(self, eight_cpu_devices):
        """A fault that fires exactly once demotes the pipelined
        attempt and the bulk retry completes — the product is served
        by the blocked rung itself, bit-exact, with the demotion
        accounted."""
        csr = _csr(grid_topology(4))
        n = int(csr.n_nodes)
        dests = np.asarray([0, n - 1], dtype=np.int32)
        mesh = blk.make_blocked_mesh(eight_cpu_devices)
        ref_dist, ref_bitmap, _ = _blocked_product_mode(
            csr, dests, mesh, 4, "0"
        )
        eng = blk.BlockedApspEngine(tile=4, mesh=mesh)
        eng.pipeline_mode = "1"
        fired = []

        def hook(op):
            if op == "blocked_round" and not fired:
                fired.append(op)
                raise RuntimeError("injected: partition mid-pipeline")

        eng.fault_hook = hook
        dist, bitmap, ok = eng.fleet_product(csr, dests, _out_ell(csr))
        assert ok
        assert eng.counters["mesh.blocked.pipeline_fallbacks"] == 1
        assert eng.counters["mesh.blocked.products"] == 1
        assert np.array_equal(np.asarray(jax.device_get(dist)), ref_dist)
        assert np.array_equal(np.asarray(jax.device_get(bitmap)), ref_bitmap)


class TestPipelineHloEvidence:
    """Compile-only evidence on the virtual mesh: the lowered
    `blocked_round_pipelined` module schedules the round-(k+1) panel
    all-gathers with no data dependence on the round-k outer-update
    while, so their async start/done spans legally bracket it —
    materialized and verified by parallel.hlo_async from the compiled
    module's real def-use chains."""

    def _lowered_text(self, eight_cpu_devices, s=1, t=3, b=8):
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = blk.make_blocked_mesh(eight_cpu_devices)
        sds = jax.ShapeDtypeStruct
        args = (
            sds(
                (s, t, b, t, b),
                jnp.uint32,
                sharding=NamedSharding(
                    mesh, P("batch", None, "row", None, "col")
                ),
            ),
            sds(
                (s, b, t, b),
                jnp.uint32,
                sharding=NamedSharding(mesh, P("batch", None, None, "col")),
            ),
            sds(
                (s, t, b, b),
                jnp.uint32,
                sharding=NamedSharding(mesh, P("batch", None, "row", None)),
            ),
            sds((t * b,), jnp.bool_, sharding=NamedSharding(mesh, P())),
            sds((), jnp.int32, sharding=NamedSharding(mesh, P())),
        )
        return (
            blk.blocked_round_pipelined.lower(*args, mesh=mesh)
            .compile()
            .as_text()
        )

    def test_async_spans_bracket_outer_update(self, eight_cpu_devices):
        from openr_tpu.parallel import hlo_async

        txt = self._lowered_text(eight_cpu_devices)
        header = txt.split("\n", 1)[0]
        assert "is_scheduled=true" in header
        # donation survives the double-buffered carry: dist aliases
        # output 0 in the compiled module
        assert "input_output_alias={ {0}: (0" in header
        rep = hlo_async.async_report(txt)
        # the outer update is identifiable: the only rank-5 u32 while
        assert rep["outer_update"] is not None
        # row panel + col panel + diagonal replication
        assert rep["n_collectives"] >= 3
        # every span is legal per the def-use graph (checked, not
        # assumed from the scheduler's construction)
        assert all(s["legal"] for s in rep["spans"]), rep["spans"]
        # headline: both PANEL gathers' spans bracket the outer update
        assert rep["panel_overlap_ok"], rep["spans"]
        spanning = [s for s in rep["spans"] if s["spans_outer_update"]]
        assert len(spanning) >= 2
        for s in spanning:
            # the pair brackets real compute, not an empty window
            assert len(s["compute_in_span"]) >= 1, s
        assert rep["collective_bytes"] > 0
        assert rep["overlap_frac_est"] > 0

    def test_materialized_pairs_bracket_while_textually(
        self, eight_cpu_devices
    ):
        from openr_tpu.parallel import hlo_async

        txt = self._lowered_text(eight_cpu_devices)
        rep = hlo_async.async_report(txt)
        mat = rep["materialized"]
        assert mat.count("all-gather-start(") == rep["n_collectives"]
        assert mat.count("all-gather-done(") == rep["n_collectives"]
        lines = mat.splitlines()
        w = next(
            i
            for i, l in enumerate(lines)
            if l.lstrip().startswith(f"%{rep['outer_update']} =")
        )
        spanning = [s for s in rep["spans"] if s["spans_outer_update"]]
        for s in spanning:
            si = next(
                i
                for i, l in enumerate(lines)
                if l.lstrip().startswith(f"%{s['name']}-start =")
            )
            di = next(
                i
                for i, l in enumerate(lines)
                if l.lstrip().startswith(f"%{s['name']} = ")
            )
            assert si < w < di, (s["name"], si, w, di)

    def test_rejects_unscheduled_module(self, eight_cpu_devices):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from openr_tpu.parallel import hlo_async

        mesh = blk.make_blocked_mesh(eight_cpu_devices)
        sds = jax.ShapeDtypeStruct
        lowered = blk.blocked_diag.lower(
            sds(
                (1, 2, 8, 2, 8),
                jnp.uint32,
                sharding=NamedSharding(
                    mesh, P("batch", None, "row", None, "col")
                ),
            ),
            sds((16,), jnp.bool_, sharding=NamedSharding(mesh, P())),
            sds((), jnp.int32, sharding=NamedSharding(mesh, P())),
            mesh=mesh,
        )
        with pytest.raises(ValueError, match="is_scheduled"):
            hlo_async.parse_entry(lowered.as_text())
