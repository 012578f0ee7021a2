"""Sharded-mesh SPF tests on the virtual 8-device CPU mesh (conftest forces
JAX_PLATFORMS=cpu with xla_force_host_platform_device_count=8).

Covers openr_tpu/parallel/mesh.py — the multi-chip layout the driver
dry-runs — plus the __graft_entry__ dryrun itself, so a sharding regression
is caught by pytest rather than only by the driver.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from openr_tpu.decision.csr import CsrTopology
from openr_tpu.decision.link_state import LinkState
from openr_tpu.parallel.mesh import make_mesh, sharded_spf_forward, spf_step_sharded
from openr_tpu.utils.topo import grid_topology


def _grid_csr(n_side: int) -> CsrTopology:
    ls = LinkState()
    for db in grid_topology(n_side):
        ls.update_adjacency_database(db)
    return CsrTopology.from_link_state(ls)


def _pad_sources(n: int, batch_axis: int) -> np.ndarray:
    sources = np.arange(n, dtype=np.int32)
    per = -(-n // batch_axis)
    pad = batch_axis * per - n
    if pad:
        sources = np.concatenate([sources, np.zeros(pad, dtype=np.int32)])
    return sources


@pytest.fixture(scope="module")
def eight_cpu_devices():
    devices = jax.devices("cpu")
    if len(devices) < 8:
        pytest.skip("needs xla_force_host_platform_device_count=8")
    return devices[:8]


class TestMeshSpf:
    def test_batch_only_mesh_matches_single_device(self, eight_cpu_devices):
        """8x1 mesh (collective-free layout): sharded distances must equal
        the unsharded kernel's output exactly."""
        from openr_tpu.ops.sssp import spf_forward

        csr = _grid_csr(4)
        mesh = make_mesh(eight_cpu_devices)  # all devices on "batch"
        sources = _pad_sources(csr.n_nodes, 8)

        dist_sharded, dag_sharded = sharded_spf_forward(
            mesh,
            sources,
            csr.ell,
            csr.edge_src,
            csr.edge_dst,
            csr.edge_metric,
            csr.edge_up,
            csr.node_overloaded,
        )
        dist_ref, dag_ref = spf_forward(
            sources,
            csr.edge_src,
            csr.edge_dst,
            csr.edge_metric,
            csr.edge_up,
            csr.node_overloaded,
        )
        np.testing.assert_array_equal(
            np.asarray(dist_sharded), np.asarray(dist_ref)
        )
        np.testing.assert_array_equal(
            np.asarray(dag_sharded), np.asarray(dag_ref)
        )

    def test_2d_mesh_node_axis_collectives(self, eight_cpu_devices):
        """4x2 mesh: the [S, N] distance tensor is sharded over the node
        axis too, forcing cross-shard gathers; results must be unchanged."""
        from openr_tpu.ops.sssp import spf_forward

        csr = _grid_csr(4)
        assert csr.node_capacity % 2 == 0
        mesh = make_mesh(eight_cpu_devices, batch_axis=4)
        sources = _pad_sources(csr.n_nodes, 4)

        step = spf_step_sharded(mesh)
        s_batch = NamedSharding(mesh, P("batch"))
        s_repl = NamedSharding(mesh, P())
        dist, dag = step(
            jax.device_put(sources, s_batch),
            jax.device_put(csr.ell, s_repl),
            jax.device_put(np.asarray(csr.edge_src), s_repl),
            jax.device_put(np.asarray(csr.edge_dst), s_repl),
            jax.device_put(np.asarray(csr.edge_metric), s_repl),
            jax.device_put(np.asarray(csr.edge_up), s_repl),
            jax.device_put(np.asarray(csr.node_overloaded), s_repl),
        )
        jax.block_until_ready((dist, dag))
        # output sharding: dist over ("batch", "node")
        assert dist.sharding.spec == P("batch", "node")

        dist_ref, _ = spf_forward(
            sources,
            csr.edge_src,
            csr.edge_dst,
            csr.edge_metric,
            csr.edge_up,
            csr.node_overloaded,
        )
        np.testing.assert_array_equal(np.asarray(dist), np.asarray(dist_ref))

    def test_distance_values_on_grid(self, eight_cpu_devices):
        """Spot-check actual metrics: corner-to-corner on a unit 4x4 grid."""
        csr = _grid_csr(4)
        mesh = make_mesh(eight_cpu_devices, batch_axis=4)
        sources = _pad_sources(csr.n_nodes, 4)
        dist, _ = sharded_spf_forward(
            mesh,
            sources,
            csr.ell,
            csr.edge_src,
            csr.edge_dst,
            csr.edge_metric,
            csr.edge_up,
            csr.node_overloaded,
        )
        d = np.asarray(dist)
        a = csr.node_id["node-0-0"]
        b = csr.node_id["node-3-3"]
        assert d[a, b] == 6
        assert d[b, a] == 6
        assert d[a, a] == 0


class TestGraftDryrun:
    @pytest.mark.parametrize("n", [2, 4, 8])
    def test_dryrun_multichip(self, n, eight_cpu_devices):
        import __graft_entry__ as graft

        graft.dryrun_multichip(n)


class TestMeshWhatIf:
    def test_sharded_whatif_matches_masked_kernel(self, eight_cpu_devices):
        """Failure-scenario fleet sharded over the mesh: each of 16 rows
        fails one link (both directions); distances must equal the
        unsharded masked kernel exactly."""
        from openr_tpu.ops.sssp import spf_forward_ell_masked
        from openr_tpu.parallel.mesh import whatif_step_sharded

        csr = _grid_csr(6)
        n_rows = 16
        rng = np.random.default_rng(3)
        fail = rng.integers(0, csr.n_edges, size=n_rows)
        mask = np.ones((n_rows, csr.edge_capacity), dtype=bool)
        for row, e in enumerate(fail):
            mask[row, e] = False
            # reverse directed edge of the same link
            src, dst = csr.edge_src[e], csr.edge_dst[e]
            for e2 in range(csr.n_edges):
                if csr.edge_src[e2] == dst and csr.edge_dst[e2] == src:
                    mask[row, e2] = False
                    break
        sources = np.zeros(n_rows, dtype=np.int32)

        ref_dist, ref_dag = spf_forward_ell_masked(
            sources,
            csr.ell,
            csr.edge_src,
            csr.edge_dst,
            csr.edge_metric,
            csr.edge_up,
            csr.node_overloaded,
            mask,
        )

        mesh = make_mesh(eight_cpu_devices, batch_axis=4)  # 4 x 2
        s_batch = NamedSharding(mesh, P("batch"))
        s_mask_t = NamedSharding(mesh, P(None, "batch"))
        s_repl = NamedSharding(mesh, P())
        step = whatif_step_sharded(mesh)
        dist, dag = step(
            jax.device_put(sources, s_batch),
            jax.device_put(csr.ell, s_repl),
            jax.device_put(np.asarray(csr.edge_src), s_repl),
            jax.device_put(np.asarray(csr.edge_dst), s_repl),
            jax.device_put(np.asarray(csr.edge_metric), s_repl),
            jax.device_put(np.asarray(csr.edge_up), s_repl),
            jax.device_put(np.asarray(csr.node_overloaded), s_repl),
            jax.device_put(mask.T.copy(), s_mask_t),
        )
        np.testing.assert_array_equal(np.asarray(dist), np.asarray(ref_dist))
        np.testing.assert_array_equal(np.asarray(dag), np.asarray(ref_dag))


def _fat_tree_link_state(
    pods: int = 8, planes: int = 4, ssw_per_plane: int = 6, rsw_per_pod: int = 64
) -> LinkState:
    """Fat-tree fabric as a LinkState — built from the product generator
    (openr_tpu.utils.topo.fabric_topology) so the test validates the same
    wiring the bench rows use."""
    from openr_tpu.utils.topo import fabric_topology

    ls = LinkState()
    for db in fabric_topology(
        pods, planes=planes, ssw_per_plane=ssw_per_plane, rsw_per_pod=rsw_per_pod
    ):
        ls.update_adjacency_database(db)
    return ls


class TestMeshThroughSolver:
    def test_fat_tree_mesh_prefetch_route_equality(self, eight_cpu_devices):
        """VERDICT r2 #8: a realistically-sized fabric sharded over the
        8-device mesh, driven through DeviceSpfBackend ->
        SpfSolver.build_route_db, must produce route-level equality with
        the host-Dijkstra backend — ECMP sets, MPLS labels and all."""
        from openr_tpu.decision.prefix_state import PrefixState
        from openr_tpu.decision.spf_solver import DeviceSpfBackend, SpfSolver
        from openr_tpu.types import PrefixEntry

        ls = _fat_tree_link_state()
        nodes = ls.node_names
        assert len(nodes) > 500  # realistic fabric, not a toy
        ps = PrefixState()
        for i in range(0, len(nodes), 16):
            ps.update_prefix(
                nodes[i], "0", PrefixEntry(prefix=f"fc00:{i:x}::/64")
            )

        mesh = make_mesh(eight_cpu_devices)
        backend = DeviceSpfBackend(min_device_nodes=64, min_device_sources=1)
        # prefetch EVERY node's SPF through the sharded mesh step
        backend.prefetch_via_mesh(ls, nodes, mesh)

        for my_node in ("rsw-0-0", "fsw-3-2", "ssw-1-4"):
            dev_solver = SpfSolver(my_node, spf_backend=backend)
            host_solver = SpfSolver(my_node)
            rdb_dev = dev_solver.build_route_db({"0": ls}, ps)
            rdb_host = host_solver.build_route_db({"0": ls}, ps)
            assert rdb_dev.unicast_routes == rdb_host.unicast_routes
            assert rdb_dev.mpls_routes == rdb_host.mpls_routes

    def test_whatif_fleet_1k_variants(self, eight_cpu_devices):
        """A 1k-variant failure fleet sharded over the mesh matches the
        single-device masked kernel row-for-row."""
        import numpy as np

        from openr_tpu.ops.sssp import spf_forward_ell_masked
        from openr_tpu.parallel.mesh import whatif_step_sharded

        csr = _grid_csr(8)  # 64 nodes
        n_variants = 1024
        rng = np.random.default_rng(7)
        fail = rng.integers(0, csr.n_edges, size=n_variants)
        mask = np.ones((n_variants, csr.edge_capacity), dtype=bool)
        mask[np.arange(n_variants), fail] = False
        sources = rng.integers(
            0, csr.n_nodes, size=n_variants
        ).astype(np.int32)

        mesh = make_mesh(eight_cpu_devices)
        step = whatif_step_sharded(mesh)
        dist_m, dag_m = step(
            sources,
            csr.ell,
            csr.edge_src,
            csr.edge_dst,
            csr.edge_metric,
            csr.edge_up,
            csr.node_overloaded,
            np.ascontiguousarray(mask.T),  # step takes edge-major [E, S]
        )
        dist_1, dag_1 = spf_forward_ell_masked(
            sources,
            csr.ell,
            csr.edge_src,
            csr.edge_dst,
            csr.edge_metric,
            csr.edge_up,
            csr.node_overloaded,
            mask,
        )
        np.testing.assert_array_equal(np.asarray(dist_m), np.asarray(dist_1))
        np.testing.assert_array_equal(np.asarray(dag_m), np.asarray(dag_1))


class TestShardingLinearity:
    def test_per_device_flops_divide_by_batch_factor(self, eight_cpu_devices):
        """The linear-scaling assumption behind the multi-chip
        projections, validated structurally (r3 next #8): the per-device
        compiled FLOPs of the sharded SPF step must divide by the
        batch-axis factor (no hidden replication), and the batch-only
        layout's collectives must be only the O(1) convergence-verdict
        scalar reductions.  Full artifact: benchmarks/mesh_scaling.py."""
        import jax
        import jax.numpy as jnp

        from benchmarks import synthetic
        from benchmarks.mesh_scaling import _collect
        from openr_tpu.parallel import mesh as pmesh

        topo = synthetic.grid(16)  # 256 nodes
        sources = jnp.arange(256, dtype=jnp.int32)
        args = (
            sources,
            topo.ell,
            jnp.asarray(topo.edge_src),
            jnp.asarray(topo.edge_dst),
            jnp.asarray(topo.edge_metric),
            jnp.asarray(topo.edge_up),
            jnp.asarray(topo.node_overloaded),
        )
        rows = {}
        for b in (1, 8):
            mesh = pmesh.make_mesh(eight_cpu_devices[:b], batch_axis=b)
            rows[b] = _collect(
                pmesh.spf_step_sharded(mesh), args, f"batch={b}"
            )
        ratio = rows[8]["flops_per_device"] / rows[1]["flops_per_device"]
        # near 1/8 with slack for the O(1) verdict/bookkeeping terms
        assert 0.1 < ratio < 0.2, ratio
        # only the scalar convergence reductions may appear as collectives
        assert rows[8]["collective_ops"] <= 4, rows[8]["collective_ops"]


class TestShardedFleetProduct:
    """The reduced all-sources product with the DEST axis sharded over
    the mesh batch axis (parallel/mesh.fleet_product_sharded) must equal
    the single-device product bit-for-bit, and stay collective-free in
    the relax/bitmap (only the verdict reduces)."""

    def test_matches_single_device_product(self, eight_cpu_devices):
        from benchmarks.synthetic import reversed_topology, wan
        from openr_tpu.ops import allsources as asrc
        from openr_tpu.parallel.mesh import fleet_product_sharded

        topo = wan(256, chords=2, seed=9)
        rev = reversed_topology(topo)
        runner = rev.runner
        assert runner.bg is not None  # banded path required
        rng = np.random.default_rng(3)
        dests = np.sort(
            rng.choice(topo.n_nodes, size=32, replace=False).astype(
                np.int32
            )
        )
        out = asrc.build_out_ell(
            topo.edge_src, topo.edge_dst, topo.n_edges, topo.n_nodes
        )

        # single-device reference (adaptive: learns the sweep count)
        dist_ref, bitmap_ref, ok = asrc.reduced_all_sources(
            dests,
            runner,
            out,
            topo.edge_metric,
            topo.edge_up,
            topo.node_overloaded,
        )
        assert bool(ok)

        mesh = make_mesh(eight_cpu_devices)  # 8x1, dest axis sharded
        step = fleet_product_sharded(
            mesh,
            n_sweeps=runner.hint,
            n_words=out.n_words,
            depth=runner.depth,
            resid_rounds=runner.resid_rounds,
            small_dist=runner.small_dist,
            chord_mode=runner.chord_mode,
        )
        es, ed, em, eu, ov = runner.arrays
        import jax.numpy as jnp

        dist_sh, bitmap_sh, ok_sh = step(
            dests,
            runner.bg,
            jnp.asarray(es),
            jnp.asarray(ed),
            jnp.asarray(em),
            jnp.asarray(eu),
            jnp.asarray(ov),
            out,
            jnp.asarray(topo.edge_metric),
            jnp.asarray(topo.edge_up),
        )
        assert bool(ok_sh)
        np.testing.assert_array_equal(
            np.asarray(dist_sh), np.asarray(dist_ref)
        )
        np.testing.assert_array_equal(
            np.asarray(bitmap_sh), np.asarray(bitmap_ref)
        )
        # the dest axis really is sharded over the 8 devices
        assert len(dist_sh.sharding.device_set) == 8

    def test_drain_semantics_survive_sharding(self, eight_cpu_devices):
        from benchmarks.synthetic import reversed_topology, wan
        from openr_tpu.ops import allsources as asrc
        from openr_tpu.parallel.mesh import fleet_product_sharded

        topo = wan(128, chords=2, seed=5)
        topo.node_overloaded[[7, 40]] = True
        topo.edge_up[np.arange(0, topo.n_edges, 17)] = False
        rev = reversed_topology(topo)
        runner = rev.runner
        if runner.bg is None:
            pytest.skip("banded decomposition not found at this size")
        rng = np.random.default_rng(4)
        # exactly 16 dests (batch axis 8 requires divisibility), with the
        # two drained nodes among them
        pool = np.setdiff1d(np.arange(topo.n_nodes), [7, 40])
        dests = np.sort(
            np.concatenate(
                [rng.choice(pool, size=14, replace=False), [7, 40]]
            )
        ).astype(np.int32)
        out = asrc.build_out_ell(
            topo.edge_src, topo.edge_dst, topo.n_edges, topo.n_nodes
        )
        dist_ref, bitmap_ref, ok = asrc.reduced_all_sources(
            dests, runner, out, topo.edge_metric, topo.edge_up,
            topo.node_overloaded,
        )
        assert bool(ok)
        mesh = make_mesh(eight_cpu_devices)
        step = fleet_product_sharded(
            mesh,
            n_sweeps=runner.hint,
            n_words=out.n_words,
            depth=runner.depth,
            resid_rounds=runner.resid_rounds,
            small_dist=runner.small_dist,
            chord_mode=runner.chord_mode,
        )
        es, ed, em, eu, ov = runner.arrays
        import jax.numpy as jnp

        dist_sh, bitmap_sh, ok_sh = step(
            dests, runner.bg, jnp.asarray(es), jnp.asarray(ed),
            jnp.asarray(em), jnp.asarray(eu), jnp.asarray(ov), out,
            jnp.asarray(topo.edge_metric), jnp.asarray(topo.edge_up),
        )
        assert bool(ok_sh)
        np.testing.assert_array_equal(
            np.asarray(dist_sh), np.asarray(dist_ref)
        )
        np.testing.assert_array_equal(
            np.asarray(bitmap_sh), np.asarray(bitmap_ref)
        )
