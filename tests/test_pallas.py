"""Pallas min-plus kernels (openr_tpu/ops/pallas_kernels.py) in
interpreter mode on CPU — the roofline rung's correctness surface.

Covers: bit-exact parity of the fused verify+bitmap epilogue against the
lax epilogue on every banded topology family (ring, grid, wan-shaped
with chords, drained, odd-N padding), unit + engine-integrated parity of
the blocked rank-B outer kernel (fat-tree rides this one — fat-trees are
never banded, so the blocked rung is their Pallas surface), the
OPENR_PALLAS policy knob, the graceful-demotion contract with its
device.engine.pallas_* accounting, the compiled-mode conformance gates,
and a seeded chaos fault at the engine:pallas site.  Real roofline
fractions are device-only and live behind -m slow.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from openr_tpu.decision.csr import CsrTopology
from openr_tpu.decision.fleet import FleetViewCache, _reverse_runner, _row_i32
from openr_tpu.decision.link_state import LinkState
from openr_tpu.device.engine import ENGINE_COUNTER_KEYS, DeviceResidencyEngine
from openr_tpu.ops import allsources as asrc
from openr_tpu.ops import pallas_kernels as pk
from openr_tpu.parallel import blocked as blk
from openr_tpu.utils.topo import (
    fat_tree_topology,
    grid_topology,
    ring_topology,
)

pytestmark = pytest.mark.pallas

PALLAS_KEYS = sorted(k for k in ENGINE_COUNTER_KEYS if ".pallas_" in k)


def _overload(dbs, name):
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


def _ls(dbs) -> LinkState:
    ls = LinkState()
    for db in dbs:
        ls.update_adjacency_database(db)
    return ls


def _out_ell(topo):
    return asrc.build_out_ell(
        topo.edge_src,
        topo.edge_dst,
        int(topo.n_edges),
        int(topo.n_nodes),
        out_slot=getattr(topo, "out_slot", None),
    )


def _fused(topo, dest_ids, mode: str):
    """(dist [N, P] int32-normalized, bitmap, counters) through the
    unblocked fused product with the Pallas policy pinned to `mode` —
    the counters dict proves which path actually served the product."""
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
    counters: dict = {}
    dist, bitmap, ok = asrc.reduced_all_sources(
        np.asarray(dest_ids, dtype=np.int32),
        runner,
        out,
        topo.edge_metric,
        topo.edge_up,
        topo.node_overloaded,
        maps=maps,
        pallas_run=lambda kind, pt, xt: pk.run_with_fallback(
            kind, pt, xt, counters=counters, mode=mode
        ),
    )
    assert ok
    n = int(topo.n_nodes)
    dist = _row_i32(np.asarray(jax.device_get(dist)))[:n]
    bitmap = np.asarray(jax.device_get(bitmap))[:n]
    return dist, bitmap, counters


def _one_device_mesh():
    return blk.make_blocked_mesh(jax.devices("cpu")[:1])


# ---------------------------------------------------------------------------
# Kernel 1: fused verify+bitmap epilogue
# ---------------------------------------------------------------------------


class TestEpilogueParity:
    """Forced-interpret Pallas epilogue vs the forced-XLA lax epilogue,
    bit for bit on dist AND bitmap, on every banded topology family.
    The products counter proves the kernel path engaged (build_banded
    only exists at N >= 64, so sub-64 fixtures would vacuously pass)."""

    def _assert_parity(self, topo, dest_ids):
        dp, bp, cp = _fused(topo, dest_ids, "interpret")
        dx, bx, cx = _fused(topo, dest_ids, "off")
        assert cp.get("device.engine.pallas_products") == 1, cp
        assert "device.engine.pallas_fallbacks" not in cp, cp
        assert cx.get("device.engine.pallas_skips", 0) >= 1, cx
        assert np.array_equal(dp, dx)
        assert np.array_equal(bp, bx)

    def test_ring_odd_n(self):
        csr = _csr(ring_topology(65))  # odd N: padding rows live
        self._assert_parity(csr, [0, 7, 31, 64])

    def test_grid(self):
        csr = _csr(grid_topology(10))
        self._assert_parity(csr, list(range(0, 100, 9)))

    def test_wan_shaped_chords(self):
        from benchmarks import synthetic

        topo = synthetic.wan(96, chords=2, seed=3)
        self._assert_parity(topo, [0, 5, 17, 48, 95])

    def test_ring_drained_node(self):
        csr = _csr(_overload(ring_topology(65), "r7"))
        self._assert_parity(csr, [0, 7, 40])

    def test_grid_drained_node(self):
        dbs = grid_topology(10)
        name = dbs[37].this_node_name
        csr = _csr(_overload(dbs, name))
        self._assert_parity(csr, [0, 37, 99])


# ---------------------------------------------------------------------------
# Kernel 2: blocked rank-B outer update
# ---------------------------------------------------------------------------


class TestBlockedOuterKernel:
    def _random_inputs(self, s=2, t=3, b=16, seed=0):
        rng = np.random.default_rng(seed)
        np_ = t * b
        dist = rng.integers(0, 1 << 20, size=(s, t, b, t, b)).astype(
            np.uint32
        )
        dist[rng.random(dist.shape) < 0.1] = np.uint32(1 << 30)
        row_p = rng.integers(0, 1 << 20, size=(s, b, t, b)).astype(np.uint32)
        col_p = rng.integers(0, 1 << 20, size=(s, t, b, b)).astype(np.uint32)
        ov = rng.random(np_) < 0.2
        return dist, jnp.asarray(row_p), jnp.asarray(col_p), jnp.asarray(ov)

    def test_unit_parity_all_k_with_drain_mask(self):
        dist, row_p, col_p, ov = self._random_inputs()
        mesh = _one_device_mesh()
        for k in range(3):
            got = pk.blocked_outer_pallas(
                jnp.asarray(dist), row_p, col_p, ov, k, interpret=True
            )
            want = blk.blocked_outer(
                jnp.asarray(dist), row_p, col_p, ov, k, mesh=mesh
            )
            assert np.array_equal(
                np.asarray(jax.device_get(got)),
                np.asarray(jax.device_get(want)),
            ), f"k={k}"

    def test_unit_parity_no_mask(self):
        dist, row_p, col_p, ov = self._random_inputs(s=1, t=4, b=8, seed=3)
        ov = jnp.zeros_like(ov)
        mesh = _one_device_mesh()
        got = pk.blocked_outer_pallas(
            jnp.asarray(dist), row_p, col_p, ov, 2, interpret=True
        )
        want = blk.blocked_outer(
            jnp.asarray(dist), row_p, col_p, ov, 2, mesh=mesh
        )
        assert np.array_equal(
            np.asarray(jax.device_get(got)), np.asarray(jax.device_get(want))
        )

    def test_compiled_mode_gates_nonconformant_tiles(self):
        """b=16 tiles can't lower on Mosaic (last dim must be 128s);
        the gate raises at trace time so the demotion path re-runs on
        an intact buffer — never a mid-kernel abort on device."""
        dist, row_p, col_p, ov = self._random_inputs()
        with pytest.raises(ValueError):
            pk.blocked_outer_pallas(
                jnp.asarray(dist), row_p, col_p, ov, 0, interpret=False
            )


# ---------------------------------------------------------------------------
# Policy knob + demotion contract
# ---------------------------------------------------------------------------


class TestPolicyAndFallback:
    def test_mode_parsing(self):
        assert pk.pallas_mode(env="0") == "off"
        assert pk.pallas_mode(env="off") == "off"
        assert pk.pallas_mode(env="interpret") == "interpret"
        assert pk.pallas_mode(env="compiled") == "compiled"
        on_tpu = jax.default_backend() == "tpu"
        assert pk.pallas_mode(env="1") == (
            "compiled" if on_tpu else "interpret"
        )
        # auto: off everywhere (neither kernel is on the TPU default;
        # the interpreter is a correctness tool, never an implicit path)
        assert pk.pallas_mode(env="") == "off"
        assert pk.pallas_mode(env="auto") == pk.pallas_mode(env="")
        assert pk.pallas_mode(env="bogus") == pk.pallas_mode(env="auto")

    def test_env_is_the_default_policy(self, monkeypatch):
        monkeypatch.setenv("OPENR_PALLAS", "interpret")
        assert pk.pallas_mode() == "interpret"
        monkeypatch.setenv("OPENR_PALLAS", "0")
        assert pk.pallas_mode() == "off"

    def test_off_mode_skips_and_accounts(self):
        counters: dict = {}
        out = pk.run_with_fallback(
            "product",
            lambda interpret: pytest.fail("pallas thunk must not run"),
            lambda: "xla",
            counters=counters,
            mode="off",
        )
        assert out == "xla"
        assert counters == {"device.engine.pallas_skips": 1}

    def test_failure_demotes_and_accounts(self):
        def boom(interpret):
            raise RuntimeError("tile mismatch")

        counters: dict = {}
        out = pk.run_with_fallback(
            "product", boom, lambda: "xla", counters=counters, mode="interpret"
        )
        assert out == "xla"
        assert counters == {"device.engine.pallas_fallbacks": 1}

    def test_success_accounts_per_kind(self):
        counters: dict = {}
        assert (
            pk.run_with_fallback(
                "product", lambda i: "p", lambda: "x",
                counters=counters, mode="interpret",
            )
            == "p"
        )
        assert (
            pk.run_with_fallback(
                "outer", lambda i: "o", lambda: "x",
                counters=counters, mode="interpret",
            )
            == "o"
        )
        assert counters == {
            "device.engine.pallas_products": 1,
            "device.engine.pallas_outer_updates": 1,
        }

    def test_epilogue_refuses_row_exclusions(self):
        from types import SimpleNamespace

        ops = SimpleNamespace(resid_excl=np.zeros((4, 2), bool))
        with pytest.raises(ValueError, match="row exclusions"):
            pk.fused_epilogue(
                ops, None, jnp.zeros((4, 2), jnp.uint16), None, None, 1,
                interpret=True,
            )


# ---------------------------------------------------------------------------
# Engine-routed integration (the production dispatch path)
# ---------------------------------------------------------------------------


class TestEngineIntegration:
    def test_counters_preseeded_on_engine(self):
        eng = DeviceResidencyEngine()
        c = eng.get_counters()
        assert PALLAS_KEYS and set(PALLAS_KEYS) <= set(c)
        assert all(c[k] == 0 for k in PALLAS_KEYS)

    def test_fused_product_parity_through_view(self):
        ls = _ls(ring_topology(65))
        dests = ["r0", "r7", "r64"]
        ep = DeviceResidencyEngine()
        ep.pallas_mode = "interpret"
        vp = FleetViewCache().view(ls, dests, engine=ep)
        assert vp.converged
        cp = ep.get_counters()
        assert cp["device.engine.pallas_products"] == 1
        assert cp["device.engine.pallas_fallbacks"] == 0
        ex = DeviceResidencyEngine()
        ex.pallas_mode = "off"
        vx = FleetViewCache().view(_ls(ring_topology(65)), dests, engine=ex)
        assert vx.converged
        assert ex.get_counters()["device.engine.pallas_skips"] >= 1
        for node in sorted(ls.node_names):
            assert np.array_equal(vp._row(node), vx._row(node))
        assert np.array_equal(
            np.asarray(jax.device_get(vp._bitmap_dev)),
            np.asarray(jax.device_get(vx._bitmap_dev)),
        )

    def test_blocked_rung_parity_on_fattree(self):
        """Fat-trees are never banded, so the blocked rung is their
        Pallas surface: single-device mesh engages the outer kernel,
        and the view must match the plain XLA blocked closure."""
        dbs = fat_tree_topology(4)
        ls = _ls(dbs)
        nodes = sorted(ls.node_names)
        dests = [nodes[0], nodes[3], nodes[-1]]
        ep = DeviceResidencyEngine()
        ep.pallas_mode = "interpret"
        ep.blocked.node_shard_threshold = 0
        ep.blocked._mesh = _one_device_mesh()
        vp = FleetViewCache().view(ls, dests, engine=ep)
        assert vp.converged and vp.node_sharded
        cp = ep.get_counters()
        assert cp["device.engine.pallas_outer_updates"] > 0
        assert cp["device.engine.pallas_fallbacks"] == 0
        ex = DeviceResidencyEngine()
        ex.pallas_mode = "off"
        ex.blocked.node_shard_threshold = 0
        ex.blocked._mesh = _one_device_mesh()
        vx = FleetViewCache().view(_ls(fat_tree_topology(4)), dests, engine=ex)
        assert vx.converged and vx.node_sharded
        assert ex.get_counters()["device.engine.pallas_skips"] >= 1
        for node in nodes:
            assert np.array_equal(vp._row(node), vx._row(node))

    def test_multi_device_mesh_stays_on_xla(self):
        """The outer kernel owns single-device meshes only: sharded
        meshes keep the collective-aware XLA kernel, no pallas counter
        moves (and no demotion is charged — this is rung placement,
        not a failure)."""
        devices = jax.devices("cpu")
        if len(devices) < 8:
            pytest.skip("needs xla_force_host_platform_device_count=8")
        ls = _ls(grid_topology(4))
        nodes = sorted(ls.node_names)
        eng = DeviceResidencyEngine()
        eng.pallas_mode = "interpret"
        eng.blocked.node_shard_threshold = 0
        view = FleetViewCache().view(ls, [nodes[0], nodes[-1]], engine=eng)
        assert view.converged and view.node_sharded
        c = eng.get_counters()
        assert all(c[k] == 0 for k in PALLAS_KEYS), c


class TestChaosPallas:
    def test_seeded_fault_demotes_with_parity(self):
        """Armed engine:pallas fault fires inside the launch try-block:
        the product demotes through the real failure path — fallback
        counter bumped, failure event logged, view served bit-exactly
        by the XLA epilogue."""
        from types import SimpleNamespace

        from openr_tpu.chaos.chaos import ChaosSpfBackend

        ls = _ls(ring_topology(65))
        dests = ["r0", "r31", "r64"]
        engine = DeviceResidencyEngine()
        engine.pallas_mode = "interpret"
        chaos = ChaosSpfBackend(
            SimpleNamespace(engine=engine),
            seed=7,
            fail_prob=1.0,
            fail_ops={"engine:pallas"},
        )
        view = FleetViewCache().view(ls, dests, engine=engine)
        assert view.converged
        c = engine.get_counters()
        assert c["device.engine.pallas_fallbacks"] == 1
        assert c["device.engine.pallas_products"] == 0
        spf_stream = chaos.log.streams().get("spf", [])
        assert any("engine:pallas:fail" in e for e in spf_stream)
        chaos.disarm()
        vf = FleetViewCache().view(_ls(ring_topology(65)), dests)
        for node in sorted(ls.node_names):
            assert np.array_equal(view._row(node), vf._row(node))


# ---------------------------------------------------------------------------
# Device-only roofline assertions (-m slow; skipped off-TPU)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestRooflineOnDevice:
    """Real achieved-fraction-of-roofline assertions: compiled kernels
    on actual TPU HBM.  Interpreter walls measure the interpreter, so
    these are meaningless off-device — hard skip."""

    @pytest.fixture(autouse=True)
    def _tpu_only(self):
        if jax.default_backend() != "tpu":
            pytest.skip("roofline fractions need a real TPU backend")

    def test_blocked_outer_reaches_roofline_fraction(self):
        import time

        from benchmarks.util import achieved_bw_frac

        rng = np.random.default_rng(14)
        s, t, b = 1, 8, 128
        np_ = t * b
        dist_h = rng.integers(0, 1 << 20, size=(s, t, b, t, b)).astype(
            np.uint32
        )
        row_p = jnp.asarray(
            rng.integers(0, 1 << 20, size=(s, b, t, b)).astype(np.uint32)
        )
        col_p = jnp.asarray(
            rng.integers(0, 1 << 20, size=(s, t, b, b)).astype(np.uint32)
        )
        ov = jnp.zeros(np_, bool)
        staged = [jax.device_put(dist_h) for _ in range(6)]
        jax.block_until_ready(staged)
        pk.blocked_outer_pallas(  # compile + warm
            staged[0], row_p, col_p, ov, 0, interpret=False
        )
        walls = []
        for d in staged[1:]:
            t0 = time.perf_counter()
            jax.block_until_ready(
                pk.blocked_outer_pallas(d, row_p, col_p, ov, 0, interpret=False)
            )
            walls.append((time.perf_counter() - t0) * 1e3)
        bytes_tm = 2 * s * np_ * np_ * 4 + 2 * t * s * np_ * b * 4
        frac = achieved_bw_frac(bytes_tm, min(walls))
        assert frac is not None and frac > 0.2, (frac, walls)
