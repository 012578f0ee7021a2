"""Described-v5e compiles of the daemon path's programs at real widths.

Nothing here runs: each test lowers a program for one chip of a
described (not attached) `v5e:2x2` topology and compiles it with the
TPU compiler installed here, which refuses what the chip would refuse —
Mosaic lowerings, tile shapes, device memory.  Shapes are the fat-tree
of BASELINE config #2 (10,080 switches, 95,232 directed adjacencies);
on the chip, `python -m perf.run` runs the daemon path (BENCHMARK.json).

The topology is described inside a fixture only (never at import, in
conftest or in a parametrize/skipif): one process at a time may load
the TPU library, and every xdist worker imports this file.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from openr_tpu.ops import pallas_kernels as pk

V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def fattree10k():
    """Host-side CSR mirror of the fat-tree 10k (numpy only)."""
    from openr_tpu.decision.csr import CsrTopology
    from openr_tpu.decision.link_state import LinkState
    from openr_tpu.utils.topo import fabric_topology

    ls = LinkState()
    for db in fabric_topology(
        96, planes=4, ssw_per_plane=24, rsw_per_pod=100
    ):
        ls.update_adjacency_database(db)
    csr = CsrTopology.from_link_state(ls)
    assert (csr.n_nodes, csr.n_edges) == (10080, 95232)
    return csr


def _sds(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            np.shape(x), np.asarray(x).dtype, sharding=sharding
        ),
        tree,
    )


def _fits_one_chip(compiled) -> None:
    mem = compiled.memory_analysis()
    total = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
    )
    assert total < V5E_HBM_BYTES, total


def test_fleet_product_fattree10k(one_chip, fattree10k):
    """The fleet product FleetRouteView.compute dispatches for the
    daemon at fat-tree 10k: fat-trees are not banded, so the reverse
    relax is the fixed-sweep ELL program (raw uint16 when metrics
    allow, native [N_cap, P] layout) followed by the ECMP bitmap pass,
    with every switch a destination (P = 10,080)."""
    from openr_tpu.decision.fleet import _reverse_runner
    from openr_tpu.ops import allsources as asrc
    from openr_tpu.ops.banded import pick_small_dist
    from openr_tpu.ops.sssp import spf_forward_ell_sweeps

    csr = fattree10k
    runner = _reverse_runner(csr)
    assert runner.bg is None
    out = asrc.build_out_ell(
        csr.edge_src, csr.edge_dst, csr.n_edges, csr.n_nodes,
        out_slot=csr.out_slot,
    )
    small = runner.small_allowed and pick_small_dist(
        runner.arrays[2], runner.n_edges
    )
    dests = np.arange(csr.n_nodes, dtype=np.int32)
    relax = spf_forward_ell_sweeps.lower(
        *_sds((dests, runner.ell, *runner.call_arrays()), one_chip),
        n_sweeps=max(runner.hint, 2),
        want_dag=False,
        small_dist=small,
        raw_u16=True,
        transpose=False,
    ).compile()
    _fits_one_chip(relax)
    drev = jax.ShapeDtypeStruct(
        (csr.node_capacity, len(dests)),
        jnp.uint16 if small else jnp.int32,
        sharding=one_chip,
    )
    bitmap = asrc.ecmp_bitmap_from_reverse_dist.lower(
        drev,
        *_sds(
            (out, csr.edge_metric, csr.edge_up, csr.node_overloaded),
            one_chip,
        ),
        n_words=out.n_words,
    ).compile()
    _fits_one_chip(bitmap)


def test_engine_spf_program_fattree10k(one_chip, fattree10k):
    """The residency engine's SPF program at fat-tree 10k, in the S=64
    bucket the ctrl queryPaths burst dispatches (the daemon's own S=1
    route build is the same body at a smaller static shape)."""
    from openr_tpu.device import engine as eng

    s_bucket = 64
    csr = fattree10k
    n_words = max(1, -(-csr.max_out_slots // 32))
    n_cap = csr.node_capacity
    small = s_bucket * n_cap <= eng.DeviceResidencyEngine().small_threshold
    fn = eng._forward_body(small, True, 16, n_words)
    args = (
        np.zeros((n_cap, s_bucket), np.int32),
        np.zeros(s_bucket, np.int32),
        csr.ell,
        csr.edge_src,
        csr.edge_dst,
        csr.edge_metric,
        csr.edge_up,
        csr.node_overloaded,
        csr.out_slot,
    )
    compiled = (
        jax.jit(fn, donate_argnums=() if small else (0,))
        .lower(*_sds(args, one_chip))
        .compile()
    )
    _fits_one_chip(compiled)


def test_graft_entry_spf_forward_banded(one_chip):
    import __graft_entry__ as g

    fn, args = g.entry()
    compiled = jax.jit(fn).lower(*_sds(args, one_chip)).compile()
    _fits_one_chip(compiled)


@pytest.mark.parametrize("t", [8, 79])
def test_blocked_outer_pallas_compiles(one_chip, t):
    """The blocked rank-B outer kernel is off the TPU default
    (OUTER_DEFAULT_REFUSAL) but stays opt-in, so it must still lower
    through Mosaic (T=79 is the fat-tree 10k tile count at B=128)."""
    s, b = 1, 128
    np_ = t * b
    args = (
        jax.ShapeDtypeStruct((s, t, b, t, b), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((s, b, t, b), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((s, t, b, b), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((np_,), jnp.bool_, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
    )
    assert pk.outer_conformance(s, t, b) is None
    compiled = pk.blocked_outer_pallas.lower(*args, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


class TestTpuDefaultPolicy:
    """The auto policy as a TPU backend resolves it (the backend query
    is steered here; nothing is compiled)."""

    @pytest.fixture(autouse=True)
    def _on_tpu(self, monkeypatch):
        monkeypatch.setattr(pk.jax, "default_backend", lambda: "tpu")
        monkeypatch.delenv("OPENR_PALLAS", raising=False)

    def test_auto_is_off_for_both_kernels(self):
        assert pk.pallas_mode() == "off"
        assert pk.pallas_mode(env="auto") == "off"
        assert pk.pallas_mode(env="1") == "compiled"

    @pytest.mark.parametrize("kind", ["product", "outer"])
    def test_auto_counts_a_skip(self, kind):
        counters: dict = {}
        out = pk.run_with_fallback(
            kind,
            lambda interpret: pytest.fail(f"{kind} must not launch"),
            lambda: "xla",
            counters=counters,
        )
        assert out == "xla"
        assert counters == {"device.engine.pallas_skips": 1}

    def test_forced_compiled_epilogue_is_refused_before_dispatch(self):
        counters: dict = {}
        out = pk.run_with_fallback(
            "product",
            lambda interpret: pytest.fail("epilogue must not launch"),
            lambda: "xla",
            counters=counters,
            mode="compiled",
        )
        assert out == "xla"
        assert counters == {"device.engine.pallas_skips": 1}

    def test_compiled_failure_raises_instead_of_demoting(self):
        def boom(interpret):
            assert not interpret
            raise RuntimeError("lowering failed")

        counters: dict = {}
        with pytest.raises(RuntimeError, match="lowering failed"):
            pk.run_with_fallback(
                "outer",
                boom,
                lambda: pytest.fail("compiled mode never demotes"),
                counters=counters,
                mode="compiled",
            )
        assert "device.engine.pallas_fallbacks" not in counters


def test_nonconformant_outer_tiles_are_one_counted_skip():
    """A forced compiled outer kernel on the blocked rung's default
    one-device tile (B=16) is refused once, in run_apsp, and every
    round takes the XLA phase (which runs here on the CPU)."""
    from openr_tpu.decision.fleet import FleetViewCache
    from openr_tpu.decision.link_state import LinkState
    from openr_tpu.device import DeviceResidencyEngine
    from openr_tpu.parallel.blocked import make_blocked_mesh
    from openr_tpu.utils.topo import fat_tree_topology

    ls = LinkState()
    for db in fat_tree_topology(4):
        ls.update_adjacency_database(db)
    eng = DeviceResidencyEngine()
    eng.pallas_mode = "compiled"
    eng.blocked.node_shard_threshold = 0
    eng.blocked._mesh = make_blocked_mesh(jax.devices()[:1])
    view = FleetViewCache().view(ls, sorted(ls.node_names), engine=eng)
    assert view.converged and view.node_sharded
    c = eng.get_counters()
    assert eng.blocked.get_counters()["mesh.blocked.rounds"] > 1
    assert c["device.engine.pallas_skips"] == 1
    assert c["device.engine.pallas_outer_updates"] == 0
    assert c["device.engine.pallas_fallbacks"] == 0
