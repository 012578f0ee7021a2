"""Described-v5e compiles of the daemon path's programs at real widths.

Nothing here runs on a chip: each test lowers a program for one chip of
a described (not attached) `v5e:2x2` topology and compiles it with the
TPU compiler installed here, which refuses what the chip would refuse —
tile shapes, device memory.  Shapes are the fat-tree of BASELINE config
#2 (10,080 switches, 95,232 directed adjacencies) and the benchmark's
deployments (`perf/configs/*.json`, built by their own generators under
`perf/topologies/`): each program a cell runs is compiled with the
shapes, statics and dtypes that cell's call passes.  On the chip,
`python -m perf.run` runs the cells (BENCHMARK.json).

The topology is described inside a fixture only (never at import, in
conftest or in a parametrize/skipif): one process at a time may load
the TPU library, and every xdist worker imports this file.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

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
    _fits_one_chip(_lower_engine_program(fattree10k, 64, one_chip).compile())


def _lower_engine_program(csr, s_bucket: int, sharding):
    """DeviceResidencyEngine.spf_results' program for one S bucket,
    keyed and donated as DeviceResidencyEngine._program builds it."""
    from openr_tpu.device import engine as eng

    n_words = max(1, -(-csr.max_out_slots // 32))
    n_cap = csr.node_capacity
    small = s_bucket * n_cap <= eng.DeviceResidencyEngine().small_threshold
    fn = eng._forward_body(small, True, csr._sweep_hint, n_words)
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
    return jax.jit(fn, donate_argnums=() if small else (0,)).lower(
        *_sds(args, sharding)
    )


def test_graft_entry_spf_forward_banded(one_chip):
    import __graft_entry__ as g

    fn, args = g.entry()
    compiled = jax.jit(fn).lower(*_sds(args, one_chip)).compile()
    _fits_one_chip(compiled)


def _deployment_mirror(name: str):
    """(deployment, LinkState, CsrTopology) of one benchmark
    configuration: the adjacency databases the harness publishes for
    it (perf.harness.Harness.adj_db), built into the daemon's mirror."""
    from openr_tpu.decision.csr import CsrTopology
    from openr_tpu.decision.link_state import LinkState
    from perf.deployment import build, load_config

    dep = build(load_config(name))
    ls = LinkState(dep.area)
    for node in dep.nodes:
        ls.update_adjacency_database(_adj_db(dep, node))
    return dep, ls, CsrTopology.from_link_state(ls)


def _adj_db(dep, node: str, down=frozenset()):
    from openr_tpu.types import Adjacency, AdjacencyDatabase

    return AdjacencyDatabase(
        this_node_name=node,
        adjacencies=[
            Adjacency(
                other_node_name=a.other,
                if_name=a.if_name,
                other_if_name=a.other_if_name,
                metric=a.metric,
                next_hop_v6=a.next_hop_v6,
            )
            for a in dep.adj[node]
            if frozenset((node, a.other)) not in down
        ],
        area=dep.area,
        node_label=dep.index[node] + 1,
    )


@pytest.fixture(scope="module")
def fabric10k():
    _, _, csr = _deployment_mirror("fabric10k")
    assert (csr.n_nodes, csr.n_edges) == (10020, 145152)
    return csr


@pytest.fixture(scope="module")
def grid10k():
    _, _, csr = _deployment_mirror("grid10k")
    assert (csr.n_nodes, csr.n_edges) == (10000, 39600)
    return csr


@pytest.fixture(scope="module")
def grid1k_ksp2():
    _, _, csr = _deployment_mirror("grid1k_ksp2")
    assert (csr.n_nodes, csr.n_edges) == (1024, 3968)
    return csr


# what-if traffic (perf/traffic/whatif.json): 8 sources, 16 SRLGs a
# query plus the no-failure baseline row; four coalesced queries
# concatenate their scenarios behind one baseline
WHATIF_SOURCES = 8
WHATIF_ONE_QUERY = 17
WHATIF_FOUR_QUERIES = 65


def _lower_what_if(csr, n_rows: int, sharding):
    """ops.protection.srlg_what_if's device program (its ELL path) with
    the arguments decision.protection_api.what_if passes."""
    from openr_tpu.ops.protection import _srlg_what_if_device

    return _srlg_what_if_device.lower(
        *_sds(
            (
                np.zeros(WHATIF_SOURCES, np.int32),
                csr.edge_src,
                csr.edge_dst,
                csr.edge_metric,
                csr.edge_up,
                csr.node_overloaded,
                np.ones((n_rows, csr.edge_capacity), bool),
                csr.ell,
            ),
            sharding,
        )
    )


def _lower_ksp2_rows(csr, rows: int, sharding):
    """The KSP2 cell's masked rows as SpfRunner.forward dispatches them
    (the grid's mirror has no circulant bands): one row per destination
    with a second path to find, each masking its first paths' edges,
    with the SP-DAG the decode walks."""
    from openr_tpu.ops.banded import pick_small_dist
    from openr_tpu.ops.sssp import spf_forward_ell_sweeps

    runner = csr.runner
    assert runner.bg is None
    return spf_forward_ell_sweeps.lower(
        *_sds(
            (
                np.zeros(rows, np.int32),
                csr.ell,
                csr.edge_src,
                csr.edge_dst,
                csr.edge_metric,
                csr.edge_up,
                csr.node_overloaded,
            ),
            sharding,
        ),
        n_sweeps=max(runner.hint_masked, 2),
        use_link_metric=True,
        extra_edge_mask=jax.ShapeDtypeStruct(
            (rows, csr.edge_capacity), jnp.bool_, sharding=sharding
        ),
        want_dag=True,
        small_dist=runner.small_allowed
        and pick_small_dist(csr.edge_metric, csr.n_edges),
        raw_u16=False,
        transpose=True,
    )


KSP2_ROWS = 1023  # every destination of the 32 x 32 grid but the daemon

LOWER = {
    "engine": _lower_engine_program,
    "whatif": _lower_what_if,
    "ksp2": _lower_ksp2_rows,
}

# each configuration's cell programs as (kind, size), largest first
CELL_PROGRAMS = {
    "fabric10k": (
        ("engine", 64),
        ("engine", 1),
        ("whatif", WHATIF_FOUR_QUERIES),
        ("whatif", WHATIF_ONE_QUERY),
    ),
    "grid10k": (
        ("whatif", WHATIF_FOUR_QUERIES),
        ("whatif", WHATIF_ONE_QUERY),
    ),
    "grid1k_ksp2": (("ksp2", KSP2_ROWS), ("engine", 64), ("engine", 1)),
}


class _CellPrograms:
    """A configuration's programs, lowered together on first use and
    compiled on a thread pool (the TPU compiler releases the GIL, and
    each of the fabric's largest programs takes minutes on one CPU)."""

    def __init__(self, request, sharding, pool) -> None:
        self._request = request
        self._sharding = sharding
        self._pool = pool
        self._programs: dict = {}

    def get(self, config: str, kind: str, size: int):
        """(lowered, compiled) of one program."""
        if (config, kind, size) not in self._programs:
            csr = self._request.getfixturevalue(config)
            for k, n in CELL_PROGRAMS[config]:
                lowered = LOWER[k](csr, n, self._sharding)
                self._programs[(config, k, n)] = (
                    lowered,
                    self._pool.submit(lowered.compile),
                )
        lowered, compiled = self._programs[(config, kind, size)]
        return lowered, compiled.result()


@pytest.fixture(scope="module")
def cell_programs(request, one_chip):
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(2) as pool:
        yield _CellPrograms(request, one_chip, pool)


@pytest.mark.parametrize("n_rows", [WHATIF_ONE_QUERY, WHATIF_FOUR_QUERIES])
@pytest.mark.parametrize("config", ["grid10k", "fabric10k"])
def test_srlg_what_if_relax(cell_programs, config, n_rows):
    """The what-if cells' masked ELL relax: every (scenario, source)
    row of one query, or of four coalesced queries, in one program."""
    _, compiled = cell_programs.get(config, "whatif", n_rows)
    _fits_one_chip(compiled)


@pytest.mark.parametrize("config", ["grid10k", "fabric10k"])
def test_srlg_reachability_loss(request, one_chip, cell_programs, config):
    """The what-if reduce over one query's rows: the relax's output,
    baseline row apart, restricted to the real nodes."""
    from openr_tpu.ops.protection import srlg_reachability_loss

    lowered, _ = cell_programs.get(config, "whatif", WHATIF_ONE_QUERY)
    dtype = lowered.out_info.dtype
    n = request.getfixturevalue(config).n_nodes
    compiled = srlg_reachability_loss.lower(
        jax.ShapeDtypeStruct((WHATIF_SOURCES, n), dtype, sharding=one_chip),
        jax.ShapeDtypeStruct(
            (WHATIF_ONE_QUERY - 1, WHATIF_SOURCES, n),
            dtype,
            sharding=one_chip,
        ),
    ).compile()
    _fits_one_chip(compiled)


def test_ksp2_masked_rows(cell_programs):
    """The KSP2 cell's 1,023 masked rows with their SP-DAG
    (`_lower_ksp2_rows`)."""
    _, compiled = cell_programs.get("grid1k_ksp2", "ksp2", KSP2_ROWS)
    _fits_one_chip(compiled)


@pytest.mark.parametrize("s_bucket", [1, 64])
@pytest.mark.parametrize("config", ["fabric10k", "grid1k_ksp2"])
def test_engine_spf_program(cell_programs, config, s_bucket):
    """The converge cells' `spf_forward_resident`: S=1 for the daemon's
    own route build, S=64 for the 32-source queryPaths of set-up."""
    from openr_tpu.device.engine import _s_bucket

    assert s_bucket in (_s_bucket(1), _s_bucket(32))
    _, compiled = cell_programs.get(config, "engine", s_bucket)
    _fits_one_chip(compiled)


def test_link_down_sync_masked_writes(one_chip, monkeypatch):
    """Every masked-write program one remote link-down's engine sync
    dispatches on fabric10k (the converge cell's event), recorded from
    a real sync on the host and compiled for the chip."""
    from openr_tpu.device import engine as eng

    dep, ls, csr = _deployment_mirror("fabric10k")
    engine = eng.DeviceResidencyEngine()
    engine.sync(csr)
    seen: dict = {}
    for name in (
        "_masked_write_i32",
        "_masked_write_bool",
        "_masked_write_rows_i32",
        "_masked_write_rows_bool",
    ):
        prog = getattr(eng, name)

        def record(*args, _prog=prog, _name=name):
            sig = tuple((a.shape, a.dtype) for a in args)
            seen[(_name, sig)] = _prog
            return _prog(*args)

        monkeypatch.setattr(eng, name, record)
    a, b, _, _ = dep.links[len(dep.links) // 2]
    down = frozenset({frozenset((a, b))})
    ls.update_adjacency_database(_adj_db(dep, a, down))
    ls.update_adjacency_database(_adj_db(dep, b, down))
    csr.refresh(ls)
    engine.sync(csr)
    assert engine.get_counters()["device.engine.rewires"] == 1
    assert {name for name, _ in seen} >= {
        "_masked_write_i32",
        "_masked_write_bool",
    }
    for (_name, sig), prog in seen.items():
        compiled = prog.lower(
            *(
                jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in sig
            )
        ).compile()
        _fits_one_chip(compiled)


@pytest.mark.parametrize("t", [8, 79])
def test_blocked_outer_compiles(one_chip, t):
    """The blocked rung's rank-B outer update and its pipelined round
    root, on a one-chip mesh at B=128 (T=79 is the fat-tree 10k tile
    count)."""
    from openr_tpu.parallel import blocked as blk

    mesh = blk.make_blocked_mesh(list(one_chip.device_set))
    s, b = 1, 128
    args = (
        jax.ShapeDtypeStruct((s, t, b, t, b), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((s, b, t, b), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((s, t, b, b), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((t * b,), jnp.bool_, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
    )
    for prog in (blk.blocked_outer, blk.blocked_round_pipelined):
        _fits_one_chip(prog.lower(*args, mesh=mesh).compile())
