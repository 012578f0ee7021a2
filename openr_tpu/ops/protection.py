"""Batched failure-protection kernels: SRLG what-if + TI-LFA backups.

These are the NEW capabilities unlocked by the batch dimension
(BASELINE.json configs #4/#5) — the reference computes nothing like them
(its solver answers one source at a time; what-if analysis would need a
full Decision re-run per scenario).

- `srlg_what_if`: evaluate F failure scenarios (each an edge mask, e.g.
  all members of a shared-risk link group) x S sources in ONE device
  call: dist [F, S, N].  Operators use this for maintenance planning:
  "which prefixes lose reachability / degrade if this conduit is cut?"

- `ti_lfa_backups`: per-source per-out-edge post-convergence distances:
  for each of a source's out-edges, distances with that edge (and its
  reverse) failed — exactly the state TI-LFA needs to pick loop-free
  backup next-hops and repair segments (P/Q analysis happens on these
  distance tensors).

Both reuse the fixed-point relaxation kernel (ops.sssp.batched_sssp);
the batch rows are independent, so they shard collective-free over the
"batch" mesh axis (openr_tpu.parallel).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .sssp import (
    INF32,
    batched_sssp,
    make_dist0,
    make_relax_allowed,
    sp_dag_mask,
    spf_forward_ell_masked,
)


def srlg_what_if(
    sources: jax.Array,  # [S] int32
    edge_src: jax.Array,  # [E]
    edge_dst: jax.Array,  # [E]
    edge_metric: jax.Array,  # [E]
    edge_up: jax.Array,  # [E] bool
    node_overloaded: jax.Array,  # [N] bool
    scenario_masks: jax.Array,  # [F, E] bool — True = edge SURVIVES
    ell=None,  # ops.sssp.EllGraph: run the production bucketed-ELL kernel
    runner=None,  # ops.banded.SpfRunner: band-aware fixed-sweep execution
) -> jax.Array:
    """Distances under each failure scenario: [F, S, N] int32.

    With `runner` (the production path), the (scenario x source) cross
    product flattens onto the fixed-sweep band-aware kernel and the
    result is host numpy.  With `ell`, the flattened batch runs the
    while_loop masked-ELL kernel on device; the bare edge-list fallback
    remains for tiny graphs.  Distances only: the SP-DAG nobody reads
    here is never built."""
    if runner is not None:
        _check_runner_arrays(
            runner, edge_src, edge_dst, edge_metric, edge_up, node_overloaded
        )
        f_dim = scenario_masks.shape[0]
        s_dim = sources.shape[0]
        flat_sources = jnp.tile(jnp.asarray(sources), f_dim)
        flat_masks = jnp.repeat(
            jnp.asarray(scenario_masks), s_dim, axis=0
        )
        dist, _ = runner.forward(
            flat_sources, extra_edge_mask=flat_masks, want_dag=False
        )
        return dist.reshape(f_dim, s_dim, -1)
    return _srlg_what_if_device(
        sources,
        edge_src,
        edge_dst,
        edge_metric,
        edge_up,
        node_overloaded,
        scenario_masks,
        ell,
    )


@jax.jit
def _srlg_what_if_device(
    sources,
    edge_src,
    edge_dst,
    edge_metric,
    edge_up,
    node_overloaded,
    scenario_masks,
    ell=None,
):
    n_nodes = node_overloaded.shape[0]
    if ell is not None:
        f_dim = scenario_masks.shape[0]
        s_dim = sources.shape[0]
        flat_sources = jnp.tile(sources, f_dim)  # [F*S]
        flat_masks = jnp.repeat(scenario_masks, s_dim, axis=0)  # [F*S, E]
        with jax.named_scope("srlg_relax"):
            dist, _ = spf_forward_ell_masked(
                flat_sources,
                ell,
                edge_src,
                edge_dst,
                edge_metric,
                edge_up,
                node_overloaded,
                flat_masks,
                want_dag=False,
            )
        return dist.reshape(f_dim, s_dim, n_nodes)
    base_allowed = make_relax_allowed(
        sources, edge_src, edge_up, node_overloaded
    )  # [S, E]

    def one_scenario(mask):
        allowed = base_allowed & mask[None, :]
        return batched_sssp(
            make_dist0(sources, n_nodes), edge_src, edge_dst, edge_metric, allowed
        )

    with jax.named_scope("srlg_relax"):
        return jax.lax.map(one_scenario, scenario_masks)


@jax.jit
def srlg_reachability_loss(
    baseline_dist: jax.Array,  # [S, N]
    scenario_dist: jax.Array,  # [F, S, N]
) -> tuple[jax.Array, jax.Array]:
    """Per scenario: (#newly-unreachable pairs, #degraded pairs)."""
    with jax.named_scope("srlg_reduce"):
        was_reachable = baseline_dist < INF32
        now_unreachable = was_reachable[None] & (scenario_dist >= INF32)
        degraded = (
            was_reachable[None]
            & (scenario_dist < INF32)
            & (scenario_dist > baseline_dist[None])
        )
        axes = (1, 2)
        return now_unreachable.sum(axes), degraded.sum(axes)


def ti_lfa_backups(
    source: jax.Array,  # scalar int32 — protected source node
    out_edge_ids: jax.Array,  # [D] int32 — source's out-edge ids (-1 pad)
    edge_src: jax.Array,  # [E]
    edge_dst: jax.Array,  # [E]
    edge_metric: jax.Array,  # [E]
    edge_up: jax.Array,  # [E] bool
    node_overloaded: jax.Array,  # [N] bool
    reverse_edge_ids: jax.Array,  # [E] int32 — id of each edge's reverse
    max_degree: int,
    ell=None,  # ops.sssp.EllGraph: run the production bucketed-ELL kernel
    runner=None,  # ops.banded.SpfRunner: band-aware fixed-sweep execution
):
    """Post-convergence SPF per protected out-edge.

    Returns (dist [D, N], dag [D, E]): row d = distances / SP-DAG with
    out_edge_ids[d] (and its reverse) removed.  A backup next-hop for
    destination v on failure of edge d is any first hop of row d's DAG;
    TI-LFA P/Q spaces and repair-segment endpoints derive from these plus
    per-neighbor distance rows (computed by the same kernel batched over
    sources).  With `runner` the masks run the band-aware fixed-sweep
    kernel and numpy arrays come back; otherwise device arrays."""
    if runner is not None:
        import numpy as _np

        _check_runner_arrays(
            runner, edge_src, edge_dst, edge_metric, edge_up, node_overloaded
        )
        d_dim = int(out_edge_ids.shape[0])
        survives = build_edge_failure_masks(
            out_edge_ids, reverse_edge_ids, edge_src.shape[0]
        )
        sources = _np.full(d_dim, int(source), dtype=_np.int32)
        return runner.forward(sources, extra_edge_mask=survives)
    return _ti_lfa_backups_device(
        source,
        out_edge_ids,
        edge_src,
        edge_dst,
        edge_metric,
        edge_up,
        node_overloaded,
        reverse_edge_ids,
        max_degree=max_degree,
        ell=ell,
    )


@functools.partial(jax.jit, static_argnames=("max_degree",))
def _ti_lfa_backups_device(
    source,
    out_edge_ids,
    edge_src,
    edge_dst,
    edge_metric,
    edge_up,
    node_overloaded,
    reverse_edge_ids,
    max_degree: int,
    ell=None,
) -> tuple[jax.Array, jax.Array]:
    del max_degree  # shape already fixed by out_edge_ids
    n_edges = edge_src.shape[0]
    d_dim = out_edge_ids.shape[0]

    edge_ids = jnp.arange(n_edges, dtype=jnp.int32)
    fail = out_edge_ids  # [D]
    fail_rev = jnp.where(
        fail >= 0, reverse_edge_ids[jnp.maximum(fail, 0)], -1
    )  # [D]
    # per-row exclusion mask: True = edge survives
    survives = (edge_ids[None, :] != fail[:, None]) & (
        edge_ids[None, :] != fail_rev[:, None]
    )  # [D, E]

    sources = jnp.broadcast_to(source, (d_dim,)).astype(jnp.int32)
    if ell is not None:
        return spf_forward_ell_masked(
            sources,
            ell,
            edge_src,
            edge_dst,
            edge_metric,
            edge_up,
            node_overloaded,
            survives,
        )
    allowed = make_relax_allowed(
        sources, edge_src, edge_up, node_overloaded, survives
    )
    n_nodes = node_overloaded.shape[0]
    dist = batched_sssp(
        make_dist0(sources, n_nodes), edge_src, edge_dst, edge_metric, allowed
    )
    dag = sp_dag_mask(dist, edge_src, edge_dst, edge_metric, allowed)
    return dist, dag


def _check_runner_arrays(
    runner, edge_src, edge_dst, edge_metric, edge_up, node_overloaded
) -> None:
    """The runner path answers from the arrays captured in the runner —
    reject a call that passes DIFFERENT arrays (e.g. a modified edge_up
    copy), which would otherwise be silently ignored."""
    import numpy as _np

    r_src, r_dst, r_metric, r_up, r_ov = runner.arrays
    for mine, theirs, name in (
        (edge_src, r_src, "edge_src"),
        (edge_dst, r_dst, "edge_dst"),
        (edge_metric, r_metric, "edge_metric"),
        (edge_up, r_up, "edge_up"),
        (node_overloaded, r_ov, "node_overloaded"),
    ):
        if _np.asarray(mine) is not _np.asarray(theirs) and not (
            _np.shares_memory(_np.asarray(mine), _np.asarray(theirs))
            or _np.array_equal(_np.asarray(mine), _np.asarray(theirs))
        ):
            raise ValueError(
                f"runner path: {name} differs from the runner's captured "
                "array; mutate the runner's arrays (or drop runner=) "
                "instead of passing a modified copy"
            )


def build_edge_failure_masks(
    out_edge_ids, reverse_edge_ids, edge_capacity: int
):
    """[D, E_cap] survives-mask for per-edge failure rows: row d excludes
    out_edge_ids[d] and its reverse (-1 pads exclude nothing).  Shared by
    ti_lfa_backups and the bench harness so the pad-guard semantics live
    in exactly one place."""
    import numpy as np

    fail = np.asarray(out_edge_ids)
    rev = np.asarray(reverse_edge_ids)
    fail_rev = np.where(fail >= 0, rev[np.maximum(fail, 0)], -1)
    edge_ids = np.arange(edge_capacity, dtype=np.int64)
    # a -1 entry (pad) must exclude NO edge: compare against -2 sentinels
    fail_cmp = np.where(fail >= 0, fail, -2)
    rev_cmp = np.where(fail_rev >= 0, fail_rev, -2)
    return (edge_ids[None, :] != fail_cmp[:, None]) & (
        edge_ids[None, :] != rev_cmp[:, None]
    )


def build_reverse_edge_ids(edge_src, edge_dst) -> "jax.Array":
    """Host helper: for each directed edge (u, v), the id of (v, u); -1 if
    absent.  O(E) dict pass over numpy arrays."""
    import numpy as np

    src = np.asarray(edge_src)
    dst = np.asarray(edge_dst)
    # Parallel links between the same node pair must pair up one-to-one:
    # the k-th (u, v) edge reverses to the k-th (v, u) edge, so a failed
    # directed edge is paired with the reverse of *its own* link instance,
    # not the first parallel link found.
    index: dict[tuple[int, int], list[int]] = {}
    occurrence = np.zeros(len(src), dtype=np.int64)
    for e in range(len(src)):
        bucket = index.setdefault((int(src[e]), int(dst[e])), [])
        occurrence[e] = len(bucket)
        bucket.append(e)
    rev = np.full(len(src), -1, dtype=np.int32)
    for e in range(len(src)):
        candidates = index.get((int(dst[e]), int(src[e])), [])
        k = int(occurrence[e])
        if k < len(candidates):
            rev[e] = candidates[k]
    return jnp.asarray(rev)
