"""Batch backends for the QueryScheduler.

Two deployments of the same contract:

- `EngineBatchBackend` — standalone: the scheduler owns `{area:
  LinkState}` views plus a `DeviceSpfBackend`, and dispatches straight
  into the residency engine.  This is the bench/test harness shape and
  the building block for serving tiers that hold their own topology
  mirror.
- `DecisionBatchBackend` — in-daemon: queries marshal onto the Decision
  event thread (the reference's runInEventBaseThread RPC discipline) and
  compute over Decision's own LinkStates through its SpfSolver backend.
  The serving win is unchanged: N coalesced queries cost ONE cross-
  thread marshal and one device dispatch instead of N.

Contract (all methods raise `device.engine.EpochMismatchError` when the
area's topology version no longer matches `expect_epoch`):

- ``epoch(area) -> int`` — current topology version (cheap, lock-free).
- ``run_paths(area, sources, use_link_metric, expect_epoch)`` ->
  ``{source: SpfResult}``.
- ``run_what_if(area, sources, scenarios, expect_epoch)`` -> per-
  scenario impact dicts (protection_api.what_if shape).
- ``run_ksp(area, source, dests, k, expect_epoch)`` ->
  ``{dest: [Path]}``.
- ``run_optimize_metrics(area, demand, bounds, steps, expect_epoch)`` ->
  wire dict of exactly-validated proposed metrics + objective delta (the
  te.TeOptimizer run; epoch-checked per descent step, never retried).

The degradation ladder's host rung lives here: when the engine rejects a
paths dispatch for any non-epoch reason (chaos fault, device loss), the
backend bumps ``serving.host_fallbacks`` and serves the same answer from
the host Dijkstra oracle — overload may shed, but faults keep serving.
"""

from __future__ import annotations

import logging
from typing import Callable, Optional

from ..device.engine import EpochMismatchError

log = logging.getLogger(__name__)


def _noop_bump(name: str, delta: int = 1) -> None:
    return None


def _te_problem_from_csr(csr, demand, bounds):
    """Build a te.TeProblem over a CSR mirror from wire-shaped demand
    triples ((src_name, dest_name, volume), ...).  Edge arrays are COPIED:
    the optimizer runs for many steps on the serving executor while the
    owner thread may refresh the mirror in place — the epoch check aborts
    a moved topology, the copy keeps the in-flight arrays coherent until
    it does.  Unknown node names raise KeyError (a loud error reply)."""
    import numpy as np

    from ..te import TeProblem

    dest_names = sorted({d for (_s, d, _v) in demand})
    if not dest_names:
        raise ValueError("optimize_metrics: empty demand matrix")
    col = {d: j for j, d in enumerate(dest_names)}
    dest_ids = np.array([csr.node_id[d] for d in dest_names], dtype=np.int32)
    dm = np.zeros((csr.node_capacity, len(dest_names)), dtype=np.float32)
    for s, d, v in demand:
        dm[csr.node_id[s], col[d]] += float(v)
    lo, hi = int(bounds[0]), int(bounds[1])
    return TeProblem(
        edge_src=csr.edge_src.copy(),
        edge_dst=csr.edge_dst.copy(),
        edge_metric=csr.edge_metric.copy(),
        edge_up=csr.edge_up.copy(),
        node_overloaded=csr.node_overloaded.copy(),
        n_edges=int(csr.n_edges),
        n_nodes=int(csr.n_nodes),
        dest_ids=dest_ids,
        demand=dm,
        metric_lo=lo,
        metric_hi=hi,
    )


def _shape_te_result(node_names, result) -> dict:
    """TeResult -> wire dict; proposed metrics only for edges the run
    actually changed (and exactly validated), as (src, dest, metric)
    name triples."""
    return {
        "proposedMetrics": [
            [node_names[u], node_names[v], int(m)]
            for (u, v, m) in result.changed_edges
        ],
        "objectiveBefore": float(result.objective_before),
        "objectiveAfter": float(result.objective_after),
        "improved": bool(result.improved),
        "steps": int(result.steps),
        "roundTrips": int(result.round_trips),
        "accepted": int(result.accepted),
        "rejected": int(result.rejected),
    }


class EngineBatchBackend:
    """Standalone backend: {area: LinkState} + DeviceSpfBackend."""

    def __init__(
        self,
        link_states: dict,
        spf_backend=None,
        bump: Optional[Callable[..., None]] = None,
        te=None,
    ) -> None:
        if spf_backend is None:
            from ..decision.spf_solver import DeviceSpfBackend

            spf_backend = DeviceSpfBackend()
        self.link_states = link_states
        self.spf = spf_backend
        self._bump = bump or _noop_bump
        if te is None:
            from ..te import TeOptimizer

            te = TeOptimizer(engine=getattr(spf_backend, "engine", None))
        # TE optimizer rides the same backend so its exact round trips
        # dispatch through the same residency engine; te.* counters are
        # exported by whoever holds this backend (handler te= kwarg)
        self.te = te

    def _ls(self, area: str):
        ls = self.link_states.get(area)
        if ls is None:
            raise KeyError(f"no link state for area {area!r}")
        return ls

    def epoch(self, area: str) -> int:
        return int(self._ls(area).version)

    def _check_epoch(self, ls, expect_epoch: int) -> None:
        if int(ls.version) != int(expect_epoch):
            raise EpochMismatchError(int(expect_epoch), int(ls.version))

    def run_paths(
        self,
        area: str,
        sources: list,
        use_link_metric: bool = True,
        expect_epoch: int = 0,
    ) -> dict:
        ls = self._ls(area)
        self._check_epoch(ls, expect_epoch)
        known = [s for s in sources if ls.links_from_node(s)]
        csr = self.spf.csr_mirror(ls)
        try:
            # engine-level epoch tagging: csr.version mirrors ls.version,
            # so a flap between coalescing and this dispatch raises
            # EpochMismatchError before any device work
            results = self.spf.engine.spf_results(
                csr,
                known,
                use_link_metric=use_link_metric,
                expect_epoch=expect_epoch,
            )
        except EpochMismatchError:
            raise
        except Exception:
            # degradation ladder host rung: the serving layer must keep
            # answering through device faults; same bit-exact contract
            # (to_spf_results is validated against run_spf in tier-1)
            log.debug("serving: engine paths failed; host oracle", exc_info=True)
            self._bump("serving.host_fallbacks")
            self._check_epoch(ls, expect_epoch)
            results = {
                s: ls.get_spf_result(s, use_link_metric=use_link_metric)
                for s in known
            }
        for s in sources:
            if s not in results:
                results[s] = ls.get_spf_result(
                    s, use_link_metric=use_link_metric
                )
        return results

    def run_what_if(
        self,
        area: str,
        sources: list,
        scenarios: list,
        expect_epoch: int = 0,
    ) -> list:
        from ..decision.protection_api import what_if

        ls = self._ls(area)
        self._check_epoch(ls, expect_epoch)
        csr = self.spf.csr_mirror(ls)
        csr.pair_edge_ids(self._bump)  # counted here, read by the resolve
        return what_if(
            ls,
            [[tuple(link) for link in sc] for sc in scenarios],
            sources=list(sources) or None,
            csr=csr,
        )

    def run_ksp(
        self,
        area: str,
        source: str,
        dests: list,
        k: int = 2,
        expect_epoch: int = 0,
    ) -> dict:
        ls = self._ls(area)
        self._check_epoch(ls, expect_epoch)
        # one masked device run amortized over the destination set
        self.spf.prefetch_kth_paths(ls, source, list(dests))
        return {d: self.spf.get_kth_paths(ls, source, d, k) for d in dests}

    def run_optimize_metrics(
        self,
        area: str,
        demand,
        bounds,
        steps: int = 32,
        expect_epoch: int = 0,
    ) -> dict:
        ls = self._ls(area)
        self._check_epoch(ls, expect_epoch)
        csr = self.spf.csr_mirror(ls)
        problem = _te_problem_from_csr(csr, demand, bounds)
        result = self.te.optimize(
            problem,
            steps=int(steps),
            # live epoch read: every descent step and exact round trip
            # re-checks; a flap aborts the run (EpochMismatchError), the
            # scheduler does NOT retry this op
            epoch_fn=lambda: int(ls.version),
            expect_epoch=expect_epoch,
        )
        return _shape_te_result(list(csr.node_names), result)


class DecisionBatchBackend:
    """In-daemon backend: batches marshal onto the Decision thread."""

    def __init__(
        self,
        decision,
        bump: Optional[Callable[..., None]] = None,
        te=None,
    ) -> None:
        self.decision = decision
        self._bump = bump or _noop_bump
        if te is None:
            from ..te import TeOptimizer

            te = TeOptimizer(
                engine=getattr(decision.spf_solver.spf, "engine", None)
            )
        self.te = te

    def epoch(self, area: str) -> int:
        # plain read of the version counter: int reads are atomic and the
        # batch re-validates under the Decision thread before computing
        ls = self.decision.area_link_states.get(area)
        return int(ls.version) if ls is not None else -1

    def _ls_checked(self, area: str, expect_epoch: int):
        ls = self.decision.area_link_states.get(area)
        actual = int(ls.version) if ls is not None else -1
        if actual != int(expect_epoch):
            raise EpochMismatchError(int(expect_epoch), actual)
        if ls is None:
            raise KeyError(f"no link state for area {area!r}")
        return ls

    def run_paths(
        self,
        area: str,
        sources: list,
        use_link_metric: bool = True,
        expect_epoch: int = 0,
    ) -> dict:
        def _compute() -> dict:
            ls = self._ls_checked(area, expect_epoch)
            spf = self.decision.spf_solver.spf
            prefetch = getattr(spf, "prefetch", None)
            if prefetch is not None:
                try:
                    # ONE batched device call for the whole source set
                    prefetch(ls, list(sources))
                except EpochMismatchError:
                    raise
                except Exception:
                    log.debug(
                        "serving: decision prefetch failed; host oracle",
                        exc_info=True,
                    )
                    self._bump("serving.host_fallbacks")
            return {
                s: spf.get_spf_result(ls, s)
                for s in sources
                if ls.links_from_node(s)
            }

        return self.decision.run_in_event_base_thread(_compute).result()

    def run_what_if(
        self,
        area: str,
        sources: list,
        scenarios: list,
        expect_epoch: int = 0,
    ) -> list:
        def _check():
            self._ls_checked(area, expect_epoch)

        self.decision.run_in_event_base_thread(_check).result()
        return self.decision.what_if(
            [[tuple(link) for link in sc] for sc in scenarios],
            area=area,
            sources=list(sources) or None,
        )

    def run_ksp(
        self,
        area: str,
        source: str,
        dests: list,
        k: int = 2,
        expect_epoch: int = 0,
    ) -> dict:
        def _compute() -> dict:
            ls = self._ls_checked(area, expect_epoch)
            spf = self.decision.spf_solver.spf
            prefetch = getattr(spf, "prefetch_kth_paths", None)
            if prefetch is not None:
                prefetch(ls, source, list(dests))
            return {d: spf.get_kth_paths(ls, source, d, k) for d in dests}

        return self.decision.run_in_event_base_thread(_compute).result()

    def run_optimize_metrics(
        self,
        area: str,
        demand,
        bounds,
        steps: int = 32,
        expect_epoch: int = 0,
    ) -> dict:
        # only the SNAPSHOT marshals onto the Decision thread (mirror
        # access is single-threaded there); the descent itself runs on
        # the serving executor — a whole optimization must not starve
        # route programming.  The copied problem arrays plus the per-step
        # epoch check keep the off-thread run coherent: a topology event
        # bumps ls.version and the optimizer aborts.
        def _snapshot():
            ls = self._ls_checked(area, expect_epoch)
            spf = self.decision.spf_solver.spf
            mirror = getattr(spf, "csr_mirror", None)
            if mirror is None:
                raise RuntimeError(
                    "optimize_metrics requires the device SPF backend"
                )
            csr = mirror(ls)
            return (
                _te_problem_from_csr(csr, demand, bounds),
                list(csr.node_names),
                ls,
            )

        problem, node_names, ls = self.decision.run_in_event_base_thread(
            _snapshot
        ).result()
        result = self.te.optimize(
            problem,
            steps=int(steps),
            epoch_fn=lambda: int(ls.version),
            expect_epoch=expect_epoch,
        )
        return _shape_te_result(node_names, result)
