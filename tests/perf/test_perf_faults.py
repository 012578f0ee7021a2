"""Faults planted in the program under the timed path: each must turn a
run's `correct` false (CPU, tiny deployments, the rest of the run as
`perf.run` makes it)."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from test_perf_cells import run_tiny  # noqa: E402


def _stale_decision(monkeypatch):
    """A step that returns its state unchanged: once the daemon has
    booted, Decision pushes an empty route update for every event."""
    from openr_tpu.decision import decision
    from openr_tpu.decision.rib import DecisionRouteUpdate
    from perf.drivers import link_events

    real_warm = link_events.Driver.warm

    def warm(self):
        monkeypatch.setattr(
            decision.Decision, "_compute_route_update", lambda self: DecisionRouteUpdate()
        )
        return real_warm(self)

    monkeypatch.setattr(link_events.Driver, "warm", warm)


def _drop_next_hop(monkeypatch):
    """An answer altered where it is produced: the FIB agent programs
    every multi-path route with one next hop fewer."""
    from openr_tpu.fib import fib

    real = fib.MockFibAgent.add_unicast_routes

    def altered(self, client_id, routes):
        for r in routes:
            if len(r.next_hops) > 1:
                r.next_hops = r.next_hops[1:]
        return real(self, client_id, routes)

    monkeypatch.setattr(fib.MockFibAgent, "add_unicast_routes", altered)


def _what_if_patch(monkeypatch, mutate):
    from openr_tpu.decision import protection_api

    real = protection_api.what_if

    def patched(link_state, scenarios, sources=None, csr=None):
        return mutate(real, link_state, scenarios, sources, csr)

    monkeypatch.setattr(protection_api, "what_if", patched)


def _what_if_altered(real, ls, scenarios, sources, csr):
    rows = real(ls, scenarios, sources, csr)
    rows[0]["degraded_pairs"] += 1
    return rows


def _what_if_half_batch(real, ls, scenarios, sources, csr):
    """Half of the batch left out: the second half of the scenarios
    answered with the first half's rows."""
    half = max(1, len(scenarios) // 2)
    rows = real(ls, scenarios[:half], sources, csr)
    return [dict(rows[i % half], scenario=i) for i in range(len(scenarios))]


def _what_if_unchanged(real, ls, scenarios, sources, csr):
    """A step that returns its state unchanged: no link of any scenario
    is failed."""
    return real(ls, [[] for _ in scenarios], sources, csr)


def test_converge_stale_decision_is_not_correct(monkeypatch):
    _stale_decision(monkeypatch)
    result, _ = run_tiny("fabric10k.converge")
    assert not result["correct"]
    assert result["compared"]["routes_wrong"]["value"] > 0


def test_converge_altered_next_hops_is_not_correct(monkeypatch):
    _drop_next_hop(monkeypatch)
    result, _ = run_tiny("fabric10k.converge")
    assert not result["correct"]


@pytest.mark.parametrize(
    "fault", [_what_if_altered, _what_if_half_batch, _what_if_unchanged]
)
def test_what_if_fault_is_not_correct(monkeypatch, fault):
    _what_if_patch(monkeypatch, fault)
    result, _ = run_tiny("grid10k.whatif")
    assert not result["correct"]
    assert result["compared"]["rows_wrong"]["value"] > 0
