"""Acceptance gate for the device-residency engine (openr_tpu/device).

The scripted 25-flap sequence drives one LinkState through metric raises
and restores, node-overload set/clear and link-overload set/clear — both
directions of every knob — while querying the engine at source-set sizes
that cross shape-bucket boundaries.  Every step is asserted bit-exact
against the host Dijkstra oracle (LinkState.run_spf), and the counters
must prove the residency contract:

- ``full_restages == 1``: the graph is uploaded once, at first contact;
  all 25 flaps thereafter sync incrementally on device;
- bucket changes force >= 1 recompile of an evicted key, and the small
  ``max_programs`` budget forces >= 1 eviction;
- per-query staged bytes stay O(sources + changed slots), never O(graph)
  (the recorded attribution is the CPU-CI stand-in for the wan-scale
  device_vs_host wall claim; see docs/OPERATIONS.md).
"""

from __future__ import annotations

import pytest

from openr_tpu.decision import LinkState
from openr_tpu.decision.csr import CsrTopology
from openr_tpu.device import (
    ENGINE_COUNTER_KEYS,
    S_BUCKETS,
    DeviceResidencyEngine,
    EngineSanitizer,
)
from openr_tpu.utils.topo import grid_topology

from test_link_state import build


def _assert_oracle(engine, csr, ls, sources):
    got = engine.spf_results(csr, sources)
    assert set(got) == set(sources)
    for src in sources:
        oracle = ls.run_spf(src)
        res = got[src]
        assert {k: v.metric for k, v in oracle.items()} == {
            k: v.metric for k, v in res.items()
        }, src
        for n in oracle:
            assert oracle[n].next_hops == res[n].next_hops, (src, n)


def _flap_script(dbs):
    """25 attribute-only mutations: (db, kind, link, value) tuples.

    Attribute-only is load-bearing: none of these change the edge set, so
    csr.refresh stays in place and the engine must absorb every one of
    them as an incremental device update (full_restages frozen at 1).
    """
    muts = []
    # metric raise + restore on six distinct directed links
    for i in range(6):
        db = dbs[2 * i]
        lnk = db.adjacencies[0]
        muts.append((db, "metric", lnk, 40 + 10 * i))
        muts.append((db, "metric", lnk, 10))
    # node overload set + clear on four distinct nodes
    for i in range(4):
        db = dbs[3 * i + 1]
        muts.append((db, "node_overload", None, True))
        muts.append((db, "node_overload", None, False))
    # link overload (soft link-down) set + clear on two links
    for i in range(2):
        db = dbs[5 * i + 2]
        lnk = db.adjacencies[-1]
        muts.append((db, "link_overload", lnk, True))
        muts.append((db, "link_overload", lnk, False))
    # one unrestored metric change so the sequence ends off-baseline
    muts.append((dbs[7], "metric", dbs[7].adjacencies[1], 33))
    assert len(muts) == 25
    return muts


class TestTwentyFiveFlapSequence:
    def test_bit_exact_with_incremental_residency(self):
        dbs = grid_topology(5)  # 25 nodes, node_capacity 32
        ls = build(dbs)
        csr = CsrTopology.from_link_state(ls)
        names = ls.node_names
        # max_programs=2 with three source buckets in rotation: the third
        # key always evicts one of the other two, so the next rotation
        # recompiles it — the eviction/recompile half of the acceptance
        engine = DeviceResidencyEngine(max_programs=2)

        # first contact: the one and only full staging
        _assert_oracle(engine, csr, ls, [names[0]])
        c = engine.get_counters()
        assert c["device.engine.full_restages"] == 1
        initial_bytes = c["device.engine.bytes_staged"]
        assert initial_bytes > 0

        # every post-warmup dispatch runs under the transfer sanitizer:
        # all host->device traffic in the flap loop must go through the
        # engine's explicit device_put staging (sanitizer.py; compiles
        # are legitimate here — the bucket rotation forces evictions)
        san = EngineSanitizer(engine)
        attribution = []  # (flap index, staged bytes, query us)
        for i, (db, kind, lnk, val) in enumerate(_flap_script(dbs)):
            if kind == "metric":
                lnk.metric = val
            elif kind == "node_overload":
                db.is_overloaded = val
            else:
                lnk.is_overloaded = val
            ls.update_adjacency_database(db)
            assert csr.refresh(ls) is True, (i, kind)  # stayed in place
            # rotate source-set sizes across the 1 / 8 / 64 buckets
            size = (1, 5, 25)[i % 3]
            start = i % len(names)
            sources = (names + names)[start : start + size]
            with san.transfer_guard():
                _assert_oracle(engine, csr, ls, sources)
            attribution.append(
                (i, engine.last_query_bytes, engine.last_query_us)
            )

        c = engine.get_counters()
        # the residency contract: one upload, then 25 incremental syncs
        assert c["device.engine.full_restages"] == 1
        assert c["device.engine.incremental_updates"] == 25
        assert c["device.engine.queries"] == 26
        # three bucket keys under a two-program budget
        assert len(engine.cached_program_keys()) <= 2
        assert c["device.engine.evictions"] >= 1
        assert c["device.engine.compiles"] >= 4  # >=1 key compiled twice
        assert c["device.engine.bucket_misses"] == c["device.engine.compiles"]
        assert (
            c["device.engine.bucket_hits"]
            == c["device.engine.queries"] - c["device.engine.compiles"]
        )
        # per-query attribution: every warm query stages O(sources +
        # changed slots) bytes, nowhere near the initial graph upload
        worst = max(b for _, b, _us in attribution)
        assert worst < initial_bytes / 4, (worst, initial_bytes)
        assert all(us >= 0 for _, _b, us in attribution)

    def test_counters_pre_seeded_and_registry_shaped(self):
        engine = DeviceResidencyEngine()
        c = engine.get_counters()
        assert set(ENGINE_COUNTER_KEYS) <= set(c)
        assert all(v == 0 for v in c.values())
        assert all(k.startswith("device.engine.") for k in c)

    def test_bucket_ladder_is_monotone(self):
        assert S_BUCKETS == (1, 8, 64, 512)


class TestResidencyIdentity:
    def test_edge_set_change_rides_rewire_rung(self):
        """A bounded edge-set change no longer restages: the slot
        freelist keeps the ELL identity and the engine replays the
        rewire delta on device.  Attribute flaps before and after stay
        incremental."""
        dbs = grid_topology(4)
        ls = build(dbs)
        csr = CsrTopology.from_link_state(ls)
        engine = DeviceResidencyEngine()
        _assert_oracle(engine, csr, ls, ls.node_names[:2])
        assert engine.has_residency(csr) and engine.is_warm(csr)

        # attribute flap: incremental
        dbs[0].adjacencies[0].metric = 25
        ls.update_adjacency_database(dbs[0])
        assert csr.refresh(ls) is True
        assert engine.has_residency(csr) and not engine.is_warm(csr)
        _assert_oracle(engine, csr, ls, ls.node_names[:2])

        # edge-set change within capacity: rewire in place, same ell ->
        # residency survives, no second upload
        dbs[1].adjacencies = [
            a
            for a in dbs[1].adjacencies
            if a.other_node_name != dbs[1].adjacencies[-1].other_node_name
        ]
        ls.update_adjacency_database(dbs[1])
        assert csr.refresh(ls) is True  # rewired in place
        assert engine.has_residency(csr)
        _assert_oracle(engine, csr, ls, ls.node_names[:2])
        c = engine.get_counters()
        assert c["device.engine.full_restages"] == 1
        assert c["device.engine.rewires"] == 1
        assert c["device.engine.rewire_fallbacks"] == 0
        assert c["device.engine.incremental_updates"] == 1

    def test_node_set_change_forces_restage(self):
        """A rebuild (new ELL identity) is the one legitimate second
        upload: a node joining is out of rewire scope."""
        dbs = grid_topology(4)
        ls = build(dbs)
        csr = CsrTopology.from_link_state(ls)
        engine = DeviceResidencyEngine()
        _assert_oracle(engine, csr, ls, ls.node_names[:2])

        from test_link_state import adj, adj_db

        corner = dbs[0]  # node-0-0
        ls.update_adjacency_database(
            adj_db("newbie", [adj("newbie", corner.this_node_name)])
        )
        corner.adjacencies = corner.adjacencies + [
            adj(corner.this_node_name, "newbie")
        ]
        ls.update_adjacency_database(corner)
        assert csr.refresh(ls) is False  # rebuilt
        _assert_oracle(engine, csr, ls, ls.node_names[:2])
        c = engine.get_counters()
        assert c["device.engine.full_restages"] == 2
        assert c["device.engine.rewires"] == 0

    def test_drop_releases_residency(self):
        ls = build(grid_topology(3))
        csr = CsrTopology.from_link_state(ls)
        engine = DeviceResidencyEngine()
        engine.spf_results(csr, ls.node_names[:1])
        assert engine.has_residency(csr)
        engine.drop(csr)
        assert not engine.has_residency(csr)
        engine.spf_results(csr, ls.node_names[:1])
        assert engine.get_counters()["device.engine.full_restages"] == 2


# -- OCS rewire acceptance (ISSUE 11) ---------------------------------------


RING_N = 12


def _ring_dbs(chords):
    """RING_N-node ring plus the given chord set (pairs (i, j), i < j).

    Chords model OCS circuits: the ring is the static fabric, the chord
    set is the reprogrammable logical topology."""
    from test_link_state import adj, adj_db

    def nm(i):
        return f"r{i:02d}"

    adjs = {i: [] for i in range(RING_N)}
    for i in range(RING_N):
        j = (i + 1) % RING_N
        adjs[i].append(adj(nm(i), nm(j)))
        adjs[j].append(adj(nm(j), nm(i)))
    for i, j in sorted(chords):
        m = 1 + (i * 7 + j * 3) % 5
        adjs[i].append(adj(nm(i), nm(j), metric=m))
        adjs[j].append(adj(nm(j), nm(i), metric=m))
    return [adj_db(nm(i), adjs[i]) for i in range(RING_N)]


def _chord_candidates(chords):
    """Legal chords to add: not a ring edge, and no endpoint carrying
    two chords already (keeps in-degree within the build-time ELL row
    headroom, so every step stays a bounded rewire)."""
    deg = {}
    for i, j in chords:
        deg[i] = deg.get(i, 0) + 1
        deg[j] = deg.get(j, 0) + 1
    out = []
    for i in range(RING_N):
        for j in range(i + 2, RING_N):
            if i == 0 and j == RING_N - 1:
                continue  # ring edge
            if (i, j) in chords:
                continue
            if deg.get(i, 0) >= 2 or deg.get(j, 0) >= 2:
                continue
            out.append((i, j))
    return out


def _push_ring(ls, chords):
    for db in _ring_dbs(chords):
        ls.update_adjacency_database(db)


class TestOcsRewireAcceptance:
    """ISSUE 11 acceptance: >= 20 seeded bounded rewires (adds, removes,
    swaps within capacity) keep full_restages == 1, bit-exact against a
    cold host rebuild every step; overflow and mid-rewire faults demote
    cleanly to restage with counters accounted."""

    def _rewire_schedule(self, seed, steps):
        """Deterministic (op, chords-after) schedule starting from the
        4-chord baseline: remove / add / swap in rotation."""
        import random

        rng = random.Random(seed)
        chords = {(0, 5), (2, 8), (3, 9), (4, 10)}
        plan = [set(chords)]
        for step in range(steps):
            op = ("remove", "add", "swap")[step % 3]
            if op == "remove":
                chords.discard(rng.choice(sorted(chords)))
            elif op == "add":
                chords.add(rng.choice(_chord_candidates(chords)))
            else:
                chords.discard(rng.choice(sorted(chords)))
                chords.add(rng.choice(_chord_candidates(chords)))
            plan.append(set(chords))
        return plan

    def test_twenty_bounded_rewires_single_restage(self):
        plan = self._rewire_schedule(seed=1107, steps=20)
        ls = build(_ring_dbs(plan[0]))
        csr = CsrTopology.from_link_state(ls)
        assert csr.edge_capacity == 32  # 24 ring + 8 chord slots: tight
        engine = DeviceResidencyEngine()
        names = ls.node_names
        _assert_oracle(engine, csr, ls, names[:2])
        c0 = engine.get_counters()
        assert c0["device.engine.full_restages"] == 1
        initial_bytes = c0["device.engine.bytes_staged"]

        for step, chords in enumerate(plan[1:]):
            _push_ring(ls, chords)
            assert csr.refresh(ls) is True, (step, chords)  # rewired
            sources = [names[(step * 5 + k) % RING_N] for k in range(3)]
            # engine vs the host Dijkstra oracle
            _assert_oracle(engine, csr, ls, sources)
            # and bit-exact vs a COLD rebuild of the mirror itself
            cold = CsrTopology.from_link_state(ls)
            got = engine.spf_results(csr, sources)
            ref = cold.spf_from(sources)
            for s in sources:
                assert {k: v.metric for k, v in ref[s].items()} == {
                    k: v.metric for k, v in got[s].items()
                }, (step, s)
                for n in ref[s]:
                    assert ref[s][n].next_hops == got[s][n].next_hops

        c = engine.get_counters()
        assert c["device.engine.full_restages"] == 1  # the contract
        assert c["device.engine.rewires"] == 20
        assert c["device.engine.rewire_dispatches"] == 20
        assert c["device.engine.rewire_fallbacks"] == 0
        assert c["device.engine.rewire_slots"] >= 40  # >= 2 slots/rewire
        assert c["device.engine.rewire_rows"] >= 20
        assert c["device.engine.rewire_bytes_staged"] > 0
        # each rewire uploads O(touched slots + rows), bounded by the
        # one-time graph staging even on this toy topology
        assert c["device.engine.rewire_bytes_staged"] / 20 < initial_bytes

    def test_capacity_overflow_demotes_to_rebuild_restage(self):
        chords = {(0, 5), (2, 8), (3, 9), (4, 10)}
        ls = build(_ring_dbs(chords))
        csr = CsrTopology.from_link_state(ls)
        engine = DeviceResidencyEngine()
        _assert_oracle(engine, csr, ls, ls.node_names[:2])

        # 4 more chords do not fit the 32-slot bucket: the freelist
        # refuses, refresh falls back to a (larger-capacity) rebuild and
        # the engine restages — gracefully, never an error
        chords |= {(1, 6), (5, 11), (2, 7), (6, 10)}
        _push_ring(ls, chords)
        assert csr.refresh(ls) is False  # rebuilt
        assert csr.edge_capacity > 32
        _assert_oracle(engine, csr, ls, ls.node_names[:2])
        c = engine.get_counters()
        assert c["device.engine.full_restages"] == 2
        assert c["device.engine.rewires"] == 0
        assert c["device.engine.rewire_fallbacks"] == 0

    def test_mid_rewire_fault_demotes_to_restage(self):
        chords = {(0, 5), (2, 8), (3, 9)}
        ls = build(_ring_dbs(chords))
        csr = CsrTopology.from_link_state(ls)
        engine = DeviceResidencyEngine()
        _assert_oracle(engine, csr, ls, ls.node_names[:2])

        armed = {"n": 0}

        def hook(op):
            if op == "rewire" and armed["n"] == 0:
                armed["n"] = 1
                raise RuntimeError("injected mid-rewire device fault")

        engine.fault_hook = hook
        chords.discard((2, 8))
        chords.add((1, 7))
        _push_ring(ls, chords)
        assert csr.refresh(ls) is True  # host-side rewire fine
        _assert_oracle(engine, csr, ls, ls.node_names[:2])  # still exact
        c = engine.get_counters()
        assert c["device.engine.rewire_fallbacks"] == 1
        assert c["device.engine.full_restages"] == 2  # the demotion
        assert c["device.engine.rewires"] == 0
        # next rewire (fault disarmed) rides the rung again
        chords.discard((1, 7))
        chords.add((1, 6))
        _push_ring(ls, chords)
        assert csr.refresh(ls) is True
        _assert_oracle(engine, csr, ls, ls.node_names[:2])
        c = engine.get_counters()
        assert c["device.engine.rewires"] == 1
        assert c["device.engine.full_restages"] == 2

    def test_rewire_log_gap_demotes_to_restage(self):
        """A resident that fell behind the bounded delta window cannot
        replay a contiguous chain — it restages instead of erroring."""
        plan = self._rewire_schedule(seed=22, steps=6)
        ls = build(_ring_dbs(plan[0]))
        csr = CsrTopology.from_link_state(ls)
        csr.REWIRE_LOG_DEPTH = 4  # shrink the window for the test
        engine = DeviceResidencyEngine()
        _assert_oracle(engine, csr, ls, ls.node_names[:2])
        # six rewires with no sync in between: the log only retains 4
        for chords in plan[1:]:
            _push_ring(ls, chords)
            assert csr.refresh(ls) is True
        assert len(csr._rewire_log) == 4
        _assert_oracle(engine, csr, ls, ls.node_names[:2])
        c = engine.get_counters()
        assert c["device.engine.rewire_fallbacks"] == 1
        assert c["device.engine.full_restages"] == 2
        assert c["device.engine.rewires"] == 0

    def test_rewire_bumps_epoch_like_a_flap(self):
        """Serving epoch invalidation fires for rewires exactly as for
        flaps: a pinned epoch older than the rewire raises before any
        device work."""
        from openr_tpu.device import EpochMismatchError

        chords = {(0, 5), (2, 8), (3, 9)}
        ls = build(_ring_dbs(chords))
        csr = CsrTopology.from_link_state(ls)
        engine = DeviceResidencyEngine()
        _assert_oracle(engine, csr, ls, ls.node_names[:2])
        pinned = int(csr.version)

        chords.discard((0, 5))
        chords.add((1, 7))
        _push_ring(ls, chords)
        assert csr.refresh(ls) is True
        with pytest.raises(EpochMismatchError):
            engine.spf_results(
                csr, ls.node_names[:2], expect_epoch=pinned
            )
        c = engine.get_counters()
        assert c["device.engine.epoch_invalidations"] == 1
        assert c["device.engine.rewires"] == 0  # raised pre-sync
        # fresh pin dispatches normally through the rewire rung
        engine.spf_results(
            csr, ls.node_names[:2], expect_epoch=int(csr.version)
        )
        assert engine.get_counters()["device.engine.rewires"] == 1
