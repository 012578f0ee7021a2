"""Coverage-guided chaos fuzzer: tier-1 smoke, determinism, shrinker,
and the auto-collected chaos_corpus regression replays.

The smoke is BUDGETED: a wall budget sheds runs
loudly (`session.shed`) instead of letting a slow box time the whole
suite out — a shed smoke FAILS with a message naming the knob, never
hangs.  The `-m slow` soak logs its seed so any failure replays.
"""

from __future__ import annotations

import glob
import os
import time

import pytest

from openr_tpu.chaos import fuzz as fz

pytestmark = pytest.mark.fuzz

CORPUS_DIR = os.path.join(os.path.dirname(__file__), "chaos_corpus")

# acceptance: >= 25 mutated/crossover timelines in the tier-1 smoke
SMOKE_N = 26
SMOKE_SEED = 20260807
# generous on purpose: ~0.7s/run warm on a 1-CPU box + first-contact
# compiles; the budget exists to shed loudly on a pathological box, not
# to race a healthy one
SMOKE_BUDGET_S = 420.0


def _corpus_entries() -> list:
    return sorted(glob.glob(os.path.join(CORPUS_DIR, "*.json")))


def _load(path: str) -> fz.FuzzTimeline:
    with open(path) as fh:
        return fz.FuzzTimeline.loads(fh.read())


class TestFuzzSmoke:
    def test_smoke_oracles_coverage_and_same_seed_replay(self):
        c0 = fz.FUZZ_COUNTERS.get_counters()
        t0 = time.monotonic()
        s1 = fz.fuzz(SMOKE_N, seed=SMOKE_SEED, budget_s=SMOKE_BUDGET_S)
        wall = time.monotonic() - t0
        assert s1.shed == 0, (
            f"fuzz smoke shed {s1.shed}/{SMOKE_N} runs after "
            f"{wall:.0f}s — box too slow for the {SMOKE_BUDGET_S:.0f}s "
            "budget; raise SMOKE_BUDGET_S / OPENR_FUZZ_BUDGET_S"
        )
        assert len(s1.results) == SMOKE_N

        # every timeline composes >= 3 chaos families and every oracle
        # in the bundle holds on every run
        for res in s1.results:
            fams = res.timeline.families()
            assert len(fams) >= 3, (sorted(fams), res.timeline.dumps())
            assert res.ok, (res.failures, res.timeline.dumps())

        # the coverage fingerprint strictly grows over the run:
        # cumulative token count is monotone and the searched part
        # (mutants + crossovers) discovers tokens the seeds didn't
        hist = s1.coverage_history
        assert hist == sorted(hist)
        assert hist[-1] > hist[0]
        assert hist[-1] > hist[2], (
            "mutation/crossover search added no coverage beyond the 3 "
            "seed timelines"
        )

        # novelty + mutation + crossover all actually exercised
        c1 = fz.FUZZ_COUNTERS.get_counters()
        assert c1["chaos.fuzz.runs"] - c0["chaos.fuzz.runs"] == SMOKE_N
        assert c1["chaos.fuzz.mutations"] > c0["chaos.fuzz.mutations"]
        assert c1["chaos.fuzz.crossovers"] > c0["chaos.fuzz.crossovers"]
        assert (
            c1["chaos.fuzz.novel_fingerprints"]
            > c0["chaos.fuzz.novel_fingerprints"]
        )

        # same-seed rerun: identical corpus, identical timelines,
        # identical per-run event logs (ChaosEventLog.matches) and
        # fingerprints — the determinism contract that makes any corpus
        # entry a replayable reproducer
        s2 = fz.fuzz(SMOKE_N, seed=SMOKE_SEED, budget_s=SMOKE_BUDGET_S)
        assert [t.to_json() for t in s1.corpus] == [
            t.to_json() for t in s2.corpus
        ]
        assert len(s2.results) == len(s1.results)
        for a, b in zip(s1.results, s2.results):
            assert a.timeline.to_json() == b.timeline.to_json()
            assert a.log.matches(b.log)
            assert a.fingerprint == b.fingerprint
            assert a.counters == b.counters

    def test_single_timeline_replay_is_deterministic(self):
        t = fz.seed_timeline(5)
        r1 = fz.run_timeline(t)
        r2 = fz.run_timeline(t)
        assert r1.ok and r2.ok, (r1.failures, r2.failures)
        assert r1.log.matches(r2.log)
        assert r1.fingerprint == r2.fingerprint
        assert r1.counters == r2.counters

    def test_corpus_json_round_trips(self):
        t = fz.seed_timeline(9)
        again = fz.FuzzTimeline.loads(t.dumps())
        assert again.to_json() == t.to_json()
        with pytest.raises(ValueError, match="corpus version"):
            fz.FuzzTimeline.from_json({"version": 99, "seed": 0})


class TestShrinker:
    def test_planted_bug_found_and_shrunk_end_to_end(self):
        c0 = fz.FUZZ_COUNTERS.get_counters()
        s = fz.fuzz(6, seed=7, plant=True, stop_on_failure=True)
        assert s.failures, "fuzzer missed the planted kv-ledger bug"
        bad = s.failures[0]
        assert "ledger_kv" in bad.failures

        mini = fz.shrink(bad.timeline, plant=True, oracle="ledger_kv")
        assert len(mini.events) <= 10, mini.dumps()
        assert len(mini.events) < len(bad.timeline.events)
        assert mini.oracle == "ledger_kv"
        c1 = fz.FUZZ_COUNTERS.get_counters()
        assert c1["chaos.fuzz.shrink_steps"] > c0["chaos.fuzz.shrink_steps"]
        assert (
            c1["chaos.fuzz.oracle_failures"] > c0["chaos.fuzz.oracle_failures"]
        )

        # the minimal reproducer reproduces: fails armed, passes unarmed
        armed = fz.run_timeline(mini, plant=True)
        assert not armed.ok and "ledger_kv" in armed.failures
        clean = fz.run_timeline(mini)
        assert clean.ok, clean.failures

    def test_shrink_refuses_a_clean_timeline(self):
        t = fz.FuzzTimeline(
            seed=1, events=[fz.FuzzEvent("engine", "spf", {"off": 0})]
        )
        with pytest.raises(ValueError, match="does not violate"):
            fz.shrink(t)


class TestChaosCorpus:
    """Every checked-in reproducer replays as a tier-1 regression."""

    def test_corpus_directory_is_nonempty(self):
        assert _corpus_entries(), (
            f"no corpus entries under {CORPUS_DIR} — the shrinker's "
            "end-to-end proof entry must stay checked in"
        )

    @pytest.mark.parametrize(
        "path", _corpus_entries(), ids=[os.path.basename(p) for p in _corpus_entries()]
    )
    def test_corpus_entry_replays_clean_unarmed(self, path):
        res = fz.run_timeline(_load(path))
        assert res.ok, (os.path.basename(path), res.failures)

    def test_planted_reproducer_still_fails_armed(self):
        path = os.path.join(CORPUS_DIR, "planted_kv_ledger.json")
        t = _load(path)
        assert t.oracle == "ledger_kv" and len(t.events) <= 10
        res = fz.run_timeline(t, plant=True)
        assert not res.ok and "ledger_kv" in res.failures


class TestFuzzCli:
    def test_cli_fuzz_shrink_and_budget_shed(self, tmp_path, capsys):
        out = tmp_path / "corpus"
        assert fz.main(["--fuzz-n", "2", "--seed", "11", "--out", str(out)]) == 0

        # planted session: finds, shrinks, writes reproducers, rc 1
        # (seed 7's second seed timeline carries a TTL storm, so the
        # planted ledger bug is reachable within two runs)
        rc = fz.main(
            ["--fuzz-n", "2", "--seed", "7", "--plant", "--out", str(out)]
        )
        assert rc == 1
        entries = sorted(out.glob("*.json"))
        assert entries and all("ledger_kv" in e.name for e in entries)

        # --shrink mode writes <entry>.min.json next to the input
        rc = fz.main(["--shrink", str(entries[0]), "--plant"])
        assert rc == 0
        assert (out / (entries[0].name[: -len(".json")] + ".min.json")).exists()

        # an exhausted budget sheds loudly instead of hanging: with a
        # sub-second budget the shed note names the knob on stderr
        capsys.readouterr()
        assert fz.main(["--fuzz-n", "50", "--seed", "11", "--budget-s", "0.01"]) == 0
        err = capsys.readouterr().err
        assert "shedding" in err and "--budget-s" in err


@pytest.mark.slow
class TestFuzzSoak:
    def test_long_fuzz_soak_logs_its_seed(self):
        seed = int(os.environ.get("OPENR_FUZZ_SEED", "0"))
        budget = float(os.environ.get("OPENR_FUZZ_BUDGET_S", "900"))
        print(
            f"chaos.fuzz soak: seed={seed} budget={budget:.0f}s "
            "(reproduce with OPENR_FUZZ_SEED)"
        )
        s = fz.fuzz(200, seed=seed, budget_s=budget)
        for res in s.results:
            assert res.ok, (
                f"seed={seed}",
                res.failures,
                res.timeline.dumps(),
            )
        assert s.coverage_history[-1] >= s.coverage_history[0]
