"""Both traffic drivers end to end on the CPU at a tiny size (a 2-pod
fabric, a 10 x 10 grid): the whole run as `perf.run` makes it, minus its
look for a TPU; the control put in the program's place comes out not
correct; set-up warms every rewire write a window uses."""

import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf import run  # noqa: E402

DATA = os.path.join(ROOT, "tests", "perf", "data")
CELLS = {
    "fabric10k.converge": "fabric_tiny.json",
    "grid10k.whatif": "grid_tiny.json",
    "fabric10k.whatif": "fabric_tiny.json",
}
SEED = 2**31 + 77  # more than 32 signed bits hold


def run_tiny(cell, seed=SEED, seconds=1.0, trace=False, config=None):
    manifest = run.load_manifest(ROOT)
    return run.run_cell(
        manifest,
        cell,
        seed,
        seconds,
        trace,
        ROOT,
        t_process=time.perf_counter(),
        config_file=os.path.join(DATA, config or CELLS[cell]),
    )


def _limits_hold(result):
    return all(c["value"] <= c["limit"] for c in result["compared"].values())


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_runs_correct_with_its_metrics(cell):
    result, report = run_tiny(cell)
    assert result["correct"] and _limits_hold(result)
    assert result["attempted"] > 0 and result["failed"] == 0
    manifest = run.load_manifest(ROOT)
    e2e, _ = run.cell_metrics(manifest, cell)
    assert set(result["metrics"]) == {m["name"] for m in e2e}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result)[-1] == "compared"  # the compared numbers come last
    assert report["window_compiles"]["executables"] == 0
    assert {"topology_s", "full_sync_and_cold_build_s", "warm_s"} <= set(report["setup"])


@pytest.mark.parametrize("cell", ["fabric10k.converge", "grid10k.whatif"])
def test_traced_run_reads_span_and_counter_metrics(cell):
    result, report = run_tiny(cell, trace=True)
    assert result["correct"]
    manifest = run.load_manifest(ROOT)
    _, layer = run.cell_metrics(manifest, cell)
    names = {m["name"] for m in layer if m["source"] != "device_trace"}
    # no device plane on the CPU: the device readers find nothing
    assert set(result["metrics"]) == names
    assert report["spans"]["roots"] > 0


@pytest.mark.parametrize("seed", [11, 2**31 + 5, 4242])
@pytest.mark.parametrize("cell", ["fabric10k.converge", "grid10k.whatif"])
def test_control_in_the_programs_place_is_not_correct(cell, seed):
    result, report = run_tiny(cell, seed=seed)
    assert result["correct"]
    drv = report["driver"]
    drv.put_control()
    compared, correct = run.judge(drv)
    assert not correct
    assert set(compared) == set(result["compared"])


def test_warm_up_obtains_every_rewire_write_the_window_uses(monkeypatch):
    """The engine pads each flap's writes to a power-of-two bucket; the
    set-up's flaps drive every bucket a window's flap then drives."""
    from openr_tpu.device import engine
    from perf.drivers import link_events

    seen = {"warm": set(), "window": set()}
    phase = ["warm"]
    real_pad = engine._pad_updates

    def pad(idx, vals, pad_val):
        out = real_pad(idx, vals, pad_val)
        seen[phase[0]].add(len(out[0]))
        return out

    real_window = link_events.Driver.window

    def window(self, seconds):
        phase[0] = "window"
        return real_window(self, seconds)

    monkeypatch.setattr(engine, "_pad_updates", pad)
    monkeypatch.setattr(link_events.Driver, "window", window)
    # the tiny fabric stays on the host Dijkstra; this one reaches the engine
    result, report = run_tiny("fabric10k.converge", seconds=3.0, config="fabric_mid.json")
    assert result["correct"] and seen["window"]
    assert seen["window"] <= seen["warm"]
    assert report["window_compiles"]["executables"] == 0
