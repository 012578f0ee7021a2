"""Run one benchmark cell:

    python -m perf.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell (BENCHMARK.json `workloads`)
names a configuration (`perf/configs/<config>.json`) and a traffic mix
(`perf/traffic/<traffic>.json`, whose `driver` names the module under
`perf/drivers/` that drives it).  A run:

1. builds the deployment and starts one daemon, which full-syncs its
   KvStore from a peer store (boot convergence: the cold route build);
2. warms every shape the window uses (the driver's `warm`);
3. drives the traffic for `--seconds` (the driver's `window`); with
   `--trace 1` it arms the program's obs spans for the window and records
   a profiler trace of a steady part of it;
4. stops the daemon and compares what the window produced with the plain
   reference (the driver's `compare`);
5. prints the set-up's parts, the sample counts and any compile inside
   the window, then every compared number beside its limit (the last
   lines on stderr), then one JSON result line (the last line on stdout).

It exits non-zero, printing no result, without a TPU or with fewer chips
than the cell asks for, and where the program is not in the checkout.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

from . import compile_stats, deployment, trace_reduce  # noqa: E402

PERF_DIR = os.path.dirname(os.path.abspath(__file__))
TRACE_DIR = ".perf_trace"  # under the checkout, git-ignored, removed after reading
OBS_RING = 1 << 16


def load_manifest(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find(entries: list, name: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(name)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell_metrics(manifest: dict, cell: str) -> tuple[list, list]:
    """The cell's end-to-end metrics, and the per-layer metrics that are
    read in it: those that list it, or list no cells and move one of
    its end-to-end metrics."""
    e2e = [m for m in manifest["end_to_end"] if applies(m, cell)]
    names = {m["name"] for m in e2e}
    layer = [
        m
        for m in manifest["per_layer"]
        if (cell in m["workloads"] if "workloads" in m else m["moves"] in names)
    ]
    return e2e, layer


def load_reader(name: str):
    path = os.path.join(PERF_DIR, "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"perf_layer_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Profiler(threading.Thread):
    """Records a profiler trace of [start_s, start_s + length_s] of the
    window, from its own thread."""

    def __init__(self, log_dir: str, start_s: float, length_s: float) -> None:
        super().__init__(name="perf-profiler", daemon=True)
        self.log_dir, self.start_s, self.length_s = log_dir, start_s, length_s
        self.t0_ns = self.t1_ns = 0
        self.error = None

    def run(self) -> None:
        import jax

        try:
            time.sleep(self.start_s)
            # the Python tracer off: it slows the daemon's host code
            # several times over, and the spans time that code already
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            self.t0_ns = time.perf_counter_ns()
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            time.sleep(self.length_s)
            self.t1_ns = time.perf_counter_ns()
            jax.profiler.stop_trace()
        except Exception as e:  # reported by the caller
            self.error = e


def _span_intervals(roots) -> list:
    out = []

    def walk(sp):
        if sp.t_end_us is not None:
            out.append((sp.name, sp.t_start_us * 1000, sp.t_end_us * 1000))
        for c in sp.children:
            walk(c)

    for r in roots:
        walk(r)
    return out


def judge(drv) -> tuple[dict, bool]:
    """Every number the driver compares beside its limit, and whether
    all are within: the verdict `correct`."""
    limits = drv.traffic["limits"]
    compared = {k: {"value": v, "limit": limits[k]} for k, v in drv.compare().items()}
    return compared, all(c["value"] <= c["limit"] for c in compared.values())


def run_cell(
    manifest: dict,
    cell_name: str,
    seed: int,
    seconds: float,
    trace: bool,
    root: str,
    t_process: float = T_PROCESS,
    config_file: str | None = None,
    stats=None,
) -> tuple[dict, dict]:
    """One run of a cell; returns (result line, report of earlier lines,
    with the driver under "driver" for `perf.control`).  `config_file`
    overrides the cell's configuration (tests)."""
    import jax

    from .harness import Harness

    cell = find(manifest["workloads"], cell_name)
    cfg_entry = find(manifest["configs"], cell["config"])
    cfg_path = config_file or os.path.join(root, cfg_entry["file"])
    cfg = deployment.load_config(cfg_path)
    cfg["_path"] = cfg_path
    with open(os.path.join(PERF_DIR, "traffic", f"{cell['traffic']}.json")) as f:
        traffic = json.load(f)
    driver = importlib.import_module(f"perf.drivers.{traffic['driver']}")
    e2e, layer = cell_metrics(manifest, cell_name)
    stats = stats or compile_stats.CompileStats()
    report: dict = {"setup": {}}

    t = time.perf_counter()
    topo = deployment.build(cfg)
    report["setup"]["topology_s"] = time.perf_counter() - t
    h = Harness(cfg, topo)
    drv = None
    prof = None
    try:
        h.start()
        report["setup"].update(h.parts)
        drv = driver.Driver(h, topo, cfg, traffic, seed, root)
        t = time.perf_counter()
        drv.warm()
        report["setup"]["warm_s"] = time.perf_counter() - t
        setup_s = time.perf_counter() - t_process
        report["setup"]["compile"] = c_setup = stats.snapshot()
        before = h.counters()
        tracer = None
        if trace:
            from openr_tpu.obs import trace as obs

            tracer = obs.enable(ring=OBS_RING)
            trace_dir = os.path.join(root, TRACE_DIR)
            shutil.rmtree(trace_dir, ignore_errors=True)
            start = min(traffic["trace_start_s"], 0.2 * seconds)
            prof = Profiler(trace_dir, start, min(traffic["trace_s"], 0.7 * seconds))
        # a compile inside the window is logged by name (stderr)
        jax.config.update("jax_log_compiles", True)
        t_w0 = time.perf_counter()
        if prof is not None:
            prof.start()
        drv.window(seconds)
        jax.config.update("jax_log_compiles", False)
        if prof is not None:
            prof.join(timeout=seconds + 120)
        roots = []
        if tracer is not None:
            # the tracer's completed roots (no public accessor returns spans)
            roots = [r for r in tracer._ring if r.t_start_us >= t_w0 * 1e6]
            obs.disable()
        after = h.counters()
        c_end = stats.snapshot()
        report["window_compiles"] = {
            k: c_end[k] - c_setup[k] for k in ("executables", "cache_requests", "compile_or_load_s")
        }
        dev = jax.devices()
        device = {
            "platform": dev[0].platform,
            "kind": dev[0].device_kind,
            "count": len(dev),
            "memory_peak_bytes": max(
                (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in dev
            ),
        }
    finally:
        if drv is not None and hasattr(drv, "stop"):
            drv.stop()
        h.stop()
    report["samples"] = drv.samples()

    metrics: dict = {}
    breakdown = None
    if trace:
        reduced = None
        if prof is not None and prof.error is None and prof.t1_ns:
            path = trace_reduce.find_trace(os.path.join(root, TRACE_DIR))
            if path is not None:
                t0 = prof.t0_ns
                intervals = [
                    (n, s - t0, e - t0)
                    for n, s, e in drv.host_intervals() + _span_intervals(roots)
                ]
                reduced = trace_reduce.reduce(
                    path, prof.t1_ns - t0, intervals, traffic["gap_labels"]
                )
        elif prof is not None and prof.error is not None:
            report["trace_error"] = repr(prof.error)
        shutil.rmtree(os.path.join(root, TRACE_DIR), ignore_errors=True)
        ctx = {"roots": roots, "counters": {"before": before, "after": after}, "trace": reduced}
        for m in layer:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = {k: reduced[k] for k in ("device_ops", "idle_gaps")}
        report["spans"] = {"roots": len(roots)}
    else:
        values = dict(drv.metrics(seconds), setup_s=setup_s)
        for m in e2e:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    t = time.perf_counter()
    compared, correct = judge(drv)
    report["compare_s"] = time.perf_counter() - t
    report["driver"] = drv
    result = {
        "correct": correct,
        "attempted": drv.attempted,
        "failed": drv.failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    return result, report


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="Run one benchmark cell.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    root = os.getcwd()
    manifest = load_manifest(root)
    try:
        cell = find(manifest["workloads"], args.workload)
    except KeyError:
        print(f"perf.run: no cell {args.workload!r} in BENCHMARK.json", file=sys.stderr)
        return 2

    compile_stats.configure(root)
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"perf.run: JAX finds no device: {e}", file=sys.stderr)
        return 3
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(
            f"perf.run: cell {cell['name']} needs {cell['chips']} TPU chip(s); JAX "
            f"sees {len(devices)} {devices[0].platform} device(s). Nothing was run.",
            file=sys.stderr,
        )
        return 3
    try:
        import openr_tpu  # noqa: F401
    except ImportError as e:
        print(f"perf.run: the system under test is not in this checkout: {e}", file=sys.stderr)
        return 4

    stats = compile_stats.CompileStats()
    result, report = run_cell(
        manifest, args.workload, args.seed, args.seconds, bool(args.trace), root, stats=stats
    )
    print("setup: " + json.dumps(report["setup"]), flush=True)
    print("samples: " + json.dumps(report["samples"]), flush=True)
    print(f"compare: {report['compare_s']:.3f} s", flush=True)
    wc = report["window_compiles"]
    print(
        ("COMPILES INSIDE THE WINDOW: " if wc["executables"] else "window compiles: ")
        + json.dumps(wc),
        flush=True,
    )
    import resource

    print(
        "host: "
        + json.dumps({"maxrss_bytes": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024}),
        flush=True,
    )
    if "trace_error" in report:
        print("trace error: " + report["trace_error"], flush=True)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
