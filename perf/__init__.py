"""The chip benchmark of openr-tpu (BENCHMARK.json at the repo root).

Driven by data: a configuration is `perf/configs/<name>.json`, a traffic
mix `perf/traffic/<name>.json` (read by the driver it names under
`perf/drivers/`), a per-layer metric `perf/layer_metrics/<name>.py`.
`python -m perf.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
runs one cell.  Nothing here but `run.py`'s set-up and the drivers
imports the program; the plain reference (`reference.py`) imports none
of it.
"""
