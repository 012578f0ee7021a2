"""Per-layer metric readers, one file per metric named as in
BENCHMARK.json (`<name>.py`), each with `read(ctx) -> float | None`.

`ctx` holds what a traced run collected over its window:
- `roots`: the program's obs trace roots (`openr_tpu.obs.trace.Span`:
  name, children, t_start_us, t_end_us) finished in the window;
- `counters`: `{"before": ..., "after": ...}`, the daemon's getCounters
  around the window;
- `trace`: `perf.trace_reduce.reduce` of the profiler trace, or None.

A reader that finds nothing to read returns None and the metric is left
out of the result line.  Shared helpers live in `_spans.py`.
"""
