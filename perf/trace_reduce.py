"""Reduce a `jax.profiler` trace (`.xplane.pb`) to what the ledger keeps.

- busy: the union of the device's op intervals inside the traced
  window, averaged over the chips traced;
- device ops: total time per op name, the largest first;
- idle gaps: the stretches inside the window where no op ran on any
  chip, the longest first, each labelled with what the host was doing
  then (the benchmark's own stages and the program's spans, given as
  host-clock intervals).

Shared by every cell; it imports nothing of the program.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PREFIXES = ("/device:TPU:", "/device:GPU:")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
MIN_GAP_NS = 1000  # shorter stretches between ops are not idle time worth naming


def find_trace(log_dir: str):
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    return paths[-1] if paths else None


def op_name(module: str, op: str) -> str:
    """`<program>:<op>`: the jitted program without its fingerprint and
    the HLO instruction's name without its text (`%while.11 = (...) ...`
    becomes `while.11`)."""
    module = re.sub(r"\(\d+\)$", "", module)
    return f"{module}:{op.split(' = ', 1)[0].lstrip('%')}"


def load_device_ops(path: str) -> list[list[tuple[str, int, int]]]:
    """Per traced chip, its ops as (name, start_ns, end_ns), on the
    trace's own clock (nanoseconds from the start of tracing)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    chips = []
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PREFIXES):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        modules = sorted(
            (int(ev.start_ns), ev.name) for ev in lines.get(MODULES_LINE, [])
        )
        starts = [m[0] for m in modules]
        ops = []
        for ev in lines[OPS_LINE]:
            s = int(ev.start_ns)
            i = bisect.bisect_right(starts, s) - 1
            module = modules[i][1] if i >= 0 else "?"
            ops.append((op_name(module, ev.name), s, s + int(ev.duration_ns)))
        chips.append(ops)
    return chips


def _union(spans) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _label(t: int, intervals, priority) -> str:
    """The highest-priority host stage open at `t`, else "none"."""
    open_ = {name for name, s, e in intervals if s <= t < e}
    for name in priority:
        if name in open_:
            return name
    return "none"


def reduce_ops(chips, window_ns: int, intervals=(), priority=()):
    """Busy and idle over [0, window_ns] from per-chip op lists; None
    when no chip was traced or no op ran (nothing to read)."""
    if not chips:
        return None
    clipped = [
        [(n, max(s, 0), min(e, window_ns)) for n, s, e in ops if e > 0 and s < window_ns]
        for ops in chips
    ]
    if not any(clipped):
        return None
    busy = [sum(e - s for s, e in _union((s, e) for _, s, e in ops)) for ops in clipped]
    per_op: dict[str, int] = {}
    for ops in clipped:
        for n, s, e in ops:
            per_op[n] = per_op.get(n, 0) + (e - s)
    all_busy = _union((s, e) for ops in clipped for _, s, e in ops)
    gaps, t = [], 0
    for s, e in all_busy + [(window_ns, window_ns)]:
        if s - t >= MIN_GAP_NS:
            gaps.append((t, s))
        t = max(t, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    n = len(chips)
    return {
        "busy_s": sum(busy) / n / 1e9,
        "window_s": window_ns / 1e9,
        "device_ops": [
            [name, ns / n / 1e9]
            for name, ns in sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
        ],
        "idle_gaps": [
            [_label((s + e) // 2, intervals, priority), (e - s) / 1e9]
            for s, e in gaps[:TOP]
        ],
    }


def reduce(path: str, window_ns: int, intervals=(), priority=()):
    return reduce_ops(load_device_ops(path), window_ns, intervals, priority)
