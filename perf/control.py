"""The control of a cell's comparison, run at the cell's own size:

    python -m perf.control --workload <cell> --seeds <n,n,...> --seconds <s>

For each seed, one run of the cell as `perf.run` makes it, judged as
`perf.run` judges it; then the control's answers are put where the
program's went (each driver's `put_control`: the reference with one of
the configuration's guarantees broken) and judged again by the same
`compare` and the same verdict.  Each seed prints one line with both
verdicts and both sets of numbers beside their limits.  The control has
to come out `correct: false` on every seed.  The benchmark's own runs
never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from . import compile_stats, run


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="Read a cell's control on several seeds.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    root = os.getcwd()
    manifest = run.load_manifest(root)
    compile_stats.configure(root)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("perf.control: no TPU; nothing was run", file=sys.stderr)
        return 3
    stats = compile_stats.CompileStats()
    for seed in (int(s) for s in args.seeds.split(",")):
        result, report = run.run_cell(
            manifest, args.workload, seed, args.seconds, False, root,
            t_process=time.perf_counter(), stats=stats,
        )
        drv = report["driver"]
        drv.put_control()
        compared, correct = run.judge(drv)
        print(
            json.dumps(
                {
                    "seed": seed,
                    "program": {"correct": result["correct"], "compared": result["compared"]},
                    "control": {"correct": correct, "compared": compared},
                    "metrics": result["metrics"],
                    "samples": report["samples"],
                }
            ),
            flush=True,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
