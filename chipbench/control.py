"""Read the numbers the check compares, on sound runs and under the
control, at a cell's own size: the readings each limit is set from.

Usage (on the chip):
    python chipbench/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds <s> [--plant fit_control|<fault>]

Runs the cell once per seed in this one process (set-up, window, check),
with the named control or fault planted underneath when ``--plant`` is
given (see ``chipbench/controls.py``), and prints one JSON line per seed
with ``correct`` and every compared number beside its limit.  The
benchmark's own runs never plant anything.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Patches:
    """``setattr`` that remembers what it replaced, for ``undo``."""

    def __init__(self):
        self.saved = []

    def setattr(self, obj, name, value, raising=True):
        self.saved.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    def undo(self):
        while self.saved:
            obj, name, value = self.saved.pop()
            setattr(obj, name, value)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--plant", default="")
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import controls, harness
    from repro.compile_cache import enable_compile_cache

    harness.compile_counter()
    enable_compile_cache()
    bench = harness.load_benchmark()
    for seed in (int(s) for s in args.seeds.split(",")):
        patches = _Patches()
        if args.plant:
            controls.install(args.plant, patches)
        t = time.perf_counter()
        try:
            run = harness.build_run(args.workload, seed, args.seconds, False,
                                    bench=bench)
            res = harness.run_cell(run, bench=bench)
        finally:
            patches.undo()
        print(json.dumps({
            "workload": args.workload, "seed": seed, "plant": args.plant,
            "correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": res["metrics"],
            "wall_s": time.perf_counter() - t, "checks": res["checks"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
