"""Readings that set the limits of ``correct``: the program's numbers and
the control's, seed after seed, in one process.

    python3 -m bench.control --workload <cell> --seconds <s> --seeds <n> [<n> ...]

Each seed runs the cell's own window (``--seconds`` long, at the cell's
load) and then computes every number twice: for what the program
produced, and for the control, the reference put in the program's place
in the nearest precision below the configuration's (the planner in
bfloat16 for its float32 solve; the forward in three-pass bfloat16, what
TPUs call ``high``, for float32 at ``highest``).  One JSON line per seed;
the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

from bench import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.ROOT / "src"))
    import ml_dtypes
    spec = run.load_spec()
    for seed in args.seeds:
        x = run.execute(spec, args.workload, seed, args.seconds, False)
        prog = run.compare(x, seed)
        ctl = run.compare(x, seed, control=ml_dtypes.bfloat16)
        print(json.dumps({"seed": seed, "flushes": len(x["flushes"]),
                          "requests": sum(f.n for f in x["flushes"]),
                          "kept": len(x["answers"].get("kept", {})),
                          "program": {k: v["value"] for k, v in prog.items()},
                          "control": {k: v["value"] for k, v in ctl.items()}}),
              flush=True)
        del x
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
