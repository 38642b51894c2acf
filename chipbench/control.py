#!/usr/bin/env python3
"""Readings for the limits of a cell's correctness comparison, on the
chip, in one process (the benchmark's own runs never run this):

    python3 chipbench/control.py --workload <name> --seconds <s> \
        --program-seeds 1,2,... --control-seeds 7,8,9

For each program seed, a whole run of the cell (set-up loop, window,
reference, comparison) with the program; for each control seed, the
same with the plain reference put in the program's place and computed
in bfloat16, the precision below the float32 the configuration states.
One JSON line per run: the seed, which side, ``correct``, the loops and
each compared number.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def _seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--program-seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from chipbench import harness
    cell = harness.load_cell(ROOT, args.workload)
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    ref = cell.module("reference", cell.cfg["app"])
    runs = ([("program", s, None) for s in args.program_seeds]
            + [("control", s, ref.chunk_fn(cell.cfg, jnp.bfloat16))
               for s in args.control_seeds])
    for side, seed, chunk_fn in runs:
        out = harness.run_cell(cell, seed=seed, seconds=args.seconds,
                               trace=False, t_start=time.perf_counter(),
                               chunk_fn=chunk_fn)
        print(json.dumps({
            "workload": cell.name, "side": side, "seed": seed,
            "correct": out["correct"], "attempted": out["attempted"],
            "loop_s": out["metrics"].get("loop_s", {}).get("value"),
            "checks": {k: c["value"] for k, c in out["checks"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
