#!/usr/bin/env python3
"""Run one cell of the chip benchmark once, on the TPU it is started on.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``BENCHMARK.json`` -> ``workloads``) names a configuration and
a traffic mix; ``chipbench/harness.py`` says how they are found.  The
last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each number the
correctness comparison made, beside its limit.  The same checks are the
last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness
    import repro  # noqa: F401  (the system under test must be there)
    cell = harness.load_cell(ROOT, args.workload)
    import jax
    devices = jax.devices()
    chips = cell.workload["chips"]
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"chipbench: cell {cell.name} needs {chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    out = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                           trace=bool(args.trace), t_start=T_START)
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
