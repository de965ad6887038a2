#!/usr/bin/env python3
"""The control of a cell, or a fault planted in its timed path, at the
cell's own size on the chip: each seed's numbers compared, one JSON line a
seed.

    python3 chipbench/tests/run_control.py --workload er20.walk \
        --seconds 10 --seeds 11 12 13 [--fault walks_half_left_out]

The control of a walk cell is the program walking with q = 1 (a
first-order walk that breaks the configured law); that of the SGNS cell is
the reference computed in bfloat16 in the trainer's place. Faults are
named as in ``faults.py``. The benchmark's
own runs never run it; ``test_faults.py`` runs it at a tiny size on the CPU.
"""
import argparse
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", default=None,
                    help="run with this fault of faults.py planted in the "
                         "timed path, in place of the control")
    args = ap.parse_args()

    import jax
    from chipbench.core import spec
    from chipbench.run import run_cell
    from repro.launch.compile_cache import enable_compile_cache
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("run_control: needs a TPU", file=sys.stderr)
        return 1
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = spec.load_cell(args.workload)
    if args.fault:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import faults
        # planted for the life of the process
        {**faults.WALK, **faults.TRAIN}[args.fault](
            types.SimpleNamespace(setattr=setattr))
    for seed in args.seeds:
        r = run_cell(cell, seed, args.seconds, False, devices[:cell.chips],
                     control=not args.fault, t_process=time.perf_counter())
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "fault": args.fault or "control",
                          "correct": r["correct"],
                          "compared": {k: v["value"] for k, v in
                                       r["compared"].items()},
                          "metrics": r["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
