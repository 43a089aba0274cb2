"""Readings that the limits of the ``correct`` comparison are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 ... \\
        --control-seeds 4 5 6 --seconds 3

For each of ``--seeds`` it runs the cell as ``bench/run.py`` does, with a
short window, and prints the numbers the comparison read (the lower
reading is the largest over the seeds).  For each of ``--control-seeds``
it puts the int8 control in the program's place: the reference computed
with int8 operands, compared with the float32 reference over the same pool
of images (the upper reading is the smallest over the seeds).  All in one
process, so that the program compiles once.  The benchmark's own runs
never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness, model  # noqa: E402


def control_reading(cell: dict, seed: int) -> dict:
    """The int8 control's numbers over the cell's pool of images."""
    config = model.load_config(cell["config"])
    traffic = harness.load_traffic(cell["traffic"])
    params = model.init_params(config, seed)
    pool = model.make_images(config, seed, traffic["pool_images"])
    reference = model.logits_in_blocks(config, params, pool)
    control = model.logits_in_blocks(config, params, pool, int8=True)
    return {"logit_rms_err": float(model.logit_rms_error(control, reference).max())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    cell = {w["name"]: w for w in harness.load_spec()["workloads"]}[args.workload]
    harness.device_info(cell["chips"])
    program = []
    for seed in args.seeds:
        result = harness.run_cell(args.workload, seed, args.seconds, False,
                                  time.perf_counter(), say=lambda s: None)
        checks = {k: v["value"] for k, v in result["checks"].items()}
        program.append(checks)
        print(json.dumps({"side": "program", "seed": seed, "checks": checks,
                          "attempted": result["attempted"]}), flush=True)
    control = []
    for seed in args.control_seeds:
        control.append(control_reading(cell, seed))
        print(json.dumps({"side": "control", "seed": seed, "checks": control[-1]}),
              flush=True)
    names = sorted({k for c in control for k in c} | {k for c in program for k in c})
    print(json.dumps({"workload": args.workload,
                      "lower": {k: max(c[k] for c in program) for k in names if program},
                      "upper": {k: min(c[k] for c in control) for k in names
                                if control and k in control[0]}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
