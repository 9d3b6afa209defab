"""Run a cell on the chip at its own size with the control, or a fault,
planted (plants.py), for several seeds in one process:

    python3 benchmark/tests/control_on_chip.py --workload <cell> \
        --plant control --seconds 10 --seeds 1 2 3

Prints one line per seed with every compared number and ``correct``,
which must read false. The benchmark's own runs never run this."""

import argparse
import json
import sys
import time
from pathlib import Path

T_PROCESS = time.perf_counter()
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE), str(HERE.parents[1])]

import plants  # noqa: E402
from lib.harness import run_cell  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--plant", default="control",
                    choices=["control", *plants.FAULTS])
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    plant = getattr(plants, args.plant)
    patched: set = set()

    def once(obj, attr, value):
        """A class is patched once per process (a second wrap of a wrapped
        method would stack), a cluster's own objects on every run."""
        if isinstance(obj, type):
            if (obj, attr) in patched:
                return
            patched.add((obj, attr))
        setattr(obj, attr, value)

    for seed in args.seeds:
        result = run_cell(args.workload, seed, args.seconds, False,
                          t_process=T_PROCESS,
                          plant=lambda c: plant(c, once))
        print(json.dumps({"plant": args.plant, "seed": seed,
                          "correct": result["correct"],
                          "attempted": result["attempted"],
                          "checks": {k: v["value"] for k, v in
                                     result["checks"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
