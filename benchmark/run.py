"""The benchmark's command (BENCHMARK.json "command"):

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell on the chips of the machine it starts on and prints, as the
last line of stdout, one JSON result. On a machine without a TPU, or with
fewer chips than the cell asks for, it exits 2 and prints no result.
"""

import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(1, str(Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from lib.harness import NoChip, run_cell

    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_process=T_PROCESS)
    except NoChip as exc:
        print(f"no result: {exc}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)  # "checks" is its last key
    return 0


if __name__ == "__main__":
    sys.exit(main())
