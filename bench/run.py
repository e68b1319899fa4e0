"""Run one cell of the benchmark and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout that holds ``BENCHMARK.json``, this
directory and the program (``src/``). It refuses to run off a TPU, or on
fewer chips than the cell asks for, and when a scheduling kernel ran off
the compiled Pallas path: it then exits with code 2 and prints no result.
Logs, and the numbers the check compared with their limits, go to
standard error; the last line of standard output is one JSON object.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    try:
        out = harness.run(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START)
    except (harness.NoChip, harness.OffKernel) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
