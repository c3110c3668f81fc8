"""Run a cell's control: the operation's reference, with one guarantee
broken (`ops/<op>.py` `Control`), served in the program's place through
the same frontend, window and comparison as a benchmark run.  Its
`correct` has to read false; the numbers it compares are the upper
readings that the limits in `PERF.md` are set against.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 10

Prints one JSON line per seed.  The benchmark's own runs never run it.
The control is pure Python and never touches the device, so it runs on
any machine, the CPU included, and reads the same there.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    import harness
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run(args.workload, seed, args.seconds, False,
                          t_start=time.perf_counter(), control=True,
                          need_chip=False)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "compared": out["compared"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
