"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The last line of standard output is
one JSON object (`correct`, `attempted`, `failed`, `metrics`, `device`,
with `--trace 1` also `breakdown`, and last `compared`: each number the
correctness check compared, beside its limit).  Without a TPU, or with
fewer chips than the cell asks for, it prints no result and exits 2;
where a per-layer metric the cell declares reads nothing in a traced
run, it prints no result and exits 3.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def finite(x):
    """JSON has no infinity: a latency that never came reads null."""
    if isinstance(x, float) and not math.isfinite(x):
        return None
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [finite(v) for v in x]
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import jax
    import harness
    cache = harness.enable_compile_cache(jax)
    harness.log(f"[bench] compile cache {cache}")
    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START)
    except harness.NoDevice as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    except harness.MissingMetric as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 3
    print(json.dumps(finite(out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
