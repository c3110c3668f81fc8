"""Benchmark orchestrator: one function per paper table.

Prints ``name,us_per_call,derived`` CSV rows:
  * table1_mul_<bits>   -- our batched multiplication (paper Table 1
                           col 3); derived = limb-mults/s throughput
  * table1_div_<bits>   -- our batched division; derived = div/mul
                           ratio (paper Table 1 col 5, target ~5-7x)
  * costmodel_<bits>    -- full-multiplication count (median; paper
                           Sec 2.3, target [5, 7])
  * bigserve            -- end-to-end batched division service latency
  * roofline summary    -- from dry-run records when present
"""

from __future__ import annotations

import sys

from repro.utils import compile_cache


def main() -> None:
    rows = []

    from . import table1_div
    for r in table1_div.run(sizes=(2 ** 10, 2 ** 12, 2 ** 14),
                            validate=True):
        us_mul = r["mul_ms"] * 1e3
        us_div = r["div_ms"] * 1e3
        m = r["bits"] // 16
        thru = r["insts"] * m * m / (r["mul_ms"] / 1e3)
        rows.append((f"table1_mul_{r['bits']}", us_mul,
                     f"{thru:.3e}_limbmults_per_s"))
        rows.append((f"table1_div_{r['bits']}", us_div,
                     f"{r['div_over_mul']:.2f}x_mul"))
        assert r["exact"], "division mismatch vs python ints"

    from . import costmodel
    for r in costmodel.run(sizes=(256, 1024), trials=25):
        rows.append((f"costmodel_{r['bits']}", 0.0,
                     f"median_{r['median']}_full_mults"))

    from . import bigserve
    r = bigserve.run()
    rows.append(("bigserve_batch256", r["us_per_batch"],
                 f"{r['divs_per_s']:.0f}_divs_per_s"))

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    # roofline summary (if the dry-run sweep has been run)
    try:
        from . import roofline
        recs = roofline.load()
        if recs:
            ok = sum(1 for x in recs if x["status"] == "ok")
            sk = sum(1 for x in recs if x["status"] == "skipped")
            er = sum(1 for x in recs if x["status"] == "error")
            print(f"# dryrun cells: {ok} ok / {sk} skipped / {er} error")
    except Exception as e:                       # noqa: BLE001
        print(f"# roofline summary unavailable: {e}", file=sys.stderr)


if __name__ == "__main__":
    compile_cache.enable()
    main()
