#!/usr/bin/env python3
"""The control of ``correct``, at a cell's own size: the plain
reference with one stated guarantee broken (exact delivery; see
reference/netref.py), put in the program's place and compared with the
reference as lib/compare.py compares the program. Each seed's numbers
must come out not correct; the smallest of them is the upper reading
the limits in PERF.md rest on. Host only: no chip is used.

  python3 perfbench/control_check.py --workload NAME --seeds 1,2,3
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from perfbench.lib import compare, deploy
    from perfbench.lib.spec import Spec

    spec = Spec(ROOT)
    cell = spec.cell(args.workload)
    config = spec.config(cell)
    ref = importlib.import_module(
        f"perfbench.reference.{config['reference']}")
    failing = True
    for seed in (int(s) for s in args.seeds.split(",")):
        dep = deploy.build(config, spec.traffic(cell), seed)
        t = time.perf_counter()
        stats, names = ref.run(dep)
        ctl, ctl_names = ref.run(dep, control=True)
        nums = compare.numbers([{"stats": ctl, "digest": ""}], ctl_names,
                               stats, names, lambda r: 0)
        ok = compare.judge(nums)
        failing &= not ok
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_correct": ok, "numbers": nums,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0 if failing else 1


if __name__ == "__main__":
    sys.exit(main())
