#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, in one process.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \
        --seeds 11,12,... [--control-seeds 21,22,23] [--out FILE]

For every ``--seeds`` seed it runs the cell as ``run.py`` does (the same
window, served path and reference comparison) and records the numbers
compared with the reference. For every ``--control-seeds`` seed it runs
the precision control: the program's own int8 path (int8 packed weights
and int8 KV, one step below the bf16 the configurations state) in the
program's place. The limit of each number is then set between the
program's largest reading and the control's smallest (PERF.md). The
benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run  # noqa: E402

CONTROL = {"weight_dtype": "int8", "kv_dtype": "int8"}


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", default="")
    p.add_argument("--control-seeds", default="")
    p.add_argument("--out", default="")
    a = p.parse_args(argv)
    rows = []
    plan = ([(int(s), False) for s in a.seeds.split(",") if s]
            + [(int(s), True) for s in a.control_seeds.split(",") if s])
    for seed, control in plan:
        res = run.run_cell(a.workload, seed, a.seconds, False,
                           engine_overrides=CONTROL if control else None)
        row = {"seed": seed, "control": control, "readings": res["readings"],
               "correct": res["correct"], "attempted": res["attempted"],
               "failed": res["failed"], "metrics": res["metrics"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
        if a.out:
            with open(a.out, "w") as f:
                json.dump(rows, f, indent=1)
    for control in (False, True):
        sel = [r["readings"] for r in rows if r["control"] == control]
        if not sel:
            continue
        keys = sorted({k for r in sel for k, v in r.items() if v is not None})
        summary = {k: [min(r[k] for r in sel if r[k] is not None),
                       max(r[k] for r in sel if r[k] is not None)]
                   for k in keys}
        print(json.dumps({"control": control, "n": len(sel),
                          "min_max": summary}), flush=True)


if __name__ == "__main__":
    main()
