#!/usr/bin/env python3
"""Readings a cell's limits are set from, many seeds in one process.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed it runs the cell as ``bench/run.py`` does (set-up, window,
comparison) and then judges each of the configuration's ``controls`` by
the same comparison: the plain reference one precision down, put in the
program's place, on the same answers.  One JSON line per seed and run
(``"run": "program"`` or the control's name), with ``correct`` and the
compared numbers beside their limits; a control has to come out not
correct.  The benchmark's own runs never compute a control.

    --control-seeds <n>   judge the controls on the first n seeds only
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import manifest as mf  # noqa: E402
from bench import run as br  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", type=int, default=1 << 30)
    args = ap.parse_args(argv)
    cell = mf.resolve(mf.load_manifest(ROOT), args.workload)
    try:
        devices = br.check_devices(cell.chips)
    except br.NoChip as e:
        print(f"readings: {e}", file=sys.stderr)
        return 3
    br.compile_cache()
    names = list(cell.config.get("controls", {}))
    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        run = br.Run(cell, seed, args.seconds, False, devices)
        run.controls = names if k < args.control_seeds else []
        cell.runner().run(run)
        for name, r in [("program", run)] + list(run.control_runs.items()):
            out = br.result(r)
            print(json.dumps({
                "seed": seed, "run": name, "correct": out["correct"],
                "checks": out["checks"], "metrics": out["metrics"],
                "counters": {k: v for k, v in r.counters.items()
                             if k.startswith(("gap.", "check_s"))},
                "compiles_in_window": out["compiles_in_window"]}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
