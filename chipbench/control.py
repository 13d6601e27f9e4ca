"""Readings that set a cell's limits: the system's own numbers and its
control's, on several seeds in one process.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

Each seed is one run of the cell (``run.run_cell``) at the cell's own
size and load with a short window.  Beside the comparison the run always
makes, the control is the plain reference put in the system's place at
the next precision down (bfloat16 for the stated float32, JAX on the
chip) and held to the same comparison and limits: it must come out not
correct where the system comes out correct.  Each seed prints one JSON
line, and on standard error each number of both beside its limit; the
last line sums up.  Exits 1 where a control came out correct or the
system did not.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import List, Optional

from run import DeviceError, M, log, run_cell  # noqa: E402


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    manifest = M.load_manifest()
    seeds = [int(s) for s in args.seeds.split(",")]
    sound = failed = 0
    for seed in seeds:
        try:
            out = run_cell(manifest, args.workload, seed, args.seconds,
                           trace=False, control=True,
                           t_process=time.perf_counter())
        except (DeviceError, M.ManifestError) as exc:
            log(f"chipbench: refused: {exc}")
            return 2
        ctl = out["control"]
        sound += out["correct"]
        failed += not ctl["correct"]
        for who, checks in (("program", out["checks"]),
                            ("control", ctl["checks"])):
            for key, (v, lim) in checks.items():
                log(f"seed {seed} {who} {key} {v} limit {lim}")
        print(json.dumps({
            "seed": seed, "correct": out["correct"],
            "control_correct": ctl["correct"], "checked": ctl["checked"],
            "program": out["checks"], "control": ctl["checks"],
        }), flush=True)
    print(json.dumps({"seeds": len(seeds), "program_correct": sound,
                      "control_not_correct": failed}), flush=True)
    return 0 if sound == failed == len(seeds) else 1


if __name__ == "__main__":
    sys.exit(main())
