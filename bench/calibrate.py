"""Readings for a cell's correctness limits: the program's numbers and the
control's verdict, over many seeds in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 51
    python3 bench/calibrate.py --workload <cell> --seeds 4,5,6 --seconds 51 \
        --fault token_altered

Each seed is a whole run of the cell (``bench/run.py``), at the cell's own
size and load, followed by the comparison with the reference and by the
control: the reference computed with float8 operands in the program's
place, read at the same positions and judged by the same limits
(``bench/check.py``).  ``--fault`` plants one of ``bench/faults.py`` in the
timed path instead of reading the control.  One JSON line per seed, with
the program's ``correct`` and numbers and the control's.  The limits in
``bench/checks/<cell>.json`` are set between the largest sound reading and
the smallest control or fault reading; the benchmark's own runs never run
the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import faults, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    every = {**faults.FAULTS, **faults.STATE_SPACE_FAULTS}
    ap.add_argument("--fault", choices=sorted(every))
    a = ap.parse_args(argv)
    fault = every[a.fault] if a.fault else None
    for seed in (int(s) for s in a.seeds.split(",")):
        args = argparse.Namespace(workload=a.workload, seed=seed,
                                  seconds=a.seconds, trace=0)
        try:
            out = run.run(args, fault=fault,
                          control=None if fault else "fp8")
        except run.Refused as e:
            print(f"calibrate: {e}", file=sys.stderr)
            return e.code
        line = {"seed": seed, "fault": a.fault, "correct": out["correct"],
                **{k: c["value"] for k, c in out["checks"].items()}}
        if "control" in out:
            line.update({f"control_{k}": v
                         for k, v in out["control"].items()})
        line.update(metrics=out["metrics"],
                    memory_peak_bytes=out["device"]["memory_peak_bytes"])
        print(json.dumps(line), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
