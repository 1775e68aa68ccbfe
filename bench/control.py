#!/usr/bin/env python3
"""Readings that set the limits of a cell's compared numbers, on the chip.

    python3 bench/control.py --workload susy.fit --seeds 11 12 13 \
        --faults half_rows_left_out answer_altered

For each seed, in one process: the cell's set-up, then one unit of work in
each mode, each judged by the benchmark's own ``verdict`` against the one
reference result of that seed's program run: ``program``, the program as
the window runs it (the lower readings); ``bf16``, the control, the program
with its own lower-precision path switched on (``PallasBackend(bf16=True)``:
bfloat16 operands in the kernels' main products), which has to come out as
not correct (the upper readings); and each fault of ``bench/faults.py``
named, planted under the timed path, which has to come out as not correct
too. One JSON line per seed. Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json

import run


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="*", default=[])
    args = ap.parse_args()
    cell = run.prepare(args.workload)
    import repro.api as api
    from repro.core.backend import PallasBackend

    import cell as cells
    import faults

    for seed in args.seeds:
        unit = cells.load_unit(api, cell, cells.make_data(cell.config, seed), seed)
        unit.setup()
        unit.run()
        prog = unit.program_outputs()
        want = unit.reference_outputs(prog)
        line = {"workload": cell.name, "seed": seed}

        def reading(mode):
            try:
                unit.run()
                checks, ok = cells.verdict(unit.compare(unit.program_outputs(), want),
                                           cell.limits)
            except Exception as e:  # a run that crashes has failed
                line[mode] = {"error": f"{type(e).__name__}: {e}", "correct": False}
                return
            line[mode] = {**{k: c["value"] for k, c in checks.items()}, "correct": ok}

        checks, ok = cells.verdict(unit.compare(prog, want), cell.limits)
        line["program"] = {**{k: c["value"] for k, c in checks.items()}, "correct": ok}
        unit.backend = PallasBackend(bf16=True)
        reading("bf16")
        unit.backend = None
        for name in args.faults:
            with faults.planted(name):
                reading(name)
        line["limits"] = cell.limits
        print(json.dumps(line), flush=True)
        del unit, prog, want


if __name__ == "__main__":
    main()
