"""Readings behind the correctness limits of a cell, on the chip.

    python bench/control.py --workload <cell> --seeds <n> [<n> ...]

For each seed, in one process: the cell's set-up, one timed sweep, the
reference replay at the configuration's precision, and the control —
the same reference one precision lower (``reference.control_dtype``)
put in the program's place.  The program has no lower-precision path of
its own, so this is the control the comparison must fail.  A second
sweep with its bisection left out (the grid's probes alone) is the fault
that moves the knee, which the control cannot: it replays the program's
own probe loads.  Prints one JSON line per seed with each side's
compared numbers and the verdict of ``bench/check.py`` under the cell's
own limits; ``bench/limits/<cell>.json`` records the limits set from
them.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

import check
import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    _bench, cell, config, mix = run.load_cell(args.workload)
    from repro.jaxenv import enable_compile_cache
    enable_compile_cache()
    run.check_device(int(cell["chips"]))
    low = run.reference.control_dtype(config["precision"])
    limits = check.load_limits(cell["name"], mix)
    for seed in args.seeds:
        s = run.setup(config, mix, seed)
        sweeps = run.window(s, config, mix, 0.0)["sweeps"]
        gc.collect()
        ref = run.replay(s, config, sweeps, config["precision"])
        prog = run.compare(s, sweeps, ref)
        ctl = run.compare(s, sweeps, ref, run.replay(s, config, sweeps, low))
        grid = [run.sweep(s["g"], s["dem"], config, dict(mix, refine=0),
                          s["theta"])]
        no_bisect = run.compare(s, grid, ref)
        for side in (prog, ctl, no_bisect):
            side["correct"] = check.verdict(side["values"], limits)
        print(json.dumps({"seed": seed, "backend": s["backend"],
                          "knee": sweeps[0].theta, "theta": s["theta"],
                          "program": prog, "control": ctl,
                          "control_dtype": low, "no_bisection": no_bisect,
                          "limits": limits}), flush=True)
        del s, sweeps, ref, grid
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
