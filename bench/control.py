"""The control: the plain reference with exactness broken, run in the
program's place, which the comparison must find not correct.

    python3 bench/control.py --workload <name> --seeds 11 12 13 --seconds 10

For each seed it runs the cell as ``bench/run.py`` does, on the chip it
asks for, with the store kind's reference answering at a fixed
``fanout`` (each vertex's first neighbors only: sampled answers, not
exact ones), and prints each run's compared numbers.  The benchmark's
own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
FANOUT = 10


def control_program(cell, fanout: int = FANOUT):
    """``(build, ops)`` that put the sampled reference, prepared over the
    whole graph, in the program's place for ``cell``."""

    def build(cfg, data):
        return cell.ref.prepare(cfg, data, None)

    def op(name):
        return lambda ref, **args: cell.ref.OPS[name](ref, **args,
                                                      fanout=fanout)

    return build, {name: op(name) for name in cell.ref.OPS}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    cell = harness.Cell(ROOT, args.workload)
    build, ops = control_program(cell)
    for seed in args.seeds:
        try:
            out = harness.run_cell(ROOT, args.workload, seed, args.seconds,
                                   False, time.perf_counter(), build=build,
                                   ops=ops, counters=dict)
        except harness.NoDevice as e:
            print(f"control: {e}; nothing was run", file=sys.stderr)
            return 2
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "attempted": out["attempted"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
