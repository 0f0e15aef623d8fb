"""python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of its standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and
``breakdown`` with ``--trace 1``).  Earlier lines (``bench: ...``) say
what set-up did, the rung, compile counts and per-job walls.  A machine
without the cell's TPU chips is an error: non-zero exit, no result line.
``--list`` prints the cells and exits.
"""

import time

T_PROCESS = time.perf_counter()     # set-up is counted from here

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    from benchmark import cells, harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[1])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args(argv)
    if args.list:
        for name in cells.cell_names():
            c = cells.load_cell(name)
            print(f"{name}\tchips={c.chips}\tconfig={c.config_name}\t"
                  f"traffic={c.traffic_name}\tkind={c.traffic['kind']}")
        return 0
    if not args.workload:
        ap.error("--workload is required")
    cell = cells.load_cell(args.workload)
    seconds = (args.seconds if args.seconds is not None
               else cells.load_benchmark()["run_seconds"])
    try:
        line = harness.run_cell(cell, args.seed, seconds, bool(args.trace),
                                T_PROCESS)
    except harness.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
