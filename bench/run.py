#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload L16-chat-edge --seed 7 --seconds 30 \
        --trace 0

From the root of a checkout.  Exits non-zero, printing no result, when
JAX finds no TPU or fewer chips than the cell needs.  The last stdout line
is the result: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device`` and, last, ``checks`` (each compared number beside its limit,
also the last lines of stderr).  ``--control 1`` also puts the float8
control's tokens in the served tokens' place at the same sample and
reports that verdict under ``control`` (``correct`` and ``checks``); the
benchmark's own runs leave it off.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import harness as H
    from bench import traffic as T
    spec = H.load_spec()
    cells = {w["name"]: w for w in spec["workloads"]}
    if args.workload not in cells:
        print(f"bench: unknown workload {args.workload!r}; known: "
              f"{sorted(cells)}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    try:
        result = H.run_cell(args.workload, cell,
                            H.load_config(cell["config"]),
                            T.load_mix(cell["traffic"]), spec,
                            seed=args.seed, seconds=args.seconds,
                            trace=bool(args.trace),
                            control=bool(args.control), t_start=T0)
    except H.ChipError as e:
        print(e, file=sys.stderr)
        return 3
    H.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
