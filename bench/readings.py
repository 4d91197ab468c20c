#!/usr/bin/env python3
"""Read a cell's compared numbers over many seeds in one process.

    python3 bench/readings.py --workload L16-chat-edge \
        --seeds 11,12,13 --seconds 10 --control 1

One set-up and warm-up, then per seed: that seed's weights, a window of
``--seconds`` at the cell's own load, and the comparison with the
reference (with ``--control 1`` also the float8 control's reading and
verdict).  One
``READING`` line per seed.  The limits in the configuration files were
set from these readings (``PERF.md``); the benchmark's own runs never
call this.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import harness as H
    from bench import traffic as T
    spec = H.load_spec()
    cell = {w["name"]: w for w in spec["workloads"]}[args.workload]
    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        s = H.Session(args.workload, cell, H.load_config(cell["config"]),
                      T.load_mix(cell["traffic"]), spec, seed=seeds[0],
                      t_start=T0)
    except H.ChipError as e:
        print(e, file=sys.stderr)
        return 3
    s.warm()
    for i, seed in enumerate(seeds):
        if i:
            s.reseed(seed)
        t = time.perf_counter()
        res = s.measure(seed, args.seconds, trace=False,
                        control=bool(args.control))
        print("READING " + json.dumps({
            "seed": seed, "correct": res["correct"],
            "attempted": res["attempted"], "failed": res["failed"],
            "checks": res["checks"], "control": res.get("control"),
            "metrics": res["metrics"],
            "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
