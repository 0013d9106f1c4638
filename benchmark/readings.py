"""Readings that the limits of ``check.py`` are set from, on the chip.

    python benchmark/readings.py --workload <cell> --seeds 1,2,3 [--control 1] [--seconds s]

For each seed, in one process: the cell's set-up (the first seed's warms
the programs), a window of ``--seconds`` (a library cell's shortest
window is one solve), and the numbers ``check.py`` compares.  With
``--control 1`` the run is the cell's control (``apply_control`` of its
driver), which has to read beyond the limits.  One JSON line per seed.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchmark import run as R  # noqa: E402


def main(argv=None, platform: str = "tpu", config_override=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--seconds", type=float, default=0.001)
    ap.add_argument("--noise", type=int, default=1,
                    help="1: each seed also draws the pool's noise (readings "
                         "over more realizations than the cell's pool)")
    args = ap.parse_args(argv)
    bench = R.load_json(R.ROOT, "BENCHMARK.json")
    cell = R.find(bench["workloads"], args.workload, "workload")
    config = R.load_json(R.ROOT, R.find(bench["configs"], cell["config"],
                                        "config")["file"])
    config.update(config_override or {})
    traffic = R.load_json(R.HERE, "traffic", cell["traffic"] + ".json")
    tmp = tempfile.mkdtemp(prefix="bench-")
    try:
        R.set_up_environment(tmp)
        R.require_chips(cell["chips"], platform)
        from benchmark import check
        from benchmark.drivers import common

        mod = importlib.import_module(
            f"benchmark.drivers.{traffic['driver']}")
        kw = {"cache_dir": R.CACHE, "tmp_dir": tmp} \
            if traffic["driver"] == "served" else {}
        for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
            drv = mod.Cell(cell, config, traffic, seed, common.Spans(), **kw)
            if args.control:
                drv.apply_control()
            if args.noise:
                drv.noise_seeds = [seed * 1000 + j for j in
                                   range(traffic["pool"])]
            drv.warm = k == 0 or traffic["driver"] == "served"
            try:
                drv.set_up()
                drv.window(args.seconds)
            finally:
                drv.drop_program_state()
            nums, ok = check.NUMBERS[traffic["driver"]](drv)
            print(json.dumps({"seed": seed, "control": args.control,
                              "numbers": nums, "failed": len(drv.failures)
                              + sum(not x for x in ok),
                              "driver": drv.readings()}), flush=True)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
