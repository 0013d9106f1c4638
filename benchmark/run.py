"""Run one benchmark cell on the chips of this machine.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell names its
configuration (``benchmark/configs/<config>.json``) and its traffic mix
(``benchmark/traffic/<traffic>.json``); the traffic names the driver that
runs it (``benchmark/drivers/<driver>.py``); each per-layer metric is read
by ``benchmark/metrics/<metric>.py``; the limits of the comparison that
decides ``correct`` are in ``benchmark/limits/<cell>.json``.

The last line of standard output is the result, one JSON object.  The
numbers compared, each beside its limit, are the last lines of standard
error and the result's last key.  Without a TPU, or with fewer chips than
the cell asks for, the run prints no result and exits 3.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE = os.path.join(HERE, ".cache")


def load_json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find(items: list, name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def cells_of(metric: dict, bench: dict) -> list[str]:
    if "workloads" in metric:
        return metric["workloads"]
    moves = metric.get("moves")
    if moves is None:
        return [c["name"] for c in bench["workloads"]]
    return cells_of(find(bench["end_to_end"], moves, "metric"), bench)


def set_up_environment(tmp: str) -> None:
    """Caches at fixed paths inside the checkout, logs in the run's
    temporary directory; set before JAX is imported."""
    # JAX writes into the cache directory but does not create it.
    os.makedirs(os.path.join(CACHE, "jax"), exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE, "jax")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    os.environ["TPU_LOG_DIR"] = os.path.join(tmp, "tpu_logs")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)


def require_chips(n: int, platform: str = "tpu"):
    import jax

    devs = jax.devices()
    if devs[0].platform != platform:
        print(f"the benchmark needs a {platform.upper()}; JAX found "
              f"{devs[0].platform} devices only", file=sys.stderr)
        raise SystemExit(3)
    if len(devs) < n:
        print(f"the cell asks for {n} chips; JAX found {len(devs)}",
              file=sys.stderr)
        raise SystemExit(3)
    return devs[:n]


@contextlib.contextmanager
def profiler(trace_dir: str, spans):
    import jax

    jax.profiler.start_trace(trace_dir)
    spans.annotate = True
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            yield
    finally:
        spans.annotate = False
        jax.profiler.stop_trace()


def read_events(run_dir: str) -> list[dict]:
    path = os.path.join(run_dir, "events.jsonl")
    if not os.path.exists(path):
        return []
    with open(path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


def main(argv=None, platform: str = "tpu", drive=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    cell = find(bench["workloads"], args.workload, "workload")
    config = load_json(ROOT, find(bench["configs"], cell["config"],
                                  "config")["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    limits = load_json(HERE, "limits", cell["name"] + ".json")["numbers"]

    tmp = tempfile.mkdtemp(prefix="bench-")
    try:
        set_up_environment(tmp)
        return run_cell(args, bench, cell, config, traffic, limits, tmp,
                        platform, drive)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run_cell(args, bench, cell, config, traffic, limits, tmp, platform,
             drive) -> int:
    devices = require_chips(cell["chips"], platform)
    t_chips = time.perf_counter() - T_START

    from benchmark import check
    from benchmark.drivers import common

    meter = common.CompileMeter()
    spans = common.Spans()
    obs_run = None
    if args.trace:
        # The program's own counters exist only with a telemetry run.
        from dpgo_tpu import obs

        obs_run = obs.start_run(os.path.join(tmp, "obs"))
    drv_mod = importlib.import_module(
        f"benchmark.drivers.{traffic['driver']}")
    kw = {"cache_dir": CACHE, "tmp_dir": tmp} \
        if traffic["driver"] == "served" else {}
    drv = drv_mod.Cell(cell, config, traffic, args.seed, spans, **kw)
    if drive is not None:
        drive(drv)   # tests plant faults here
    try:
        drv.set_up()
        setup_s = time.perf_counter() - T_START
        print(f"set-up: {t_chips:.3f} s to the chip, {setup_s:.3f} s in all, "
              f"{meter.seconds:.3f} s building programs ({meter.builds} "
              f"built, {meter.cache_hits} from the cache)", file=sys.stderr)
        spans.records.clear()
        before, n_before = meter.compiles, len(meter.names)
        program = {}
        if obs_run is not None:
            program["counters_before"] = obs_run.registry.snapshot()
        tracer = (lambda: profiler(os.path.join(tmp, "trace"), spans)) \
            if args.trace else None
        out = drv.window(args.seconds, tracer)
        compiled = meter.compiles - before
        if compiled:
            print("programs built in the window: "
                  + ", ".join(meter.names[n_before:]), file=sys.stderr)
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices)
        if obs_run is not None:
            program["counters"] = obs_run.registry.snapshot()
            obs.end_run()
            program["events"] = read_events(os.path.join(tmp, "obs"))
    finally:
        drv.drop_program_state()

    nums, ok = check.NUMBERS[traffic["driver"]](drv)
    judged = check.judge(nums, limits)
    failed = len(drv.failures) + sum(not x for x in ok)
    correct = (failed == 0 and compiled == 0 and out["attempted"] > 0
               and all(v <= lim for _, v, lim in judged))

    metrics, breakdown, dev_extra = {}, None, {}
    if not args.trace:
        e2e = dict(out["e2e"])
        if traffic["driver"] == "served":
            e2e["served_solves_per_s"] = sum(ok) / drv.window_s
        e2e["setup_s"] = setup_s
        for m in bench["end_to_end"]:
            if cell["name"] in cells_of(m, bench):
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    else:
        from benchmark import trace as trace_mod

        readers = {m["name"]: load_module(
            os.path.join(HERE, "metrics", m["name"] + ".py"),
            "metric_" + m["name"].replace(".", "_"))
            for m in bench["per_layer"] if cell["name"] in cells_of(m, bench)}
        kernels = {}
        for r in readers.values():
            kernels.update(getattr(r, "KERNELS", {}))
        t_read = time.perf_counter()
        tr = trace_mod.summarize(
            trace_mod.read(trace_mod.latest_xplane(os.path.join(tmp,
                                                                "trace"))),
            kernels)
        rec = {"cell": cell, "config": config, "traffic": traffic,
               "spans": {n: spans.seconds(n)
                         for n in {n for n, _ in spans.records}},
               "driver": drv.readings(), "program": program, "trace": tr,
               "graph": (getattr(drv, "problems", None) or [None])[0],
               "peaks": load_json(HERE, "peaks.json")["devices"].get(
                   devices[0].device_kind)}
        for m in bench["per_layer"]:
            if m["name"] in readers:
                v = readers[m["name"]].read(rec)
                if v is not None:
                    metrics[m["name"]] = {"value": float(v),
                                          "unit": m["unit"]}
        print(f"trace read in {time.perf_counter() - t_read:.3f} s",
              file=sys.stderr)
        breakdown = tr["breakdown"]
        dev_extra = {"busy_s": tr["busy_s"], "window_s": tr["window_s"]}

    checks = {k: {"value": v, "limit": lim} for k, v, lim in judged}
    checks["failed"] = {"value": failed, "limit": 0}
    checks["compiles_in_window"] = {"value": compiled, "limit": 0}
    result = {
        "correct": bool(correct),
        "attempted": int(out["attempted"]),
        "failed": int(failed),
        "metrics": metrics,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": len(devices),
                   "memory_peak_bytes": peak, **dev_extra},
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    print(json.dumps({"readings": nums, "driver": drv.readings()}),
          file=sys.stderr)
    for k, v in checks.items():
        print(f"check {k} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
