"""What every driver shares: compile counting, host spans, problems."""

from __future__ import annotations

import contextlib
import time

import numpy as np

from ..gen import synthetic as gen


class CompileMeter:
    """XLA compilations, from JAX's monitoring events (a copy of
    ``chip_smoke.CompileMeter``, PR 21).  ``backend_compile_duration``
    is recorded for every executable JAX builds, a persistent-cache hit
    included; ``cache_hits`` counts the hits.  Their difference is the
    number of programs compiled afresh."""

    def __init__(self):
        import jax

        self.builds = 0
        self.cache_hits = 0
        self.seconds = 0.0
        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, fun_name=None, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.builds += 1
            self.seconds += secs
            self.names.append(str(fun_name))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @property
    def compiles(self) -> int:
        return self.builds - self.cache_hits


class Spans:
    """The benchmark's own host spans around its calls into the program.
    Each is kept as (name, seconds) and, when a profiler trace is being
    taken, also written into it as a ``TraceAnnotation`` of that name."""

    def __init__(self):
        self.records: list[tuple[str, float]] = []
        self.annotate = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        ctx = contextlib.nullcontext()
        if self.annotate:
            import jax

            ctx = jax.profiler.TraceAnnotation(name)
        t0 = time.perf_counter()
        with ctx:
            yield
        self.records.append((name, time.perf_counter() - t0))

    def seconds(self, name: str) -> list[float]:
        return [s for n, s in self.records if n == name]


def base_problem(config: dict, structure_seed: int,
                 outliers: int = 0) -> tuple[gen.Problem, np.ndarray]:
    """The deployment's graph without noise: ground truth, odometry chain
    and loop closures from a seed of the configuration, so that every
    run's problem has one set of sizes.  Returns the problem and the
    indices of the last ``outliers`` loop closures (to be corrupted)."""
    rng = np.random.default_rng(structure_seed)
    n, d = config["poses"], config["d"]
    lc = config["loop_closures"]
    if config.get("lc_radius") is not None:
        prob, _ = gen.make_measurements_vectorized(
            rng, n=n, d=d, num_lc=lc, kappa=config["kappa"],
            tau=config["tau"], lc_radius=config["lc_radius"])
    else:
        prob, _ = gen.make_measurements(rng, n=n, d=d, num_lc=lc,
                                        kappa=config["kappa"],
                                        tau=config["tau"])
    if len(prob) != config["edges"]:
        raise ValueError(f"{config['name']}: {len(prob)} edges generated, "
                         f"{config['edges']} in the configuration")
    return prob, np.arange(len(prob) - outliers, len(prob))


def seeded_problem(base: gen.Problem, outlier_rows: np.ndarray, config: dict,
                   rng) -> gen.Problem:
    """The base graph with noise and gross outliers drawn from ``rng``."""
    prob = gen.with_noise(base, rng, config["rot_noise"],
                          config["trans_noise"])
    if outlier_rows.size:
        prob = gen.with_outliers(prob, rng, outlier_rows)
    return prob


def pool(config: dict, size: int, outliers: int = 0,
         noise_seeds=None) -> list[tuple[gen.Problem, np.ndarray]]:
    """A traffic's pool of problems, fixed by the configuration: one graph
    from ``structure_seed``, the k-th problem's noise (and outliers) from
    ``noise_seed + k``.  ``noise_seeds`` replaces the configuration's, for
    readings over more realizations than a pool holds."""
    seeds = noise_seeds if noise_seeds is not None else \
        [config["noise_seed"] + k for k in range(size)]
    base, rows = base_problem(config, config["structure_seed"], outliers)
    return [(seeded_problem(base, rows, config, np.random.default_rng(ns)),
             rows) for ns in seeds]


def to_measurements(prob: gen.Problem):
    """The program's input type for a generated problem."""
    from dpgo_tpu.types import Measurements

    m = len(prob)
    return Measurements(
        d=prob.d, num_poses=prob.n,
        r1=np.zeros(m, np.int32), p1=prob.i.astype(np.int64),
        r2=np.zeros(m, np.int32), p2=prob.j.astype(np.int64),
        R=prob.R.copy(), t=prob.t.copy(), kappa=prob.kappa.copy(),
        tau=prob.tau.copy(), weight=np.ones(m),
        is_known_inlier=np.zeros(m, bool))


def counter_delta(program: dict, name: str) -> float:
    """A program counter's growth over the window, summed over its label
    sets (``program`` holds ``registry.snapshot()`` before and after)."""
    def total(snap):
        fam = snap.get(name)
        return sum(float(x["value"]) for x in fam["series"]) if fam else 0.0
    return total(program["counters"]) - total(program["counters_before"])


def histogram_delta_mean(program: dict, name: str) -> float | None:
    """Mean of what a program histogram observed over the window."""
    def total(snap):
        fam = snap.get(name)
        if not fam:
            return 0.0, 0
        return (sum(x["sum"] for x in fam["series"]),
                sum(x["count"] for x in fam["series"]))
    (s1, n1), (s0, n0) = total(program["counters"]), \
        total(program["counters_before"])
    return (s1 - s0) / (n1 - n0) if n1 > n0 else None
