"""Tests for ``tools.dpgolint``: per-rule fixtures (positive, negative,
suppressed, guard-dominated), the wire-symmetry check over both codec
vocabularies, the seeded-violation smoke on real project files, the
self-check that the tree is clean, and the leakcheck plugin contract."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from tools.dpgolint import Config, run_lint
from tools.dpgolint.config import project_config

REPO = Path(__file__).resolve().parents[1]


def lint_src(tmp_path, source, rule, config=None, name="mod.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    return run_lint([str(p)], config or Config(), rules=[rule])


# ---------------------------------------------------------------------------
# DPG001 jit-purity
# ---------------------------------------------------------------------------

JIT_SRC = """
    import time
    import random
    import jax
    import numpy as np

    def helper(x):
        t = time.time()
        return x * t

    @jax.jit
    def entry(x):
        print("tracing")
        r = random.random()
        s = np.random.default_rng(0).normal()
        v = x.item()
        return helper(x) + r + s + v

    def host_driver(x):
        time.sleep(0.1)          # host code: clocks are fine here
        return float(x)
"""


def test_dpg001_flags_impurities_in_reachable_functions(tmp_path):
    findings = lint_src(tmp_path, JIT_SRC, "DPG001")
    msgs = "\n".join(f.message for f in findings)
    assert "time.time" in msgs                      # via reachable helper
    assert "print()" in msgs
    assert "random.random" in msgs
    assert "np.random.default_rng" in msgs
    assert ".item() host sync" in msgs
    # host_driver is not reachable from any jit entry: nothing of its
    # body is flagged.
    assert "time.sleep" not in msgs
    assert all("host_driver" not in f.message for f in findings)


def test_dpg001_vmap_arg_and_partial_decorator_are_entries(tmp_path):
    src = """
        import time
        import jax
        from functools import partial

        def body(x):
            return time.monotonic() + x

        mapped = jax.vmap(body)

        @partial(jax.jit, static_argnums=0)
        def seg(k, x):
            time.perf_counter()
            return x
    """
    findings = lint_src(tmp_path, src, "DPG001")
    msgs = "\n".join(f.message for f in findings)
    assert "time.monotonic" in msgs and "time.perf_counter" in msgs


def test_dpg001_global_mutation_and_suppression(tmp_path):
    src = """
        import jax

        COUNT = 0

        @jax.jit
        def entry(x):
            global COUNT
            COUNT += 1
            return x

        @jax.jit
        def entry2(x):
            global COUNT  # reviewed: dpgolint: disable=DPG001
            COUNT += 1
            return x
    """
    findings = lint_src(tmp_path, src, "DPG001")
    assert len(findings) == 1 and "global mutation" in findings[0].message


def test_dpg001_jax_random_is_not_flagged(tmp_path):
    src = """
        import jax

        @jax.jit
        def entry(x, key):
            k1, k2 = jax.random.split(key)
            return x + jax.random.normal(k1, x.shape)
    """
    assert lint_src(tmp_path, src, "DPG001") == []


# ---------------------------------------------------------------------------
# DPG002 telemetry fence
# ---------------------------------------------------------------------------

def test_dpg002_unguarded_construction_flagged(tmp_path):
    src = """
        from dpgo_tpu import obs
        from dpgo_tpu.obs.health import HealthMonitor

        def setup():
            mon = HealthMonitor(obs.get_run())
            return mon
    """
    findings = lint_src(tmp_path, src, "DPG002")
    assert len(findings) == 1
    assert "HealthMonitor" in findings[0].message
    assert "telemetry-enabled guard" in findings[0].message


@pytest.mark.parametrize("body", [
    # if-dominated
    """
    run = obs.get_run()
    if run is not None:
        mon = HealthMonitor(run)
    """,
    # early-exit dominated
    """
    run = obs.get_run()
    if run is None:
        return None
    mon = HealthMonitor(run)
    """,
    # two-level guard variable (the run_rbcd `telemetry` idiom)
    """
    run = obs.get_run()
    telemetry = run is not None
    if telemetry:
        mon = HealthMonitor(run)
    """,
    # else-branch of the negated test
    """
    run = obs.get_run()
    if run is None:
        mon = None
    else:
        mon = HealthMonitor(run)
    """,
    # conjunction guard
    """
    run = obs.get_run()
    flag = True
    if run is not None and flag:
        mon = HealthMonitor(run)
    """,
])
def test_dpg002_guard_dominated_constructions_pass(tmp_path, body):
    src = ("from dpgo_tpu import obs\n"
           "from dpgo_tpu.obs.health import HealthMonitor\n\n"
           "def setup():\n"
           + textwrap.indent(textwrap.dedent(body), "    ")
           + "    return mon\n")
    p = tmp_path / "mod.py"
    p.write_text(src)
    assert run_lint([str(p)], Config(), rules=["DPG002"]) == []


def test_dpg002_suppression_and_span(tmp_path):
    src = """
        from dpgo_tpu.obs.trace import Span

        def f(run):
            a = Span(run, "x")  # dpgolint: disable=DPG002 -- test fixture
            b = Span(run, "y")
            return a, b
    """
    findings = lint_src(tmp_path, src, "DPG002")
    assert len(findings) == 1 and "Span" in findings[0].message


def test_dpg002_guarded_builder_lambda_passes(tmp_path):
    # The executable-cache idiom: construction deferred into a lambda,
    # dominated by the enclosing early exit.
    src = """
        from dpgo_tpu import obs
        from dpgo_tpu.obs.profile import ProfiledExecutable

        def cached(cache, fp, make):
            run = obs.get_run()
            if run is None:
                return cache.get(fp, make)
            return cache.get(fp, lambda: ProfiledExecutable(make()))
    """
    assert lint_src(tmp_path, src, "DPG002") == []


# ---------------------------------------------------------------------------
# DPG003 host-sync hazards
# ---------------------------------------------------------------------------

HOT_CFG = Config(options={"DPG003": {"per_file": {
    "*": {"hot_functions": ["hot"]}}}})


def test_dpg003_sync_in_loop_flagged(tmp_path):
    src = """
        import numpy as np

        def hot(step, xs, n):
            out = []
            for _ in range(n):
                xs = step(xs)
                out.append(np.asarray(xs))     # implicit transfer
                xs.block_until_ready()
                v = float(step(xs))            # cast of a fresh call result
            return out, v
    """
    findings = lint_src(tmp_path, src, "DPG003", HOT_CFG)
    msgs = "\n".join(f.message for f in findings)
    assert "np.asarray" in msgs
    assert ".block_until_ready()" in msgs
    assert "float() on a call result" in msgs
    assert len(findings) == 3


def test_dpg003_host_values_and_cold_functions_pass(tmp_path):
    src = """
        import numpy as np

        def hot(vec, n):
            for i in range(n):
                f = vec[i]
                x = float(f)          # plain name: already host-side
            y = np.asarray(vec)       # outside any loop: the seam
            return x, y

        def cold(step, xs, n):
            for _ in range(n):
                xs = np.asarray(step(xs))   # not a configured hot path
            return xs
    """
    assert lint_src(tmp_path, src, "DPG003", HOT_CFG) == []


def test_dpg003_suppressed_seam(tmp_path):
    src = """
        import numpy as np

        def hot(step, xs, n):
            while n > 0:
                # sanctioned seam. dpgolint: disable=DPG003
                vec = np.asarray(step(xs))
                n -= 1
            return vec
    """
    assert lint_src(tmp_path, src, "DPG003", HOT_CFG) == []


# ---------------------------------------------------------------------------
# DPG004 lock discipline
# ---------------------------------------------------------------------------

LOCKED_SRC = """
    import threading

    class Server:
        def __init__(self):
            self._lock = threading.Lock()
            self._count = 0          # guarded-by: _lock
            self._items = []         # guarded-by: _lock

        def good(self):
            with self._lock:
                self._count += 1
                return list(self._items)

        def helper(self):  # holds: _lock
            self._items.append(self._count)

        def good_call(self):
            with self._lock:
                self.helper()
"""


def test_dpg004_locked_accesses_pass(tmp_path):
    assert lint_src(tmp_path, LOCKED_SRC, "DPG004") == []


def test_dpg004_unlocked_access_and_call_flagged(tmp_path):
    # (8-space indent pre-dedent: append methods into the class body)
    src = LOCKED_SRC + textwrap.indent(textwrap.dedent("""
        def bad_read(self):
            return self._count

        def bad_call(self):
            self.helper()
    """), " " * 8)
    findings = lint_src(tmp_path, src, "DPG004")
    msgs = "\n".join(f.message for f in findings)
    assert "read of self._count outside `with self._lock`" in msgs
    assert "call to self.helper() outside `with self._lock`" in msgs
    assert len(findings) == 2


def test_dpg004_suppression(tmp_path):
    src = LOCKED_SRC + textwrap.indent(textwrap.dedent("""
        def snapshot(self):
            # single-threaded init phase. dpgolint: disable=DPG004
            return self._count
    """), " " * 8)
    assert lint_src(tmp_path, src, "DPG004") == []


def test_dpg004_inconsistent_lock_order_flagged(tmp_path):
    src = """
        import threading

        class Two:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def one(self):
                with self._a:
                    with self._b:
                        pass

            def two(self):
                with self._b:
                    with self._a:
                        pass
    """
    findings = lint_src(tmp_path, src, "DPG004")
    assert len(findings) == 1
    assert "inconsistent lock order" in findings[0].message


# ---------------------------------------------------------------------------
# DPG005 wire-schema symmetry
# ---------------------------------------------------------------------------

WIRE_CFG = Config(options={"DPG005": {"per_file": {"*": {
    "pack_functions": ["pack_v1", "pack_v2"],
    "unpack_functions": ["unpack_v1", "unpack_v2"],
}}}})

WIRE_OK = """
    import numpy as np

    KEY = "_meta"

    def pack_v1(prefix, d):
        out = {f"{prefix}_{r}_{p}": v for (r, p), v in d.items()}
        out[KEY] = np.asarray([1])
        return out

    def pack_v2(prefix, robots, poses, vals):
        return {f"{prefix}:r": robots, f"{prefix}:p": poses,
                f"{prefix}:x": vals}

    def unpack_v1(frame, prefix):
        meta = frame.get(KEY)
        return {k: v for k, v in frame.items()
                if k.startswith(prefix + "_")}, meta

    def unpack_v2(frame, prefix):
        if f"{prefix}:r" not in frame:
            return None
        return frame[f"{prefix}:r"], frame[f"{prefix}:p"], \\
            frame[f"{prefix}:x"]
"""


def test_dpg005_symmetric_codecs_pass(tmp_path):
    assert lint_src(tmp_path, WIRE_OK, "DPG005", WIRE_CFG) == []


def test_dpg005_pack_only_key_flagged_in_v2_codec(tmp_path):
    src = WIRE_OK.replace(
        'return {f"{prefix}:r": robots, f"{prefix}:p": poses,',
        'return {f"{prefix}:zz": 0, f"{prefix}:r": robots, '
        'f"{prefix}:p": poses,')
    findings = lint_src(tmp_path, src, "DPG005", WIRE_CFG)
    assert len(findings) == 1
    assert "'*:zz' is packed but never unpacked" in findings[0].message


def test_dpg005_unpack_only_key_flagged_in_v1_codec(tmp_path):
    src = WIRE_OK.replace("meta = frame.get(KEY)",
                          "meta = frame.get(KEY)\n"
                          "        legacy = frame.pop('_legacy', None)")
    findings = lint_src(tmp_path, src, "DPG005", WIRE_CFG)
    assert len(findings) == 1
    assert "'_legacy' is unpacked but never packed" in findings[0].message


def test_dpg005_suppression(tmp_path):
    src = WIRE_OK.replace(
        "out[KEY] = np.asarray([1])",
        "out[KEY] = np.asarray([1])\n"
        "        out['_v3_future'] = 0  # dpgolint: disable=DPG005")
    assert lint_src(tmp_path, src, "DPG005", WIRE_CFG) == []


# ---------------------------------------------------------------------------
# Project self-check + seeded-violation smoke (the acceptance criteria)
# ---------------------------------------------------------------------------

def test_project_tree_is_clean_under_all_passes(monkeypatch):
    monkeypatch.chdir(REPO)
    findings = run_lint(["dpgo_tpu", "tools"], project_config())
    assert findings == [], "\n".join(
        f"{f.location}: {f.rule} {f.message}" for f in findings)


def test_seeded_violations_fail_with_rule_and_location(tmp_path):
    """Acceptance smoke: an unguarded HealthMonitor() and an unlocked
    guarded-attribute write seeded into a copy of the real serving plane
    must fail citing DPG002/DPG004 with file:line."""
    serve = tmp_path / "dpgo_tpu" / "serve"
    serve.mkdir(parents=True)
    src = (REPO / "dpgo_tpu" / "serve" / "server.py").read_text()
    bad = src.replace(
        "self.cache = ExecutableCache(disk=disk)",
        "self.cache = ExecutableCache(disk=disk)\n"
        "        from ..obs.health import HealthMonitor\n"
        "        self._boom = HealthMonitor(None)")
    bad = bad.replace(
        "        with self._cond:\n            self._n_shed += 1",
        "        self._n_shed += 1")
    assert bad != src
    (serve / "server.py").write_text(bad)
    findings = run_lint([str(tmp_path / "dpgo_tpu")], project_config())
    rules = {f.rule for f in findings}
    assert "DPG002" in rules and "DPG004" in rules, findings
    for f in findings:
        assert f.path.endswith("serve/server.py") and f.line > 0


def test_cli_clean_tree_exits_zero_and_json_shape():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.dpgolint", "dpgo_tpu", "tools",
         "--format", "json"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout)
    assert out["count"] == 0 and out["findings"] == []


def test_cli_baseline_accepts_known_findings(tmp_path):
    # Project-shaped path so the scoped DPG001 pass applies to it.
    mod = tmp_path / "dpgo_tpu" / "models" / "rbcd.py"
    mod.parent.mkdir(parents=True)
    mod.write_text(textwrap.dedent("""
        import jax
        import time

        @jax.jit
        def entry(x):
            time.time()
            return x
    """))
    env = dict(os.environ, PYTHONPATH=str(REPO))
    cmd = [sys.executable, "-m", "tools.dpgolint",
           str(tmp_path / "dpgo_tpu"),
           "--baseline", str(tmp_path / "baseline.json")]
    proc = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True,
                          env=env, timeout=120)
    assert proc.returncode == 1 and "DPG001" in proc.stdout
    # Accept the debt, then the same tree passes.
    subprocess.run(cmd + ["--write-baseline"], cwd=REPO, check=True,
                   capture_output=True, env=env, timeout=120)
    proc2 = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True,
                           env=env, timeout=120)
    assert proc2.returncode == 0, proc2.stdout + proc2.stderr


# ---------------------------------------------------------------------------
# leakcheck plugin
# ---------------------------------------------------------------------------

def test_leakcheck_fails_leaking_test_and_passes_clean(tmp_path):
    """A deliberately-leaking fixture test (open socket kept alive) must
    fail under ``-p tests.plugins.leakcheck``; a clean test and an
    ``allow_leaks``-marked one must pass."""
    (tmp_path / "test_fixture_leaks.py").write_text(textwrap.dedent("""
        import socket
        import pytest

        def test_leaky():
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            globals()["_keep"] = s      # never closed

        def test_clean():
            s = socket.socket()
            try:
                s.bind(("127.0.0.1", 0))
            finally:
                s.close()

        @pytest.mark.allow_leaks(reason="fixture exercising the opt-out")
        def test_opted_out():
            s = socket.socket()
            globals()["_keep2"] = s
    """))
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "test_fixture_leaks.py", "-q",
         "-p", "tests.plugins.leakcheck", "-p", "no:cacheprovider"],
        cwd=tmp_path, env=dict(os.environ, PYTHONPATH=str(REPO)),
        capture_output=True, text=True, timeout=180)
    assert proc.returncode != 0
    assert "test_leaky" in proc.stdout and "leakcheck" in proc.stdout
    assert "sockets still open" in proc.stdout
    # exactly one error (the leak); clean + opted-out tests pass
    assert "3 passed, 1 error" in proc.stdout, proc.stdout


def test_seeded_verdict_loop_sync_violations(tmp_path):
    """ISSUE-9 seams: the device-resident driver's ONE sanctioned
    verdict-word fetch rides a reviewed suppression — but (a) a NEW
    ``_host_fetch`` call seeded into the verdict hot loop and (b) the
    same seeded into ``run_bucket``'s dispatch loop must be flagged by
    DPG003 via the configured ``sync_calls`` seam list, with file:line."""
    # (a) models/rbcd.py: unsanctioned extra fetch in run_rbcd's loop.
    mdir = tmp_path / "dpgo_tpu" / "models"
    mdir.mkdir(parents=True)
    src = (REPO / "dpgo_tpu" / "models" / "rbcd.py").read_text()
    bad = src.replace(
        "            n_pre = len(eval_its)\n\n    cost_hist",
        "            n_pre = len(eval_its)\n"
        "            _dbg = _host_fetch(state.X)\n\n    cost_hist")
    assert bad != src
    (mdir / "rbcd.py").write_text(bad)
    findings = run_lint([str(tmp_path / "dpgo_tpu")], project_config())
    hits = [f for f in findings if f.rule == "DPG003"
            and "sync seam" in f.message]
    assert hits, findings
    assert all(f.path.endswith("models/rbcd.py") and f.line > 0
               for f in hits)

    # (b) serve/runner.py: unsanctioned fetch inside the bucket loop.
    sdir = tmp_path / "b" / "dpgo_tpu" / "serve"
    sdir.mkdir(parents=True)
    rsrc = (REPO / "dpgo_tpu" / "serve" / "runner.py").read_text()
    rbad = rsrc.replace(
        "        all_terminal = ",
        "        _dbg = rbcd._host_fetch(vs.hist)\n"
        "        all_terminal = ")
    assert rbad != rsrc
    (sdir / "runner.py").write_text(rbad)
    findings = run_lint([str(tmp_path / "b" / "dpgo_tpu")],
                        project_config())
    hits = [f for f in findings if f.rule == "DPG003"
            and "sync seam" in f.message]
    assert hits, findings
    assert all(f.path.endswith("serve/runner.py") for f in hits)


def test_seeded_sharded_gn_tail_sync_violation(tmp_path):
    """ISSUE-11 seam: the sharded GN tail's outer loop reads exactly one
    gate scalar + one stats vector per outer step through the sanctioned
    ``rbcd._host_fetch`` seam — a NEW ``_host_fetch`` call seeded into
    that loop must be flagged by DPG003 via the configured ``sync_calls``
    list, with file:line."""
    pdir = tmp_path / "dpgo_tpu" / "parallel"
    pdir.mkdir(parents=True)
    src = (REPO / "dpgo_tpu" / "parallel" / "sharded.py").read_text()
    bad = src.replace(
        "            cost_hist.append(f_new)\n            X = X_new",
        "            cost_hist.append(f_new)\n"
        "            _dbg = rbcd._host_fetch(X_new)\n"
        "            X = X_new")
    assert bad != src
    (pdir / "sharded.py").write_text(bad)
    findings = run_lint([str(tmp_path / "dpgo_tpu")], project_config())
    hits = [f for f in findings if f.rule == "DPG003"
            and "sync seam" in f.message]
    assert hits, findings
    assert all(f.path.endswith("parallel/sharded.py") and f.line > 0
               for f in hits)


def test_sanctioned_sharded_gn_tail_fetches_stay_suppressed(tmp_path):
    """The two reviewed GN-tail fetch sites (gate scalar, per-outer
    stats) must remain suppressed on the real tree: stripping either
    suppression makes DPG003 fire at that site."""
    src = (REPO / "dpgo_tpu" / "parallel" / "sharded.py").read_text()
    for marker in (
            "            # dpgolint: disable=DPG003 -- sanctioned "
            "GN-tail gate fetch\n",
            "            # dpgolint: disable=DPG003 -- sanctioned "
            "per-outer stats fetch\n"):
        stripped = src.replace(marker, "")
        assert stripped != src, marker
        pdir = tmp_path / marker.split()[-2] / "dpgo_tpu" / "parallel"
        pdir.mkdir(parents=True)
        (pdir / "sharded.py").write_text(stripped)
        findings = run_lint([str(pdir.parent.parent / "dpgo_tpu")],
                            project_config())
        assert any(f.rule == "DPG003" and "_host_fetch" in f.message
                   for f in findings), (marker, findings)


def test_seeded_resilience_checkpoint_sync_violation(tmp_path):
    """ISSUE-14 seam: the checkpoint gather is the resilience layer's
    ONE sanctioned device->host transfer — a NEW ``_host_fetch`` call
    seeded into the ``checkpoint_arrays`` field loop must be flagged by
    DPG003 via the configured ``sync_calls`` list, with file:line."""
    pdir = tmp_path / "dpgo_tpu" / "parallel"
    pdir.mkdir(parents=True)
    src = (REPO / "dpgo_tpu" / "parallel" / "resilience.py").read_text()
    bad = src.replace(
        "        host[f] = _host_fetch(v)",
        "        host[f] = _host_fetch(v)\n"
        "        _dbg = _host_fetch(v)")
    assert bad != src
    (pdir / "resilience.py").write_text(bad)
    findings = run_lint([str(tmp_path / "dpgo_tpu")], project_config())
    hits = [f for f in findings if f.rule == "DPG003"
            and "sync seam" in f.message]
    assert hits, findings
    assert all(f.path.endswith("parallel/resilience.py") and f.line > 0
               for f in hits)


def test_sanctioned_resilience_checkpoint_gather_stays_suppressed(
        tmp_path):
    """The reviewed checkpoint-gather fetch must remain suppressed on
    the real tree: stripping the suppression makes DPG003 fire at that
    site, and the real module lints clean under the full policy."""
    src = (REPO / "dpgo_tpu" / "parallel" / "resilience.py").read_text()
    marker = ("        # dpgolint: disable=DPG003 -- sanctioned mesh "
              "checkpoint gather\n")
    stripped = src.replace(marker, "")
    assert stripped != src
    pdir = tmp_path / "dpgo_tpu" / "parallel"
    pdir.mkdir(parents=True)
    (pdir / "resilience.py").write_text(stripped)
    findings = run_lint([str(tmp_path / "dpgo_tpu")], project_config())
    assert any(f.rule == "DPG003" and "_host_fetch" in f.message
               and f.path.endswith("parallel/resilience.py")
               for f in findings), findings


def test_sanctioned_verdict_fetches_stay_suppressed(monkeypatch):
    """The three reviewed verdict-loop fetch sites (word, lazy history,
    terminal bookkeeping) must remain suppressed on the real tree — the
    clean-tree check above covers it, but pin the intent: stripping any
    one suppression makes DPG003 fire at that site."""
    src = (REPO / "dpgo_tpu" / "models" / "rbcd.py").read_text()
    stripped = src.replace(
        "                # dpgolint: disable=DPG003 -- sanctioned "
        "verdict-word fetch\n", "")
    assert stripped != src
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        mdir = os.path.join(td, "dpgo_tpu", "models")
        os.makedirs(mdir)
        with open(os.path.join(mdir, "rbcd.py"), "w") as fh:
            fh.write(stripped)
        findings = run_lint([os.path.join(td, "dpgo_tpu")],
                            project_config())
    assert any(f.rule == "DPG003" and "_host_fetch" in f.message
               for f in findings), findings


def test_project_policy_covers_fleet_subpackage():
    """The serve/fleet sub-subpackage (ISSUE 13) sits one directory level
    deeper than the rest of the tree: pin that the project policy's
    DPG002 globs reach it and that DPG004 (run-everywhere) applies, and
    that the real fleet modules lint clean under the full policy."""
    cfg = project_config()
    for rel in ("dpgo_tpu/serve/fleet/router.py",
                "dpgo_tpu/serve/fleet/manager.py",
                "dpgo_tpu/serve/fleet/aotcache.py"):
        assert cfg.applies("DPG002", rel), rel
        assert cfg.applies("DPG004", rel), rel
    findings = run_lint([str(REPO / "dpgo_tpu" / "serve" / "fleet")],
                        project_config())
    assert findings == [], findings


def test_seeded_multihost_lockstep_sync_violation(tmp_path):
    """ISSUE-17 seam: the multi-host lockstep trades ONLY host bytes —
    ``verdict_sync`` rides the word the driver already fetched, so a
    ``_host_fetch`` call seeded into a loop inside it must be flagged by
    DPG003 via the configured ``sync_calls`` list, with file:line."""
    pdir = tmp_path / "dpgo_tpu" / "parallel"
    pdir.mkdir(parents=True)
    src = (REPO / "dpgo_tpu" / "parallel" / "multihost.py").read_text()
    bad = src.replace(
        "        self.boundaries += 1\n        run = obs.get_run()",
        "        for _v in (it,):\n"
        "            _dbg = _host_fetch(_v)\n"
        "        self.boundaries += 1\n        run = obs.get_run()")
    assert bad != src
    (pdir / "multihost.py").write_text(bad)
    findings = run_lint([str(tmp_path / "dpgo_tpu")], project_config())
    hits = [f for f in findings if f.rule == "DPG003"
            and "sync seam" in f.message]
    assert hits, findings
    assert all(f.path.endswith("parallel/multihost.py") and f.line > 0
               for f in hits)


def test_seeded_proc_fleet_heartbeat_sync_violation(tmp_path):
    """ISSUE-17 seam: the parent-side pump/heartbeat threads are
    host-only — an ad-hoc ``_rpc`` or a numpy materialization seeded
    into the heartbeat's poll loop must be flagged by DPG003 under the
    ``serve/fleet/procs.py`` scope (both classifiers: the configured
    ``_rpc`` sync seam and the ``np.asarray`` fetcher)."""
    fdir = tmp_path / "dpgo_tpu" / "serve" / "fleet"
    fdir.mkdir(parents=True)
    src = (REPO / "dpgo_tpu" / "serve" / "fleet" / "procs.py").read_text()
    bad = src.replace(
        "            st = self._beat_once()",
        "            _dbg = self._rpc({\"op\": 0}, timeout=0.1)\n"
        "            _mat = np.asarray(_dbg)\n"
        "            st = self._beat_once()")
    assert bad != src
    (fdir / "procs.py").write_text(bad)
    findings = run_lint([str(tmp_path / "dpgo_tpu")], project_config())
    hits = [f for f in findings if f.rule == "DPG003"
            and f.path.endswith("serve/fleet/procs.py")]
    assert any("sync seam" in f.message for f in hits), findings
    assert any("np.asarray" in f.message for f in hits), findings
    assert all(f.line > 0 for f in hits)


def test_measurements_codec_symmetry_under_dpg005(tmp_path):
    """ISSUE-17 wire vocabulary: ``pack_measurements`` /
    ``unpack_measurements`` (the columnar payload the out-of-process
    replicas solve from) participate in DPG005's symmetry check — a
    pack-only key seeded into the codec is flagged, and the real module
    stays symmetric under the project policy."""
    cfg = project_config()
    for rel in ("dpgo_tpu/parallel/multihost.py",
                "dpgo_tpu/serve/fleet/procs.py"):
        # DPG002 via the package globs, DPG004 everywhere (procs.py's
        # process-table locks carry # guarded-by: annotations), DPG003
        # via the explicit hot-path scope.
        assert cfg.applies("DPG002", rel), rel
        assert cfg.applies("DPG003", rel), rel
        assert cfg.applies("DPG004", rel), rel
    opts = cfg.file_options("DPG005", "dpgo_tpu/comms/protocol.py")
    assert "pack_measurements" in opts["pack_functions"]
    assert "unpack_measurements" in opts["unpack_functions"]

    cdir = tmp_path / "dpgo_tpu" / "comms"
    cdir.mkdir(parents=True)
    src = (REPO / "dpgo_tpu" / "comms" / "protocol.py").read_text()
    bad = src.replace(
        '        f"{prefix}:d": np.int32(meas.d),',
        '        f"{prefix}:zz": np.int32(0),\n'
        '        f"{prefix}:d": np.int32(meas.d),')
    assert bad != src
    (cdir / "protocol.py").write_text(bad)
    findings = run_lint([str(tmp_path / "dpgo_tpu")], project_config())
    hits = [f for f in findings if f.rule == "DPG005"]
    assert any("'*:zz' is packed but never unpacked" in f.message
               for f in hits), findings
