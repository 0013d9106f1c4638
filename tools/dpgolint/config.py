"""dpgolint configuration: which rules look where, and what they trust.

``Config`` carries two maps:

* ``files`` — rule id -> list of path globs (lint-root-relative, forward
  slashes) the rule runs on.  ``None``/missing = every file.  This is how
  each invariant stays scoped to the layer that owes it (DPG001 to the
  jit hot paths, DPG005 to the wire modules) instead of pattern-matching
  the whole tree.
* ``options`` — rule id -> rule-specific settings dict.  Per-file
  settings nest one level deeper keyed by path glob (see
  ``Config.file_options``).

``project_config()`` is the checked-in project policy — the single place
the sanctioned constructor seams, hot-path function lists, and codec
pairs are declared.  Tests build ad-hoc ``Config``\\ s pointing rules at
fixture files instead.
"""

from __future__ import annotations

import dataclasses

from .core import glob_match


@dataclasses.dataclass
class Config:
    files: dict = dataclasses.field(default_factory=dict)
    options: dict = dataclasses.field(default_factory=dict)

    def applies(self, rule_id: str, relpath: str) -> bool:
        globs = self.files.get(rule_id)
        if globs is None:
            return True
        return glob_match(relpath, globs)

    def rule_options(self, rule_id: str) -> dict:
        return self.options.get(rule_id, {})

    def file_options(self, rule_id: str, relpath: str) -> dict:
        """The per-file settings block for ``relpath``: the value under the
        first glob key in ``options[rule_id]["per_file"]`` that matches."""
        per_file = self.rule_options(rule_id).get("per_file", {})
        for pat, opts in per_file.items():
            if glob_match(relpath, [pat]):
                return opts
        return {}


def project_config() -> Config:
    """The dpgo_tpu project policy (see docs/ARCHITECTURE.md, "Static
    analysis & invariants")."""
    return Config(
        files={
            # DPG001: functions reachable from jax.jit/vmap/fused-segment
            # entry points must be pure — these are the modules that build
            # the compiled solver/serving programs.
            "DPG001": [
                "dpgo_tpu/models/rbcd.py",
                "dpgo_tpu/models/incremental.py",
                "dpgo_tpu/serve/runner.py",
                "dpgo_tpu/parallel/sharded.py",
                "dpgo_tpu/parallel/resilience.py",
            ],
            # DPG002: obs-owned constructions anywhere in the package must
            # sit behind the telemetry fence; the obs internals that ARE
            # the fence (run/trace/health/recorder construct their own
            # objects behind documented contracts + boom tests) are the
            # sanctioned seams.  The third-level glob keeps sub-subpackages
            # (serve/fleet) explicitly in scope.
            "DPG002": ["dpgo_tpu/*", "dpgo_tpu/*/*", "dpgo_tpu/*/*/*"],
            # DPG003: host-sync hazards in the solver/serving hot loops.
            "DPG003": [
                "dpgo_tpu/models/rbcd.py",
                "dpgo_tpu/models/incremental.py",
                "dpgo_tpu/models/certify.py",
                "dpgo_tpu/serve/runner.py",
                "dpgo_tpu/parallel/sharded.py",
                "dpgo_tpu/parallel/certify.py",
                "dpgo_tpu/parallel/resilience.py",
                "dpgo_tpu/parallel/multihost.py",
                "dpgo_tpu/serve/fleet/procs.py",
            ],
            # DPG004 is annotation-driven (# guarded-by) — run everywhere;
            # files without annotations produce nothing.
            "DPG004": None,
            # DPG005: the wire vocabulary modules.
            "DPG005": [
                "dpgo_tpu/comms/protocol.py",
                "dpgo_tpu/comms/reliable.py",
                "dpgo_tpu/comms/bus.py",
            ],
        },
        options={
            "DPG001": {
                # Fused-segment entry points that are jitted indirectly
                # (module-level jax.jit(...) wrappers already detect most).
                "extra_entries": ["_rbcd_segment", "_rbcd_round",
                                  "_rbcd_rounds"],
            },
            "DPG002": {
                "constructors": ["TelemetryRun", "HealthMonitor",
                                 "FlightRecorder", "MetricsSidecar",
                                 "ProfiledExecutable", "ProfilerWindow",
                                 "Span", "DeviceTraceWindow",
                                 "PerfLedger", "ResourceSampler",
                                 "FleetSidecar"],
                # Obs-owned modules where construction IS the sanctioned
                # implementation of the fence (each carries its own boom
                # test): start_run/run_scope, span()/start_span(),
                # monitor_for, FlightRecorder.attach + the replay CLI.
                # devprof constructs its own trace windows behind
                # ``get_run()`` checks; ledger.py is offline tooling
                # whose PerfLedger only ever exists via load_ledger.
                "allowed_files": [
                    "dpgo_tpu/obs/run.py",
                    "dpgo_tpu/obs/trace.py",
                    "dpgo_tpu/obs/health.py",
                    "dpgo_tpu/obs/recorder.py",
                    "dpgo_tpu/obs/devprof.py",
                    "dpgo_tpu/obs/ledger.py",
                    "dpgo_tpu/obs/fleetobs.py",
                ],
            },
            "DPG003": {
                "per_file": {
                    "dpgo_tpu/models/rbcd.py": {
                        # run_rbcd is the device-resident driver: its
                        # ONLY sanctioned in-loop fetches are the verdict
                        # word, the lazy history, and the terminal
                        # epilogue — each carries a reviewed suppression;
                        # _host_fetch is the seam they all route through,
                        # and any new call to it inside a hot loop is
                        # flagged.
                        "hot_functions": ["run_rbcd", "dispatch_prepared",
                                          "solve_rbcd"],
                        "sync_calls": ["_host_fetch"],
                    },
                    "dpgo_tpu/serve/runner.py": {
                        "hot_functions": ["run_bucket"],
                        "sync_calls": ["_host_fetch"],
                    },
                    # The live-session layer (ISSUE 10): delta application
                    # and the warm-restart dispatch are host-side by
                    # design, but they sit on the serving worker's request
                    # path — a device sync creeping into their loops would
                    # stall every batch behind a stream.
                    "dpgo_tpu/models/incremental.py": {
                        "hot_functions": ["apply_edges", "_try_delta",
                                          "warm_dispatch", "_adapt_state"],
                    },
                    # The sharded driver loop (ISSUE 11): the sharded
                    # GN-CG tail's outer loop reads one gate scalar and
                    # one stats vector per outer step through the same
                    # sanctioned seam as the verdict loop; anything else
                    # inside it (or inside a future solve_rbcd_sharded
                    # loop) is a hot-loop regression on the mesh path.
                    "dpgo_tpu/parallel/sharded.py": {
                        "hot_functions": ["gn_tail_sharded",
                                          "solve_rbcd_sharded"],
                        "sync_calls": ["_host_fetch"],
                    },
                    # The resilience layer (ISSUE 14): the checkpoint
                    # gather is the ONE sanctioned device->host transfer
                    # of the whole subsystem — it runs only at a verdict
                    # boundary the driver already paid a word-fetch for,
                    # through resilience.py's own _host_fetch seam (so
                    # the driver's sync-rate contract is untouched), and
                    # carries a reviewed suppression.  Any other fetch in
                    # the checkpoint/boundary loop is a new steady-state
                    # sync and is flagged.
                    "dpgo_tpu/parallel/resilience.py": {
                        "hot_functions": ["checkpoint_arrays",
                                          "boundary_cb"],
                        "sync_calls": ["_host_fetch"],
                    },
                    # The certificate layer (ISSUE 15): the device
                    # certificate rides the solve's fused terminal
                    # epilogue, so the ONE sanctioned transfer is that
                    # terminal ``_host_fetch`` — the staircase loops
                    # (which re-certify per rank) must route every
                    # readback through it rather than fetching scalars
                    # ad hoc between escapes.
                    "dpgo_tpu/models/certify.py": {
                        "hot_functions": ["solve_staircase",
                                          "device_certificate_payload",
                                          "decide_device_certificate"],
                        "sync_calls": ["_host_fetch"],
                    },
                    "dpgo_tpu/parallel/certify.py": {
                        "hot_functions": ["solve_staircase_sharded",
                                          "certify_sharded",
                                          "make_sharded_certificate"],
                        "sync_calls": ["_host_fetch"],
                    },
                    # The multi-host lockstep (ISSUE 17): verdict_sync
                    # rides the ONE word the driver already fetched — it
                    # trades host bytes over the coordination service and
                    # must never touch the device; a fetch creeping into
                    # its publish/cross-check loop (or into the per-round
                    # boundary_cb it hangs off) would multiply the
                    # cross-process sync rate past 100/K.
                    "dpgo_tpu/parallel/multihost.py": {
                        "hot_functions": ["verdict_sync", "boundary_cb",
                                          "run_worker"],
                        "sync_calls": ["_host_fetch"],
                    },
                    # The out-of-process fleet (ISSUE 17): the pump and
                    # heartbeat threads sit on the parent's request path —
                    # host-only by design (the device lives in the child),
                    # so any numpy materialization or ad-hoc ``_rpc`` in
                    # their loops is a new blocking stall behind a live
                    # replica socket.
                    "dpgo_tpu/serve/fleet/procs.py": {
                        "hot_functions": ["_pump", "_heartbeat_loop",
                                          "submit"],
                        "sync_calls": ["_rpc"],
                    },
                },
            },
            "DPG005": {
                "per_file": {
                    "dpgo_tpu/comms/protocol.py": {
                        "pack_functions": ["pack_pose_dict",
                                           "pack_pose_arrays",
                                           "pack_trace_entries",
                                           "pack_measurements",
                                           "attach_clock"],
                        "unpack_functions": ["unpack_pose_dict",
                                             "unpack_pose_arrays",
                                             "unpack_trace_entries",
                                             "unpack_measurements",
                                             "pop_clock"],
                    },
                    "dpgo_tpu/comms/reliable.py": {
                        "pack_functions": ["send"],
                        "unpack_functions": ["_recv"],
                        # Imported from protocol.py; pinned so the clock
                        # stamp participates in the symmetry check.
                        "constants": {"CLOCK_KEY": "_ts"},
                    },
                    "dpgo_tpu/comms/bus.py": {
                        "pack_functions": ["pack_agent_frame", "hello",
                                           "round"],
                        "unpack_functions": ["apply_peer_frame",
                                             "_apply_peer_frame",
                                             "collect", "accept_robots",
                                             "_gather_one"],
                        # The hub namespaces rebroadcast keys r{id}|...;
                        # receivers split the prefix off before parsing.
                        "strip_prefixes": ["r*|"],
                    },
                },
            },
        },
    )
