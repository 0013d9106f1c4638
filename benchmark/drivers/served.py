"""Served cells: closed-loop clients send g2o requests over TCP to the
program's ``ServeFrontend`` -> ``SolveServer`` (bucketing, batched
runner), each waiting for its reply before it sends the next.

The pool of requests is fixed by the configuration: ``pool`` distinct
problems of one graph (from ``structure_seed``), the k-th with noise from
``noise_seed + k``, so all land in one shape bucket and batch together;
``--seed`` draws the order in which they are sent, so every seed offers
the same work.  Every request carries a new session
id, so none warm-starts.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from . import common
from ..gen.g2o import g2o_bytes


class Cell:
    def __init__(self, cell: dict, config: dict, traffic: dict, seed: int,
                 spans: common.Spans, cache_dir: str, tmp_dir: str):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed = seed
        self.spans = spans
        self.cache_dir, self.tmp_dir = cache_dir, tmp_dir
        self.tol_scale = 1.0
        self.max_iters = None
        self.noise_seeds = None
        self.replies: list[dict] = []
        self.failures: list[str] = []
        self._stack = []

    def set_up(self) -> None:
        from dpgo_tpu.serve import SolveServer
        from dpgo_tpu.serve.frontend import ServeFrontend

        cfg, tr = self.config, self.traffic
        self.pool = [p for p, _ in common.pool(
            cfg, tr["pool"], noise_seeds=self.noise_seeds)]
        self.blobs = [g2o_bytes(p) for p in self.pool]
        self.order = np.random.default_rng(self.seed).permutation(
            len(self.pool))
        self.server = SolveServer(
            max_batch=tr["max_batch"], verdict_every=tr["verdict_every"],
            session_store=os.path.join(self.tmp_dir, "sessions"),
            aot_cache_dir=os.path.join(self.cache_dir, "aot"))
        self._stack.append(self.server)
        self.frontend = ServeFrontend(self.server, port=0)
        self._stack.append(self.frontend)
        self._warm()

    def apply_control(self) -> None:
        """The control of ``check.py``: requests loosen their gate by the
        traffic's ``control.grad_norm_tol_scale`` and stop after at most
        ``control.max_iters`` rounds."""
        c = self.traffic["control"]
        self.tol_scale = float(c.get("grad_norm_tol_scale", 1.0))
        self.max_iters = c.get("max_iters")

    def _request(self, k: int):
        from dpgo_tpu.config import AgentParams
        from dpgo_tpu.serve import SolveRequest
        from dpgo_tpu.utils.g2o import read_g2o

        # As the front-end builds it from the frame (serve/frontend.py).
        meas = read_g2o(self.blobs[k])
        tr = self.traffic
        return SolveRequest(
            meas=meas, num_robots=self.config["agents"],
            params=AgentParams(d=meas.d, r=self.config["rank"],
                               num_robots=self.config["agents"]),
            grad_norm_tol=tr["grad_norm_tol"] * self.tol_scale,
            eval_every=tr["eval_every"], max_iters=self.max_iters)

    def _warm(self) -> None:
        """Every batch width (1, 2, 4, 8: the runner pads to a power of
        two) of every bucket the pool lands in, through the call the
        server's worker makes, so the window compiles nothing."""
        from dpgo_tpu.serve.runner import run_bucket

        srv, tr = self.server, self.traffic
        store = srv.session_store

        def snapshot(iteration, states):
            # What the server's session hook does with each member.
            for b, st in enumerate(states):
                store.save(f"warm-{b}", st, iteration=int(iteration),
                           meta={"tenant": "default", "bucket": []})

        buckets: dict[str, list] = {}
        for k in range(len(self.pool)):
            padded, key, _ = srv._prepare(self._request(k))
            buckets.setdefault(key, []).append(padded)
        self.buckets = len(buckets)
        w = 1
        while w <= tr["max_batch"]:
            for members in buckets.values():
                run_bucket((members * w)[:w], srv.cache,
                           max_iters=self.max_iters,
                           grad_norm_tol=tr["grad_norm_tol"] * self.tol_scale,
                           eval_every=tr["eval_every"],
                           verdict_every=tr["verdict_every"],
                           session_cb=snapshot,
                           session_every=srv.session_every)
            w *= 2

    def _client(self, c: int, t0: float, seconds: float, nxt: list,
                lock: threading.Lock) -> None:
        from dpgo_tpu.serve.frontend import solve_g2o

        tr = self.traffic
        while True:
            with lock:
                if time.perf_counter() - t0 >= seconds:
                    return
                idx = nxt[0]
                nxt[0] += 1
            k = int(self.order[idx % len(self.order)])
            t_sub = time.perf_counter()
            try:
                with self.spans("bench.client_request"):
                    rep = solve_g2o(
                        self.frontend.host, self.frontend.port,
                        self.blobs[k], num_robots=self.config["agents"],
                        rank=self.config["rank"],
                        session_id=f"req-{idx}",
                        grad_norm_tol=tr["grad_norm_tol"] * self.tol_scale,
                        eval_every=tr["eval_every"],
                        max_iters=self.max_iters, timeout=300)
            except Exception as e:
                rep = {"ok": False, "error": f"{type(e).__name__}: {e}"}
            t_rep = time.perf_counter()
            with lock:
                self.replies.append({
                    "idx": idx, "k": k, "latency": t_rep - t_sub,
                    "t_reply": t_rep, "ok": bool(rep.get("ok")),
                    "T": rep.get("T"), "error": rep.get("error"),
                    "terminated_by": rep.get("terminated_by"),
                    "iterations": rep.get("iterations")})

    def window(self, seconds: float, tracer=None) -> dict:
        t0 = time.perf_counter()
        nxt, lock = [0], threading.Lock()
        threads = [threading.Thread(target=self._client,
                                    args=(c, t0, seconds, nxt, lock),
                                    name=f"bench-client-{c}", daemon=True)
                   for c in range(self.traffic["clients"])]
        for t in threads:
            t.start()
        if tracer is not None:
            # A few seconds of steady traffic, past the first batches.
            time.sleep(min(2.0, seconds / 4))
            with tracer():
                time.sleep(min(5.0, seconds / 2))
        for t in threads:
            t.join(timeout=seconds + 600)
        if any(t.is_alive() for t in threads):
            raise RuntimeError("a client did not finish")
        t_last = max(r["t_reply"] for r in self.replies)
        self.failures = [r["error"] for r in self.replies if not r["ok"]]
        lat = sorted(r["latency"] if r["ok"] else float("inf")
                     for r in self.replies)
        self.window_s = t_last - t0
        p90 = float(np.quantile(lat, 0.9, method="higher"))
        return {"attempted": len(self.replies),
                "e2e": {"served_p90_s": p90}}

    def close(self) -> None:
        while self._stack:
            self._stack.pop().close()

    def drop_program_state(self) -> None:
        self.close()

    def readings(self) -> dict:
        its = [r["iterations"] for r in self.replies if r["ok"]]
        return {"replies": len(self.replies), "buckets": self.buckets,
                "rounds_mean": sum(its) / len(its) if its else None,
                "terminated_by": sorted({str(r["terminated_by"])
                                         for r in self.replies})}
