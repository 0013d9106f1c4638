"""Sharded RBCD: agents distributed over a TPU device mesh.

This is the framework's distributed communication backend (SURVEY.md
section 2.4).  The reference has *no* networking code in-library — transport
is supplied externally (in-process calls in ``examples/MultiRobotExample.cpp``,
ROS pub/sub in ``dpgo_ros``).  Here the transport is the device mesh itself:

* agents = shards of a 1-D mesh axis ``"agent"`` (several agents per device
  when ``num_robots > mesh size``), or of the flattened ``("dcn", "ici")``
  product axis of a multi-slice mesh (``make_multislice_mesh`` — BASELINE
  config #5's 64-agents-across-slices deployment);
* public-pose exchange (``getSharedPoseDict`` -> ``updateNeighborPoses``,
  reference ``PGOAgent.cpp:95-105``, ``434-458``) = one ``all_gather`` of the
  padded public-pose table over ICI (DCN across slices — same code);
* status consensus (``PGOAgentStatus`` gossip + ``shouldTerminate``,
  reference ``PGOAgent.cpp:1007-1031``) = the driver reducing the sharded
  ``ready`` flags (a tiny all-reduce under jit);
* the lifting matrix / global anchor broadcast
  (``MultiRobotExample.cpp:139-146``, ``258-263``) = replicated arrays.

The per-shard round body is ``models.rbcd._rbcd_round`` with ``axis_name``
set to the mesh's full axis-name tuple (``("agent",)``, or
``("dcn", "ici")`` on a multi-slice mesh) — identical math to the
single-device path, so the sharded and unsharded solvers agree bitwise up
to XLA reduction order.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import obs
from ..config import AgentParams
from ..ops import manifold, quadratic
from ..types import Measurements, edge_set_from_measurements
from ..utils.partition import Partition, partition_contiguous
from ..utils.profiling import RoundTimer
from ..models import rbcd, refine
from ..models.rbcd import (GraphMeta, MultiAgentGraph, RBCDState,
                           init_state)
from . import resilience as resilience_mod

AXIS = "agent"


def make_mesh(num_devices: int | None = None, devices=None) -> Mesh:
    """A 1-D mesh over the ``"agent"`` axis.

    On real hardware this spans the TPU slice (ICI); under
    ``--xla_force_host_platform_device_count=N`` it spans N virtual CPU
    devices, which is how the collective paths are tested without a TPU.
    """
    devices = np.asarray(devices if devices is not None else jax.devices())
    if num_devices is not None:
        if num_devices > devices.size:
            raise ValueError(
                f"requested {num_devices} devices but only {devices.size} "
                "are available")
        devices = devices[:num_devices]
    return Mesh(devices, (AXIS,))


def make_multislice_mesh(num_slices: int, devices=None) -> Mesh:
    """A 2-D ``("dcn", "ici")`` mesh: ``num_slices`` TPU slices (DCN edges
    between them) x devices-per-slice (ICI within).  Agents shard over the
    flattened product axis; XLA routes each hop of the pose-exchange
    collective over the interconnect that actually links the devices — the
    multi-slice deployment of SURVEY.md section 2.4 / BASELINE config #5
    (64 agents across slices).  On real multi-slice hardware pass the
    devices in slice-major order (``jax.devices()`` already is); under the
    virtual CPU mesh the axis split exercises the identical program.
    """
    devices = np.asarray(devices if devices is not None else jax.devices())
    if devices.size % num_slices != 0:
        raise ValueError(
            f"{devices.size} devices do not split into {num_slices} slices")
    return Mesh(devices.reshape(num_slices, -1), ("dcn", "ici"))


def _axes(mesh: Mesh) -> tuple:
    """All mesh axis names — the agent axis is their flattened product."""
    return tuple(mesh.axis_names)


def _shard_map(f, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` across JAX versions: the public API (``check_vma``)
    when present, the experimental one (``check_rep``) otherwise — jax
    0.4.x ships only the latter, and without this shim the whole sharded
    plane is untestable on such an image (the per-eval readback era's
    "13 environmental failures" were exactly this)."""
    if hasattr(jax, "shard_map"):
        return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)
    from jax.experimental.shard_map import shard_map as _sm

    return _sm(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
               check_rep=False)


def _specs(mesh: Mesh, tree):
    """PartitionSpec pytree: leading axis over agents for [A, ...] arrays,
    replicated for scalars."""
    ax = _axes(mesh)
    def spec(x):
        return P(ax) if jnp.ndim(x) >= 1 else P()
    return jax.tree.map(spec, tree)


def shard_problem(mesh: Mesh, state: RBCDState, graph: MultiAgentGraph):
    """Place state and graph on the mesh: agent-sharded leading axes.

    ``num_robots`` must be a multiple of the mesh size (each device holds
    the same number of agent blocks).
    """
    A = state.X.shape[0]
    n_dev = mesh.devices.size
    if A % n_dev != 0:
        raise ValueError(
            f"num_robots={A} must be a multiple of mesh size {n_dev}; "
            "pick a divisible robot count or a smaller mesh")

    def put(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))

    state = jax.tree.map(put, state, _specs(mesh, state))
    graph = jax.tree.map(put, graph, _specs(mesh, graph))
    return state, graph


def _exchange_plan(mesh: Mesh, meta: GraphMeta, graph: MultiAgentGraph,
                   exchange: str):
    """Resolve the pose-exchange backend: ``"all_gather"`` (v1, full public
    table to every device) or ``"ppermute"`` (one collective per device
    shift that actually carries an edge — the optimized ICI route of
    SURVEY.md section 2.4).  Returns ``(shifts, plan)`` with plan arrays
    placed like the rest of the per-agent graph data."""
    if exchange == "all_gather":
        return (), None
    if exchange != "ppermute":
        raise ValueError(f"unknown exchange backend {exchange!r}")
    if len(_axes(mesh)) > 1:
        raise ValueError(
            "ppermute exchange plans device-ring shifts over a 1-D mesh; "
            "use exchange='all_gather' on a multi-slice mesh (XLA routes "
            "each gather hop over the linking interconnect)")
    shifts, plan = rbcd.plan_ppermute(graph, meta.num_robots,
                                      mesh.devices.size)
    plan = jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P(_axes(mesh)))), plan)
    return shifts, plan


def make_sharded_step(mesh: Mesh, meta: GraphMeta, params: AgentParams,
                      shifts: tuple = (), plan=None):
    """Compile the sharded RBCD round: shard_map of the per-shard body over
    the agent axis, jitted as one XLA program (collectives included).

    The returned callable takes the driver's two static schedule flags
    (``update_weights``, ``restart``); each (True/False) combination compiles
    once.  ``shifts``/``plan`` (from ``_exchange_plan``) select the ppermute
    pose exchange; default is the all_gather v1."""

    @partial(jax.jit, static_argnames=("update_weights", "restart"))
    def step(state: RBCDState, graph: MultiAgentGraph,
             update_weights: bool = False, restart: bool = False) -> RBCDState:
        def body(s, g, p):
            return rbcd._rbcd_round(s, g, meta=meta, params=params,
                                    axis_name=_axes(mesh),
                                    update_weights=update_weights,
                                    restart=restart, plan=p, shifts=shifts)

        in_specs = (_specs(mesh, state), _specs(mesh, graph),
                    _specs(mesh, plan))
        out_specs = _specs(mesh, state)
        return _shard_map(body, mesh, in_specs, out_specs)(state, graph, plan)

    return step


def make_sharded_multi_step(mesh: Mesh, meta: GraphMeta, params: AgentParams,
                            shifts: tuple = (), plan=None,
                            overlap: bool = True):
    """Compile the fused plain-round loop for the mesh path: ``k`` consecutive
    rounds (collective pose exchange included in each) as one on-device
    ``fori_loop`` inside shard_map — one dispatch per schedule segment
    instead of per round (see ``models.rbcd.rbcd_steps``).  ``k`` is traced,
    so one compile serves every segment length.

    ``overlap`` (default on — the mesh fast path) software-pipelines the
    halo exchange: the loop carries each round's neighbor buffer and
    issues the next round's ``ppermute``/``all_gather`` right after the
    Stiefel update produces the poses it carries, so the interconnect
    transfer overlaps the round's trailing status/momentum math instead of
    gating the next round's solve (``models.rbcd._rbcd_rounds``; identical
    values round for round)."""

    @jax.jit
    def steps(state: RBCDState, graph: MultiAgentGraph, num_rounds) -> RBCDState:
        def body(s, g, n, p):
            return rbcd._rbcd_rounds(s, g, n, meta, params, axis_name=_axes(mesh),
                                     plan=p, shifts=shifts, overlap=overlap)

        in_specs = (_specs(mesh, state), _specs(mesh, graph), P(),
                    _specs(mesh, plan))
        out_specs = _specs(mesh, state)
        return _shard_map(body, mesh, in_specs, out_specs)(state, graph, num_rounds, plan)

    return steps


def make_sharded_segment(mesh: Mesh, meta: GraphMeta, params: AgentParams,
                         shifts: tuple = (), plan=None,
                         overlap: bool = True):
    """Compile the fused schedule segment for the mesh path: a (possibly
    flagged) first round + the plain stretch as one dispatch
    (``models.rbcd.rbcd_segment``).  ``k`` is traced; the two first-round
    flags are static (<= 4 compiled variants).  ``overlap`` pipelines the
    plain stretch's halo exchange (see ``make_sharded_multi_step``)."""

    @partial(jax.jit, static_argnames=("update_weights", "restart"))
    def seg(state: RBCDState, graph: MultiAgentGraph, num_rounds,
            update_weights: bool = False, restart: bool = False) -> RBCDState:
        def body(s, g, n, p):
            return rbcd._rbcd_segment(s, g, n, meta, params, axis_name=_axes(mesh),
                                      plan=p, shifts=shifts,
                                      first_update_weights=update_weights,
                                      first_restart=restart,
                                      overlap=overlap)

        in_specs = (_specs(mesh, state), _specs(mesh, graph), P(),
                    _specs(mesh, plan))
        out_specs = _specs(mesh, state)
        return _shard_map(body, mesh, in_specs, out_specs)(state, graph, num_rounds, plan)

    return seg


def comm_bytes_per_round(meta: GraphMeta, mesh_size: int,
                         shifts: tuple | None = None,
                         accel: bool = False, itemsize: int = 4,
                         greedy: bool = False) -> int:
    """Modeled per-device ICI/DCN bytes for one round's pose exchange —
    the mesh analog of the reference driver's hand-counted communication
    bytes (``MultiRobotExample.cpp:60,143,195,209,274-279``; the in-process
    model lives in ``examples/multi_robot_example.py``).

    all_gather (``shifts=None``) moves each device's public table to every
    other device: ``mesh_size - 1`` table hops on a ring.  The ppermute
    route moves it once per planned shift (``len(shifts)`` hops).  Nesterov
    acceleration doubles the volume (aux poses Y exchanged too);
    ``greedy`` adds the greedy schedule's [A]-float gradient-norm
    all_gather (absent from the compiled Jacobi/async rounds —
    tests/test_sharded.py validates the model against the collectives XLA
    actually emits).
    """
    if meta.num_robots % mesh_size != 0:
        raise ValueError(
            f"num_robots={meta.num_robots} must be a multiple of "
            f"mesh_size={mesh_size} (shard_problem's layout)")
    A_loc = meta.num_robots // mesh_size
    table = A_loc * meta.p_max * meta.rank * (meta.d + 1) * itemsize
    hops = (mesh_size - 1) if shifts is None else len(shifts)
    exchanges = 2 if accel else 1
    greedy_gather = (mesh_size - 1) * A_loc * itemsize if greedy else 0
    return exchanges * hops * table + greedy_gather


# ---------------------------------------------------------------------------
# Sharded verdict program (the device-resident loop under shard_map)
# ---------------------------------------------------------------------------

#: Collective fault-injection hook (``parallel.resilience``) — the
#: shard_map twin of ``rbcd._exchange_wrap``: when set, every exchange
#: closure built below passes through it at trace time.
_gather_wrap = None


def _gather_exchange(graph: MultiAgentGraph, ax):
    """Neighbor-buffer exchange inside a shard_map body: all_gather of the
    public table over the mesh axes, then the slot resolve — the same v1
    exchange as the solver round (``rbcd.neighbor_buffer``)."""
    gather = lambda t: jax.lax.all_gather(t, ax, axis=0, tiled=True)
    exchange = lambda Vl: rbcd.neighbor_buffer(
        gather(rbcd.public_table(Vl, graph)), graph)
    if _gather_wrap is not None:
        exchange = _gather_wrap(exchange)
    return exchange


def local_grad_rows(V, Vz, graph: MultiAgentGraph):
    """Complete local gradient rows of the global linear map ``V Q`` for
    every agent held by this shard: the per-agent edge list applied to the
    ``[local | neighbor]`` buffer through the gather-only ELL incidence
    (``quadratic.egrad_ell`` is linear, so it doubles as the ``Q`` matvec
    on probe blocks).  Shared edges appear in both endpoint agents' lists
    with the remote endpoint in a neighbor slot, so local rows accumulate
    exactly the global rows with no double counting — the matvec of the
    sharded certificate AND the sharded GN-CG tail."""

    def one(vl, vz, e, s, m):
        return quadratic.egrad_ell(jnp.concatenate([vl, vz]), e, s, m)

    return jax.vmap(one)(V, Vz, graph.edges, graph.inc_slot, graph.inc_mask)


def make_sharded_metrics_body(mesh: Mesh, graph: MultiAgentGraph,
                              edges_g, n_total: int, num_meas: int,
                              telemetry: bool):
    """The stacked-metrics body of the verdict program, traced under
    ``shard_map`` — ``rbcd._central_metrics_body`` with every centralized
    reduction expressed as a mesh collective:

    * the global iterate assembly is a ``psum`` of each shard's
      owner-scatter (disjoint supports — each global pose has exactly one
      owner agent, so the sum adds one value to zeros and is EXACT, not
      merely reduction-order-close);
    * the per-measurement weight collapse psums the per-shard scatter
      numerators/denominators (a measurement has at most two owner copies
      with identical weights, so this too is exact);
    * agent consensus is a psum of the not-ready count;
    * the telemetry extras (GNC inlier fraction, mean weight) psum their
      per-shard partial sums, and the per-agent relative-change row is an
      ``all_gather`` in agent order.

    The centralized cost/gradient then evaluate REPLICATED on every shard
    from the psum'd global assembly — identical math to the single-device
    body, so the verdict word, history rows, and termination latch carry
    over unchanged (``make_verdict_program(metrics_body=...)`` keeps all
    of that downstream logic shared).  Fed to ``rbcd.run_rbcd`` via its
    ``metrics_body_factory`` seam by ``solve_rbcd_sharded``."""
    ax = _axes(mesh)
    rep = lambda tree: jax.tree.map(lambda _: P(), tree)

    def shard_body(Xa, weights, ready, mu, rel, graph_s, eg):
        psum = lambda v: jax.lax.psum(v, ax)
        Xg = psum(rbcd.gather_to_global(Xa, graph_s, n_total))
        ids = graph_s.meas_id.reshape(-1)
        m = graph_s.edges.mask.reshape(-1)
        w = weights.reshape(-1)
        num = psum(jnp.zeros((num_meas,), weights.dtype).at[ids].add(w * m))
        den = psum(jnp.zeros((num_meas,), weights.dtype).at[ids].add(m))
        w_glob = jnp.where(den > 0, num / jnp.maximum(den, 1.0), 1.0)
        eg = eg._replace(weight=w_glob)
        f = quadratic.cost(Xg, eg)
        g = manifold.rgrad(Xg, quadratic.egrad(Xg, eg))
        not_ready = psum(jnp.sum(jnp.logical_not(ready).astype(jnp.int32)))
        vals = [f, manifold.norm(g), (not_ready == 0).astype(f.dtype)]
        if telemetry:
            e = graph_s.edges
            upd = e.mask * e.is_lc * (1.0 - e.fixed_weight)
            n_upd = jnp.maximum(psum(jnp.sum(upd)), 1.0)
            vals += [mu.astype(f.dtype),
                     psum(jnp.sum((weights > 0.5) * upd)) / n_upd,
                     psum(jnp.sum(weights * upd)) / n_upd]
            rel_all = jax.lax.all_gather(rel.astype(f.dtype), ax, axis=0,
                                         tiled=True)
            return jnp.concatenate([jnp.stack(vals), rel_all])
        return jnp.stack(vals)

    def metrics_body(Xa, weights, ready, mu, rel_change):
        in_specs = (P(ax), P(ax), P(ax), P(), P(ax),
                    _specs(mesh, graph), rep(edges_g))
        return _shard_map(shard_body, mesh, in_specs, P())(
            Xa, weights, ready, mu, rel_change, graph, edges_g)

    return metrics_body


# ---------------------------------------------------------------------------
# Sharded device-resident Gauss-Newton-CG tail
# ---------------------------------------------------------------------------
#
# ``refine.gn_tail`` breaks the block-coordinate floor with a centralized
# Gauss-Newton-CG polish, but it assembles S = Q - Lambda on the HOST in
# f64 scipy — a full global round-trip per outer step that cannot fit the
# serve plane's budget at 100k+ poses.  Here the same algorithm runs
# device-resident on the agent mesh: the S matvec is each shard's local
# ELL edge product plus the halo pose exchange (``local_grad_rows`` — the
# identical sharding as the solver round and the distributed certificate),
# every CG dot product is a psum, the block-Jacobi preconditioner is
# ``refine.gn_precond_blocks`` vectorized per shard, and the whole inner
# CG + backtracking retraction executes as ONE jitted shard_map program
# per outer step — zero host transfers inside the CG loop.  The host
# driver reads one small stats vector per outer step through the
# sanctioned ``rbcd._host_fetch`` seam.


def _gn_outer_shard(X, graph: MultiAgentGraph, *, ax, meta: GraphMeta,
                    cfg: "refine.GNTailConfig"):
    """shard_map body of one GN outer step: gradient, preconditioned
    Steihaug-CG Newton solve, backtracking projective retraction —
    ``refine.gn_tail``'s per-outer-iteration math on the agent-sharded
    layout.  Returns ``(X_new [A_loc, ...], stats [7] replicated)`` with
    stats = [cost, grad_norm, cg_iters, neg_curv, accepted, new_cost,
    step]."""
    d = meta.d
    n_max = meta.n_max
    dtype = X.dtype
    psum = lambda v: jax.lax.psum(v, ax)
    pdot = lambda u, w: psum(jnp.sum(u * w))
    exchange = _gather_exchange(graph, ax)
    pmask = graph.pose_mask[..., None, None]
    edges = graph.edges
    # Each cross-robot measurement appears in BOTH endpoint agents' edge
    # lists (neighbor-slot endpoint >= n_max marks it), so the global cost
    # halves the shared copies before the psum.
    shared = ((edges.i >= n_max) | (edges.j >= n_max)).astype(dtype)
    cscale = edges.mask * edges.weight * (1.0 - 0.5 * shared)

    def grad_rows(V):
        return local_grad_rows(V, exchange(V), graph)

    def cost_of(V):
        Vz = exchange(V)

        def one(vl, vz, e, cs):
            rR, rt = quadratic._edge_terms(jnp.concatenate([vl, vz]), e)
            return 0.5 * jnp.sum(
                cs * (e.kappa * jnp.sum(rR * rR, axis=(-2, -1))
                      + e.tau * jnp.sum(rt * rt, axis=-1)))

        return psum(jnp.sum(jax.vmap(one)(V, Vz, edges, cscale)))

    def tangent(W):
        return manifold.tangent_project(X, W) * pmask

    # Gradient and dual blocks: G = rows of X Q; Lambda_i = sym(Y_i^T G_Y,i)
    # per pose; rgrad = X S = G - [Y Lambda | 0] (already tangent — Lambda
    # IS the projection multiplier; re-project for hygiene, as the host
    # tail does).
    G = grad_rows(X)
    lam = manifold.sym(
        jnp.einsum("xnra,xnrb->xnab", X[..., :d], G[..., :d]))
    lam_of = lambda V: jnp.concatenate(
        [jnp.einsum("xnra,xnab->xnrb", V[..., :d], lam),
         jnp.zeros_like(V[..., -1:])], axis=-1)
    grad = tangent(G - lam_of(X))
    f0 = cost_of(X)
    gn = jnp.sqrt(pdot(grad, grad))

    blocks = refine.gn_precond_blocks(edges, lam, n_max, meta.s_max, d,
                                      cfg.precond_shift)

    def Av(V):
        W = grad_rows(V) - lam_of(V)
        if cfg.damping:
            W = W + cfg.damping * V
        return tangent(W)

    def Minv(V):
        W = jnp.linalg.solve(blocks, jnp.swapaxes(V, -1, -2))
        return tangent(jnp.swapaxes(W, -1, -2))

    # Preconditioned CG on the tangent space, Steihaug negative-curvature
    # exit — the host tail's loop as a lax.while_loop (no host in sight).
    b = -grad
    b_norm = jnp.sqrt(pdot(b, b))
    z0 = Minv(b)
    eps = jnp.asarray(1e-300 if dtype == jnp.float64 else 1e-30, dtype)

    def cg_cond(c):
        k, _v, _res, _p, _rz, done, _neg = c
        return (k < cfg.cg_max_iters) & jnp.logical_not(done)

    def cg_body(c):
        k, v, res, p, rz, done, neg_seen = c
        Ap = Av(p)
        pAp = pdot(p, Ap)
        neg = pAp <= 0
        # Negative curvature on the very first iteration: fall back to
        # the gradient direction; later: keep the accumulated step.
        v_fallback = jnp.where(k == 0, b, v)
        alpha = rz / jnp.where(neg, jnp.ones_like(pAp), pAp)
        v_new = v + alpha * p
        res_new = res - alpha * Ap
        small = jnp.sqrt(pdot(res_new, res_new)) <= cfg.cg_rtol * b_norm
        z = Minv(res_new)
        rz_new = pdot(res_new, z)
        p_new = z + (rz_new / jnp.maximum(rz, eps)) * p
        stop = neg | small
        return (k + 1,
                jnp.where(neg, v_fallback, v_new),
                jnp.where(neg, res, res_new),
                jnp.where(stop, p, p_new),
                jnp.where(stop, rz, rz_new),
                stop, neg_seen | neg)

    k0 = jnp.zeros((), jnp.int32)
    cg_iters, v, _res, _p, _rz, _done, neg_seen = jax.lax.while_loop(
        cg_cond, cg_body,
        (k0, jnp.zeros_like(b), b, z0, pdot(b, z0),
         jnp.zeros((), bool), jnp.zeros((), bool)))

    # Backtracking projective retraction on the true (psum'd) cost.
    def bt_cond(c):
        j, _step, _Xb, _fb, acc = c
        return (j < cfg.max_backtracks) & jnp.logical_not(acc)

    def bt_body(c):
        j, step, Xb, fb, acc = c
        Xc = manifold.project(X + step * v)
        fc = cost_of(Xc)
        ok = jnp.isfinite(fc) & (fc < f0)
        return (j + 1, step * cfg.step_shrink,
                jnp.where(ok, Xc, Xb), jnp.where(ok, fc, fb), acc | ok)

    _j, last_step, X_new, f_new, accepted = jax.lax.while_loop(
        bt_cond, bt_body,
        (jnp.zeros((), jnp.int32), jnp.asarray(1.0, dtype), X, f0,
         jnp.zeros((), bool)))

    stats = jnp.stack([f0, gn, cg_iters.astype(dtype),
                       neg_seen.astype(dtype), accepted.astype(dtype),
                       f_new, last_step])
    return X_new, stats


def _gn_gradnorm_shard(X, graph: MultiAgentGraph, *, ax, meta: GraphMeta):
    """shard_map body: the centralized Riemannian gradient norm of the
    agent-sharded iterate (the GN tail's gate quantity) — one matvec."""
    d = meta.d
    psum = lambda v: jax.lax.psum(v, ax)
    exchange = _gather_exchange(graph, ax)
    G = local_grad_rows(X, exchange(X), graph)
    lam = manifold.sym(
        jnp.einsum("xnra,xnrb->xnab", X[..., :d], G[..., :d]))
    S_rot = G[..., :d] - jnp.einsum("xnra,xnab->xnrb", X[..., :d], lam)
    grad = jnp.concatenate([S_rot, G[..., -1:]], axis=-1)
    grad = manifold.tangent_project(X, grad) \
        * graph.pose_mask[..., None, None]
    return jnp.sqrt(psum(jnp.sum(grad * grad)))


#: Compiled sharded-GN-tail program cache, FIFO-bounded for the same
#: reason as the certificate cache: each entry pins a Mesh.
_GN_CACHE: dict = {}
_GN_CACHE_MAX = 8


def _gn_programs(mesh: Mesh, meta: GraphMeta, cfg):
    key = (mesh, meta, cfg)
    progs = _GN_CACHE.get(key)
    if progs is not None:
        return progs
    ax = _axes(mesh)

    @jax.jit
    def outer(X, graph):
        body = partial(_gn_outer_shard, ax=ax, meta=meta, cfg=cfg)
        return _shard_map(body, mesh, (P(ax), _specs(mesh, graph)),
                          (P(ax), P()))(X, graph)

    @jax.jit
    def gradnorm(X, graph):
        body = partial(_gn_gradnorm_shard, ax=ax, meta=meta)
        return _shard_map(body, mesh, (P(ax), _specs(mesh, graph)),
                          P())(X, graph)

    while len(_GN_CACHE) >= _GN_CACHE_MAX:
        _GN_CACHE.pop(next(iter(_GN_CACHE)))
    _GN_CACHE[key] = (outer, gradnorm)
    return outer, gradnorm


def gn_tail_sharded(X, graph: MultiAgentGraph, meta: GraphMeta,
                    mesh: Mesh | None = None,
                    cfg: "refine.GNTailConfig | None" = None,
                    weights=None, log=None,
                    fetch_deadline_s: float | None = None):
    """Sharded, device-resident Gauss-Newton-CG polish of an
    agent-partitioned iterate — ``refine.gn_tail`` without the host-f64
    scipy round-trip.

    ``X [A, n_max, r, d+1]`` and ``graph`` may be host or mesh-placed;
    they are sharded over ``mesh`` (default: all devices).  ``weights
    [A, E]``, when given, replaces ``graph.edges.weight`` — pass the final
    GNC weights when polishing a robust solve.  Per outer step ONE small
    stats vector crosses the link (through ``rbcd._host_fetch``); the CG
    loop and the backtracking retraction run entirely on device.

    ``fetch_deadline_s`` arms a ``parallel.resilience.Watchdog`` around
    those blocking reads: a dead mesh raises a phase-naming
    ``MeshFaultError`` instead of hanging the caller forever.  (Inside
    ``solve_rbcd_sharded(resilience=...)`` the solve's own guard already
    covers this tail — leave it None there.)

    Returns ``(X_agents, refine.GNTailResult)`` — the polished iterate in
    the sharded per-agent layout plus the host result record (global
    assembly, histories, totals) in ``gn_tail``'s schema."""
    mesh = mesh or make_mesh()
    cfg = cfg or refine.GNTailConfig()
    if weights is not None:
        graph = rbcd.with_weights(graph, weights)
    put = lambda t: jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        t, _specs(mesh, t))
    X = put(jnp.asarray(X))
    graph = put(graph)
    outer, gradnorm = _gn_programs(mesh, meta, cfg)

    cost_hist: list = []
    gn_hist: list = []
    cg_total = 0
    outer_done = 0
    terminated_by = "max_outer"
    with contextlib.ExitStack() as stack:
        if fetch_deadline_s is not None:
            # Watchdog scope: the two sanctioned fetches below route
            # through rbcd._host_fetch, which the guard deadline-wraps.
            stack.enter_context(resilience_mod.fetch_guard(
                resilience_mod.Watchdog(fetch_deadline_s), None,
                ["gn_tail"], close=True))
        for k in range(int(cfg.max_outer) + 1):
            # One scalar per outer step: the gate quantity.  The stats
            # fetch below is the only other transfer — the CG loop itself
            # never touches the host.
            # dpgolint: disable=DPG003 -- sanctioned GN-tail gate fetch
            gn = float(rbcd._host_fetch(gradnorm(X, graph)))
            gn_hist.append(gn)
            if log is not None:
                cst = cost_hist[-1] if cost_hist else float("nan")
                log(f"  gn_tail_sharded outer {k}: cost {cst:.9g} "
                    f"gn {gn:.4g}")
            if gn < cfg.grad_norm_tol:
                terminated_by = "grad_norm"
                break
            if k == int(cfg.max_outer):
                break  # budget exhausted; final point's gate value recorded
            X_new, stats = outer(X, graph)
            # dpgolint: disable=DPG003 -- sanctioned per-outer stats fetch
            st = rbcd._host_fetch(stats)
            f0, _gn_s, cg_iters, _neg, accepted, f_new, _step = \
                (float(v) for v in st)
            if not cost_hist:
                cost_hist.append(f0)
            cg_total += int(cg_iters)
            outer_done = k + 1
            if accepted <= 0:
                cost_hist.append(f0)
                terminated_by = "no_decrease"
                break
            cost_hist.append(f_new)
            X = X_new

    n_total = int(np.asarray(graph.global_index).max()) + 1
    Xg = np.asarray(rbcd.gather_to_global(X, graph, n_total), np.float64)
    result = refine.GNTailResult(
        X=Xg, cost_history=cost_hist, grad_norm_history=gn_hist,
        outer_iterations=outer_done, cg_iterations=cg_total,
        converged=terminated_by == "grad_norm", terminated_by=terminated_by)
    return X, result


#: Fused rounds per arm of the ``overlap="auto"`` calibration, timed
#: repetitions per arm (best-of, alternating), and the A/B efficiency
#: the overlapped arm must clear to win.  The threshold is deliberate
#: hysteresis sized ABOVE the scheduling-noise band of short best-of-N
#: walls on a shared-core mesh: measured across ~100 calibrations on the
#: 8-virtual-device CPU mesh the A/B efficiency of two equivalent
#: schedules wanders in roughly [-0.10, +0.18], so anything below 0.25
#: is indistinguishable from noise there.  Pipelining's genuine win is
#: the hidden collective fraction of the round — tens of percent on a
#: real interconnect when it pays at all (its loss on the CPU mesh is
#: what MULTICHIP_r06 measured at -0.05) — so the gate only flips to
#: overlapped on a decisive, better-than-noise win and resolves
#: everything else to the simpler lockstep schedule.
_AUTO_CALIB_ROUNDS = 8
_AUTO_CALIB_REPS = 3
_AUTO_THRESHOLD = 0.25


def _resolve_overlap_auto(mesh, state, graph, meta, params, exchange,
                          calib_rounds: int = _AUTO_CALIB_ROUNDS) -> bool:
    """The adaptive overlap gate: a bounded lockstep-vs-overlapped
    calibration on the real sharded problem, arbitrated by
    ``obs.devprof.decide_overlap``.

    Each arm compiles its fused multi-round program, warms it (paying the
    compile outside the timed window), then times ``calib_rounds``-round
    segments to a ``jax.block_until_ready`` fence — alternating arms,
    best of ``_AUTO_CALIB_REPS``, with NO profiler active: trace capture
    slows the traced
    program, so the decision walls stay clean.  With telemetry on, one
    additional segment per arm then runs under a ``DeviceTraceWindow``
    so the ``overlap_decision`` event carries the measured device-time
    evidence (collective/compute split, measured overlap efficiency)
    next to the A/B walls.  Calibration segments are pure functions of
    the sharded state and are discarded — the solve proper starts from
    the untouched initial state, so forced ``overlap=True/False`` modes
    remain bitwise references."""
    from ..obs import devprof

    run = obs.get_run()
    size = int(mesh.devices.size)
    if size == 1:
        # No collectives to hide on one device — nothing to calibrate.
        if run is not None:
            run.event("overlap_decision", phase="setup", mesh_size=size,
                      exchange=exchange, overlap=False,
                      reason="single_device_mesh", calib_rounds=0)
        return False
    shifts, plan = _exchange_plan(mesh, meta, graph, exchange)
    names = ("lockstep", "overlapped")
    multis = {}
    arms = {}
    for name, ov in zip(names, (False, True)):
        multis[name] = make_sharded_multi_step(mesh, meta, params, shifts,
                                               plan, overlap=ov)
        devprof.time_arm(multis[name], state, graph,
                         calib_rounds)  # compile + warm
        arms[name] = {"seconds": float("inf"), "rounds": calib_rounds,
                      "attribution": None}
    for _rep in range(_AUTO_CALIB_REPS):
        for name in names:
            dt = devprof.time_arm(multis[name], state, graph, calib_rounds)
            arms[name]["seconds"] = min(arms[name]["seconds"], dt)
    if run is not None:
        for name in names:
            window = devprof.DeviceTraceWindow(
                os.path.join(run.run_dir, f"devprof_auto_{name}"),
                plane="sharded").start()
            devprof.time_arm(multis[name], state, graph, calib_rounds)
            arms[name]["attribution"] = window.stop(
                num_rounds=calib_rounds, label=f"auto_{name}")
    decision = devprof.decide_overlap(arms, threshold=_AUTO_THRESHOLD)
    if run is not None:
        run.event("overlap_decision", phase="setup", mesh_size=size,
                  exchange=exchange, **decision)
    return bool(decision["overlap"])


def _resume_from_store(sup, mesh, graph_host, meta, params, run):
    """Warm entry for ``solve_rbcd_sharded(resume=True)``: the newest
    usable snapshot of the supervisor's session, resharded onto the
    caller's mesh (snapshots are mesh-shape-agnostic), or ``None`` for a
    cold start.  Same refresh-then-shard order as fault recovery, so a
    same-mesh resume is bitwise."""
    flush = getattr(sup.store, "flush", None)
    if flush is not None:
        flush()
    snap = sup.store.load_newest(sup.session_id)
    if snap is None:
        return None
    if snap.global_index is not None and not np.array_equal(
            np.asarray(snap.global_index), sup._gidx):
        return None  # different problem layout — fail open to cold start
    host_state = rbcd.refresh_problem(snap.state, graph_host, meta, params)
    state, graph = shard_problem(mesh, host_state, graph_host)
    if run is not None:
        run.event("mesh_resume", phase="resilience",
                  session=sup.session_id, iteration=int(snap.iteration),
                  mesh_size=int(mesh.devices.size))
    return state, graph, int(snap.iteration), int(snap.num_weight_updates)


def solve_rbcd_sharded(
    meas: Measurements,
    num_robots: int,
    mesh: Mesh | None = None,
    params: AgentParams | None = None,
    max_iters: int | None = None,
    grad_norm_tol: float = 0.1,
    eval_every: int = 1,
    dtype=jnp.float64,
    part: Partition | None = None,
    init: str = "chordal",
    exchange: str = "all_gather",
    verdict_every: int | None = None,
    overlap: "bool | str" = True,
    gn_tail: "refine.GNTailConfig | None" = None,
    resilience: "resilience_mod.ResilienceConfig | None" = None,
    boundary_cb=None,
    resume: bool = False,
) -> rbcd.RBCDResult:
    """Distributed solve over a device mesh — the deployment path of the
    framework (``models.rbcd.solve_rbcd`` is the single-device debug path).
    Shares the driver loop (``rbcd.run_rbcd``); only problem placement and
    the segment function differ.  ``exchange`` selects the pose-exchange
    collective: ``"all_gather"`` (v1) or ``"ppermute"`` (one collective per
    ring offset that carries a cross-device edge — fewer hops than the
    all_gather ring when the device adjacency is near-chain).

    Termination is decided on the device, as on one device: the
    centralized metrics trace under shard_map with their reductions as
    psums (``make_sharded_metrics_body``), the verdict word latches the
    terminal eval, and the host reads back ONE replicated packed int32
    every ``verdict_every`` rounds (K, a positive multiple of
    ``eval_every``; None reads it at every eval) through the same
    ``rbcd._host_fetch`` seam as the single-device loop.

    ``overlap`` (default on) software-pipelines the halo exchange inside
    the fused round loops (``make_sharded_multi_step``);
    ``overlap="auto"`` runs a bounded
    lockstep-vs-overlapped calibration on the sharded problem
    (``_resolve_overlap_auto``) and picks the winner, recording an
    ``overlap_decision`` event with the A/B walls and — with telemetry on
    — the measured device-time attribution as evidence.  Forced
    ``overlap=True/False`` stay bitwise-unchanged reference modes.
    ``gn_tail`` (a ``refine.GNTailConfig``)
    appends the sharded device-resident Gauss-Newton-CG polish
    (``gn_tail_sharded``) after the BCD loop, extending the returned
    histories with the tail's trajectory and re-finalizing the rounded
    trajectory from the polished iterate.

    ``resilience`` (a ``resilience_mod.ResilienceConfig``) arms the
    pod-scale fault story: mesh-elastic checkpoints at verdict
    boundaries, watchdog deadlines on every
    blocking fetch, and a supervisor that catches latched verdict
    anomalies and ``MeshFaultError``\\ s, rewinds to the last good
    checkpoint — on a smaller mesh after a device loss — and resumes at
    the exact absolute round index.  The returned result then carries a
    ``resilience`` summary dict and ``recovered=True`` if any rewind
    happened; its histories cover the final (resumed) attempt — a
    numerically-pinned suffix of the undisturbed run's.

    ``boundary_cb(it, nwu, state, word, terminal)`` is an external
    verdict-boundary hook that runs BEFORE the resilience supervisor's
    own: the multihost lockstep
    (``parallel.multihost``) rides it to cross-check the replicated
    verdict word across processes and surface a dead peer as
    ``MeshFaultError(kind="process_lost")``.  ``resume=True`` (requires
    ``resilience``) enters the solve at the newest usable checkpoint of
    ``resilience.session_id`` instead of the initial guess — the restart
    path of a multihost generation whose predecessor lost a process —
    falling back to a cold start when the store holds nothing usable."""
    mesh = mesh or make_mesh()
    mesh_size = int(mesh.devices.size)
    if num_robots % mesh_size != 0:
        # Validated up front — the alternative is an opaque failure deep
        # inside shard_problem/comm_bytes_per_round after the full graph
        # build has already been paid for.
        raise ValueError(
            f"num_robots={num_robots} is not divisible by the mesh size "
            f"{mesh_size}: solve_rbcd_sharded lays agents out in equal "
            f"contiguous blocks per device.  Pick num_robots as a "
            f"multiple of {mesh_size}, or build a smaller mesh "
            f"(make_mesh(n) with n dividing {num_robots}).")
    if resume and resilience is None:
        raise ValueError(
            "resume=True restores from the resilience checkpoint store; "
            "pass resilience=ResilienceConfig(...) to use it")
    params = params or AgentParams(d=meas.d, r=5, num_robots=num_robots)
    max_iters = params.max_num_iters if max_iters is None else max_iters

    # Telemetry (dpgo_tpu.obs): per-phase setup timings and the per-device
    # communication model for this mesh.  With no ambient run the timer is
    # never created and the path below is the uninstrumented one.
    run = obs.get_run()
    timer = RoundTimer() if run is not None else None

    part = part or partition_contiguous(meas, num_robots)
    if timer is not None:
        timer.start("build_graph")
    graph_host, meta = rbcd.build_graph(
        part, params.r, dtype, sel_mode=rbcd.resolved_sel_mode(params))
    if timer is not None:
        timer.stop("build_graph")
        timer.start("init")
    X0 = rbcd.initial_state_for(init, part, meta, graph_host, params, dtype)
    state_host0 = init_state(graph_host, meta, X0, params=params)
    if timer is not None:
        # The init chord/odometry solve runs on device; the obs-owned fence
        # materializes it so the phase boundary is trustworthy (telemetry-on
        # only — the off path never reaches this transfer).
        timer.stop("init", sync=obs.materialize(state_host0.X))
        timer.start("shard")
    state, graph = shard_problem(mesh, state_host0, graph_host)
    if timer is not None:
        timer.stop("shard")
        run.event("phase_timings", phase="setup", timings=timer.as_dict())

    if overlap == "auto":
        # Adaptive overlap gate (ISSUE 16): decide pipelining from a
        # measured A/B on this mesh/problem, not a hand-set flag.
        overlap = _resolve_overlap_auto(mesh, state, graph, meta, params,
                                        exchange)
    elif not isinstance(overlap, bool):
        raise ValueError(
            f"overlap={overlap!r}: expected True, False, or 'auto'")

    n_total = part.meas_global.num_poses
    num_meas = len(part.meas_global)
    certify_mode = getattr(params, "certify_mode", "off")
    edges_g = edge_set_from_measurements(part.meas_global, dtype=dtype)

    def _attempt(mesh_a, state_a, graph_a, start_it, start_nwu,
                 boundary_cb, injector):
        """One driver entry on one mesh: build the compiled segment
        wrapper for ``mesh_a`` and run ``rbcd.run_rbcd`` from the given
        absolute round index.  The supervisor loop below re-invokes this
        after a rewind — possibly on a smaller mesh."""
        size_a = int(mesh_a.devices.size)
        shifts, plan = _exchange_plan(mesh_a, meta, graph_a, exchange)
        sharded_seg = make_sharded_segment(mesh_a, meta, params, shifts,
                                           plan, overlap=overlap)
        if run is not None:
            # Compile accounting with the bytes-per-flop roofline for the
            # hot sharded program (devprof.profiled_program AOT-compiles
            # once per static combo — same compile count as the plain jit
            # path — and falls back to it on any probe failure).
            # Fence-guarded: telemetry off keeps the bare jit callable.
            from ..obs import devprof

            sharded_seg = devprof.profiled_program(
                run, sharded_seg, key=f"sharded/{size_a}/segment",
                label="sharded_segment", plane="sharded",
                static_names=("update_weights", "restart"),
                mesh_size=size_a)
        if injector is not None:
            # Chaos seam (parallel.resilience): the injector counts
            # dispatched rounds and may poison a seeded public pose —
            # an async device op, never a host sync.
            seg = lambda s, k, uw, rs: sharded_seg(
                injector.before_dispatch(s, k), graph_a, k,
                update_weights=uw, restart=rs)
        else:
            seg = lambda s, k, uw, rs: sharded_seg(s, graph_a, k,
                                                   update_weights=uw,
                                                   restart=rs)
        # The shared driver (run_rbcd), with the stacked-metrics body
        # traced inside shard_map, reductions as psums.
        metrics_factory = lambda telemetry: make_sharded_metrics_body(
            mesh_a, graph_a, edges_g, n_total, num_meas, telemetry)
        if run is not None:
            bytes_round = comm_bytes_per_round(
                meta, size_a,
                shifts=shifts if exchange == "ppermute" else None,
                accel=params.acceleration,
                itemsize=np.dtype(dtype).itemsize,
                greedy=params.schedule.value == "greedy")
            run.event("sharded_solve", phase="setup", mesh_size=size_a,
                      mesh_axes=list(mesh_a.axis_names), exchange=exchange,
                      num_robots=num_robots,
                      agents_per_shard=num_robots // size_a,
                      comm_bytes_per_round=bytes_round,
                      overlap=overlap, verdict_every=verdict_every,
                      start_iteration=int(start_it))
            run.gauge("sharded_comm_bytes_per_round",
                      "modeled per-device interconnect bytes per round",
                      unit="bytes").set(bytes_round)
            # Mesh identity into the run fingerprint: a 1-device and an
            # 8-device solve of the same problem are not comparable runs
            # for the convergence regression gate (report --compare).
            run.set_fingerprint(solver="solve_rbcd_sharded",
                                mesh_size=size_a, exchange=exchange)
        return rbcd.run_rbcd(state_a, graph_a, meta, seg, part, max_iters,
                             grad_norm_tol, eval_every, dtype, params=params,
                             verdict_every=verdict_every,
                             metrics_body_factory=metrics_factory,
                             start_iteration=start_it,
                             start_num_weight_updates=start_nwu,
                             boundary_cb=boundary_cb)

    def _append_gn_tail(res, graph_a, mesh_a):
        """Device-resident GN-CG polish on the terminal iterate (the
        sharded stall-breaker): same weighted objective the solve
        minimized."""
        Xa, tail = gn_tail_sharded(res.state.X, graph_a, meta, mesh=mesh_a,
                                   cfg=gn_tail, weights=res.state.weights)
        if run is not None:
            run.event("gn_tail", phase="refine", sharded=True,
                      outer_iterations=tail.outer_iterations,
                      cg_iterations=tail.cg_iterations,
                      terminated_by=tail.terminated_by,
                      cost=tail.cost_history[-1]
                      if tail.cost_history else None,
                      grad_norm=tail.grad_norm_history[-1]
                      if tail.grad_norm_history else None)

        # Re-finalize from the polished iterate through the shared fused
        # epilogue: with a certify mode on, the certificate is recomputed
        # on the POLISHED iterate (superseding the loop's) and rides the
        # same single terminal fetch.
        epilogue = rbcd.make_terminal_epilogue(
            graph_a, edges_g, n_total, num_meas, meta,
            certify_mode=certify_mode)
        fin = epilogue(Xa, res.state.weights, {})
        certificate = res.certificate
        if certify_mode != "off":
            # dpgolint: disable=DPG003 -- sanctioned terminal epilogue fetch
            fin = rbcd._host_fetch(fin)
            certificate = rbcd._epilogue_certificate(fin, edges_g, params,
                                                     dtype)
        T, w_glob = fin["T"], fin["w_glob"]
        return dataclasses.replace(
            res, T=T, X=Xa, weights=w_glob,
            cost_history=res.cost_history + tail.cost_history,
            grad_norm_history=res.grad_norm_history
            + tail.grad_norm_history,
            terminated_by=tail.terminated_by if tail.converged
            else res.terminated_by,
            state=res.state._replace(X=Xa),
            certificate=certificate)

    if resilience is None:
        res = _attempt(mesh, state, graph, 0, 0, boundary_cb, None)
        return res if gn_tail is None else _append_gn_tail(res, graph, mesh)

    # -- the rewind supervisor (parallel.resilience) ------------------------
    cfg = resilience
    store = cfg.resolve_store()
    sup = resilience_mod.CheckpointSupervisor(cfg, store, graph_host)
    injector = cfg.injector
    if injector is not None:
        injector.arm(graph_host)
    watchdog = resilience_mod.Watchdog(cfg.fetch_deadline_s) \
        if cfg.fetch_deadline_s is not None else None
    phase = ["sharded_verdict"]
    mesh_cur, state_cur, graph_cur = mesh, state, graph
    start_it = start_nwu = 0
    if boundary_cb is None:
        chained_cb = sup.boundary_cb
    else:
        def chained_cb(it, nwu, st, word, terminal, _ext=boundary_cb):
            # External hook first: the multihost lockstep must agree the
            # boundary is clean ACROSS processes before this rank commits
            # a checkpoint of it (a desync or dead peer aborts the save).
            _ext(it, nwu, st, word, terminal)
            sup.boundary_cb(it, nwu, st, word, terminal)
    if resume:
        restored = _resume_from_store(sup, mesh_cur, graph_host, meta,
                                      params, run)
        if restored is not None:
            state_cur, graph_cur, start_it, start_nwu = restored
    sup.attach_mesh(mesh_size)
    try:
        with resilience_mod.fetch_guard(watchdog, injector, phase):
            while True:
                try:
                    res = _attempt(mesh_cur, state_cur, graph_cur,
                                   start_it, start_nwu, chained_cb,
                                   injector)
                    break
                except (resilience_mod.AnomalyRewind,
                        resilience_mod.MeshFaultError) as e:
                    t0 = time.perf_counter()
                    if injector is not None:
                        # Unblock any simulated hang so abandoned
                        # watchdog workers can exit.
                        injector.release_hangs()
                    new_size, host_state, start_it, start_nwu = \
                        sup.recover(e, int(mesh_cur.devices.size),
                                    num_robots)
                    if new_size != int(mesh_cur.devices.size):
                        mesh_cur = make_mesh(new_size)
                    if host_state is None:
                        # Cold restart: no usable snapshot — back to the
                        # initial guess (factors already baked).
                        host_state = state_host0
                    else:
                        # Rebake the factors from the stored weights
                        # BEFORE sharding — the same host-then-shard
                        # order as the initial build, so a same-mesh
                        # resume is bitwise.
                        host_state = rbcd.refresh_problem(
                            host_state, graph_host, meta, params)
                    state_cur, graph_cur = shard_problem(
                        mesh_cur, host_state, graph_host)
                    sup.attach_mesh(new_size)
                    sup.note_overhead(time.perf_counter() - t0)
            if gn_tail is not None:
                phase[0] = "gn_tail"
                res = _append_gn_tail(res, graph_cur, mesh_cur)
    finally:
        if injector is not None:
            injector.release_hangs()
        if watchdog is not None:
            watchdog.close()
    return dataclasses.replace(
        res, recovered=res.recovered or sup.recoveries > 0,
        resilience=sup.finish(injector))
