"""Multi-agent Riemannian block-coordinate descent (RBCD) — the distributed
core of the framework.

Replaces the reference's per-robot ``PGOAgent`` object graph
(``src/PGOAgent.cpp``) and the in-process message loop of
``examples/MultiRobotExample.cpp`` with a TPU-native design (SURVEY.md
section 7): all agents' states live in one batched array ``X: [A, n_max, r,
d+1]``, a single jitted step function updates the selected/all blocks, and
"communication" is an array gather of the public-pose table (a collective in
the sharded path, ``dpgo_tpu.parallel``).

Mapping to the reference:

* measurement classification odometry / private LC / shared LC
  (``PGOAgent.cpp:197-248``)  ->  host-side graph builder, one padded
  ``EdgeSet`` per agent whose indices point into a per-agent buffer
  ``[local poses | neighbor slots]``.
* ``constructQMatrix`` / ``constructGMatrix`` (``PGOAgent.cpp:720-859``)
  ->  nothing to construct: the per-agent cost/gradient/Hessian evaluate
  edge-wise against the buffer (``ops.quadratic``); fixed neighbor slots
  reproduce Q's shared-edge diagonal blocks and the linear term G exactly.
* ``iterate(true)`` + ``QuadraticOptimizer`` (``PGOAgent.cpp:642-718``,
  ``1093-1145``)  ->  ``ops.solver.rtr_single_step`` vmapped over agents.
* greedy selection by block gradient norm
  (``MultiRobotExample.cpp:242-256``)  ->  GREEDY schedule (argmax of the
  per-agent Riemannian gradient norms, computed locally — no centralized
  oracle needed).  JACOBI updates all agents each round (the TPU-native
  default; Jacobi-style parallel RBCD is what the reference's async mode
  realizes in wall-clock).  ASYNC fires each agent with an independent
  Bernoulli clock per round (``PGOAgent.cpp:876-898`` semantics).
* termination status gossip (``PGOAgent.h:163-207``, ``shouldTerminate``,
  ``PGOAgent.cpp:1007-1031``)  ->  per-agent relative-change array reduced
  with ``all``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import (AgentParams, ROptAlg, RobustCostParams,
                      RobustCostType, Schedule)
from .. import obs
from ..obs import trace
from .. import robust
from ..types import EdgeSet, Measurements, edge_set_from_measurements
from ..utils.graph_plan import plan_topology
from ..utils.lie import lifting_matrix as _lifting_matrix
from ..utils.partition import Partition, partition_contiguous
from ..ops import chordal, manifold, quadratic, solver
from .local_pgo import lift, round_solution


@dataclasses.dataclass(frozen=True)
class GraphMeta:
    """Static shape metadata (hashable; a jit static argument)."""

    num_robots: int
    n_max: int
    e_max: int
    s_max: int  # neighbor slots per agent
    p_max: int  # public poses per agent
    d: int
    rank: int
    # Chromatic size of the greedy agent coloring (Schedule.COLORED fires
    # color class (iteration mod num_colors) each round).
    num_colors: int = 1


class MultiAgentGraph(NamedTuple):
    """Batched per-agent problem data (pytree of [A, ...] arrays)."""

    edges: EdgeSet  # fields [A, E_max]; i/j index into [n_max + S_max] buffer
    meas_id: jax.Array  # [A, E_max] global measurement id (weight consistency)
    n: jax.Array  # [A] pose counts
    pose_mask: jax.Array  # [A, n_max]
    pub_idx: jax.Array  # [A, P_max] local indices of public poses
    pub_mask: jax.Array  # [A, P_max]
    nbr_robot: jax.Array  # [A, S_max]
    nbr_pub: jax.Array  # [A, S_max] slot into that robot's public row
    nbr_mask: jax.Array  # [A, S_max]
    global_index: jax.Array  # [A, n_max] local -> global pose id (0 for pad)
    # ELL incidence of local poses (gather-only gradient/Hessian path,
    # ``ops.quadratic.egrad_ell``): slot e = endpoint i of edge e, slot
    # E_max + e = endpoint j.  K = max local pose degree over the partition.
    inc_slot: jax.Array  # [A, n_max, K] into the [gi | gj] concatenation
    inc_mask: jax.Array  # [A, n_max, K]
    # Tile-major edge data for the Pallas VMEM solver kernels
    # (``ops.pallas_tcg``): edges padded to nt * T and stored with the tile
    # axis leading so the kernel streams one [*, T] tile per ``fori_loop``
    # step, building each one-hot selection tile on the fly from the int32
    # endpoint indices (memory O(E), vs the O(E*n) resident one-hot
    # matrices of the first design).  Padded edges carry index
    # n_max + s_max, which one-hots to all-zero in both the local and the
    # neighbor range.  None when built with pallas_sel=False.
    eidx_i: jax.Array | None = None  # [A, nt, 1, T] int32 into [n+s] buffer
    eidx_j: jax.Array | None = None  # [A, nt, 1, T]
    rot_t: jax.Array | None = None   # [A, nt, d*d, T]
    trn_t: jax.Array | None = None   # [A, nt, d, T]
    # Greedy agent coloring (``utils.graph_plan.color_agents``): agents of
    # one color share no edge; Schedule.COLORED fires one class per round.
    color: jax.Array | None = None   # [A] int32


class RBCDState(NamedTuple):
    X: jax.Array  # [A, n_max, r, d+1]
    weights: jax.Array  # [A, E_max] robust (GNC) weights per edge
    iteration: jax.Array  # int32
    key: jax.Array  # [A, 2] per-agent PRNG keys (async schedule)
    rel_change: jax.Array  # [A]
    ready: jax.Array  # [A] bool
    # Nesterov acceleration (RA-L 2020; reference PGOAgent.cpp:1054-1091).
    # V is the auxiliary sequence (None when acceleration is off); gamma and
    # alpha are the per-agent momentum scalars.  Y is recomputed every round
    # from (X, V, alpha) and never carried across rounds (the reference's
    # stored Y is always refreshed by updateY before any use).
    V: jax.Array | None  # [A, n_max, r, d+1] or None
    gamma: jax.Array  # [A]
    alpha: jax.Array  # [A]
    # GNC control parameter (reference RobustCost::mu, DPGO_robust.cpp:85-103).
    mu: jax.Array  # scalar
    # Initial guess, kept only when the robust warm start is disabled: the
    # iterate resets to it on every weight update (PGOAgent.cpp:657-662).
    X_init: jax.Array | None  # [A, n_max, r, d+1] or None
    # Block-Jacobi preconditioner factors [A, n_max, d+1, d+1].  Q's diagonal
    # blocks depend only on the GNC weights, so the factorization is carried
    # across rounds and refreshed only on weight-update rounds — the same
    # schedule as the reference's CHOLMOD refactorization
    # (constructQMatrix inside updateX only in robust mode,
    # PGOAgent.cpp:1110-1112; QuadraticProblem::setQ factorizes, cpp:37-41).
    chol: jax.Array | None = None
    # Materialized per-agent connection Laplacian over the pose buffer,
    # [A, (d+1)(n_max+s_max), (d+1)(n_max+s_max)] (``quadratic.dense_q``) —
    # the dense-Q fast path; None when the buffers are too large to
    # materialize (``use_dense_q``).  Same refresh schedule as ``chol``.
    Qbuf: jax.Array | None = None


def build_graph(part: Partition, rank: int, dtype=jnp.float32,
                pallas_sel: bool | None = None, planner: str = "auto",
                wide_tiles: bool | None = None,
                sel_mode: str | None = None):
    """Assemble padded per-agent arrays from a partitioned measurement set.

    Each shared measurement appears in both endpoint agents' edge lists with
    the remote endpoint redirected to a neighbor slot — the same double
    bookkeeping as ``PGOAgent::addSharedLoopClosure`` (reference
    ``PGOAgent.cpp:228-248``), but as index arrays instead of dictionaries.
    Topology (edge rows, slot tables, ELL incidence) comes from the planner
    (``utils.graph_plan``: native C++ when available, Python fallback —
    identical output); the per-edge payload scatter here is vectorized
    numpy.
    """
    A = part.num_robots
    meas = part.meas
    d = meas.d
    n_max = part.n_max

    plan = plan_topology(meas.r1, meas.p1, meas.r2, meas.p2, A, n_max,
                         backend=planner)
    e_max, s_max, p_max = plan.e_max, plan.s_max, plan.p_max

    cls = part.classify()  # 0 odo, 1 private LC, 2 shared

    # Vectorized per-edge payload scatter over the planned rows.
    valid = plan.emask  # [A, e_max] bool
    kk = plan.meas_id[valid]  # global measurement id per valid (a, idx)
    eR = np.tile(np.eye(d), (A, e_max, 1, 1))
    et = np.zeros((A, e_max, d))
    ekap = np.zeros((A, e_max))
    etau = np.zeros((A, e_max))
    eis_lc = np.zeros((A, e_max))
    efix = np.zeros((A, e_max))
    eweight = np.ones((A, e_max))
    eR[valid] = meas.R[kk]
    et[valid] = meas.t[kk]
    ekap[valid] = meas.kappa[kk]
    etau[valid] = meas.tau[kk]
    eis_lc[valid] = (cls[kk] != 0).astype(np.float64)
    efix[valid] = np.asarray(meas.is_known_inlier, bool)[kk].astype(np.float64)
    eweight[valid] = meas.weight[kk]

    # Tile-major edge arrays for the Pallas tCG kernel (int32 endpoint
    # indices + edge transforms, padded to nt * T — O(E) memory, so no
    # budget gate is needed at build time).  Skipped entirely
    # (pallas_sel=None -> auto) off-TPU, where the kernel would only ever
    # run in interpreter mode — force with pallas_sel=True for
    # interpreter-mode testing.
    if pallas_sel is None:
        pallas_sel = jax.default_backend() == "tpu"
    if pallas_sel:
        # Wide (T=256) tiles are sound only for bf16 selection modes
        # (half-size one-hot transients; f32 aborts in Mosaic — see
        # _edge_tile_shape).  Derive from ``sel_mode`` (the kernel
        # selection mode this graph will run under, e.g.
        # ``resolved_sel_mode(params)``) unless explicitly overridden.
        if wide_tiles is None:
            wide_tiles = sel_mode is not None and sel_mode != "f32"
        T, nt = _edge_tile_shape(n_max, s_max, e_max, wide=wide_tiles)
        Ep = nt * T
        pad_idx = n_max + s_max  # one-hots to all-zero in both ranges
        idx_i = np.full((A, Ep), pad_idx, np.int32)
        idx_j = np.full((A, Ep), pad_idx, np.int32)
        idx_i[:, :e_max][valid] = plan.ei[valid]
        idx_j[:, :e_max][valid] = plan.ej[valid]
        rot_flat = np.zeros((A, d * d, Ep), np.float32)
        trn_flat = np.zeros((A, d, Ep), np.float32)
        rot_flat[:, :, :e_max] = eR.transpose(0, 2, 3, 1).reshape(
            A, d * d, e_max)
        trn_flat[:, :, :e_max] = et.transpose(0, 2, 1)
        pallas_fields = dict(
            eidx_i=jnp.asarray(idx_i.reshape(A, nt, 1, T)),
            eidx_j=jnp.asarray(idx_j.reshape(A, nt, 1, T)),
            rot_t=jnp.asarray(np.ascontiguousarray(
                rot_flat.reshape(A, d * d, nt, T).transpose(0, 2, 1, 3))),
            trn_t=jnp.asarray(np.ascontiguousarray(
                trn_flat.reshape(A, d, nt, T).transpose(0, 2, 1, 3))))
    else:
        pallas_fields = dict(eidx_i=None, eidx_j=None, rot_t=None, trn_t=None)

    pose_mask = (np.arange(n_max)[None, :] < part.n[:, None]).astype(np.float64)

    edges = EdgeSet(
        i=jnp.asarray(plan.ei), j=jnp.asarray(plan.ej),
        R=jnp.asarray(eR, dtype), t=jnp.asarray(et, dtype),
        kappa=jnp.asarray(ekap, dtype), tau=jnp.asarray(etau, dtype),
        weight=jnp.asarray(eweight, dtype),
        mask=jnp.asarray(valid.astype(np.float64), dtype),
        is_lc=jnp.asarray(eis_lc, dtype), fixed_weight=jnp.asarray(efix, dtype),
    )
    from ..utils.graph_plan import color_agents
    color, num_colors = color_agents(plan.nbr_robot, plan.nbr_mask, A)

    graph = MultiAgentGraph(
        edges=edges,
        meas_id=jnp.asarray(plan.meas_id.astype(np.int32)),
        n=jnp.asarray(part.n, jnp.int32),
        pose_mask=jnp.asarray(pose_mask, dtype),
        pub_idx=jnp.asarray(np.maximum(plan.pub_idx, 0), jnp.int32),
        pub_mask=jnp.asarray(plan.pub_mask.astype(np.float64), dtype),
        nbr_robot=jnp.asarray(plan.nbr_robot),
        nbr_pub=jnp.asarray(plan.nbr_pub),
        nbr_mask=jnp.asarray(plan.nbr_mask.astype(np.float64), dtype),
        global_index=jnp.asarray(np.maximum(part.global_index, 0), jnp.int32),
        inc_slot=jnp.asarray(plan.inc_slot),
        inc_mask=jnp.asarray(plan.inc_mask.astype(np.float64), dtype),
        color=jnp.asarray(color),
        **pallas_fields,
    )
    meta = GraphMeta(num_robots=A, n_max=n_max, e_max=e_max, s_max=s_max,
                     p_max=p_max, d=d, rank=rank, num_colors=num_colors)
    return graph, meta


# ---------------------------------------------------------------------------
# Global <-> per-agent layout
# ---------------------------------------------------------------------------

def with_weights(graph: MultiAgentGraph, weights) -> MultiAgentGraph:
    """Graph with ``edges.weight`` replaced by ``weights [A, E_max]`` —
    use to evaluate/refine/certify the objective a robust (GNC) solve
    actually minimized (``RBCDState.weights``), since weight updates live
    in the state, not the build-time graph."""
    return graph._replace(edges=graph.edges._replace(
        weight=jnp.asarray(weights, graph.edges.weight.dtype)))


def scatter_to_agents(Xg: jax.Array, graph: MultiAgentGraph) -> jax.Array:
    """Global pose array [N, ...] -> per-agent [A, n_max, ...]."""
    return Xg[graph.global_index]


def gather_to_global(Xa: jax.Array, graph: MultiAgentGraph, n_total: int) -> jax.Array:
    """Per-agent [A, n_max, ...] -> global [N, ...] (padding dropped)."""
    flat_idx = graph.global_index.reshape(-1)
    flat = Xa.reshape((-1,) + Xa.shape[2:])
    w = graph.pose_mask.reshape(-1)
    out = jnp.zeros((n_total,) + Xa.shape[2:], Xa.dtype)
    return out.at[flat_idx].add(flat * w.reshape((-1,) + (1,) * (Xa.ndim - 2)))


def public_table(X: jax.Array, graph: MultiAgentGraph) -> jax.Array:
    """Extract each agent's public poses: [A, P_max, r, d+1].

    This is the message payload of the framework — the analog of
    ``getSharedPoseDict`` (reference ``PGOAgent.cpp:95-105``).
    """
    return jax.vmap(lambda x, idx: x[idx])(X, graph.pub_idx)


def neighbor_buffer(Xpub: jax.Array, graph: MultiAgentGraph) -> jax.Array:
    """Resolve neighbor slots from the (gathered) public table:
    [A, S_max, r, d+1].  The analog of ``updateNeighborPoses``
    (reference ``PGOAgent.cpp:434-458``)."""
    Z = Xpub[graph.nbr_robot, graph.nbr_pub]
    return Z * graph.nbr_mask[:, :, None, None]


class PPermutePlan(NamedTuple):
    """Per-agent routing for the ppermute pose exchange (all arrays [A, S_max],
    sharded over agents like the rest of the graph).

    ``src`` indexes the stacked received tables: 0 = this device's own table,
    1 + i = the table received at ``shifts[i]``; ``lrobot`` is the neighbor
    robot's *local* index on its home device."""

    src: jax.Array
    lrobot: jax.Array


def plan_ppermute(graph: MultiAgentGraph, num_robots: int, n_dev: int):
    """Host-side routing plan for the shift-based neighbor exchange.

    The all_gather v1 moves every agent's public table to every device —
    on a ring that is ``n_dev - 1`` hops of the full table regardless of who
    actually needs what (SURVEY.md section 2.4).  Robot adjacency in SLAM
    partitions is sparse and mostly local (contiguous partitions put the
    odometry-crossing edges between consecutive robots), so the set of
    *device-to-device* shifts that carry any edge is small; one
    ``lax.ppermute`` per needed shift moves only those tables.  Returns
    ``(shifts, plan)``: ``shifts`` is the static tuple of nonzero ring
    offsets (compile-time; one collective each), ``plan`` the per-agent
    routing arrays.  ``num_robots`` must be a multiple of ``n_dev``, with
    agents laid out in contiguous blocks per device (``shard_problem``)."""
    if num_robots % n_dev != 0:
        raise ValueError(
            f"num_robots={num_robots} must be a multiple of n_dev={n_dev} "
            "(contiguous agent blocks per device, as shard_problem lays out)")
    A_loc = num_robots // n_dev
    nbr_robot = np.asarray(graph.nbr_robot)
    nbr_mask = np.asarray(graph.nbr_mask) > 0
    dev_of = np.arange(num_robots) // A_loc
    da = dev_of[:, None]
    db = dev_of[nbr_robot]
    s = np.where(nbr_mask, (da - db) % n_dev, 0)
    shifts = tuple(sorted(set(s[nbr_mask].astype(int).tolist()) - {0}))
    pos = {0: 0, **{sh: i + 1 for i, sh in enumerate(shifts)}}
    src = np.zeros_like(s)
    for sh, p in pos.items():
        src[s == sh] = p
    plan = PPermutePlan(src=jnp.asarray(src, jnp.int32),
                        lrobot=jnp.asarray(nbr_robot % A_loc, jnp.int32))
    return shifts, plan


def _ppermute_exchange(Xl: jax.Array, graph: MultiAgentGraph,
                       plan: PPermutePlan, shifts: tuple, axis_name: str,
                       n_dev: int) -> jax.Array:
    """Neighbor buffer via one ppermute per needed device shift (the
    optimized ICI path; bitwise-identical result to the all_gather form)."""
    T = public_table(Xl, graph)  # this shard's own public table
    parts = [T]
    for s in shifts:
        perm = [(i, (i + s) % n_dev) for i in range(n_dev)]
        parts.append(jax.lax.ppermute(T, axis_name, perm))
    stacked = jnp.stack(parts)  # [1 + len(shifts), A_loc, P_max, r, d+1]
    Z = stacked[plan.src, plan.lrobot, graph.nbr_pub]
    return Z * graph.nbr_mask[:, :, None, None]


#: Collective fault-injection hook (``parallel.resilience``): when set,
#: every exchange closure built below is passed through it before use, so
#: chaos tests can corrupt halo payloads at the seam itself.  Trace-time —
#: only programs compiled while the hook is installed are affected.
_exchange_wrap = None


def _exchange_for(graph: MultiAgentGraph, A_tot: int, axis_name,
                  plan: PPermutePlan | None, shifts: tuple):
    """The pose-exchange closure of a round: neighbor buffer resolved from
    the all-gathered public table (v1), or the shift-based ppermute route
    when a ``plan`` is given; plain gathers with ``axis_name=None``.

    Factored out of ``_rbcd_round`` so the overlapped fused loop
    (``_rbcd_rounds(overlap=True)``) can issue the NEXT round's exchange
    outside the round body — the halo/compute-overlap restructure of the
    sharded plane."""
    if axis_name is None:
        if plan is not None:
            raise ValueError("ppermute exchange requires a mesh axis_name")
        gather = lambda t: t
    else:
        gather = lambda t: jax.lax.all_gather(t, axis_name, axis=0,
                                              tiled=True)
    if plan is None:
        exchange = lambda Xl: neighbor_buffer(
            gather(public_table(Xl, graph)), graph)
    else:
        def exchange(Xl):
            n_dev = A_tot // Xl.shape[0]
            return _ppermute_exchange(Xl, graph, plan, shifts, axis_name,
                                      n_dev)

    if _exchange_wrap is not None:
        exchange = _exchange_wrap(exchange)
    return exchange


# ---------------------------------------------------------------------------
# The jitted step
# ---------------------------------------------------------------------------

def _agent_local_problem(z, edges, chol, n_max, inc=None, qbuf=None):
    """Solver closures for one agent given fixed neighbor buffer z.

    Three gradient/Hessian formulations, fastest applicable first:

    * ``qbuf`` (materialized dense connection Laplacian over the pose
      buffer, ``ops.quadratic.dense_q``): cost/gradient/Hessian-vector are
      single MXU matmuls against precomputed ``Q`` and the per-round linear
      term ``G = Z Q_nl`` — the reference's own ``f = 0.5 <Q, X^T X> +
      <X, G>`` form (``QuadraticProblem.cpp:50-73``), dense on TPU.  The
      RBCD default while per-agent buffers stay small enough to materialize.
    * ``inc = (inc_slot, inc_mask)``: gather-only ELL edge path
      (``ops.quadratic.egrad_ell``) — O(E) memory, any problem size.
    * neither: scatter-add edge path (single-agent fallback).
    """

    def buf(Xl):
        return jnp.concatenate([Xl, z], axis=0)

    n_buf = n_max + z.shape[0]
    if qbuf is not None:
        k = z.shape[-1]  # d + 1
        nl = n_max * k
        Qll = qbuf[:nl, :nl]
        Qnl = qbuf[nl:, :nl]
        Qnn = qbuf[nl:, nl:]
        Zm = quadratic.to_mat(z)
        G = Zm @ Qnl                       # [r, (d+1) n_max], fixed per round
        const = 0.5 * jnp.sum((Zm @ Qnn) * Zm)

        def cost_d(Xl):
            Xm = quadratic.to_mat(Xl)
            return 0.5 * jnp.sum((Xm @ Qll) * Xm) + jnp.sum(Xm * G) + const

        def egrad_d(Xl):
            Xm = quadratic.to_mat(Xl)
            return quadratic.from_mat(Xm @ Qll + G, n_max)

        def ehess_d(Xl, V):
            return quadratic.from_mat(quadratic.to_mat(V) @ Qll, n_max)

        return solver.Problem(
            cost=cost_d, egrad=egrad_d, ehess=ehess_d,
            precond=lambda Xl, V: quadratic.precond_apply(chol, V),
        )
    if inc is not None:
        inc_slot, inc_mask = inc
        return solver.Problem(
            cost=lambda Xl: quadratic.cost(buf(Xl), edges),
            egrad=lambda Xl: quadratic.egrad_ell(buf(Xl), edges,
                                                 inc_slot, inc_mask),
            ehess=lambda Xl, V: quadratic.hessvec_ell(V, edges, inc_slot,
                                                      inc_mask, n_buf=n_buf),
            precond=lambda Xl, V: quadratic.precond_apply(chol, V),
        )
    return solver.Problem(
        cost=lambda Xl: quadratic.cost(buf(Xl), edges),
        egrad=lambda Xl: quadratic.egrad(buf(Xl), edges, n_out=n_max),
        ehess=lambda Xl, V: quadratic.hessvec(V, edges, n_buf=n_buf),
        precond=lambda Xl, V: quadratic.precond_apply(chol, V),
    )


def precond_chol(graph_edges: EdgeSet, n_max: int, s_max: int,
                 params: AgentParams) -> jax.Array:
    """Block-Jacobi preconditioner factors for all agents [A, n_max, k, k]."""

    def one(e):
        blocks = quadratic.diag_blocks(e, n_max + s_max, n_out=n_max)
        return quadratic.precond_factors(blocks, params.solver.precond_shift)

    return jax.vmap(one)(graph_edges)


#: Jitted ``precond_chol`` for HOST-side callers (init_state /
#: refresh_problem) — eager, the vmapped block build dispatches hundreds of
#: individual ops, one host dispatch each.  ``_rbcd_round`` calls the
#: plain function (it already traces under jit).
precond_chol_jit = jax.jit(precond_chol,
                           static_argnames=("n_max", "s_max", "params"))


#: Dense-Q memory budget: the [A, K, K] buffer Laplacians (K = (d+1)
#: (n_max + s_max)) must fit comfortably beside the rest of the problem.
#: 1 GiB covers sphere2500/8 (51 MB f32) through city10000/8 (~900 MB f32
#: at the margin).
DENSE_Q_BUDGET_BYTES = 1 << 30


def use_dense_q(meta: GraphMeta, params: AgentParams | None,
                itemsize: int) -> bool:
    """Whether the (opt-in) materialized dense-Q formulation applies:
    requested via ``SolverParams.dense_quadratic`` and within the memory
    budget at the problem's actual ``itemsize`` (4 for float32 graphs, 8
    for float64 — required so the predicate always agrees with what the
    solver will actually dispatch)."""
    if params is None or not params.solver.dense_quadratic:
        return False
    K = (meta.d + 1) * (meta.n_max + meta.s_max)
    return meta.num_robots * K * K * itemsize <= DENSE_Q_BUDGET_BYTES


#: Per-agent VMEM the Pallas tCG kernel may stage (loop vectors, tiled edge
#: payloads, and the per-tile transient one-hots must fit beside
#: double-buffering headroom on a ~16 MiB VMEM core).
PALLAS_TCG_VMEM_BUDGET_BYTES = 10 << 20


def _edge_tile_shape(n_max: int, s_max: int, e_max: int,
                     wide: bool = False) -> tuple[int, int]:
    """(T, nt) of the kernel's tile-major edge layout.  Adaptive tile: the
    kernel's transient one-hots are [n, T]; halve the tile for large pose
    buffers to keep them inside VMEM.

    ``wide``: the caller runs a bf16 selection mode, whose one-hot
    transients are HALF size — T stays at 256 up to ~3000-pose buffers.
    Measured round 5 at 100k/64 (buffer 2288): bf16x3 T=128 -> 256 is
    50.1 -> 58.5 rounds/s (fewer, wider dot issues); the SAME widening
    in f32 mode aborts in Mosaic (scoped VMEM 17.8M > 16M), which is why
    this is mode-gated rather than unconditional."""
    from ..ops.pallas_tcg import TILE

    if wide and (n_max + s_max) <= 3000:
        T = TILE
    else:
        T = TILE if (n_max + s_max) <= 1024 else TILE // 2
    T = _ab_tile_override(T)
    return T, max(1, -(-e_max // T))


def _ab_tile_override(T: int) -> int:
    """The round-5 ``PALLAS_TILE`` A/B override, scoped OUT of the
    production path: it only applies when ``DPGO_AB=1`` is also set, the
    value must be a positive lane multiple (128), and an active override
    is logged — a PALLAS_TILE leaked into a normal shell previously
    retiled every solve silently and could reproduce the Mosaic VMEM
    abort the adaptive tile exists to avoid."""
    import os
    import sys

    raw = os.environ.get("PALLAS_TILE")
    if raw is None:
        return T
    if os.environ.get("DPGO_AB") != "1":
        return T  # experiments only opt in explicitly
    try:
        t = int(raw)
    except ValueError:
        raise ValueError(f"PALLAS_TILE={raw!r} is not an integer") from None
    if t <= 0 or t % 128 != 0:
        raise ValueError(
            f"PALLAS_TILE={t} invalid: must be a positive multiple of the "
            "128-lane tile width")
    if t != T:
        print(f"[dpgo_tpu] DPGO_AB: PALLAS_TILE override {T} -> {t}",
              file=sys.stderr)
    return t


def pallas_vmem_ok(n_max: int, s_max: int, rank: int, d: int, T: int,
                   nt: int, bf16: bool = False) -> bool:
    """Scalar-shape form of ``_pallas_vmem_ok`` — also the gate for the
    per-robot deployment surface (``agent.PGOAgent``), which has no
    GraphMeta/MultiAgentGraph."""
    from ..ops.pallas_tcg import hoist_scratch_bytes, should_hoist

    rk = rank * (d + 1)
    sel_item = 2 if bf16 else 4  # bf16 one-hot tiles are half-size
    edge_tiles_b = nt * T * (d * d + d + 4) * 4
    onehots = 4 * T * (n_max + s_max) * sel_item
    vecs = 12 * rk * n_max * 4
    hoist = hoist_scratch_bytes(nt, T, n_max, sel_item) \
        if should_hoist(nt, T, n_max, sel_item) else 0
    return edge_tiles_b + onehots + vecs + hoist \
        <= PALLAS_TCG_VMEM_BUDGET_BYTES


def agent_edge_tiles(i, j, R, t, n: int, s: int, wide: bool = False):
    """Tile-major edge arrays for ONE agent's buffer-indexed edge list —
    the single-agent equivalent of ``build_graph``'s batched Pallas layout
    (``eidx_i/eidx_j [nt, 1, T]``, ``rot_t [nt, d*d, T]``,
    ``trn_t [nt, d, T]``; padding gets index ``n + s``, which one-hots to
    all-zero in both the local and neighbor ranges).  Used by the
    deployment surface (``agent.PGOAgent``) so per-robot iterates run the
    same VMEM kernel as the batched core.  ``wide`` mirrors
    ``build_graph``'s bf16-selection-mode tile widening."""
    i = np.asarray(i, np.int32)
    j = np.asarray(j, np.int32)
    R = np.asarray(R, np.float32)
    t = np.asarray(t, np.float32)
    e = i.shape[0]
    d = R.shape[-1]
    T, nt = _edge_tile_shape(n, s, e, wide=wide)
    Ep = nt * T
    pad = n + s
    ii = np.full((Ep,), pad, np.int32)
    jj = np.full((Ep,), pad, np.int32)
    ii[:e] = i
    jj[:e] = j
    rot = np.zeros((d * d, Ep), np.float32)
    trn = np.zeros((d, Ep), np.float32)
    rot[:, :e] = R.transpose(1, 2, 0).reshape(d * d, e)
    trn[:, :e] = t.T
    return (jnp.asarray(ii.reshape(nt, 1, T)),
            jnp.asarray(jj.reshape(nt, 1, T)),
            jnp.asarray(np.ascontiguousarray(
                rot.reshape(d * d, nt, T).transpose(1, 0, 2))),
            jnp.asarray(np.ascontiguousarray(
                trn.reshape(d, nt, T).transpose(1, 0, 2))))


def _pallas_vmem_ok(meta: GraphMeta, graph, bf16: bool = False) -> bool:
    """Whether the kernel's per-agent working set fits in VMEM.

    With the tile-streaming kernel the resident set is ~12 [r(d+1), n]
    loop vectors, the O(E) tiled edge payload, and the transient per-tile
    one-hot selection tiles (4 x [n or s, T] live at the cost evaluation).
    This is a budget check, not an edge-count gate — the old one-hot
    design's ~765-edge Mosaic compile ceiling is gone (e_max 1906 /
    n_max 1000 verified compiling and running on v5e); the remaining
    ceiling tracks real VMEM pressure (e_max 3793 / n_max 2000 at T=256
    crashes the compile helper, consistent with this estimate).  The
    hoisted one-hot scratch (``pallas_tcg.should_hoist``) counts toward the
    same budget when the kernel will allocate it — both gates derive from
    one estimate, so a shape cannot pass here and then overflow VMEM by
    adding the hoist scratch."""
    return pallas_vmem_ok(meta.n_max, meta.s_max, meta.rank, meta.d,
                          graph.eidx_i.shape[-1], graph.eidx_i.shape[1],
                          bf16)


def resolved_sel_mode(params: AgentParams) -> str:
    """The kernel selection-matmul mode: ``pallas_sel_mode`` when set,
    else derived from the older ``pallas_bf16_select`` flag."""
    m = params.solver.pallas_sel_mode
    if m:
        if m not in ("f32", "bf16", "bf16x3"):
            raise ValueError(f"unknown pallas_sel_mode {m!r}")
        return m
    return "bf16" if params.solver.pallas_bf16_select else "f32"


def pallas_unavailable_reason(meta: GraphMeta, params: AgentParams,
                              graph, itemsize: int = 4) -> str | None:
    """Why the Pallas kernel cannot run this problem, or None when it can
    (the backend aside: off the TPU the auto mode takes ELL regardless).
    The kernel is f32-only: routing an f64 problem through it would
    silently clamp the iterate (and the gn0 convergence metric) to f32
    every round, so a converged f64 block never stays at its fixed point
    and tight grad_norm_tols become unreachable."""
    if params.solver.algorithm != ROptAlg.RTR:
        return "algorithm is not RTR"
    if itemsize != 4:
        return ("the kernel is float32-only and the problem is float64 — "
                "build the graph/state in float32")
    if graph.eidx_i is None:
        return ("the graph was built without edge tiles "
                "(build_graph(pallas_sel=True))")
    if not _pallas_vmem_ok(meta, graph,
                           bf16=resolved_sel_mode(params) != "f32"):
        return "the per-agent problem exceeds the kernel's VMEM budget"
    return None


def _formulation(meta: GraphMeta, params: AgentParams | None, graph,
                 itemsize: int = 4) -> str:
    """Resolve which tCG/problem formulation a round will run, in priority
    order: explicitly forced Pallas kernel, explicit dense-Q opt-in, Pallas
    auto (TPU backend), ELL edge path.  Shared by ``init_state`` (which
    materializes Qbuf only when "dense" wins — never wasted) and
    ``_rbcd_round`` dispatch."""
    if params is None:
        return "ell"
    reason = pallas_unavailable_reason(meta, params, graph, itemsize)
    if params.solver.pallas_tcg is True:
        if reason is not None:
            # An explicit force that cannot be honored must not silently
            # downgrade — the caller believes the kernel is being covered.
            raise ValueError(f"pallas_tcg=True cannot run: {reason}")
        return "pallas"
    if params.solver.algorithm == ROptAlg.RTR \
            and use_dense_q(meta, params, itemsize):
        return "dense"
    if params.solver.pallas_tcg is None and reason is None \
            and jax.default_backend() == "tpu":
        return "pallas"
    return "ell"


#: Bound on the kernel-vs-ELL difference of one RBCD round on one device:
#: both paths run the same f32 math, so the difference is reduction order
#: plus the kernel's Newton-Schulz (vs SVD) retraction — observed at the
#: 1e-6..1e-5 scale; 5e-4 flags a genuine Mosaic lowering change without
#: tripping on noise.
KERNEL_PARITY_BOUND = 5e-4


def kernel_parity(state: RBCDState, graph: MultiAgentGraph, meta: GraphMeta,
                  params: AgentParams) -> float:
    """Max-abs difference of the iterate and the per-agent relative change
    after ONE plain RBCD round through the Pallas kernel and through the
    ELL formulation, on the device holding ``state`` (interpret mode off
    the TPU).  The kernels are parity-tested in interpret mode on the CPU
    (tests/test_pallas_tcg.py); run on the chip, this catches a Mosaic
    compile difference that would otherwise surface only as silent
    accuracy drift.  Callers compare it with ``KERNEL_PARITY_BOUND``."""
    outs = []
    for force in (True, False):
        p = dataclasses.replace(params, solver=dataclasses.replace(
            params.solver, pallas_tcg=force))
        outs.append(rbcd_step(state, graph, meta, p, update_weights=False,
                              restart=False))
    dx = np.abs(np.asarray(outs[0].X) - np.asarray(outs[1].X)).max()
    dg = np.abs(np.asarray(outs[0].rel_change)
                - np.asarray(outs[1].rel_change)).max()
    return float(max(dx, dg))


def dense_q_all(graph_edges: EdgeSet, meta: GraphMeta) -> jax.Array:
    """Buffer Laplacians for all agents [A, K, K] (``quadratic.dense_q``)."""
    return jax.vmap(lambda e: quadratic.dense_q(e, meta.n_max + meta.s_max))(
        graph_edges)


def _agent_update(X_local, z, edges, params: AgentParams, chol=None, inc=None,
                  qbuf=None, pallas=None):
    """One local solver step for a single agent (vmapped over A).

    Dispatches RTR vs RGD per ``params.solver.algorithm``, the reference's
    ``QuadraticOptimizer::optimize`` branch (``QuadraticOptimizer.cpp:42-47``).
    ``chol`` carries precomputed preconditioner factors (recomputed here when
    omitted — the single-shot path of ``agent.PGOAgent``); ``inc``/``qbuf``
    select the ELL / dense-Q problem formulations (``_agent_local_problem``);
    ``pallas = (eidx_i, eidx_j, rot_t, trn_t, interpret)`` (tile-major edge
    arrays) runs the whole single-step RTR in the VMEM Pallas kernel
    (``ops.pallas_tcg.rtr_call``).
    Returns the updated block and the block gradient norm at the *starting*
    point — the greedy selection metric (``MultiRobotExample.cpp:242-256``)
    — which the RTR solver computes anyway.
    """
    n_max = X_local.shape[0]
    if params.solver.algorithm == ROptAlg.RGD:
        # Fixed-step projected gradient + retraction, preconditioning off
        # (reference ``gradientDescent``, QuadraticOptimizer.cpp:124-149) —
        # no preconditioner to factor on this path.
        buf = jnp.concatenate([X_local, z], axis=0)
        g = manifold.rgrad(X_local, quadratic.egrad(buf, edges, n_out=n_max))
        gn0 = manifold.norm(g)
        return manifold.retract(X_local, -params.solver.rgd_stepsize * g), gn0
    if chol is None:
        blocks = quadratic.diag_blocks(edges, n_max + z.shape[0], n_out=n_max)
        chol = quadratic.precond_factors(blocks, params.solver.precond_shift)
    if pallas is not None:
        from ..ops import pallas_tcg as ptcg

        eidx_i, eidx_j, rot_t, trn_t, interpret = pallas
        nt, tile = eidx_i.shape[0], eidx_i.shape[-1]
        d = trn_t.shape[1]
        k = d + 1
        r = X_local.shape[-2]
        w = edges.mask * edges.weight
        wk = ptcg.edge_tiles((w * edges.kappa).astype(jnp.float32), nt, tile)
        wt = ptcg.edge_tiles((w * edges.tau).astype(jnp.float32), nt, tile)
        Lc = chol.transpose(1, 2, 0).reshape(k * k, n_max)
        # Fully-fused kernel: gradient, curvature term, gradient norm, tCG,
        # retraction, acceptance and radius retries all in VMEM, including
        # the below-tolerance early exit (QuadraticOptimizer.cpp:65-69) —
        # the per-round XLA work is just the exchange and these layout
        # transposes (measured: the old out-of-kernel ELL gradient pass was
        # ~65% of a sphere2500 round).
        X_out_c, stats = ptcg.rtr_full_call(
            eidx_i, eidx_j, rot_t, trn_t, wk, wt,
            ptcg.comp_major(X_local.astype(jnp.float32)),
            ptcg.comp_major(z.astype(jnp.float32)),
            Lc.astype(jnp.float32),
            r=r, d=d, max_iters=params.solver.max_inner_iters,
            kappa=params.solver.tcg_kappa, theta=params.solver.tcg_theta,
            initial_radius=params.solver.initial_radius,
            max_rejections=params.solver.max_rejections,
            grad_tol=params.solver.grad_norm_tol,
            interpret=interpret,
            sel_mode=resolved_sel_mode(params))
        X_new = ptcg.comp_minor(X_out_c, r, k).astype(X_local.dtype)
        gn0 = stats[0, 4].astype(X_local.dtype)
        return X_new, gn0
    problem = _agent_local_problem(z, edges, chol, n_max, inc=inc, qbuf=qbuf)
    out = solver.rtr_single_step(problem, X_local, params.solver, None,
                                 final_grad_norm=False)
    return out.X, out.grad_norm_init


def _edge_residuals(X_local, z, edges):
    """Unweighted per-edge residual norms sqrt(kappa ||rR||^2 + tau ||rt||^2)
    for one agent — ``computeMeasurementError`` (reference
    ``DPGO_utils.cpp:509-515``) evaluated in the lifted space, as
    ``updateLoopClosuresWeights`` does (``PGOAgent.cpp:1181-1245``)."""
    buf = jnp.concatenate([X_local, z], axis=0)
    rR, rt = quadratic._edge_terms(buf, edges)
    sq = edges.kappa * jnp.sum(rR * rR, axis=(-2, -1)) + \
        edges.tau * jnp.sum(rt * rt, axis=-1)
    return jnp.sqrt(jnp.maximum(sq, 0.0))


def _gnc_update_weights(X, Z, edges, mu, params: AgentParams):
    """Recompute robust weights for every loop-closure edge (all agents).

    Reference semantics (``PGOAgent::updateLoopClosuresWeights``,
    ``PGOAgent.cpp:1181-1245``): residual from the current iterate X and the
    cached neighbor pose; weight from the robust cost at the current mu;
    odometry and known-inlier edges keep weight 1.  The reference's ownership
    rule (agent i updates shared edges only toward j > i, the other endpoint
    receives the published weight) exists because cached poses may be stale
    across robots; here both endpoint agents evaluate the *same* gathered
    public poses in the same round, so independent recomputation yields
    bitwise-identical weights on both copies and no ownership/publish
    machinery is needed.
    """
    res = jax.vmap(lambda x, z, e: _edge_residuals(x, z, e))(X, Z, edges)
    w_new = robust.weight(res, params.robust, mu)
    update = edges.mask * edges.is_lc * (1.0 - edges.fixed_weight)
    return jnp.where(update > 0, w_new, edges.weight)


def _converged_weight_ratio(edges, params: AgentParams):
    """Per-agent fraction of non-known-inlier LC edges with weight in {0,1}
    (reference ``computeConvergedLoopClosureRatio``, ``PGOAgent.cpp:1247-1289``;
    meaningful for GNC_TLS only, 1.0 otherwise)."""
    if params.robust.cost_type != RobustCostType.GNC_TLS:
        return None
    lc = edges.mask * edges.is_lc * (1.0 - edges.fixed_weight)
    conv = robust.is_weight_converged(edges.weight).astype(lc.dtype)
    tot = jnp.sum(lc, axis=-1)
    return jnp.where(tot > 0, jnp.sum(lc * conv, axis=-1) / jnp.maximum(tot, 1.0),
                     jnp.ones_like(tot))


def _rbcd_round(state: RBCDState, graph: MultiAgentGraph, meta: GraphMeta,
                params: AgentParams, axis_name: str | None = None,
                update_weights: bool = False, restart: bool = False,
                plan: PPermutePlan | None = None,
                shifts: tuple = (), halo: jax.Array | None = None,
                return_halo: bool = False):
    """One synchronous RBCD round over the agents held by this device.

    Communication happens once per round: the public-pose table is built
    from X (and from the Nesterov sequence Y when accelerated — the aux-pose
    exchange of ``getAuxSharedPoseDict``/``updateAuxNeighborPoses``,
    reference ``PGOAgent.cpp:107-118``, ``460-479``) and re-distributed to
    neighbor buffers.  When ``axis_name`` is set, this function is the
    per-shard body of ``shard_map`` over a device mesh (``dpgo_tpu.parallel``):
    the table is exchanged by ``all_gather`` over ICI (the analog of the
    reference's pose message exchange, ``MultiRobotExample.cpp:186-213``) and
    the greedy schedule resolves its argmax over gathered per-agent gradient
    norms.  With ``axis_name=None`` the same body runs single-device over all
    agents (plain gathers).

    ``update_weights`` and ``restart`` are static flags the driver raises on
    the rounds where the reference's modular counters fire
    (``shouldUpdateLoopClosureWeights``: every ``robust_opt_inner_iters``;
    ``shouldRestart``: every ``restart_interval`` when accelerated) — keeping
    the schedule on the host compiles each round variant branch-free.

    A restart round reproduces ``restartNesterovAcceleration`` (reference
    ``PGOAgent.cpp:1040-1052``): the accelerated step is discarded (X reset
    to the pre-round value), a plain un-accelerated step is taken instead,
    and the aux state collapses (V = Y = X, gamma = alpha = 0) — so it
    compiles as a plain round plus aux reset, with no wasted solve.

    ``plan``/``shifts`` (mesh path only) switch the pose exchange from the
    all_gather v1 to the shift-based ppermute route (``plan_ppermute``):
    same result bitwise, with one collective per ring offset that carries
    any cross-device edge (a win when the partition's device adjacency is
    near-chain; a random partition can need up to ``n_dev - 1`` shifts —
    all_gather volume).  The greedy schedule's argmax still all_gathers its
    [A] gradient-norm vector (negligible payload).

    ``halo`` (plain rounds only — incompatible with ``update_weights``,
    whose warm-start-off path resets X and must re-exchange) supplies the
    neighbor buffer of the CURRENT iterate precomputed by the caller, and
    ``return_halo`` makes the round also return the NEXT round's exchange
    ``exchange(X_next)``, issued right after the Stiefel update so the
    collective is in flight while the trailing status/momentum math runs —
    the software-pipelined halo of ``_rbcd_rounds(overlap=True)``.  Same
    values either way: the halo of round k is always ``exchange(X_k)``.
    """
    if params.acceleration and state.V is None:
        raise ValueError(
            "params.acceleration is set but the state has no V sequence — "
            "build the state with init_state(..., params=params)")
    if (params.robust.cost_type != RobustCostType.L2
            and not params.robust_opt_warm_start and state.X_init is None):
        raise ValueError(
            "robust_opt_warm_start=False requires the state to carry the "
            "initial guess — build it with init_state(..., params=params)")
    accel = params.acceleration and state.V is not None
    if accel and params.schedule == Schedule.ASYNC:
        # The reference forbids this combination (assert at PGOAgent.cpp:863):
        # Nesterov momentum assumes lockstep gamma sequences.
        raise ValueError("acceleration is not supported with the ASYNC schedule")
    X = state.X
    weights = state.weights
    mu = state.mu
    V, gamma, alpha = state.V, state.gamma, state.alpha
    A_loc = X.shape[0]  # agents on this shard (= meta.num_robots if unsharded)
    A_tot = meta.num_robots

    if axis_name is None:
        agent_ids = jnp.arange(A_loc)
        gather = lambda t: t
        if plan is not None:
            raise ValueError("ppermute exchange requires a mesh axis_name")
    else:
        agent_ids = jax.lax.axis_index(axis_name) * A_loc + jnp.arange(A_loc)
        gather = lambda t: jax.lax.all_gather(t, axis_name, axis=0, tiled=True)

    exchange = _exchange_for(graph, A_tot, axis_name, plan, shifts)
    if halo is not None and update_weights:
        raise ValueError(
            "a precomputed halo cannot serve a weight-update round: the "
            "warm-start-off path resets X and must re-exchange")

    # Regular neighbor buffer (from X) — needed always when un-accelerated,
    # and on weight-update / restart rounds when accelerated.
    need_regular = (not accel) or restart or update_weights
    Z = (halo if halo is not None else exchange(X)) if need_regular else None

    # --- GNC weight update (before the pose update, reference iterate()
    # PGOAgent.cpp:654-668) ---
    chol = state.chol
    qbuf = state.Qbuf
    if update_weights:
        edges_r = graph.edges._replace(weight=weights)
        w_new = _gnc_update_weights(X, Z, edges_r, mu, params)
        # Weight freeze, ON DEVICE (beyond-reference, see run_rbcd's note on
        # the robust_opt_num_weight_updates cap): once the GNC inlier/outlier
        # decision has converged (fraction of LC weights in {0,1} >= the
        # reference's min ratio over ALL agents — global min, gathered on
        # the mesh path), further updates would keep annealing mu and flip
        # borderline edges, destabilizing the now-fixed-weight descent, and
        # with warm start disabled would keep resetting the iterate.  The
        # gate mirrors the former host-side check exactly: the ratio is
        # evaluated on the PRE-update weights, and only from the third
        # flagged round on (the first two updates always run; `>= 2 updates
        # before freezing` — the all-ones initialization is trivially
        # "converged").  A frozen flagged round computes the same values as
        # a plain round, so freezing is permanent without any host control
        # flow or readback.
        ratio_pre = _converged_weight_ratio(edges_r, params)
        if ratio_pre is None:
            frozen = jnp.zeros((), bool)
        else:
            ordinal = (state.iteration + 1) // params.robust_opt_inner_iters
            frozen = (ordinal >= 3) & (
                jnp.min(gather(ratio_pre))
                >= params.robust_opt_min_convergence_ratio)
        weights = jnp.where(frozen, weights, w_new)
        mu = jnp.where(frozen, mu, robust.gnc_update_mu(mu, params.robust))
        if state.X_init is not None:
            # Warm start disabled: reset the iterate to the initial guess
            # BEFORE this round's optimization (PGOAgent.cpp:657-662); the
            # reset X also refreshes the regular neighbor buffer.
            X = jnp.where(frozen, X, state.X_init)
            Z = exchange(X)
        if accel:  # initializeAcceleration (PGOAgent.cpp:1054-1063)
            V = jnp.where(frozen, V, X)
            gamma = jnp.where(frozen, gamma, jnp.zeros_like(gamma))
            alpha = jnp.where(frozen, alpha, jnp.zeros_like(alpha))
    edges = graph.edges._replace(weight=weights)
    form = _formulation(meta, params, graph, itemsize=X.dtype.itemsize)
    if form == "dense" and qbuf is None:
        # Mirror the forced-Pallas behavior: an explicit opt-in that cannot
        # run must not silently downgrade to another formulation.
        raise ValueError(
            "dense_quadratic=True but the state carries no Qbuf — build it "
            "with init_state(..., params=...) using the same params, or "
            "refresh_problem() after changing them")
    if update_weights:
        # Reweighted Q -> refactor the block-Jacobi preconditioner (and the
        # materialized dense Q when that formulation is active), the
        # reference's constructQMatrix + CHOLMOD refactorization schedule
        # (PGOAgent.cpp:1110-1112).
        chol = precond_chol(edges, meta.n_max, meta.s_max, params)
        # Refresh the dense buffer when active, and keep (refreshed) a
        # carried one even if this round's params resolve elsewhere — the
        # caller may switch formulations between rounds.
        qbuf = dense_q_all(edges, meta) \
            if (form == "dense" or qbuf is not None) else None
    elif chol is None:
        # State built without solver params (init_state(params=None)):
        # factor from the live edge weights and THIS round's solver config.
        # NOTE: factors baked by init_state follow the params given THERE —
        # stepping with a different precond_shift than the state was built
        # with requires refresh_problem(state, graph, meta, new_params).
        chol = precond_chol(edges, meta.n_max, meta.s_max, params)

    # --- Acceleration bookkeeping (PGOAgent.cpp:1065-1091) ---
    if accel and not restart:
        gamma = (1.0 + jnp.sqrt(1.0 + 4.0 * (A_tot * gamma) ** 2)) / (2.0 * A_tot)
        alpha = 1.0 / (gamma * A_tot)
        a = alpha[:, None, None, None]
        Ynes = manifold.project((1.0 - a) * X + a * V)
        Zaux = exchange(Ynes)
        start, Zuse = Ynes, Zaux
    else:
        start, Zuse = X, Z

    # tCG formulation resolution (``form`` resolved above, before the
    # factor refresh): forced Pallas > explicit dense-Q > Pallas auto (TPU)
    # > ELL edge path.
    interp = jax.default_backend() != "tpu"

    def _update_one(x, z, e, c, s, m, ii=None, ij=None, rc=None, tc=None,
                    q=None):
        """Formulation-dispatched single-agent solve (vmapped below, or
        called once on dynamically-sliced inputs by the greedy path)."""
        if form == "pallas":
            # inc rides along for the start-point gradient (gather-only
            # ELL); the full RTR step runs in the VMEM kernel.
            return _agent_update(x, z, e, params, c, inc=(s, m),
                                 pallas=(ii, ij, rc, tc, interp))
        if form == "dense":  # qbuf presence enforced above
            return _agent_update(x, z, e, params, c, qbuf=q)
        return _agent_update(x, z, e, params, c, inc=(s, m))

    def _solve_all(take=lambda t: t):
        """Per-agent solves over (a selection of) the batch axis."""
        args = [take(t) for t in (start, Zuse, edges, chol, graph.inc_slot,
                                  graph.inc_mask)]
        kw = {}
        if form == "pallas":
            kw = dict(zip("ii ij rc tc".split(),
                          (take(t) for t in (graph.eidx_i, graph.eidx_j,
                                             graph.rot_t, graph.trn_t))))
        elif form == "dense":
            kw = dict(q=take(qbuf))
        return args, kw

    schedule = params.schedule
    if schedule == Schedule.ASYNC:
        # Only the ASYNC Bernoulli clocks consume randomness; the other
        # schedules previously paid a vmapped threefry split every round
        # for keys nothing read.  The carried key is left untouched on
        # those schedules (trajectories are unchanged — the key never
        # feeds their math).
        split = jax.vmap(lambda k: jax.random.split(k, 2))(state.key)
        key, sub = split[:, 0], split[:, 1]  # [A, 2, 2] -> two [A, 2]
    else:
        key, sub = state.key, None
    if schedule == Schedule.GREEDY:
        # One agent fires per round (the reference driver's argmax-gradnorm
        # selection, ``MultiRobotExample.cpp:242-256``).  Solving every
        # block and masking all but one would burn A x the needed work
        # (the round-1/2 behavior); instead a cheap selection pass (ONE
        # edge sweep per agent: Riemannian gradient norm at the start
        # point — the same quantity the solver reports as gn0) picks the
        # agent, and each device solves only its local slot of the argmax
        # (the non-owners' solves are masked out by ``fired``; n_dev
        # solves total instead of A).  This selection gn runs the ELL
        # path in the iterate dtype; the solver's reported gn0 may come
        # from the Pallas/dense formulation (f32 inside the kernel), so
        # on near-exact ties the argmax can differ in the last ulps —
        # the same mathematical quantity either way.
        def gn_of(x, z, e, s, m):
            buf = jnp.concatenate([x, z], axis=0)
            g = manifold.rgrad(x, quadratic.egrad_ell(buf, e, s, m))
            return manifold.norm(g)

        gn0 = jax.vmap(gn_of)(start, Zuse, edges, graph.inc_slot,
                              graph.inc_mask)
        sel = jnp.argmax(gather(gn0))
        li = (sel % A_loc).astype(jnp.int32)  # local slot on every shard
        take1 = lambda t: jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, li, 0, keepdims=False),
            t)
        args1, kw1 = _solve_all(take1)
        upd1, _ = _update_one(*args1, **kw1)
        X_upd = jax.lax.dynamic_update_index_in_dim(start, upd1, li, 0)
    else:
        args, kw = _solve_all()
        X_upd, gn0 = jax.vmap(
            lambda *a: _update_one(*a[:6], **dict(zip(kw.keys(), a[6:]))))(
            *args, *kw.values())

    if schedule == Schedule.JACOBI:
        fired = None  # every agent fires: the select masks below drop out
    elif schedule == Schedule.GREEDY:
        fired = agent_ids == sel
    elif schedule == Schedule.ASYNC:
        fired = jax.vmap(
            lambda k: jax.random.bernoulli(k, params.async_update_prob))(sub)
    elif schedule == Schedule.COLORED:
        if graph.color is None:
            raise ValueError(
                "Schedule.COLORED requires a colored graph — rebuild it "
                "with build_graph (colors are always computed there)")
        # Multi-color Gauss-Seidel: fire one class of mutually non-adjacent
        # agents per round, cycling classes — state.iteration counts the
        # PREVIOUS rounds, so class (iteration mod C) is deterministic and
        # identical on every shard.
        fired = graph.color == (state.iteration % meta.num_colors)
    else:
        raise ValueError(f"unknown schedule {schedule}")
    fired_b = None if fired is None else fired[:, None, None, None]

    if accel and not restart:
        # Non-fired agents take the momentum point (updateX(false, true):
        # X = Y, PGOAgent.cpp:1094-1098); V advances for everyone.
        X_next = X_upd if fired_b is None else jnp.where(fired_b, X_upd, Ynes)
        g = gamma[:, None, None, None]
        V = manifold.project(V + g * (X_next - Ynes))
    else:
        X_next = X_upd if fired_b is None else jnp.where(fired_b, X_upd, X)
        if accel:  # restart round: collapse the aux sequences
            V = X_next
            gamma = jnp.zeros_like(gamma)
            alpha = jnp.zeros_like(alpha)

    # Status update (reference PGOAgent.cpp:703-716): masked relative change.
    # Only fired agents refresh their status — non-selected agents keep their
    # previous readiness, as iterate(false) does in the reference.  In robust
    # mode readiness additionally requires the converged-weight ratio gate
    # (PGOAgent.cpp:713-714).
    diff = (X_next - X) * graph.pose_mask[:, :, None, None]
    rel_new = jnp.sqrt(jnp.sum(diff * diff, axis=(1, 2, 3)) /
                       jnp.maximum(graph.n.astype(X.dtype), 1.0))
    ready_new = rel_new <= params.rel_change_tol
    ratio = _converged_weight_ratio(edges, params)
    if ratio is not None:
        ready_new &= ratio >= params.robust_opt_min_convergence_ratio
    if fired is None:
        rel, ready = rel_new, ready_new
    else:
        rel = jnp.where(fired, rel_new, state.rel_change)
        ready = jnp.where(fired, ready_new, state.ready)

    new_state = RBCDState(X=X_next, weights=weights,
                          iteration=state.iteration + 1, key=key,
                          rel_change=rel, ready=ready,
                          V=V, gamma=gamma, alpha=alpha, mu=mu,
                          X_init=state.X_init, chol=chol, Qbuf=qbuf)
    if not return_halo:
        return new_state
    # Next round's halo, issued here — after the Stiefel update, before
    # the caller's loop re-enters — so the interconnect collective can
    # overlap the status/momentum math above (its result feeds nothing in
    # this round) and whatever pre-solve work the next round does first.
    return new_state, exchange(X_next)


#: Jitted RBCD round. Single-device over all agents with the default
#: ``axis_name=None``; the sharded path re-wraps ``_rbcd_round`` in shard_map.
rbcd_step = jax.jit(_rbcd_round, static_argnames=(
    "meta", "params", "axis_name", "update_weights", "restart", "shifts",
    "return_halo"))


def _rbcd_rounds(state: RBCDState, graph: MultiAgentGraph, num_rounds,
                 meta: GraphMeta, params: AgentParams,
                 axis_name: str | None = None,
                 plan: PPermutePlan | None = None,
                 shifts: tuple = (), overlap: bool = False) -> RBCDState:
    """``num_rounds`` consecutive *plain* rounds (no weight update, no
    restart) as one on-device ``fori_loop``.

    The per-round jitted step leaves the host in the loop: every round pays
    a host dispatch, which dominates once the device-side round is fast.  Fusing rounds keeps the whole schedule
    segment on-device — one dispatch per segment, identical math (the body
    is ``_rbcd_round`` itself, so single-round and fused traces agree).
    ``num_rounds`` is a traced scalar: one compile serves every segment
    length.

    ``overlap`` (mesh path, un-accelerated schedules) software-pipelines
    the halo: the loop carries each round's neighbor buffer, computed as
    ``exchange(X_k)`` at the END of round k-1 instead of at the top of
    round k — so the interconnect collective for the next round's halo is
    in flight while round k-1's trailing status math (and round k's
    pre-solve bookkeeping) execute, instead of gating the whole round.
    Identical values round for round (the halo of round k is always the
    exchange of X_k); costs one extra exchange per fused call (the
    prologue).  Accelerated schedules exchange the momentum point Ynes
    in-round (it depends on the just-advanced gamma) and their plain
    rounds never read the X-halo, so they take the unpipelined loop."""
    accel = params.acceleration and state.V is not None
    if overlap and axis_name is not None and not accel:
        exchange = _exchange_for(graph, meta.num_robots, axis_name, plan,
                                 shifts)

        def body(_i, carry):
            s, Z = carry
            return _rbcd_round(s, graph, meta, params, axis_name=axis_name,
                               plan=plan, shifts=shifts, halo=Z,
                               return_halo=True)

        state, _ = jax.lax.fori_loop(0, num_rounds, body,
                                     (state, exchange(state.X)))
        return state
    body = lambda _i, s: _rbcd_round(s, graph, meta, params,
                                     axis_name=axis_name, plan=plan,
                                     shifts=shifts)
    return jax.lax.fori_loop(0, num_rounds, body, state)


#: Jitted fused rounds (single-device; ``parallel.make_sharded_multi_step``
#: embeds the same loop inside shard_map for the mesh path).
rbcd_steps = jax.jit(_rbcd_rounds, static_argnames=(
    "meta", "params", "axis_name", "shifts", "overlap"))


def _rbcd_segment(state: RBCDState, graph: MultiAgentGraph, num_rounds,
                  meta: GraphMeta, params: AgentParams,
                  axis_name: str | None = None,
                  plan: PPermutePlan | None = None,
                  shifts: tuple = (),
                  first_update_weights: bool = False,
                  first_restart: bool = False,
                  overlap: bool = False) -> RBCDState:
    """One schedule segment — a (possibly flagged) first round followed by
    ``num_rounds - 1`` plain rounds — as ONE device dispatch.

    The driver's schedule puts weight-update / Nesterov-restart flags on
    modularly-scheduled rounds (``run_rbcd``); with plain-only fusion those
    flagged rounds each cost a separate host dispatch between fused
    stretches.  Folding the flagged round into the front of its following
    stretch keeps every segment at exactly one dispatch.  With both flags False this is exactly ``_rbcd_rounds``.
    ``num_rounds`` is traced; the flags are static (<= 4 compiled variants).
    """
    state = _rbcd_round(state, graph, meta, params, axis_name=axis_name,
                        update_weights=first_update_weights,
                        restart=first_restart, plan=plan, shifts=shifts)
    return _rbcd_rounds(state, graph, num_rounds - 1, meta, params,
                        axis_name=axis_name, plan=plan, shifts=shifts,
                        overlap=overlap)


#: Jitted fused segment (single-device; ``parallel.make_sharded_segment``
#: is the mesh equivalent).
rbcd_segment = jax.jit(_rbcd_segment, static_argnames=(
    "meta", "params", "axis_name", "shifts", "first_update_weights",
    "first_restart", "overlap"))


# ---------------------------------------------------------------------------
# Initialization, rounding, and the high-level driver
# ---------------------------------------------------------------------------

def init_state(graph: MultiAgentGraph, meta: GraphMeta, X0: jax.Array,
               seed: int = 0, params: AgentParams | None = None) -> RBCDState:
    A = meta.num_robots
    dtype = X0.dtype
    accel = params is not None and params.acceleration
    mu0 = params.robust.gnc_init_mu if params is not None else 1e-4
    # Preconditioner factors are baked only when the solver params are
    # known; otherwise the round factors from its live params (the shift
    # must match what the solver was configured with).
    chol0 = precond_chol_jit(graph.edges, meta.n_max, meta.s_max, params) \
        if params is not None else None
    qbuf0 = dense_q_all(graph.edges, meta) \
        if _formulation(meta, params, graph,
                        itemsize=jnp.dtype(dtype).itemsize) == "dense" \
        else None
    return RBCDState(
        X=X0,
        weights=graph.edges.weight,
        iteration=jnp.array(0, jnp.int32),
        key=jax.random.split(jax.random.PRNGKey(seed), A),
        rel_change=jnp.full((A,), jnp.inf, dtype),
        ready=jnp.zeros((A,), bool),
        V=X0 if accel else None,  # initializeAcceleration: V = X
        gamma=jnp.zeros((A,), dtype),
        alpha=jnp.zeros((A,), dtype),
        mu=jnp.asarray(mu0, dtype),
        X_init=X0 if (params is not None
                      and params.robust.cost_type != RobustCostType.L2
                      and not params.robust_opt_warm_start) else None,
        chol=chol0,
        Qbuf=qbuf0,
    )


def refresh_problem(state: RBCDState, graph: MultiAgentGraph, meta: GraphMeta,
                    params: AgentParams) -> RBCDState:
    """Recompute the carried problem factors (preconditioner Cholesky, and
    the dense Q when that formulation is active) from ``state.weights``.

    Required after setting weights externally — e.g. resuming a mid-GNC
    solve from a checkpoint via ``state._replace(weights=...)`` — because
    ``_rbcd_round`` otherwise refreshes them only on weight-update rounds
    and would optimize against the stale (unweighted) problem until the
    next GNC update fires."""
    edges = graph.edges._replace(weight=state.weights)
    chol = precond_chol_jit(edges, meta.n_max, meta.s_max, params)
    # Decide the dense buffer from the given params (like init_state does),
    # not from its previous presence — this also (re)creates a missing Qbuf
    # when the caller switched to a dense_quadratic configuration.
    want_dense = _formulation(
        meta, params, graph, itemsize=jnp.dtype(state.X.dtype).itemsize) \
        == "dense"
    qbuf = dense_q_all(edges, meta) if (want_dense or state.Qbuf is not None) \
        else None
    return state._replace(chol=chol, Qbuf=qbuf)


def _lift_scatter(T0: jax.Array, graph: MultiAgentGraph,
                  meta: GraphMeta) -> jax.Array:
    """T0 [n, d, d+1] -> lifted per-agent blocks [A, n_max, r, d+1]."""
    X0g = lift(T0, lifting_matrix(meta, T0.dtype))
    return scatter_to_agents(X0g, graph)


@partial(jax.jit, static_argnames=("meta", "n", "init_fn"))
def _global_init_jit(edges_g: EdgeSet, graph: MultiAgentGraph,
                     meta: GraphMeta, n: int, init_fn) -> jax.Array:
    """Shared body of the centralized init policies: build T0 [n, d, d+1]
    with ``init_fn(edges, n)`` (a module-level function, so the static
    hash is stable), lift, scatter to agents."""
    return _lift_scatter(init_fn(edges_g, n), graph, meta)


@partial(jax.jit, static_argnames=("meta", "dtype"))
def _lift_scatter_jit(T0, graph: MultiAgentGraph, meta: GraphMeta,
                      dtype) -> jax.Array:
    """Cast a host-computed T0 to ``dtype``, lift, scatter to agents: one
    program keyed on shapes, so a new problem of the same shape compiles
    nothing."""
    return _lift_scatter(T0.astype(dtype), graph, meta)


def lifted_init(edges_g: EdgeSet, graph: MultiAgentGraph, meta: GraphMeta,
                n_total: int, init: str = "chordal") -> jax.Array:
    """Centralized lifted init evaluated directly on a (possibly padded)
    global edge set, scattered to agents.

    The serving plane (``dpgo_tpu.serve``) initializes on the *padded*
    bucket problem, so one compiled init program serves every problem in a
    shape bucket instead of one per raw problem size; masked padding edges
    contribute nothing to the chordal least squares, and padded per-agent
    rows resolve to global pose 0's block through the padded
    ``global_index`` (a valid Stiefel point), exactly as short agents
    already do in unpadded graphs."""
    if init == "chordal":
        fn = chordal.chordal_initialization
    elif init == "odometry":
        fn = chordal.odometry_from_edges
    else:
        raise ValueError(f"unknown centralized init policy {init!r}")
    return _global_init_jit(edges_g, graph, meta, n_total, fn)


def centralized_chordal_init(part: Partition, meta: GraphMeta, graph: MultiAgentGraph,
                             dtype=jnp.float32) -> jax.Array:
    """Centralized chordal init, lifted and scattered to agents — the demo
    initialization of ``MultiRobotExample.cpp:158-165``.

    The two least-squares solves run on the host in float64, one sparse
    factorization each (``chordal.chordal_initialization_host``, span
    ``rbcd.init.factor``); one program keyed on shapes then casts, lifts
    and scatters.  The device CG (``chordal.chordal_initialization`` in
    one program) runs instead where the certificate's fill guard trips on
    the global edges (``certify._expander_guard``, ``reason="guarded"``)
    or the pinned system is singular (``reason="singular"``).  With a
    telemetry run, ``chordal_init_total{path=host_factor|device_cg,
    reason}`` counts which path each init took."""
    from . import certify as certify_mod

    meas = part.meas_global
    n = meas.num_poses
    edges_g = edge_set_from_measurements(meas, dtype=dtype, as_numpy=True)
    T0, path, reason = None, "device_cg", "guarded"
    if not certify_mod._expander_guard(edges_g, n, meas.d):
        try:
            with trace.span("rbcd.init.factor"):
                T0 = chordal.chordal_initialization_host(meas)
            path, reason = "host_factor", "factored"
        except chordal.SingularChordalSystem:
            reason = "singular"
    run = obs.get_run()
    if run is not None:
        run.counter("chordal_init_total",
                    "centralized chordal inits by the path that solved "
                    "them").inc(path=path, reason=reason)
    if T0 is None:
        return _global_init_jit(edges_g, graph, meta, n,
                                chordal.chordal_initialization)
    return _lift_scatter_jit(T0, graph, meta, dtype)


def centralized_odometry_init(part: Partition, meta: GraphMeta,
                              graph: MultiAgentGraph,
                              dtype=jnp.float32) -> jax.Array:
    """Odometry-chain init, lifted and scattered to agents (reference
    ``odometryInitialization``, ``DPGO_utils.cpp:426-447``).

    The classic outlier-safe initialization for robust (GNC) runs:
    odometry edges are trusted, so corrupted loop closures cannot poison
    the starting basin the way they can poison the chordal init (which
    least-squares over EVERY edge, outliers included).  The tradeoff is
    accumulated drift: on long 2D trajectories the drifted start makes
    ALL loop-closure residuals large and GNC cannot separate inliers
    (measured, 10%-corrupted city10000: odometry init ends at precision
    0.64 / inlier-cost 1.2e7 where chordal + iterated GNC reaches
    precision 0.95 / inlier-cost +4% — see
    ``experiments/gnc_corruption.py``).  Prefer this init on graphs with
    tight odometry (sphere2500-like); prefer chordal +
    ``solve_rbcd_robust_iterated`` when drift dominates."""
    edges_g = edge_set_from_measurements(part.meas_global, dtype=dtype)
    return lifted_init(edges_g, graph, meta, part.meas_global.num_poses,
                       "odometry")


def lifting_matrix(meta: GraphMeta, dtype=jnp.float32) -> jax.Array:
    """The shared lifting matrix YLift for this problem's (rank, d)."""
    return _lifting_matrix(meta.rank, meta.d, dtype)


def round_global(Xg: jax.Array, ylift: jax.Array) -> jax.Array:
    """Round a global lifted solution to SE(d) and express it in the frame of
    the global anchor (pose 0 = identity), as
    ``getTrajectoryInGlobalFrame`` does (reference ``PGOAgent.cpp:500-519``)."""
    T = round_solution(Xg, ylift)
    d = ylift.shape[1]
    R, t = T[..., :d], T[..., d]
    Ra_inv = R[0].T
    R_out = jnp.einsum("ab,nbc->nac", Ra_inv, R)
    t_out = jnp.einsum("ab,nb->na", Ra_inv, t - t[0])
    return jnp.concatenate([R_out, t_out[..., None]], axis=-1)


@dataclasses.dataclass
class RBCDResult:
    T: jax.Array  # [N, d, d+1] rounded global trajectory
    X: jax.Array  # [A, n_max, r, d+1]
    cost_history: list
    grad_norm_history: list
    iterations: int
    terminated_by: str
    weights: jax.Array | None = None  # [M] final per-measurement GNC weights
    #: Exact terminal solver state (the warm-start handle of the live-session
    #: layer, ``models.incremental``): resuming ``dispatch_prepared`` from it
    #: after streaming new edges skips the centralized init entirely.  Set by
    #: the single-problem driver loops; batched serving results leave it None
    #: (their states ride the session store instead).
    state: "RBCDState | None" = None
    #: True when the serving plane completed this request by re-admitting it
    #: from a crash-recovery session snapshot (``serve.session``), or when
    #: the sharded supervisor rewound it at least once mid-solve.
    recovered: bool = False
    #: Pod-scale resilience summary (``parallel.resilience``): recoveries,
    #: checkpoint counts, fault kinds, injector stats.  None for solves
    #: run without a ``ResilienceConfig``.
    resilience: dict | None = None
    #: Terminal dual certificate (``certify.CertificateResult``) when the
    #: solve ran with ``AgentParams.certify_mode`` != "off": the device
    #: eigensolve rides the fused terminal epilogue (one blocking fetch)
    #: and the host f64 path runs only on a REFUSE.  None otherwise.
    certificate: object | None = None


def global_weights(weights: jax.Array, graph: MultiAgentGraph,
                   num_meas: int) -> jax.Array:
    """Collapse per-agent edge weights [A, E_max] to per-measurement [M].

    Shared measurements appear in both endpoint agents' edge lists with
    identical weights (see ``_gnc_update_weights``), so the masked mean over
    copies is exact; measurements nobody holds (none in practice) default
    to 1."""
    ids = graph.meas_id.reshape(-1)
    m = graph.edges.mask.reshape(-1)
    num = jnp.zeros((num_meas,), weights.dtype).at[ids].add(weights.reshape(-1) * m)
    den = jnp.zeros((num_meas,), weights.dtype).at[ids].add(m)
    return jnp.where(den > 0, num / jnp.maximum(den, 1.0), 1.0)


def schedule_bounds(n_done: int, nwu: int, *, max_iters: int,
                    eval_every: int, params: AgentParams | None,
                    robust_on: bool, accel_on: bool):
    """Host-side schedule arithmetic shared by ``run_rbcd`` and the flight
    recorder's replay (``obs.recorder``): flags for round ``n_done + 1``
    and the segment end — the plain tail runs to (exclusive) the next
    flagged round, capped (inclusive) at the next eval boundary.

    The modular counters of the reference (shouldUpdateLoopClosure-
    Weights / shouldRestart, PGOAgent.cpp:1174-1179, 1033-1038) live on
    the host: round variants compile branch-free.  Beyond-reference:
    weight updates stop after robust_opt_num_weight_updates (<=0 means
    unlimited, the reference behavior) — without the cap, post-
    convergence weight updates keep annealing mu (<- 1.4 mu) and, with
    warm start disabled, keep resetting the iterate to the initial
    guess, so the solve would never settle.  The GNC
    ratio freeze itself (computeConvergedLoopClosureRatio semantics,
    PGOAgent.cpp:1247-1289) is decided ON DEVICE inside the flagged
    round (see ``_rbcd_round``): a frozen flagged round computes exactly
    a plain round, so the host keeps flagging on the modular schedule
    with no weight readback and identical results.  Module-level so a
    replay resumed from a snapshot at round ``n_done`` re-issues the
    exact segment splits the original driver dispatched.
    """
    cap = params.robust_opt_num_weight_updates if params is not None else 0
    updates_remaining = robust_on and (cap <= 0 or nwu < cap)
    uw = updates_remaining and \
        (n_done + 1) % params.robust_opt_inner_iters == 0
    rs = accel_on and (n_done + 1) % params.restart_interval == 0
    n0 = n_done + 1
    end = max_iters
    if updates_remaining:
        end = min(end, (n0 // params.robust_opt_inner_iters + 1)
                  * params.robust_opt_inner_iters - 1)
    if accel_on:
        end = min(end, (n0 // params.restart_interval + 1)
                  * params.restart_interval - 1)
    end = min(max(end, n0),
              ((n0 - 1) // eval_every + 1) * eval_every, max_iters)
    return uw, rs, end


def _central_metrics_body(graph: MultiAgentGraph, edges_g: EdgeSet,
                          n_total: int, num_meas: int, telemetry: bool):
    """The (unjitted) stacked-eval computation shared by the fused verdict
    program (``make_verdict_program``, and its batched twin in
    ``serve.runner``) and the replay program ``_make_central_metrics``:
    all trace the *same* Python body, so the per-eval rows the verdict
    program stores in its device-side history are bit-identical to what
    the replay recomputes (pinned by ``tests/test_recorder.py``)."""

    def central_metrics(Xa, weights, ready, mu, rel_change):
        Xg = gather_to_global(Xa, graph, n_total)
        eg = edges_g._replace(weight=global_weights(weights, graph, num_meas))
        f = quadratic.cost(Xg, eg)
        g = manifold.rgrad(Xg, quadratic.egrad(Xg, eg))
        vals = [f, manifold.norm(g), jnp.all(ready).astype(f.dtype)]
        if telemetry:
            e = graph.edges
            upd = e.mask * e.is_lc * (1.0 - e.fixed_weight)
            n_upd = jnp.maximum(jnp.sum(upd), 1.0)
            vals += [mu.astype(f.dtype),
                     jnp.sum((weights > 0.5) * upd) / n_upd,
                     jnp.sum(weights * upd) / n_upd]
            return jnp.concatenate(
                [jnp.stack(vals), rel_change.astype(f.dtype)])
        return jnp.stack(vals)

    return central_metrics


@partial(jax.jit, static_argnames=("n_total", "num_meas", "telemetry"))
def _central_metrics_jit(graph, edges_g, Xa, weights, ready, mu, rel_change,
                         *, n_total, num_meas, telemetry):
    return _central_metrics_body(graph, edges_g, n_total, num_meas,
                                 telemetry)(Xa, weights, ready, mu, rel_change)


def _make_central_metrics(graph: MultiAgentGraph, edges_g: EdgeSet,
                          n_total: int, num_meas: int, telemetry: bool):
    """The jitted stacked-metrics program of the flight recorder's replay
    (``obs.recorder``): it evaluates a replayed trajectory through the
    same ``_central_metrics_body`` the verdict program stores its history
    rows from (bit-for-bit reproduction requires the same compiled
    reduction order, not merely the same math).

    The returned callable binds the problem and calls the module-level
    ``_central_metrics_jit``, which takes ``graph``/``edges_g`` as
    arguments: the executable is keyed on shapes and the static settings
    only, so every problem of one padded shape shares it."""

    def central_metrics(Xa, weights, ready, mu, rel_change):
        return _central_metrics_jit(graph, edges_g, Xa, weights, ready, mu,
                                    rel_change, n_total=n_total,
                                    num_meas=num_meas, telemetry=telemetry)

    return central_metrics


# ---------------------------------------------------------------------------
# Device-resident verdict loop
# ---------------------------------------------------------------------------
#
# The verdict word is one packed int32 the host reads back every K rounds
# (every eval by default) in place of the full per-eval scalar stack:
#
#   bits 0-2   status        0 RUNNING | 1 GRAD_NORM | 2 CONSENSUS
#   bits 3-5   anomaly class 0 none | 1 cost_spike | 2 stall
#                            | 3 grad_explosion | 4 non_finite
#                            (highest-severity class seen so far, latched)
#   bits 6+    GNC stage index (robust.gnc_stage_index, 0 when not robust)
#
# Termination latches ON DEVICE at the first eval whose gradient norm
# clears the tolerance (or whose agents reach consensus) — the one
# termination test of every driver (``_verdict_update``); the host only
# learns about it at the next K-round fetch, so the returned iterate may
# carry up to K - eval_every extra polish rounds past the terminal eval —
# histories, telemetry, and ``iterations`` are truncated at the latched
# terminal eval, so the *reported* trajectory is the same for every K.

VERDICT_RUNNING = 0
VERDICT_GRAD_NORM = 1
VERDICT_CONSENSUS = 2
_VERDICT_STATUS = {VERDICT_RUNNING: "running",
                   VERDICT_GRAD_NORM: "grad_norm",
                   VERDICT_CONSENSUS: "consensus"}

ANOMALY_NONE = 0
ANOMALY_COST_SPIKE = 1
ANOMALY_STALL = 2
ANOMALY_GRAD_EXPLOSION = 3
ANOMALY_NON_FINITE = 4
_VERDICT_ANOMALY = {ANOMALY_NONE: None, ANOMALY_COST_SPIKE: "cost_spike",
                    ANOMALY_STALL: "stall",
                    ANOMALY_GRAD_EXPLOSION: "grad_explosion",
                    ANOMALY_NON_FINITE: "non_finite"}


def pack_verdict(status: int, anomaly: int = 0, stage: int = 0) -> int:
    """Host-side packer (tests / documentation of the word layout)."""
    return int(status) | (int(anomaly) << 3) | (int(stage) << 6)


def unpack_verdict(word: int) -> dict:
    """Decode a fetched verdict word into named fields."""
    word = int(word)
    return {"status": _VERDICT_STATUS.get(word & 7, "?"),
            "anomaly": _VERDICT_ANOMALY.get((word >> 3) & 7),
            "stage": word >> 6}


def _host_fetch(x):
    """THE device->host transfer seam of the driver loops.

    Every sanctioned readback in ``run_rbcd`` (and the serving plane's
    ``run_bucket``) goes through this one function so benchmarks and
    tests can count host syncs by patching it (``bench.py``'s
    ``host_syncs_per_100_rounds`` shim — the same technique as the
    zero-overhead telemetry smoke).  Semantically ``jax.device_get``: it
    accepts arbitrary pytrees, so the fused terminal epilogue (rounded
    trajectory + collapsed weights + history + latched indices +
    certificate payload) is ONE counted blocking read."""
    return jax.device_get(x)


class VerdictState(NamedTuple):
    """Device-resident control/health state carried across evals.

    ``hist`` accumulates the exact per-eval stacked-metrics rows
    (``_central_metrics_body`` output) so the full scalar stack can be
    fetched lazily — once per verdict fetch with telemetry on, once at
    termination with telemetry off — instead of per eval."""

    word: jax.Array        # int32 packed verdict (see module constants)
    eval_idx: jax.Array    # int32 number of eval rows recorded
    term_eval: jax.Array   # int32 eval index of the terminal eval (-1)
    term_it: jax.Array     # int32 iteration of the terminal eval (-1)
    best_cost: jax.Array   # stage-best cost (cost_spike baseline)
    min_gn: jax.Array      # stage-min gradient norm (explosion baseline)
    stage: jax.Array       # int32 GNC stage index
    stall_anchor: jax.Array  # cost at the stall window anchor
    stall_len: jax.Array     # int32 evals since the anchor
    stall_fired: jax.Array   # bool, once per stage
    hist: jax.Array        # [max_evals, W] per-eval metric rows


def init_verdict_state(max_evals: int, num_robots: int, dtype,
                       telemetry: bool) -> VerdictState:
    """Fresh verdict state sized for ``max_evals`` eval boundaries.  Row
    width matches ``_central_metrics_body``: 3 scalars, +3 GNC scalars and
    the per-agent relative change with telemetry on."""
    dt = jnp.dtype(dtype)
    W = (6 + num_robots) if telemetry else 3
    inf = jnp.asarray(jnp.inf, dt)
    z32 = jnp.zeros((), jnp.int32)
    return VerdictState(
        word=z32, eval_idx=z32,
        term_eval=jnp.full((), -1, jnp.int32),
        term_it=jnp.full((), -1, jnp.int32),
        best_cost=inf, min_gn=inf, stage=z32,
        stall_anchor=inf, stall_len=z32,
        stall_fired=jnp.zeros((), bool),
        hist=jnp.zeros((max_evals, W), dt))


def _device_gnc_stage(mu, mu0: float, step: float, kmax: int):
    """Device twin of ``robust.gnc_stage_index`` (same clamp semantics);
    ``mu0``/``step``/``kmax`` are static host floats resolved by the
    program builder."""
    if mu0 <= 0 or step <= 1.0:
        return jnp.zeros((), jnp.int32)
    k = jnp.round(jnp.log(jnp.maximum(mu, mu0) / mu0) / np.log(step))
    return jnp.clip(k.astype(jnp.int32), 0, kmax)


def _verdict_update(vec, mu, rel_change, iteration, vs: VerdictState,
                    grad_norm_tol, *, gnc, health) -> VerdictState:
    """THE verdict update of one eval: everything downstream of the
    stacked metrics row ``vec`` (convergence test, health predicates,
    latch, history append) — the only place an RBCD solve's termination
    is decided.  One pure function traced by the single-device program
    (``_verdict_step_jit``), the sharded plane's ``metrics_body=``
    closure, and the serving plane's batched program (a ``vmap`` of
    ``_verdict_step_jit`` in ``serve.runner``), so the word semantics
    cannot drift between them.  ``gnc`` is None or the static
    ``(mu0, step, kmax)``; ``health`` the static thresholds of
    ``_verdict_statics``; ``grad_norm_tol`` may be traced."""
    spike_rtol, spike_atol, expl_factor, gn_floor, stall_window, \
        stall_rtol = health
    f, gn, consensus = vec[0], vec[1], vec[2]
    if gnc is not None:
        stage = _device_gnc_stage(mu, *gnc)
    else:
        stage = jnp.zeros((), jnp.int32)

    # Per-stage baselines reset on stage transitions (the monitor's
    # _new_stage); the stall anchor additionally seeds itself on the
    # first finite cost.
    fresh = stage != vs.stage
    inf = jnp.asarray(jnp.inf, vec.dtype)
    best = jnp.where(fresh, inf, vs.best_cost)
    ming = jnp.where(fresh, inf, vs.min_gn)
    seed = fresh | ~jnp.isfinite(vs.stall_anchor)
    anchor = jnp.where(seed, f, vs.stall_anchor)
    slen = jnp.where(seed, 0, vs.stall_len)
    sfired = jnp.where(fresh, False, vs.stall_fired)

    finite = jnp.isfinite(f) & jnp.isfinite(gn) \
        & jnp.all(jnp.isfinite(rel_change))
    # Judge against the PRE-update baselines (monitor order), and only
    # on finite evals (the monitor early-returns on non-finite).
    spike = finite & jnp.isfinite(best) \
        & (f > best * (1.0 + spike_rtol) + spike_atol)
    expl = finite & jnp.isfinite(ming) \
        & (gn > expl_factor * jnp.maximum(ming, gn_floor))
    if stall_window > 1:
        slen = slen + 1
        full = slen >= stall_window
        stalled = finite & full & ~sfired \
            & (anchor - f <= stall_rtol * jnp.abs(anchor))
        sfired = sfired | stalled
        anchor = jnp.where(full, f, anchor)
        slen = jnp.where(full, 0, slen)
    else:
        stalled = jnp.zeros((), bool)

    code = jnp.zeros((), jnp.int32)
    code = jnp.maximum(code, jnp.where(spike, ANOMALY_COST_SPIKE, 0))
    code = jnp.maximum(code, jnp.where(stalled, ANOMALY_STALL, 0))
    code = jnp.maximum(code, jnp.where(expl, ANOMALY_GRAD_EXPLOSION, 0))
    code = jnp.maximum(code, jnp.where(~finite, ANOMALY_NON_FINITE, 0))
    anom = jnp.maximum((vs.word >> 3) & 7, code)

    status_now = jnp.where(
        gn < grad_norm_tol, VERDICT_GRAD_NORM,
        jnp.where(consensus > 0, VERDICT_CONSENSUS,
                  VERDICT_RUNNING)).astype(jnp.int32)
    status = jnp.where(vs.term_eval >= 0, vs.word & 7, status_now)
    first_term = (vs.term_eval < 0) & (status != VERDICT_RUNNING)
    term_eval = jnp.where(first_term, vs.eval_idx, vs.term_eval)
    term_it = jnp.where(first_term, iteration.astype(jnp.int32), vs.term_it)

    best = jnp.where(finite, jnp.minimum(best, f), best)
    ming = jnp.where(finite, jnp.minimum(ming, gn), ming)
    hist = jax.lax.dynamic_update_slice(
        vs.hist, vec[None, :].astype(vs.hist.dtype),
        (vs.eval_idx, jnp.zeros((), vs.eval_idx.dtype)))
    word = (status | (anom << 3) | (stage << 6)).astype(jnp.int32)
    return VerdictState(word=word, eval_idx=vs.eval_idx + 1,
                        term_eval=term_eval, term_it=term_it,
                        best_cost=best, min_gn=ming, stage=stage,
                        stall_anchor=anchor, stall_len=slen,
                        stall_fired=sfired, hist=hist)


def _verdict_statics(robust_params: RobustCostParams | None, health_cfg):
    """The static settings of a verdict program as hashable Python
    values: ``gnc`` (None, or the GNC ``(mu0, step, kmax)``) and
    ``health`` (cost-spike rtol/atol, explosion factor, gradient floor,
    stall window, stall rtol)."""
    if health_cfg is None:
        from ..obs.health import HealthConfig

        health_cfg = HealthConfig()
    health = (float(health_cfg.cost_spike_rtol),
              float(health_cfg.cost_spike_atol),
              float(health_cfg.grad_explosion_factor),
              float(health_cfg.grad_floor),
              int(health_cfg.stall_window),
              float(health_cfg.stall_rtol))
    gnc = None
    if robust_params is not None:
        gnc = (float(robust_params.gnc_init_mu),
               float(robust_params.gnc_mu_step),
               int(robust_params.gnc_max_iters))
    return gnc, health


@partial(jax.jit, static_argnames=("n_total", "num_meas", "telemetry", "gnc",
                                   "health"))
def _verdict_step_jit(graph, edges_g, Xa, weights, ready, mu, rel_change,
                      iteration, vs, grad_norm_tol, *, n_total, num_meas,
                      telemetry, gnc, health) -> VerdictState:
    vec = _central_metrics_body(graph, edges_g, n_total, num_meas,
                                telemetry)(Xa, weights, ready, mu, rel_change)
    return _verdict_update(vec, mu, rel_change, iteration, vs, grad_norm_tol,
                           gnc=gnc, health=health)


def make_verdict_program(graph: MultiAgentGraph, edges_g: EdgeSet,
                         n_total: int, num_meas: int, telemetry: bool, *,
                         grad_norm_tol: float,
                         robust_params: RobustCostParams | None,
                         max_evals: int, health_cfg=None,
                         metrics_body=None):
    """The fused per-eval program of the device-resident loop: evaluates
    the central metrics (the byte-identical ``_central_metrics_body``
    subcomputation), appends the row to the device-side history, folds the
    convergence test and the health predicates of ``obs.health`` into the
    packed verdict word, and latches the first terminal eval.

    The on-device predicates mirror ``HealthMonitor.observe_solver``'s
    per-stage baselines (non-finite sentinel, cost spike vs stage best,
    gradient explosion vs stage min, stall over a cost window) with one
    documented simplification: the stall window is block-aligned (anchor
    cost refreshed every ``stall_window`` evals) instead of sliding.  The
    word's anomaly class is the in-band signal; with telemetry on the
    host-side monitor re-judges the fetched rows and remains the single
    authority for anomaly *events* and abort policy, so the emitted event
    stream does not depend on the fetch cadence K.

    ``max_evals`` bounds the history; the driver never records more rows
    than eval boundaries in ``max_iters``.  ``health_cfg`` duck-types
    ``obs.health.HealthConfig`` (defaults used when None).

    The returned ``verdict_step`` binds ``graph``/``edges_g`` and calls
    the module-level ``_verdict_step_jit``, which takes the problem and
    the gradient tolerance as arguments: the executable is keyed on
    shapes and the static settings (telemetry, GNC constants, health
    thresholds) only, so two solves of one padded shape share it and
    neither traces nor lowers anything the other already built.

    ``metrics_body`` overrides the stacked-metrics subcomputation — THE
    reuse seam of the sharded plane: ``parallel.sharded`` traces the same
    row schema inside ``shard_map`` with its reductions as psums
    (``make_sharded_metrics_body``), and everything downstream of the row
    (convergence test, health predicates, latch, history) is the one
    shared ``_verdict_update``, so the verdict-word semantics cannot drift
    between the single-device and mesh paths.  The override must match
    ``_central_metrics_body``'s signature and row width; its program is
    a closure built per call, since the body closes over the mesh."""
    del max_evals  # sized into the VerdictState by init_verdict_state
    gnc, health = _verdict_statics(robust_params, health_cfg)

    if metrics_body is not None:
        @jax.jit
        def verdict_step(Xa, weights, ready, mu, rel_change, iteration,
                         vs: VerdictState) -> VerdictState:
            return _verdict_update(
                metrics_body(Xa, weights, ready, mu, rel_change), mu,
                rel_change, iteration, vs, grad_norm_tol, gnc=gnc,
                health=health)

        return verdict_step

    tol = jnp.asarray(grad_norm_tol)

    def verdict_step(Xa, weights, ready, mu, rel_change, iteration,
                     vs: VerdictState) -> VerdictState:
        return _verdict_step_jit(graph, edges_g, Xa, weights, ready, mu,
                                 rel_change, iteration, vs, tol,
                                 n_total=n_total, num_meas=num_meas,
                                 telemetry=telemetry, gnc=gnc, health=health)

    return verdict_step


def _package_version() -> str:
    """The dpgo_tpu version for run fingerprints (lazy import — the
    package __init__ is not a dependency of this module at import time)."""
    try:
        from .. import __version__
        return str(__version__)
    except ImportError:  # pragma: no cover - partial installs
        return "unknown"


@contextlib.contextmanager
def _crash_dump_scope(flight_rec):
    """Dump the attached flight recorder's black box when the driver loop
    dies — a crash is exactly the moment the ring buffer pays for itself.
    ``FlightRecorder.dump`` is first-write-wins, so an anomaly dump that
    already fired (e.g. the abort policy raising SolverHealthError) is
    not overwritten by the crash handler."""
    try:
        yield
    except Exception:
        if flight_rec is not None:
            flight_rec.dump("crash")
        raise


@partial(jax.jit, static_argnames=("n_total", "num_meas", "meta",
                                   "certify_mode", "certify_seed"))
def _epilogue_jit(graph, edges_g, Xa, weights, extras: dict, *, n_total,
                  num_meas, meta: GraphMeta, certify_mode: str,
                  certify_seed: int) -> dict:
    Xg = gather_to_global(Xa, graph, n_total)
    w_glob = global_weights(weights, graph, num_meas)
    out = {"T": round_global(Xg, lifting_matrix(meta, Xg.dtype)),
           "w_glob": w_glob, **extras}
    if certify_mode in ("device", "host"):
        # The lifted global iterate: the certificate operand, and the
        # host f64 REFUSE fallback's input — riding the same fetch so
        # a REFUSE never costs a second device round-trip.
        out["Xg"] = Xg
    if certify_mode == "device":
        from . import certify as certify_mod

        eg = edges_g._replace(weight=w_glob)
        out["cert"] = certify_mod.device_certificate_payload(
            Xg, eg, jax.random.PRNGKey(certify_seed))
    return out


def make_terminal_epilogue(graph: MultiAgentGraph, edges_g: EdgeSet,
                           n_total: int, num_meas: int, meta: GraphMeta, *,
                           certify_mode: str = "off",
                           certify_seed: int = 0):
    """The fused terminal program of a solve: gather + rounding/anchoring
    (``round_global``) + the terminal weight collapse, and — with
    ``certify_mode="device"`` — the gauge-deflated device certificate
    eigensolve (``certify.device_certificate_payload``) on the gathered
    global iterate, all as ONE jitted program.

    ``epilogue(Xa, weights, extras)`` returns a dict with ``T`` (rounded
    trajectory), ``w_glob`` (per-measurement weights), ``extras`` passed
    through verbatim (the verdict loop rides its device-side history and
    latched terminal indices here), plus ``Xg``/``cert`` when a
    certificate mode is on — so the driver's entire epilogue (finalize +
    latched-index fetch + history fetch + certificate) collapses into a
    single blocking ``_host_fetch`` of the returned pytree.  The host
    decision on the fetched payload is ``_epilogue_certificate``.

    ``epilogue`` binds ``graph``/``edges_g`` and calls the module-level
    ``_epilogue_jit``, which takes the problem as arguments (sharded
    arrays included): the executable is keyed on shapes, shardings and
    the static settings (``meta``, ``certify_mode``, ``certify_seed``)
    only, so every solve of one padded shape shares it."""

    def epilogue(Xa, weights, extras: dict) -> dict:
        return _epilogue_jit(graph, edges_g, Xa, weights, extras,
                             n_total=n_total, num_meas=num_meas, meta=meta,
                             certify_mode=certify_mode,
                             certify_seed=certify_seed)

    return epilogue


def _epilogue_certificate(fin: dict, edges_g: EdgeSet, params, dtype):
    """HOST decision on a fetched epilogue dict: build the
    ``CertificateResult`` for ``RBCDResult.certificate``.

    ``certify_mode="device"``: decide the already-computed device payload
    (``certify.decide_device_certificate``); the host sparse/f64 path
    runs ONLY when the f32 verdict is REFUSEd, fed from the fetched
    ``Xg``/``w_glob`` (no further device traffic).  ``"host"``: the
    legacy post-hoc ``certify_solution`` round-trip, kept for parity
    runs."""
    from . import certify as certify_mod

    certify_mode = getattr(params, "certify_mode", "off")
    eta = float(getattr(params, "certify_eta", 1e-5))
    with trace.span("cert.decide"):
        eg = edges_g._replace(weight=jnp.asarray(fin["w_glob"]))
        if certify_mode == "host":
            return certify_mod.certify_solution(jnp.asarray(fin["Xg"]), eg,
                                                eta=eta)
        pay = fin["cert"]
        tol = eta * float(pay["wscale"])
        f64_solve = certify_mod.host_f64_solve(fin["Xg"], eg, tol,
                                               warm=pay["direction"])
        return certify_mod.decide_device_certificate(
            pay, eta, float(jnp.finfo(jnp.dtype(dtype)).eps),
            f64_solve=f64_solve)


def run_rbcd(
    state: RBCDState,
    graph: MultiAgentGraph,
    meta: GraphMeta,
    segment,
    part: Partition,
    max_iters: int,
    grad_norm_tol: float = 0.1,
    eval_every: int = 1,
    dtype=jnp.float64,
    params: AgentParams | None = None,
    verdict_every: int | None = None,
    metrics_body_factory=None,
    start_iteration: int = 0,
    start_num_weight_updates: int = 0,
    boundary_cb=None,
) -> RBCDResult:
    """The driver loop shared by the single-device and mesh-sharded solvers —
    the analog of the ``multi-robot-example`` loop
    (``MultiRobotExample.cpp:175-264``).

    ``segment(state, k, update_weights, restart)`` runs ``k`` rounds in
    one device call, the first carrying the two host-side static schedule
    flags (``rbcd_segment`` / the shard_map equivalent): the driver
    dispatches once per schedule segment — the stretch between
    weight-update/restart/eval rounds — so no round costs its own host
    round-trip.  The GNC weight freeze runs on-device (see
    ``_rbcd_round``), so nothing reads weights back between evals.
    ``params`` drives the GNC / acceleration schedules (omit for plain L2
    RBCD).

    Termination is decided on the DEVICE.  At every eval boundary the
    fused verdict program (``make_verdict_program``) evaluates the
    centralized cost/gradnorm trace (the demo's oracle), tests the
    gradient norm against ``grad_norm_tol`` (0.1 in the reference driver)
    and agent consensus (all ``ready``, the deployed alternative —
    ``shouldTerminate``, ``PGOAgent.cpp:1007-1031``), folds the health
    predicates into the packed verdict word, and latches the first
    terminal eval.  The host reads back ONE word every ``verdict_every``
    rounds (K, a positive multiple of ``eval_every``; None reads it at
    every eval).  With telemetry on, the device-side eval history is
    fetched lazily at each boundary and replayed through the
    gauges/events/health-monitor/flight-recorder calls; with telemetry
    off, only the words and ONE fused terminal epilogue fetch (rounded
    trajectory, collapsed weights, history, latched indices, and — with
    ``params.certify_mode="device"`` — the dual-certificate payload) ever
    cross the link.  Because the host learns of termination at the next
    boundary, the returned iterate may carry up to ``K - eval_every``
    extra polish rounds; reported histories and ``iterations`` are
    truncated at the latched terminal eval.

    ``metrics_body_factory`` (mesh path) supplies a replacement for the
    stacked-metrics body: called once with the resolved telemetry flag, the
    returned function is handed to ``make_verdict_program`` as its
    ``metrics_body`` — how the sharded solver runs the centralized evals
    as a shard_map program with psum reductions while sharing every
    downstream line of this driver.

    ``start_iteration`` / ``start_num_weight_updates`` resume the loop
    mid-schedule from a checkpointed state (``parallel.resilience``): the
    schedule arithmetic is a pure function of the ABSOLUTE round index, so
    a resumed solve replays the exact flag sequence of the uninterrupted
    one.  A resumed attempt gets a fresh verdict state — anomaly latches
    clear, and its history rows cover only the resumed suffix.
    ``boundary_cb(it, nwu, state, word, terminal)`` fires at every
    verdict boundary with the pre-speculation state — the
    checkpoint/rewind hook; it may raise to abort the attempt."""
    verdict_every = eval_every if verdict_every is None else verdict_every
    if verdict_every <= 0 or verdict_every % eval_every != 0:
        raise ValueError(
            f"verdict_every={verdict_every} must be a positive multiple "
            f"of eval_every={eval_every}")
    n_total = part.meas_global.num_poses
    num_meas = len(part.meas_global)
    max_evals = -(-max_iters // eval_every)
    certify_mode = getattr(params, "certify_mode", "off") \
        if params is not None else "off"

    # Telemetry (dpgo_tpu.obs): resolved ONCE per solve.  When off, the
    # programs below are byte-identical to the uninstrumented driver —
    # zero events, zero registry calls, zero added transfers.  When on, the
    # extra per-eval scalars (GNC mu, inlier fraction, per-agent relative
    # change) ride the SAME device-side history rows, fetched once per
    # verdict boundary.
    obs_run = obs.get_run()
    telemetry = obs_run is not None

    robust_on = params is not None and \
        params.robust.cost_type != RobustCostType.L2
    accel_on = params is not None and params.acceleration

    def _bounds(n_done, nwu):
        """Schedule arithmetic, shared with the flight-recorder replay —
        see ``schedule_bounds``."""
        return schedule_bounds(n_done, nwu, max_iters=max_iters,
                               eval_every=eval_every, params=params,
                               robust_on=robust_on, accel_on=accel_on)

    health_mon = flight_rec = None
    if telemetry:
        from ..obs import health as health_mod

        # Numerical-health layer (obs.health): judges the same scalars the
        # history rows already carry — no extra transfers.  The flight
        # recorder is opt-in (FlightRecorder.attach); when one is
        # attached, register the problem so its black box is
        # self-contained and replayable.
        health_mon = health_mod.monitor_for(obs_run)
        flight_rec = getattr(obs_run, "recorder", None)
        if flight_rec is not None:
            flight_rec.set_problem(part, meta, params, dtype,
                                   eval_every=eval_every,
                                   grad_norm_tol=grad_norm_tol,
                                   max_iters=max_iters)
        obs_run.set_fingerprint(
            version=_package_version(),
            solver="run_rbcd",
            num_robots=meta.num_robots, rank=meta.rank, d=meta.d,
            n_poses=n_total, n_meas=num_meas,
            dtype=str(np.dtype(dtype)),
            schedule=params.schedule.value if params is not None else None,
            robust_cost=params.robust.cost_type.value
            if params is not None else None,
            sel_mode=resolved_sel_mode(params)
            if params is not None else None,
            eval_every=eval_every)

    with trace.span("rbcd.programs"):
        edges_g = edge_set_from_measurements(part.meas_global, dtype=dtype)
        verdict_step = make_verdict_program(
            graph, edges_g, n_total, num_meas, telemetry,
            grad_norm_tol=grad_norm_tol,
            robust_params=params.robust if robust_on else None,
            max_evals=max_evals,
            health_cfg=health_mon.config if health_mon is not None else None,
            metrics_body=metrics_body_factory(telemetry)
            if metrics_body_factory is not None else None)
        vs0 = init_verdict_state(max_evals, meta.num_robots, dtype,
                                 telemetry)
        epilogue = make_terminal_epilogue(graph, edges_g, n_total, num_meas,
                                          meta, certify_mode=certify_mode)

    if telemetry:
        obs_run.event("solve_start", phase="solve",
                      num_robots=meta.num_robots, max_iters=max_iters,
                      eval_every=eval_every, grad_norm_tol=grad_norm_tol,
                      robust=robust_on, acceleration=accel_on)
        g_cost = obs_run.gauge("solver_cost", "centralized SE(d) cost")
        g_gn = obs_run.gauge("solver_grad_norm",
                             "centralized Riemannian gradient norm")
        c_rounds = obs_run.counter("solver_rounds", "RBCD rounds executed")
        c_evals = obs_run.counter("solver_evals",
                                  "centralized metric evaluations")
        h_round = obs_run.histogram(
            "round_latency_seconds",
            "wall-clock per RBCD round at phase boundaries", unit="s")
        g_agent_rel = obs_run.gauge("agent_rel_change",
                                    "per-agent iterate relative change")
        if robust_on:
            g_mu = obs_run.gauge("gnc_mu", "GNC control parameter")
            g_inl = obs_run.gauge("gnc_inlier_fraction",
                                  "fraction of updatable LC edges at w>0.5")

    def _emit_eval(it_ev, vec, rounds, per_round):
        """One eval's telemetry — gauges, metric events, flight-recorder
        ring, health verdict — fed from a host-side telemetry-width
        history row."""
        f, gn = float(vec[0]), float(vec[1])
        mu_v, inl, mean_w = (float(x) for x in vec[3:6])
        rel = vec[6:]
        g_cost.set(f)
        g_gn.set(gn)
        c_rounds.inc(rounds)
        c_evals.inc()
        h_round.observe(per_round)
        for a in range(rel.shape[0]):
            g_agent_rel.set(float(rel[a]), agent=a)
        ev = {"iteration": it_ev, "round_latency_s": per_round,
              # rel is a host-side row of an already-materialized
              # vector; .max() is numpy. dpgolint: disable=DPG003
              "rel_change_max": float(rel.max()) if rel.size else None}
        obs_run.metric("solver_cost", f, phase="eval", **ev)
        obs_run.metric("solver_grad_norm", gn, phase="eval", **ev)
        if robust_on:
            g_mu.set(mu_v)
            g_inl.set(inl)
            obs_run.metric("gnc_mu", mu_v, phase="eval", iteration=it_ev)
            obs_run.metric("gnc_inlier_fraction", inl, phase="eval",
                           iteration=it_ev, mean_weight=mean_w)
        # Flight recorder first (so an anomaly dump includes this
        # eval), then the health verdict — which may dump and, per
        # the abort policy, raise SolverHealthError.
        if flight_rec is not None:
            flight_rec.record_eval(
                it_ev, {"cost": f, "grad_norm": gn,
                        "mu": mu_v, "inlier_frac": inl,
                        "rel_change": rel})
        if health_mon is not None:
            health_mon.observe_solver(
                it_ev, f, gn,
                mu=mu_v if robust_on else None,
                inlier_frac=inl if robust_on else None,
                rel_change=rel,
                stage=robust.gnc_stage_index(mu_v, params.robust)
                if robust_on else None)

    eval_its: list[int] = []
    fetches = 0  # sanctioned device->host syncs inside the loop

    def advance(st, it, nwu, vs, target):
        """Enqueue segments + fused verdict evals up to ``target`` (no
        host synchronization — everything stays in flight)."""
        with trace.span("rbcd.enqueue", labels={"what": "rounds"}):
            while it < target:
                ev_t = min(((it // eval_every) + 1) * eval_every, target)
                while it < ev_t:
                    uw, rs, end = _bounds(it, nwu)
                    nwu += int(uw)
                    st = segment(st, end - it, uw, rs)
                    it = end
                vs = verdict_step(st.X, st.weights, st.ready, st.mu,
                                  st.rel_change, st.iteration, vs)
                eval_its.append(it)
        return st, it, nwu, vs

    # Per verdict boundary (every K rounds): dispatch the schedule segments
    # and the fused verdict evals, ENQUEUE the next boundary's work
    # (depth-1 speculation, so the word fetch's round-trip hides behind
    # device execution), then fetch ONE packed int32.
    t_solve0 = t_window = time.perf_counter()
    it_window = int(start_iteration)
    fed = 0
    snap_at = None  # evals fed when the flight recorder last snapshotted
    hist_rows = None
    terminated_by = "max_iters"
    n_keep = it_final = 0
    with _crash_dump_scope(flight_rec):
        it, nwu, vs = int(start_iteration), int(start_num_weight_updates), vs0
        bound = lambda i: min(((i // verdict_every) + 1) * verdict_every,
                              max_iters)
        state, it, nwu, vs = advance(state, it, nwu, vs, bound(it))
        n_pre = len(eval_its)
        while True:
            state_pre, it_pre, nwu_pre, vs_pre = state, it, nwu, vs
            if it < max_iters:
                # Depth-1 speculation: the NEXT boundary's segments and
                # verdict evals execute while the word fetch below blocks
                # the host for a readback; each loop iteration
                # fetches exactly one boundary's word.
                state, it, nwu, vs = advance(state, it, nwu, vs, bound(it))
            # THE verdict readback: one packed int32 per K rounds (from
            # the pre-speculation state, so it never waits on the
            # speculative work).
            with trace.span("rbcd.fetch", labels={"what": "word"}):
                # dpgolint: disable=DPG003 -- sanctioned verdict-word fetch
                word = int(_host_fetch(vs_pre.word))
            fetches += 1
            status = word & 7
            terminal = status != VERDICT_RUNNING or it_pre >= max_iters
            if boundary_cb is not None:
                # Resilience hook (parallel.resilience): checkpoint the
                # pre-speculation state, or raise to rewind on a latched
                # anomaly.  The word fetch above already drained this
                # boundary, so a checkpoint gather here adds no new
                # synchronization point.
                boundary_cb(it_pre, nwu_pre, state_pre, word, terminal)
            if telemetry and not terminal:
                # Lazy full-stack fetch: the per-eval scalar rows the
                # telemetry/health/recorder consumers see — recurring
                # (counted) with telemetry on; at termination the rows
                # ride the fused epilogue fetch below instead.
                with trace.span("rbcd.fetch", labels={"what": "history"}):
                    # dpgolint: disable=DPG003 -- sanctioned lazy history fetch
                    hist_rows = _host_fetch(vs_pre.hist)
                fetches += 1
            if terminal:
                # THE terminal blocking read: rounding/anchoring, the
                # weight collapse, the device certificate payload (when
                # certify_mode="device"), the eval history, and the
                # latched terminal indices — one pytree, one fetch.  The
                # history leg replaces the recurring telemetry fetch at
                # this boundary (same count).
                with trace.span("rbcd.enqueue", labels={"what": "epilogue"}):
                    fin = epilogue(
                        state_pre.X, state_pre.weights,
                        {"hist": vs_pre.hist,
                         "tail": jnp.stack([vs_pre.term_eval,
                                            vs_pre.term_it])})
                with trace.span("rbcd.fetch", labels={"what": "epilogue"}):
                    # dpgolint: disable=DPG003 -- sanctioned terminal epilogue fetch
                    fin = _host_fetch(fin)
                hist_rows = fin["hist"]
                fetches += int(telemetry)
                term_eval, term_it = int(fin["tail"][0]), int(fin["tail"][1])
                if term_eval >= 0:
                    n_keep, it_final = term_eval + 1, term_it
                    terminated_by = _VERDICT_STATUS.get(status, "max_iters")
                else:
                    n_keep, it_final = n_pre, it_pre
                    terminated_by = "max_iters"
            feed_to = min(n_pre, n_keep) if terminal else n_pre
            if telemetry and feed_to > fed:
                now = time.perf_counter()
                dt, t_window = now - t_window, now
                rounds_w = max(it_pre - it_window, 1)
                it_window = it_pre
                per_round = dt / rounds_w
                for r in range(fed, feed_to):
                    rounds_r = eval_its[r] - (eval_its[r - 1] if r
                                              else int(start_iteration))
                    _emit_eval(eval_its[r], hist_rows[r], max(rounds_r, 1),
                               per_round)
                fed = feed_to
                if flight_rec is not None and not terminal and (
                        snap_at is None
                        or fed - snap_at >= flight_rec.snapshot_every):
                    # Exact-state snapshot at the verdict boundary, on the
                    # recorder's eval cadence (the first eval, then every
                    # snapshot_every evals).  hist_rows is already
                    # host-side (the lazy fetch).
                    rows_finite = np.isfinite(hist_rows[:feed_to]).all()
                    flight_rec.snapshot_state(
                        it_pre, state_pre, nwu_pre,
                        healthy=bool(rows_finite))
                    snap_at = fed
            if terminal:
                state = state_pre
                break
            n_pre = len(eval_its)

    cost_hist = [float(hist_rows[r, 0]) for r in range(n_keep)]
    gn_hist = [float(hist_rows[r, 1]) for r in range(n_keep)]

    # The epilogue already crossed the link in the terminal fetch above;
    # what remains is pure host math (the certificate decision ladder —
    # which re-opens device traffic only on a REFUSE, by design).
    T, w_glob = fin["T"], fin["w_glob"]
    certificate = _epilogue_certificate(fin, edges_g, params, dtype) \
        if certify_mode != "off" else None
    if telemetry:
        _emit_sync_rate(obs_run, fetches,
                        max(it_pre - int(start_iteration), 1))
        obs_run.event(
            "solve_end", phase="solve", iterations=it_final,
            terminated_by=terminated_by,
            duration_s=time.perf_counter() - t_solve0,
            cost=cost_hist[-1] if cost_hist else None,
            grad_norm=gn_hist[-1] if gn_hist else None,
            num_weight_updates=nwu_pre,
            verdict_every=verdict_every, verdict=unpack_verdict(word))
    return RBCDResult(T=T, X=state.X, cost_history=cost_hist,
                      grad_norm_history=gn_hist, iterations=it_final,
                      terminated_by=terminated_by, weights=w_glob,
                      state=state, certificate=certificate)


def _emit_sync_rate(obs_run, fetches: int, rounds: int) -> None:
    """Record the measured in-loop host-sync rate: the readback-kill
    metric (``host_syncs_per_100_rounds``; lower is better, gated by
    ``obs.regress``).  Counts only the driver-loop fetches through the
    ``_host_fetch`` seam — the terminal epilogue transfer is excluded, as
    it is paid once per solve regardless of loop design."""
    rate = 100.0 * fetches / max(rounds, 1)
    obs_run.gauge("host_syncs_per_100_rounds",
                  "driver-loop device->host fetches per 100 RBCD rounds"
                  ).set(rate)
    obs_run.metric("host_syncs_per_100_rounds", rate, phase="solve",
                   fetches=fetches, rounds=rounds)


def initial_state_for(init: str, part: Partition, meta: GraphMeta,
                      graph: MultiAgentGraph, params: AgentParams,
                      dtype) -> jax.Array:
    """Initial lifted state by policy: ``"chordal"`` = centralized chordal
    init (the reference demo's, ``MultiRobotExample.cpp:158-165``);
    ``"odometry"`` = trusted-odometry chain init (``DPGO_utils.cpp:
    426-447`` — the outlier-safe choice for robust runs);
    ``"distributed"`` = per-agent local init + robust inter-robot frame
    alignment, no centralized solve (the deployment path,
    ``PGOAgent.cpp:250-432``)."""
    if init == "chordal":
        return centralized_chordal_init(part, meta, graph, dtype)
    if init == "odometry":
        return centralized_odometry_init(part, meta, graph, dtype)
    if init == "distributed":
        from .dist_init import distributed_initialization
        return distributed_initialization(part, meta, graph, params, dtype)
    raise ValueError(f"unknown init policy {init!r}")


@dataclasses.dataclass(frozen=True)
class PreparedProblem:
    """A built, dispatch-ready problem — the schedulable unit of the
    serving plane (``dpgo_tpu.serve``).

    Splits ``solve_rbcd`` into its two halves: *problem build* (partition,
    padded per-agent graph/EdgeSet, metadata, initial lifted state) and
    *solve dispatch* (``dispatch_prepared`` -> ``run_rbcd``).  A prepared
    problem is reusable: it can be dispatched more than once (e.g. with
    different termination settings), padded to a shape bucket and stacked
    with compatible problems for a batched ``vmap`` solve, or held in a
    queue awaiting device capacity — none of which re-runs the host-side
    graph construction.
    """

    part: Partition
    graph: MultiAgentGraph
    meta: GraphMeta
    params: AgentParams
    dtype: object
    X0: jax.Array | None = None

    @property
    def n_total(self) -> int:
        return self.part.meas_global.num_poses

    @property
    def num_meas(self) -> int:
        return len(self.part.meas_global)


def prepare_problem(
    meas: Measurements,
    num_robots: int,
    params: AgentParams | None = None,
    dtype=jnp.float64,
    part: Partition | None = None,
    init: str | None = "chordal",
    pallas_sel: bool | None = None,
) -> PreparedProblem:
    """Problem build: partition, per-agent graph assembly, and (unless
    ``init=None``) the initial lifted state.

    ``init=None`` defers initialization — the serving plane pads the
    problem to its shape bucket first and initializes on the padded
    problem, so the compiled init program is shared across the bucket."""
    params = params or AgentParams(d=meas.d, r=5, num_robots=num_robots)
    with trace.span("rbcd.prepare"):
        with trace.span("rbcd.partition"):
            part = part or partition_contiguous(meas, num_robots)
        with trace.span("rbcd.build_graph"):
            graph, meta = build_graph(part, params.r, dtype,
                                      pallas_sel=pallas_sel,
                                      sel_mode=resolved_sel_mode(params))
        # The chordal init's factorizations are host work inside this span;
        # a device init (odometry, distributed, the CG fallback) only
        # dispatches here and runs on past it.
        with trace.span("rbcd.init"):
            X0 = initial_state_for(init, part, meta, graph, params, dtype) \
                if init is not None else None
    return PreparedProblem(part=part, graph=graph, meta=meta, params=params,
                           dtype=dtype, X0=X0)


def dispatch_prepared(
    prob: PreparedProblem,
    max_iters: int | None = None,
    grad_norm_tol: float = 0.1,
    eval_every: int = 1,
    state: RBCDState | None = None,
    verdict_every: int | None = None,
) -> RBCDResult:
    """Solve dispatch for a prepared problem: build the segment closure
    and run the shared driver loop (``run_rbcd``).  ``state`` overrides
    the fresh ``init_state`` — e.g. to resume from a snapshot.
    ``verdict_every`` sets how often the host reads the verdict word (K
    rounds, a multiple of ``eval_every``; None reads it at every eval —
    see ``run_rbcd``)."""
    params = prob.params
    max_iters = params.max_num_iters if max_iters is None else max_iters
    if state is None and prob.X0 is None:
        raise ValueError(
            "prepared problem has no initial state — prepare with "
            "init=... or pass state=")
    graph, meta = prob.graph, prob.meta
    seg = lambda s, k, uw, rs: rbcd_segment(s, graph, k, meta, params,
                                            first_update_weights=uw,
                                            first_restart=rs)
    with trace.span("rbcd.dispatch"):
        if state is None:
            with trace.span("rbcd.programs"):
                state = init_state(graph, meta, prob.X0, params=params)
        return run_rbcd(state, graph, meta, seg, prob.part, max_iters,
                        grad_norm_tol, eval_every, prob.dtype, params=params,
                        verdict_every=verdict_every)


def solve_rbcd(
    meas: Measurements,
    num_robots: int,
    params: AgentParams | None = None,
    max_iters: int | None = None,
    grad_norm_tol: float = 0.1,
    eval_every: int = 1,
    dtype=jnp.float64,
    part: Partition | None = None,
    init: str = "chordal",
    verdict_every: int | None = None,
) -> RBCDResult:
    """Distributed solve on one device with centralized monitoring —
    ``prepare_problem`` + ``dispatch_prepared`` in one call."""
    prob = prepare_problem(meas, num_robots, params=params, dtype=dtype,
                           part=part, init=init)
    return dispatch_prepared(prob, max_iters=max_iters,
                             grad_norm_tol=grad_norm_tol,
                             eval_every=eval_every,
                             verdict_every=verdict_every)


def solve_rbcd_robust_iterated(
    meas: Measurements,
    num_robots: int,
    params: AgentParams | None = None,
    passes: int = 2,
    reject_thresh: float = 0.5,
    **solve_kw,
) -> tuple[RBCDResult, np.ndarray, np.ndarray]:
    """Iterated GNC: robust solve, HARD-drop rejected loop closures,
    re-anneal on the kept edges — ``passes`` times.

    A single GNC anneal at BCD inner-convergence depth can leave a few
    gross outliers at weight >= ``reject_thresh`` whose constraints bend
    the whole solution (measured on 10%-corrupted city10000: 16 of 1069
    injected outliers survive pass 1 and inflate the inlier-edge cost
    ~25x over the outlier-free optimum).  A second anneal on the filtered
    problem starts from an iterate the surviving outliers can no longer
    hide in — residuals are informative — and rejects them (same
    measurement: recall 0.985 -> 1.000, inlier-edge cost +4% over the
    outlier-free optimum).  Only loop closures are ever dropped
    (``types.loop_closure_mask``): the odometry chain stays intact, so
    the filtered graph cannot disconnect.

    The reference's GNC is single-pass (``updateLoopClosuresWeights``,
    ``PGOAgent.cpp:1181-1245``); the iteration is beyond-reference.

    Between passes, previously-dropped edges whose residual at the new
    solution falls back inside the TLS inlier boundary (``gnc_barc``) are
    REINSTATED: at heavy corruption the re-anneal over-rejects borderline
    clean edges (measured at 40%: precision 0.87-0.97), and once the
    iterate no longer carries the outliers' distortion, a wrongly-dropped
    edge is cheap to recognize — its residual is small again.  (The
    consensus re-test of RANSAC-style pipelines; beyond-reference.)

    Returns ``(result_of_last_pass, weights_full, kept_mask)`` where
    ``weights_full [M]`` maps the last pass's weights back to the
    ORIGINAL measurement indices (dropped edges report weight 0) and
    ``kept_mask [M]`` marks the measurements the last pass solved over.
    ``result.iterations`` is the TOTAL round count across passes.
    """
    from ..types import loop_closure_mask

    if passes < 1:
        raise ValueError(f"passes must be >= 1, got {passes}")
    if "part" in solve_kw:
        # solve_rbcd prefers a supplied Partition over its meas argument,
        # which would silently undo the per-pass edge filtering.
        raise ValueError("solve_rbcd_robust_iterated re-partitions each "
                         "pass; 'part' cannot be supplied")
    lc = loop_closure_mask(meas)
    kept = np.ones(len(meas), bool)
    res = None
    total_rounds = 0
    for p in range(passes):
        sub = meas.select(kept) if not kept.all() else meas
        res = solve_rbcd(sub, num_robots, params, **solve_kw)
        total_rounds += res.iterations
        if res.weights is None and passes > 1:
            # A non-robust cost (the default AgentParams) yields no GNC
            # weights, so the drop/reinstate loop below would silently
            # degenerate to a single plain solve — surface the misuse.
            raise ValueError(
                "solve_rbcd_robust_iterated needs a GNC-weighted cost "
                "(params.robust.cost_type GNC_TLS); the solve returned no "
                "weights")
        w_sub = np.asarray(res.weights) if res.weights is not None \
            else np.ones(int(kept.sum()))
        w_full = np.zeros(len(meas))
        w_full[kept] = w_sub
        if p == passes - 1:
            break
        drop = (w_full < reject_thresh) & kept & lc
        # Re-test every previously-dropped edge against the new iterate.
        reinstate = np.zeros(len(meas), bool)
        dropped = ~kept
        if dropped.any():
            rn = _global_residual_norms(res, meas, num_robots)
            barc = (params.robust if params is not None
                    else RobustCostParams()).gnc_barc
            reinstate = dropped & (rn < barc)
            w_full[reinstate] = 1.0
        new_kept = (kept & ~drop) | reinstate
        if (new_kept == kept).all():
            break
        kept = new_kept
    res = dataclasses.replace(res, iterations=total_rounds)
    return res, w_full, kept


def _global_residual_norms(res: RBCDResult, meas: Measurements,
                           num_robots: int) -> np.ndarray:
    """Per-measurement residual norms sqrt(kappa ||rR||^2 + tau ||rt||^2)
    of the FULL original measurement set at a result's iterate (the
    iterate lives on the filtered problem; poses are unchanged by edge
    filtering, so the pose layout is partition-independent)."""
    from ..utils.partition import gather_poses_to_global

    edges_g = edge_set_from_measurements(meas, dtype=jnp.float32)
    part = partition_contiguous(meas, num_robots)
    Xg = gather_poses_to_global(np.asarray(res.X, np.float32), part)
    rR, rt = quadratic._edge_terms(jnp.asarray(Xg), edges_g)
    sq = edges_g.kappa * jnp.sum(rR * rR, axis=(-2, -1)) \
        + edges_g.tau * jnp.sum(rt * rt, axis=-1)
    return np.sqrt(np.maximum(np.asarray(sq), 0.0))
