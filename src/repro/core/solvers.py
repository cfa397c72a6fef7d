"""Placement solvers for the CFN embedding problem.

The paper solves the MILP with CPLEX (24 cores, 126 GB).  CPLEX is not
available offline, and the contribution we reproduce is the *formulation* and
its energy trade-offs, so we provide a solver suite whose strongest member
(`solve_cfn`, coordinate-descent restarts x batched simulated annealing,
cross-validated by exhaustive enumeration on small instances) acts as the
CPLEX stand-in.  The hot solvers (coordinate, anneal) run on power.py's
incremental delta-evaluation engine -- a proposal changes one VM, so only
the touched load-tensor entries are re-scored; whole-placement evaluation
(exhaustive, genetic) stays on the batched tensor objective (optionally the
Pallas kernel in kernels/placement_power, which also provides a fused
annealing kernel keeping chain state resident in VMEM).

Solvers:
  fixed_layer   -- the paper's CDC / AF / MF baselines (+ IoT first-fit).
  coordinate    -- exact best-single-move sweeps via delta_sweep (monotone).
  exhaustive    -- provably optimal joint enumeration (small instances).
  anneal        -- Metropolis chains on incremental state (delta / fused
                   Pallas / legacy full-objective backends).
  genetic       -- population crossover/mutation search.
  relax           -- differentiable soft-placement + rounding (beyond-paper).
  solve_portfolio -- spec-driven portfolio = best of the above; the
                     "CFN MILP" curve (solve_cfn is its deprecated shim).

Every solver takes an optional ``eligible`` [R, P] mask -- the one
constraint surface ``repro.api.PlacementSpec.masks`` produces -- so SLA
hop bounds are enforced identically in coordinate sweeps, every anneal
backend's Metropolis proposals (one proposal stream feeds the delta scan,
the fused Pallas kernel, and the legacy full-objective path), genetic
search, exhaustive enumeration, and the relaxation.
"""
from __future__ import annotations

import functools
import warnings
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .power import (PENALTY, PlacementAux, PlacementProblem, PlacementState,
                    PowerBreakdown, apply_move, apply_pins,
                    batched_hard_loads, build_aux, delta_sweep, evaluate,
                    init_state, objective, objective_batch, _commit_entries,
                    _delta_objective, _move_core)
from .topology import CFNTopology
from ..kernels import placement_power as kpp
from ..telemetry import span


@dataclass
class SolveResult:
    X: np.ndarray                 # [R, V] placement (pins applied)
    breakdown: PowerBreakdown
    method: str
    history: List[float] = field(default_factory=list)
    # convergence trace (record_conv=True on the anneal paths): fixed-length
    # per effort bucket -- {"best_obj": [n_steps], "accept_rate": [n_steps]}
    conv: Optional[Dict[str, np.ndarray]] = None

    @property
    def objective(self) -> float:
        return float(self.breakdown.objective)

    @property
    def power(self) -> float:
        return float(self.breakdown.total)

    @property
    def feasible(self) -> bool:
        return float(self.breakdown.violation) <= 1e-6


# Fresh-compile counters for the jitted solver entries.  Tests and
# benchmarks assert on these to pin the compile-stability story (one trace
# per shape bucket; fail/recover events never retrace) -- see
# tests/test_faults.py, tests/test_federation.py, benchmarks/kernel_bench.py.
TRACE_COUNTS: Dict[str, int] = {}

# Compile-attribution hooks (repro.telemetry): called once per fresh trace
# with (entry name, abstract shape fingerprint).  Hooks run at TRACE time
# on the host -- they must not touch traced values beyond static
# attributes (.shape/.dtype), which _trace_fingerprint respects.
TRACE_HOOKS: List = []

_FINGERPRINT_MAX_LEAVES = 16


def _trace_fingerprint(args, kwargs) -> str:
    """Abstract shape fingerprint of a jitted call's arguments: per pytree
    leaf ``dtype[shape]`` (static attribute reads only -- safe on tracers),
    scalars by repr, other statics by type name.  Capped at
    ``_FINGERPRINT_MAX_LEAVES`` leaves per argument."""
    parts = []
    for a in list(args) + [kwargs[k] for k in sorted(kwargs)]:
        leaves = jax.tree_util.tree_leaves(a)
        if not leaves:
            parts.append("()" if a is None else type(a).__name__)
            continue
        sub = []
        for leaf in leaves[:_FINGERPRINT_MAX_LEAVES]:
            shp = getattr(leaf, "shape", None)
            if shp is not None:
                dt = getattr(leaf, "dtype", "?")
                sub.append(f"{dt}[{','.join(str(d) for d in shp)}]")
            else:
                sub.append(repr(leaf) if isinstance(
                    leaf, (bool, int, float, str)) else type(leaf).__name__)
        if len(leaves) > _FINGERPRINT_MAX_LEAVES:
            sub.append(f"+{len(leaves) - _FINGERPRINT_MAX_LEAVES}")
        tag = type(a).__name__
        parts.append("x".join(sub) if tag in ("ArrayImpl", "DynamicJaxprTracer",
                                              "ndarray") and len(sub) == 1
                     else f"{tag}({','.join(sub)})")
    return ";".join(parts)


def count_traces(name: str):
    """Mark a jitted solver entry: ``TRACE_COUNTS[name]`` ticks once per
    fresh TRACE (i.e. per compile), not per call.

    Apply UNDER ``jax.jit`` -- the wrapper body then runs only while jax
    traces the function, so cache hits leave the counter untouched::

        @jax.jit
        @count_traces("sweep")
        def _sweep(...): ...

    ``functools.wraps`` carries the signature through (``__wrapped__``),
    so ``jax.jit(..., static_argnames=...)`` over a counted function still
    resolves argument names.  Rule CFN104 (``repro.analysis``) enforces
    this pattern on every jitted entry here and in ``core.federation``.

    ``TRACE_HOOKS`` (registered by ``repro.telemetry``) observe each fresh
    trace with the entry name and the abstract shape fingerprint jax is
    tracing at -- the compile-attribution record.
    """
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            TRACE_COUNTS[name] = TRACE_COUNTS.get(name, 0) + 1
            if TRACE_HOOKS:
                fp = _trace_fingerprint(args, kwargs)
                for hook in list(TRACE_HOOKS):
                    hook(name, fp)
            return fn(*args, **kwargs)
        return wrapper
    return deco


_evaluate_jit = jax.jit(evaluate)  # shared wrapper: one trace per shape


def _result(problem: PlacementProblem, X, method: str,
            history: Optional[List[float]] = None) -> SolveResult:
    X = np.asarray(apply_pins(problem, jnp.asarray(X, jnp.int32)))
    bd = _evaluate_jit(problem, jnp.asarray(X))
    return SolveResult(X=X, breakdown=jax.device_get(bd), method=method,
                       history=history or [])


# ---------------------------------------------------------------------------
# Coordinate descent (exact single-VM moves, scored by the delta engine)
# ---------------------------------------------------------------------------

# objective placeholder for masked-out (SLA-ineligible) destinations: large
# enough to lose every argmin, small enough to stay finite in float32 sums
_INELIGIBLE = 1.0e30


def _eligible_np(eligible: Optional[np.ndarray]):
    """Normalize an [R, P] eligibility mask for the solver paths.

    Returns ``(el, cnt, cand)``: the bool mask with no-eligible-node rows
    fallen back to all-True (a row that cannot satisfy its SLA is placed
    best-effort rather than nowhere), per-row eligible counts [R], and the
    per-row candidate table [R, P] (eligible node ids left-packed) that
    Metropolis destination sampling draws from.  ``(None, None, None)``
    when unmasked.
    """
    if eligible is None:
        return None, None, None
    el = np.asarray(eligible, bool).copy()
    dead = ~el.any(axis=1)
    el[dead] = True
    cnt = el.sum(axis=1).astype(np.int32)
    cand = np.zeros(el.shape, np.int32)
    for r in range(el.shape[0]):
        ids = np.nonzero(el[r])[0]
        cand[r, :len(ids)] = ids
    return el, cnt, cand


def _sample_eligible(u: jnp.ndarray, rows: jnp.ndarray,
                     cnt: jnp.ndarray, cand: jnp.ndarray) -> jnp.ndarray:
    """Map uniform draws ``u`` to eligible destination nodes for service
    rows ``rows`` (broadcast against ``u``) -- the ONE sampling map behind
    every masked random draw: Metropolis proposal streams (pure-JAX delta
    scan, fused Pallas kernel, legacy full-objective backend), restart
    chains, and genetic init/mutation."""
    c = cnt[rows]
    idx = jnp.minimum((u * c).astype(jnp.int32), c - 1)
    return cand[rows, idx]


def _project_eligible(problem: PlacementProblem, X,
                      el_np: np.ndarray):
    """Move every free VM sitting on an ineligible node to its row's first
    eligible node (warm starts handed to masked solvers must start inside
    the constraint set; the solver then optimizes within it).

    Returns ``(X_proj, moved)``.  ``moved`` is a host-side bool (any VM
    actually relocated) computed from the numpy ``bad`` mask, so warm
    callers can decide whether to rebuild state WITHOUT a device round
    trip -- comparing ``X_proj`` against the incumbent on-device
    (``bool((X0 == state.X).all())``) is exactly the per-event blocking
    sync rule CFN101 exists to flag.  A bad entry always relocates (its
    current node is ineligible, the target is eligible), and pins are
    never bad, so ``moved`` is exact."""
    Xn = np.asarray(X).copy()
    fixed = np.asarray(problem.fixed_mask)
    first = el_np.argmax(axis=1).astype(Xn.dtype)
    rows = np.arange(Xn.shape[0])[:, None]
    bad = ~el_np[rows, Xn] & ~fixed
    proj = jnp.asarray(np.where(bad, first[:, None], Xn), jnp.int32)
    return proj, bool(bad.any())


def _sweep_scan(problem: PlacementProblem, aux: PlacementAux,
                state: PlacementState, positions: jnp.ndarray,
                eligible: Optional[jnp.ndarray] = None):
    """One pass over ``positions``, each VM moved to its best node, traced:
    the sweep of ``_sweep`` and of every coordinate descent.  Returns the
    state and each move's objective."""
    def body(state, pos):
        r, v = pos[0], pos[1]
        obj_all = delta_sweep(problem, aux, state, r, v)
        if eligible is not None:
            obj_all = jnp.where(eligible[r], obj_all, _INELIGIBLE)
        best = jnp.argmin(obj_all)
        state = apply_move(problem, aux, state, r, v,
                           best.astype(state.X.dtype))
        return state, obj_all[best]

    return jax.lax.scan(body, state, positions)


@jax.jit
@count_traces("sweep")
def _sweep(problem: PlacementProblem, aux: PlacementAux,
           state: PlacementState, positions: jnp.ndarray,
           eligible: Optional[jnp.ndarray] = None):
    """One pass over all free VM positions; each VM moved to its best node.

    Destinations are scored by ``delta_sweep`` (one removal + vectorized
    insertion) instead of broadcasting P full candidate placements.
    ``eligible`` [R, P] (optional) masks destinations per service row --
    the SLA hop/eligibility constraint of embed_latency_bounded threaded
    into the sweep.  ``positions`` may contain repeated rows (shape-bucket
    padding): re-sweeping a VM is idempotent up to its own argmin."""
    state, objs = _sweep_scan(problem, aux, state, positions, eligible)
    return state, objs[-1]


# ---------------------------------------------------------------------------
# Warm starts: fixed-layer first fit and coordinate descent, one program
# ---------------------------------------------------------------------------

def _layer_inputs(topo: CFNTopology, layer: str, spill_layer: str):
    """The first fit's host inputs: ``layers`` [2, P] bool (the layer's
    nodes, then the spill layer's) and each node's GFLOPS capacity [P]."""
    layers = np.zeros((2, topo.P), bool)
    layers[0, topo.layer_indices(layer)] = True
    layers[1, topo.layer_indices(spill_layer)] = True
    cap = np.array([h.cap_gflops * h.n_servers for h in topo.proc_hw],
                   np.float32)
    return layers, cap


def _first_fit(problem: PlacementProblem, aux: PlacementAux, layers, cap):
    """First-fit-decreasing, traced: the free VMs by decreasing GFLOPS
    (stable, so ties keep row-major order), each onto the least-loaded
    node of ``layers[0]`` it fits on (ties to the lowest index), else of
    ``layers[1]``, else onto the first node of ``layers[0]``.  Pinned VMs
    load their nodes first; loads and the capacity test are float32.
    Returns the [R, V] placement, pinned positions at node 0."""
    F = problem.F.reshape(-1)
    pinned = problem.fixed_mask.reshape(-1)
    load0 = (jax.nn.one_hot(problem.fixed_node.reshape(-1), problem.P,
                            dtype=jnp.float32)
             * jnp.where(pinned, F, 0.0)[:, None]).sum(0)
    order = aux.free_flat[jnp.argsort(-F[aux.free_flat], stable=True)]
    fallback = jnp.argmax(layers[0]).astype(jnp.int32)

    def place(i, carry):
        X, load = carry
        j = order[i]
        fits = load + F[j] <= cap
        ok = layers & fits[None]
        best = jnp.argmin(jnp.where(ok, load[None], jnp.inf), axis=1)
        p = jnp.where(ok[0].any(), best[0],
                      jnp.where(ok[1].any(), best[1], fallback))
        p = p.astype(jnp.int32)
        return X.at[j].set(p), load.at[p].add(F[j])

    X = jnp.zeros(F.shape, jnp.int32)
    if order.shape[0]:  # else every VM is pinned
        X, _ = jax.lax.fori_loop(0, order.shape[0], place, (X, load0))
    return X.reshape(problem.R, problem.V)


def _descend(problem: PlacementProblem, aux: PlacementAux, X, eligible,
             max_sweeps, tol, n_hist: int):
    """Coordinate descent from ``X``, traced: exact best-single-move
    sweeps over every free VM, the state refreshed exactly once per sweep
    (no float32 drift; an exact, monotone incumbent history), until the
    incumbent gains less than ``tol`` in a sweep or ``max_sweeps`` ran.
    A masked descent does not trust its start as incumbent.  Returns the
    incumbent (pinned), the incumbent history (``[n_hist]``, zero past the
    sweeps run) and the number of sweeps run."""
    hist = jnp.zeros(n_hist, jnp.float32)
    if aux.free_pos.shape[0] == 0:  # every VM pinned: nothing to move
        return X, hist, jnp.int32(0)
    state = init_state(problem, X)
    best = state.obj if eligible is None else jnp.float32(jnp.inf)

    def more(c):
        _, _, _, _, n, done = c
        return (n < max_sweeps) & ~done

    def sweep(c):
        state, best, best_X, hist, n, _ = c
        state, _ = _sweep_scan(problem, aux, state, aux.free_pos, eligible)
        state = init_state(problem, state.X)
        done = (n > 0) & (hist[n - 1] - state.obj < tol)
        better = state.obj < best
        best = jnp.where(better, state.obj, best)
        best_X = jnp.where(better, state.X, best_X)
        return state, best, best_X, hist.at[n].set(best), n + 1, done

    _, _, best_X, hist, n, _ = jax.lax.while_loop(
        more, sweep,
        (state, best, state.X, hist, jnp.int32(0), jnp.bool_(False)))
    return best_X, hist, n


@functools.partial(jax.jit, static_argnames=("n_hist",))
@count_traces("warm_start_program")
def _warm_start_program(problem: PlacementProblem, aux: PlacementAux, X0,
                        eligible, layers, cap, max_sweeps, tol, *,
                        n_hist: int):
    """Two coordinate descents as one device program: from ``X0``, then
    from the ``_first_fit`` of ``layers``/``cap``, one after the other;
    ``max_sweeps`` [2] caps each (0 returns its start, pinned, as is).
    Returns ``([(X, breakdown)] * 2, history, n_sweeps)``, the last two
    stacked over the starts."""
    starts = jnp.stack([jnp.asarray(X0, jnp.int32),
                        _first_fit(problem, aux, layers, cap)])
    bX, hist, n = jax.lax.map(
        lambda s: _descend(problem, aux, s[0], eligible, s[1], tol, n_hist),
        (starts, max_sweeps))
    # each breakdown its own output: stacked, XLA would reduce both at once
    # and round their sums otherwise than ``evaluate`` does alone
    return [_finish(problem, bX[k]) for k in range(2)], hist, n


def _tol32(tol: float) -> np.float32:
    """The least float32 at or above ``tol``: a float32 gain ``g`` then
    satisfies ``g < _tol32(tol)`` exactly when ``g < tol``."""
    t = np.float32(tol)
    return t if float(t) >= tol else np.nextafter(t, np.float32(np.inf))


def _warm_starts(problem: PlacementProblem, aux: PlacementAux, X0, eligible,
                 layers, cap, max_sweeps, tol: float = 1e-6,
                 methods=("coordinate", "coordinate")) -> List[SolveResult]:
    """Run ``_warm_start_program`` and read both results back at once."""
    el_np, _, _ = _eligible_np(eligible)
    out, hist, n = jax.device_get(_warm_start_program(
        problem, aux, X0, el_np, layers, cap,
        np.asarray(max_sweeps, np.int32), _tol32(tol),
        n_hist=_pow2(max(max_sweeps), lo=16)))
    return [SolveResult(X=X, breakdown=bd, method=m,
                        history=[float(h) for h in hist[k, :n[k]]])
            for k, ((X, bd), m) in enumerate(zip(out, methods))]


def fixed_layer(problem: PlacementProblem, topo: CFNTopology,
                layer: str, spill_layer: str = "cdc") -> SolveResult:
    """All non-input VMs at `layer`; first-fit-decreasing across that layer's
    nodes honoring GFLOPS capacity; overflow spills to ``spill_layer``
    (the paper's observed behaviour at 20 VSRs).  The fit is
    ``_first_fit``, run by the warm starts' program with no sweeps and
    read once."""
    layers, cap = _layer_inputs(topo, layer, spill_layer)
    X0 = np.zeros((problem.R, problem.V), np.int32)
    return _warm_starts(problem, build_aux(problem), X0, None, layers, cap,
                        (0, 0), methods=(None, f"fixed:{layer}"))[1]


def coordinate(problem: PlacementProblem, X0: np.ndarray,
               max_sweeps: int = 12, tol: float = 1e-6,
               eligible: Optional[np.ndarray] = None,
               telemetry=None) -> SolveResult:
    """Exact best-single-move sweeps.  ``eligible`` [R, P] (optional) masks
    each service row's destination nodes in every sweep argmin; X0 need not
    satisfy the mask (the first sweep moves every free VM onto it, and the
    incumbent is only ever taken from post-sweep states).

    The descent runs as one device program (``_warm_start_program``, its
    second start given no sweeps), read back once.  ``telemetry``
    (optional) counts ``solver.warm_start_programs{site=coordinate}`` and
    the sweeps run, ``solver.coordinate_sweeps{site=coordinate}``."""
    layers = np.zeros((2, problem.P), bool)
    cap = np.zeros(problem.P, np.float32)
    res = _warm_starts(problem, build_aux(problem), X0, eligible, layers,
                       cap, (max_sweeps, 0), tol)[0]
    if telemetry is not None:
        telemetry.inc("solver.warm_start_programs", site="coordinate")
        telemetry.inc("solver.coordinate_sweeps", len(res.history),
                      site="coordinate")
    return res


# ---------------------------------------------------------------------------
# Exhaustive enumeration (ground truth on small instances)
# ---------------------------------------------------------------------------

def exhaustive(problem: PlacementProblem, max_combos: int = 2_000_000,
               chunk: int = 8192,
               eligible: Optional[np.ndarray] = None) -> SolveResult:
    fixed_mask = np.asarray(problem.fixed_mask)
    free = np.argwhere(~fixed_mask)
    P = problem.P
    n_free = len(free)
    n_combos = P ** n_free
    if n_combos > max_combos:
        raise ValueError(f"{n_combos} combos exceed cap {max_combos}")
    el_np, _, _ = _eligible_np(eligible)
    R, V = fixed_mask.shape
    base = np.zeros((R, V), dtype=np.int32)
    best_obj, best_X = float("inf"), base
    for start in range(0, n_combos, chunk):
        idx = np.arange(start, min(start + chunk, n_combos))
        digits = np.empty((len(idx), n_free), dtype=np.int32)
        rem = idx.copy()
        for j in range(n_free - 1, -1, -1):
            digits[:, j] = rem % P
            rem //= P
        Xb = np.broadcast_to(base, (len(idx), R, V)).copy()
        Xb[:, free[:, 0], free[:, 1]] = digits
        obj = np.asarray(objective_batch(problem, jnp.asarray(Xb)))
        if el_np is not None:
            valid = el_np[free[None, :, 0], digits].all(axis=1)
            obj = np.where(valid, obj, np.inf)
        k = int(np.argmin(obj))
        if obj[k] < best_obj:
            best_obj, best_X = float(obj[k]), Xb[k]
    if not np.isfinite(best_obj):
        raise ValueError("no placement satisfies the eligibility mask")
    return _result(problem, best_X, "exhaustive", [best_obj])


# ---------------------------------------------------------------------------
# Batched simulated annealing
# ---------------------------------------------------------------------------

def _chain_step(problem: PlacementProblem, aux: PlacementAux,
                Xf, omega, theta, lam, obj, j, p_new):
    """One Metropolis proposal on ONE chain's incremental state.

    Returns the candidate state + exact objective delta; the caller decides
    acceptance.  vmapped over chains inside the anneal scan.  All updates
    are entry-wise (iota-compare selects, no [P]-wide temporaries and no
    vmapped scalar scatters, which serialize on XLA CPU)."""
    _, idx, om2, th2, lm2, _ = _move_core(problem, aux, Xf, omega, theta,
                                          lam, j, p_new)
    delta = _delta_objective(problem, omega, theta, lam, idx, om2, th2, lm2)
    Xf2 = jnp.where(jnp.arange(Xf.shape[0]) == j, p_new, Xf)
    omega2 = _commit_entries(omega, idx, om2)
    theta2 = _commit_entries(theta, idx, th2)
    return Xf2, omega2, theta2, lm2, obj + delta, delta


def _anneal_proposals(key: jax.Array, aux: PlacementAux, n_steps: int,
                      n_chains: int, P: int, V: Optional[int] = None,
                      cnt: Optional[np.ndarray] = None,
                      cand: Optional[np.ndarray] = None):
    """Free-position Metropolis proposals: flat VM index, destination, u.

    Pinned input VMs are never proposed (their placement is fixed by
    Eq. 4), so every step is a real move.  With ``cnt``/``cand`` (an
    eligibility table from ``_eligible_np``), destinations are sampled
    from the proposed VM's row-eligible set only -- the single proposal
    stream every anneal backend (delta scan, fused Pallas kernel, legacy
    full-objective) consumes, so SLA masking is enforced identically in
    all of them."""
    kf, kp, ka = jax.random.split(key, 3)
    M = aux.free_pos.shape[0]
    fi = jax.random.randint(kf, (n_steps, n_chains), 0, M)
    if cnt is None:
        p_prop = jax.random.randint(kp, (n_steps, n_chains), 0, P, jnp.int32)
    else:
        # masked branch draws from a fold_in-derived stream: independent of
        # the unmasked randint above, and the unmasked path stays
        # byte-identical (CFN106: one key, one draw)
        rows = aux.free_flat[fi] // V
        u_dst = jax.random.uniform(jax.random.fold_in(kp, 1),
                                   (n_steps, n_chains))
        p_prop = _sample_eligible(u_dst, rows, jnp.asarray(cnt),
                                  jnp.asarray(cand))
    u = jax.random.uniform(ka, (n_steps, n_chains))
    return fi, p_prop, u


def auto_backend(platform: str, P: int, N: int, J: int, D: int,
                 T: int) -> str:
    """The anneal backend ``"auto"`` resolves to: the fused Pallas kernel on
    a TPU when a problem of P processing nodes, N network nodes, J VMs and
    incidence degree D, annealed for T steps, fits the kernel's VMEM
    (``kpp.fused_fits``), else the delta scan.  Both are compiled device
    code; this is a size rule."""
    if platform == "tpu" and kpp.fused_fits(P, N, J, D, T):
        return "fused"
    return "delta"


def _ladder(t0, decay, n_steps: int) -> jnp.ndarray:
    """Geometric temperature ladder: ``t0`` down to ``t0 * decay`` over
    ``n_steps`` steps.  Callers take ``decay = t1 / t0`` on the host, in
    double precision, and the barrier hides the divisor's value from XLA,
    which would otherwise multiply by its rounded reciprocal: so the
    ladder rounds as ``t0 * (t1 / t0) ** (arange(n_steps) / (n_steps -
    1))`` does op by op."""
    last = jax.lax.optimization_barrier(jnp.float32(max(1, n_steps - 1)))
    return t0 * decay ** (jnp.arange(n_steps) / last)


def _finish(problem: PlacementProblem, X):
    """The pinned placement and its breakdown: what ``_result`` computes,
    traced inside an anneal program so its read is the program's one."""
    X = apply_pins(problem, X)
    return X, evaluate(problem, X)


def _anneal_inputs(problem: PlacementProblem, aux: PlacementAux,
                   key: jax.Array, X, t0, decay, n_steps: int,
                   n_chains: int, cnt=None, cand=None):
    """``anneal``'s chains and proposal stream, traced: chain 0 at the
    warm start ``X``, the rest restarted at random (from each row's
    eligible set when ``cnt``/``cand`` are given), pins applied, then the
    ``_anneal_proposals`` stream with its flat VM indices gathered, and
    the temperature ladder.  Returns ``(Xc, j_prop, p_prop, u_prop,
    temps)``, proposals ``[n_steps, n_chains]``."""
    R, V, P = problem.R, problem.V, problem.P
    k_init, k_prop = jax.random.split(key)
    X = apply_pins(problem, X)
    if cnt is None:
        rand = jax.random.randint(k_init, (n_chains, R, V), 0, P, jnp.int32)
    else:
        # restarted chains must also start on eligible nodes (fold_in:
        # independent of the unmasked randint, which stays byte-identical)
        u_r = jax.random.uniform(jax.random.fold_in(k_init, 1),
                                 (n_chains, R, V))
        rand = _sample_eligible(u_r, jnp.arange(R)[None, :, None], cnt, cand)
    keep = (jnp.arange(n_chains) == 0)[:, None, None]
    Xc = jax.vmap(lambda x: apply_pins(problem, x))(jnp.where(keep, X, rand))
    fi, p_prop, u_prop = _anneal_proposals(k_prop, aux, n_steps, n_chains, P,
                                           V=V, cnt=cnt, cand=cand)
    return (Xc, aux.free_flat[fi], p_prop, u_prop,
            _ladder(t0, decay, n_steps))


@functools.partial(jax.jit,
                   static_argnames=("n_steps", "n_chains", "backend"))
@count_traces("anneal_program")
def _anneal_program(problem: PlacementProblem, aux: PlacementAux,
                    key: jax.Array, X, t0, decay, cnt=None, cand=None, *,
                    n_steps: int, n_chains: int, backend: str):
    """One ``anneal`` call as one device program.

    ``backend="delta"`` / ``"full"``: chains, proposals, the scan, the
    best chain and its breakdown; returns ``(X, breakdown, sampled
    history, (best_obj, accept_rate))``, the history sampled every
    ``max(1, n_steps // 50)`` steps.  ``backend="fused"``: the kernel's
    inputs ``(Xc, j_prop.T, p_prop.T, u_prop.T, temps)`` -- the kernel
    stays its own launch (``kops.fused_anneal``) and ``_fused_pick`` takes
    its best chain."""
    Xc, j_prop, p_prop, u_prop, temps = _anneal_inputs(
        problem, aux, key, X, t0, decay, n_steps, n_chains, cnt, cand)
    if backend == "fused":
        return Xc, j_prop.T, p_prop.T, u_prop.T, temps
    if backend == "full":
        bX, _, hist = _anneal_scan_full(problem, Xc, j_prop, p_prop, u_prop,
                                        temps)
    else:
        bX, _, hist = _anneal_scan_delta(problem, aux, Xc, j_prop, p_prop,
                                         u_prop, temps)
    X, bd = _finish(problem, bX)
    return X, bd, hist[0][:: max(1, n_steps // 50)], hist


@jax.jit
@count_traces("fused_pick")
def _fused_pick(problem: PlacementProblem, bXc, stats):
    """The fused kernel's best chain, pinned, its breakdown and the
    kernel's own best objective for it."""
    k = jnp.argmin(stats[:, 0])
    X, bd = _finish(problem, bXc[k])
    return X, bd, stats[k, 0]


def anneal(problem: PlacementProblem, key: jax.Array, X0: np.ndarray,
           n_chains: int = 32, n_steps: int = 4000,
           t0: float = 50.0, t1: float = 0.05,
           backend: str = "auto",
           eligible: Optional[np.ndarray] = None,
           record_conv: bool = False,
           telemetry=None,
           aux: Optional[PlacementAux] = None) -> SolveResult:
    """Batched Metropolis chains on incremental (delta-evaluated) state.

    backend:
      * ``"delta"`` -- pure-JAX scan; per-chain loads updated in
        O(deg*N + P) per step (no full objective per proposal).
      * ``"fused"`` -- the Pallas kernel in kernels/placement_power: chain
        state stays resident in VMEM and proposal -> delta-eval -> accept is
        fused across all steps in ONE kernel launch.
      * ``"full"``  -- legacy full `objective_batch` per step (kept as the
        benchmark baseline).
      * ``"auto"``  -- ``auto_backend``: fused on a TPU where the kernel
        fits its VMEM (``kernels.placement_power.fused_fits``), delta
        elsewhere.

    ``eligible`` [R, P] (optional) restricts each service row's destination
    nodes: the warm start is projected onto the mask, restart chains are
    sampled from it, and every backend's proposal destinations are drawn
    from it (one proposal stream feeds all three), so no chain ever leaves
    the constraint set.

    ``record_conv=True`` attaches the per-step convergence trace to the
    result (``SolveResult.conv``: best-objective + acceptance-rate arrays,
    length ``n_steps`` -- fixed per effort bucket).  The jitted scans
    always COMPUTE the trace; the flag only materializes it host-side, so
    recording can never retrace (CFN108).  Not available on the fused
    Pallas backend (chain state stays in VMEM).

    The chains' start, the proposal draws, the chains and the best
    chain's breakdown run as one device program (``_anneal_program``),
    read back once; the fused backend launches its kernel between that
    program and ``_fused_pick``.  ``telemetry`` (optional) counts the
    programs launched: ``solver.anneal_programs{site=portfolio}``.
    ``aux`` (optional) is the problem's ``build_aux``, where the caller
    has it.
    """
    R, V, P = problem.R, problem.V, problem.P
    aux = build_aux(problem) if aux is None else aux
    if backend == "auto":
        backend = auto_backend(jax.default_backend(), P, problem.N, R * V,
                               aux.inc_h.shape[1], n_steps)
    if backend not in ("delta", "fused", "full"):
        raise ValueError(f"unknown anneal backend {backend!r}")
    if aux.free_pos.shape[0] == 0:
        # every VM is pinned (e.g. single-VM VSRs): nothing to anneal
        return _result(problem, jnp.asarray(X0, jnp.int32), "anneal")
    el_np, cnt_np, cand_np = _eligible_np(eligible)
    X = np.asarray(X0, np.int32)
    if el_np is not None:
        X, _ = _project_eligible(
            problem, apply_pins(problem, jnp.asarray(X)), el_np)
    out = _anneal_program(problem, aux, key, X, t0, t1 / t0, cnt_np,
                          cand_np, n_steps=n_steps, n_chains=n_chains,
                          backend=backend)
    if backend == "fused":
        from ..kernels import ops as kops
        el_j = None if el_np is None else jnp.asarray(el_np)
        bXc, stats = kops.fused_anneal(problem, aux, *out, eligible=el_j)
        Xb, bd, best = jax.device_get(_fused_pick(problem, bXc, stats))
        if telemetry is not None:
            telemetry.inc("solver.anneal_programs", 3, site="portfolio",
                          backend=backend)
        return SolveResult(X=Xb, breakdown=bd, method="anneal(fused)",
                           history=[float(best)])
    Xb, bd, hist_s, hist = out
    Xb, bd, hist_s, hist = jax.device_get(
        (Xb, bd, hist_s, hist if record_conv else None))
    if telemetry is not None:
        telemetry.inc("solver.anneal_programs", site="portfolio",
                      backend=backend)
    tag = "anneal" if backend == "delta" else f"anneal({backend})"
    res = SolveResult(X=Xb, breakdown=bd, method=tag,
                      history=[float(h) for h in hist_s])
    if record_conv:
        res.conv = {"best_obj": hist[0], "accept_rate": hist[1]}
    return res


@jax.jit
@count_traces("anneal_delta")
def _anneal_scan_delta(problem: PlacementProblem, aux: PlacementAux,
                       Xc, j_prop, p_prop, u_prop, temps):
    """Metropolis chains on incremental per-chain load state (module-level
    jit: compiles once per problem/chain/step shape, not per solve)."""
    n_chains, R, V = Xc.shape
    Xf = Xc.reshape(n_chains, -1)
    omega, theta, lam, obj = batched_hard_loads(problem, Xc)

    step_fn = jax.vmap(
        lambda Xf, om, th, lm, ob, j, pn: _chain_step(
            problem, aux, Xf, om, th, lm, ob, j, pn))

    def step(carry, inp):
        Xf, omega, theta, lam, obj, bX, bobj = carry
        j, pn, u, T = inp
        Xf2, om2, th2, lm2, obj2, delta = step_fn(
            Xf, omega, theta, lam, obj, j, pn)
        acc = (delta < 0) | (u < jnp.exp(-jnp.maximum(delta, 0.0) / T))
        a1 = acc[:, None]
        Xf = jnp.where(a1, Xf2, Xf)
        omega = jnp.where(a1, om2, omega)
        theta = jnp.where(a1, th2, theta)
        lam = jnp.where(a1, lm2, lam)
        obj = jnp.where(acc, obj2, obj)
        better = obj < bobj
        bX = jnp.where(better[:, None], Xf, bX)
        bobj = jnp.where(better, obj, bobj)
        # per-step convergence trace: incumbent best + acceptance fraction
        # (unconditional outputs -- emitting both keeps the jit cache
        # key-space identical whether or not a caller records them)
        return (Xf, omega, theta, lam, obj, bX, bobj), \
            (bobj.min(), acc.mean())

    init = (Xf, omega, theta, lam, obj, Xf, obj)
    (_, _, _, _, _, bX, bobj), hist = jax.lax.scan(
        step, init, (j_prop, p_prop, u_prop, temps))
    k = jnp.argmin(bobj)
    return bX[k].reshape(R, V), bobj[k], hist


@jax.jit
@count_traces("anneal_full")
def _anneal_scan_full(problem: PlacementProblem, Xc, j_prop, p_prop,
                      u_prop, temps):
    """Legacy annealing: one full batched objective per Metropolis step.

    Kept as the benchmark baseline the delta/fused paths are measured
    against (benchmarks/kernel_bench.py)."""
    n_chains, R, V = Xc.shape
    obj0 = objective_batch(problem, Xc)

    def step(carry, inp):
        Xc, obj, bX, bobj = carry
        j, p, u, T = inp
        ci = jnp.arange(n_chains)
        Xp = Xc.reshape(n_chains, -1).at[ci, j].set(p).reshape(Xc.shape)
        objp = objective_batch(problem, Xp)
        acc = (objp < obj) | (u < jnp.exp(-(objp - obj) / T))
        Xc = jnp.where(acc[:, None, None], Xp, Xc)
        obj = jnp.where(acc, objp, obj)
        better = obj < bobj
        bX = jnp.where(better[:, None, None], Xc, bX)
        bobj = jnp.where(better, obj, bobj)
        return (Xc, obj, bX, bobj), (bobj.min(), acc.mean())

    init = (Xc, obj0, Xc, obj0)
    (_, _, bX, bobj), hist = jax.lax.scan(
        step, init, (j_prop, p_prop, u_prop, temps))
    k = jnp.argmin(bobj)
    return bX[k], bobj[k], hist


# ---------------------------------------------------------------------------
# Genetic search
# ---------------------------------------------------------------------------

def genetic(problem: PlacementProblem, key: jax.Array, X0: np.ndarray,
            pop: int = 64, gens: int = 300, p_mut: float = 0.08,
            eligible: Optional[np.ndarray] = None) -> SolveResult:
    """Population search.  ``eligible`` [R, P] (optional): the elite is
    projected onto the mask, the initial population and every mutation are
    sampled from it, and crossover swaps whole service rows between two
    eligible parents -- so every individual ever evaluated is eligible."""
    R, V, P = problem.R, problem.V, problem.P
    el_np, cnt_np, cand_np = _eligible_np(eligible)
    k_init, k_scan = jax.random.split(key)
    elite = jnp.asarray(X0, jnp.int32)
    if el_np is None:
        Xp = jax.random.randint(k_init, (pop, R, V), 0, P, jnp.int32)
        cnt_j = cand_j = None
    else:
        elite, _ = _project_eligible(problem, elite, el_np)
        cnt_j, cand_j = jnp.asarray(cnt_np), jnp.asarray(cand_np)
        u0 = jax.random.uniform(jax.random.fold_in(k_init, 1), (pop, R, V))
        Xp = _sample_eligible(u0, jnp.arange(R)[None, :, None],
                              cnt_j, cand_j)
    Xp = Xp.at[0].set(elite)

    @jax.jit
    def run(Xp, keys):
        def gen(Xp, k):
            k1, k2, k3, k4 = jax.random.split(k, 4)
            fit = objective_batch(problem, Xp)
            # tournament selection
            a = jax.random.randint(k1, (pop,), 0, pop)
            b = jax.random.randint(k2, (pop,), 0, pop)
            parents = jnp.where((fit[a] < fit[b])[:, None, None], Xp[a], Xp[b])
            # per-VSR uniform crossover with a shifted copy
            mask = jax.random.bernoulli(k3, 0.5, (pop, R))[:, :, None]
            mates = jnp.roll(parents, 1, axis=0)
            children = jnp.where(mask, parents, mates)
            # mutation (masked: drawn from each row's eligible set)
            km1, km2 = jax.random.split(k4)
            mut = jax.random.bernoulli(km1, p_mut, (pop, R, V))
            if cnt_j is None:
                rnd = jax.random.randint(km2, (pop, R, V), 0, P, jnp.int32)
            else:
                u_m = jax.random.uniform(jax.random.fold_in(km2, 1),
                                         (pop, R, V))
                rnd = _sample_eligible(u_m, jnp.arange(R)[None, :, None],
                                       cnt_j, cand_j)
            children = jnp.where(mut, rnd, children)
            # elitism: keep the best individual
            best = jnp.argmin(fit)
            children = children.at[0].set(Xp[best])
            return children, fit[best]

        Xp, hist = jax.lax.scan(gen, Xp, keys)
        fit = objective_batch(problem, Xp)
        k = jnp.argmin(fit)
        return Xp[k], fit[k], hist

    bX, bobj, hist = run(Xp, jax.random.split(k_scan, gens))
    return _result(problem, np.asarray(bX), "genetic",
                   [float(h) for h in np.asarray(hist[:: max(1, gens // 50)])])


# ---------------------------------------------------------------------------
# Differentiable relaxation (beyond-paper)
# ---------------------------------------------------------------------------

def relax(problem: PlacementProblem, key: jax.Array,
          steps: int = 800, lr: float = 0.3,
          temp0: float = 5.0, temp1: float = 0.05,
          eligible: Optional[np.ndarray] = None) -> SolveResult:
    """Soft placement: logits -> softmax assignment, smooth power surrogate,
    Adam descent with annealed temperature, then argmax + coordinate repair.
    ``eligible`` [R, P] (optional) pins ineligible nodes' logits to -inf in
    the softmax (zero probability mass) and masks the final repair."""
    R, V, P = problem.R, problem.V, problem.P
    logits = 0.01 * jax.random.normal(key, (R, V, P))
    el_np, _, _ = _eligible_np(eligible)
    bias = (0.0 if el_np is None
            else jnp.where(jnp.asarray(el_np)[:, None, :], 0.0, -1e9))

    def loss_fn(logits, temp):
        logits = logits + bias
        soft = jax.nn.softmax(logits / jnp.maximum(temp, 1e-3), axis=-1)
        bd = evaluate(problem, soft, hard=False, temp=temp)
        # entropy push towards one-hot as temp decays
        ent = -jnp.sum(soft * jnp.log(soft + 1e-9), axis=-1).mean()
        return bd.total + 10.0 * PENALTY_W * bd.violation + 0.1 * ent

    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    m = jnp.zeros_like(logits)
    v = jnp.zeros_like(logits)
    b1, b2, eps = 0.9, 0.999, 1e-8
    history = []
    for i in range(steps):
        temp = temp0 * (temp1 / temp0) ** (i / max(1, steps - 1))
        loss, g = grad_fn(logits, temp)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mh = m / (1 - b1 ** (i + 1))
        vh = v / (1 - b2 ** (i + 1))
        logits = logits - lr * mh / (jnp.sqrt(vh) + eps)
        if i % max(1, steps // 40) == 0:
            history.append(float(loss))
    X = np.asarray(jnp.argmax(logits + bias, axis=-1), np.int32)
    res = coordinate(problem, X, max_sweeps=4, eligible=eligible)
    return SolveResult(X=res.X, breakdown=res.breakdown, method="relax",
                       history=history + res.history)


PENALTY_W = 100.0  # relative weight of violation in the relaxed loss


# ---------------------------------------------------------------------------
# Online incremental re-embedding (service churn)
# ---------------------------------------------------------------------------

def _pad_positions(pos: np.ndarray, m: Optional[int]) -> np.ndarray:
    """Pad a free-position list to a fixed length by repeating the first row
    (shape bucketing: a repeated sweep position is a harmless re-sweep, and
    a fixed length keeps the jitted ``_sweep`` scan on one compiled shape
    per bucket)."""
    if m is None or pos.shape[0] == 0 or pos.shape[0] >= m:
        return pos
    return np.concatenate(
        [pos, np.tile(pos[:1], (m - pos.shape[0], 1))])


def _resolve_anneal_inputs(key: jax.Array, target, n_target, X, t0, decay,
                           n_steps: int, n_chains: int, P: int,
                           cnt=None, cand=None):
    """The re-solve's Metropolis chains and proposals, traced: proposals
    range over the first ``n_target`` rows of the ``target`` positions
    ``[L, 2]`` (duplicates from bucket padding are a harmless bias; rows
    past ``n_target`` repeat the first and are never drawn),
    destinations from each row's eligible set when ``cnt``/``cand`` are
    given; chain 0 stays at ``X``, the rest restart at the target
    positions.  Returns ``(Xc, j_prop, p_prop, u_prop, temps)``,
    proposals ``[n_steps, n_chains]``."""
    R, V = X.shape
    shape = (n_steps, n_chains)
    flat = target[:, 0] * V + target[:, 1]
    kf, kp, ka, kx = jax.random.split(key, 4)
    j_prop = flat[jax.random.randint(kf, shape, 0, n_target)]
    if cnt is None:
        p_prop = jax.random.randint(kp, shape, 0, P, jnp.int32)
    else:
        # destinations sampled from each proposal row's eligible set
        # (fold_in: the unmasked randint stream stays byte-identical)
        u_dst = jax.random.uniform(jax.random.fold_in(kp, 1), shape)
        p_prop = _sample_eligible(u_dst, j_prop // V, cnt, cand)
    u_prop = jax.random.uniform(ka, shape)
    if cnt is None:
        rand = jax.random.randint(kx, (n_chains, R, V), 0, P, jnp.int32)
    else:
        # restarted chains must also start on eligible nodes
        u_r = jax.random.uniform(jax.random.fold_in(kx, 1), (n_chains, R, V))
        rand = _sample_eligible(u_r, jnp.arange(R)[None, :, None], cnt, cand)
    tgt = jnp.zeros((R, V), bool).at[target[:, 0], target[:, 1]].set(True)
    keep = (jnp.arange(n_chains) == 0)[:, None, None] | ~tgt[None]
    Xc = jnp.where(keep, X, rand)
    return Xc, j_prop, p_prop, u_prop, _ladder(t0, decay, n_steps)


@functools.partial(jax.jit, static_argnames=("n_steps", "n_chains"))
@count_traces("resolve_anneal")
def _resolve_anneal(problem: PlacementProblem, aux: PlacementAux,
                    key: jax.Array, target, n_target, X, t0, decay,
                    cnt=None, cand=None, *, n_steps: int, n_chains: int):
    """``resolve_incremental``'s phase 2 as one device program: the
    proposals and chains of ``_resolve_anneal_inputs`` run through
    ``_anneal_scan_delta``.  ``target`` is padded to a length fixed per
    problem shape and ``n_target`` is traced, so every wave bucket runs
    the one compiled program.  Returns the best chain's placement and the
    per-step ``(best_obj, accept_rate)`` trace."""
    Xc, j_prop, p_prop, u_prop, temps = _resolve_anneal_inputs(
        key, target, n_target, X, t0, decay, n_steps, n_chains, problem.P,
        cnt, cand)
    bX, _, hist = _anneal_scan_delta(problem, aux, Xc, j_prop, p_prop,
                                     u_prop, temps)
    return bX, hist


def resolve_incremental(problem: PlacementProblem,
                        prev_X: Optional[np.ndarray] = None,
                        key: Optional[jax.Array] = None,
                        changed_rows: Optional[Sequence[int]] = None,
                        state: Optional[PlacementState] = None,
                        sweeps: Optional[int] = None,
                        anneal_steps: Optional[int] = None,
                        anneal_chains: Optional[int] = None,
                        anneal_t0: Optional[float] = None,
                        anneal_t1: Optional[float] = None,
                        polish_sweeps: Optional[int] = None,
                        eligible: Optional[np.ndarray] = None,
                        pad_positions_to: Optional[int] = None,
                        pad_changed_to: Optional[int] = None,
                        spec=None,
                        record_conv: bool = False,
                        telemetry=None) -> SolveResult:
    """Warm-start re-solve after service churn: surviving services stay at
    their previous nodes, only the VMs of ``changed_rows`` (new arrivals /
    rows the caller distrusts) are actively re-placed.

    Three phases, all on the delta engine:
      1. targeted coordinate sweeps over the changed rows' free VMs
         (survivors act as implicit pins -- their positions are never swept);
      2. a short Metropolis refinement: with changed rows, proposals touch
         ONLY those VMs (chains randomized there escape the greedy local
         minimum); without them (a departure), proposals range over ALL
         free VMs with random-restart chains, re-packing survivors;
      3. ``polish_sweeps`` full sweeps over ALL free VMs (monotone).

    ``spec`` (a ``repro.api.PlacementSpec``, optional) supplies the solver
    knobs and -- unless ``eligible`` is passed explicitly -- the constraint
    masks via ``spec.masks(problem)``; explicit keyword arguments override
    the spec.  ``eligible`` [R, P] bool restricts each row's destination
    nodes through every phase (sweep argmins are masked; Metropolis
    destinations are sampled from each row's eligible set).
    ``pad_positions_to`` pads the all-free-VM sweep lists to a fixed length
    so the jitted sweep compiles once per shape bucket
    (core.dynamic.OnlineEmbedder); ``pad_changed_to`` does the same for the
    changed-rows position list (the wave axis -- see ``resolve_wave``).

    This is LOCAL re-optimization -- a periodic full-portfolio defrag
    (`solve_portfolio`) bounds its drift; see core.dynamic.OnlineEmbedder.

    Warm callers that already carry a ``state`` (``power.warm_state``)
    should pass ``prev_X=None``: the previous placement is only read when
    ``state`` is absent, and materializing ``np.asarray(state.X)`` just to
    fill the argument is a dead device->host transfer per churn event.

    ``telemetry`` (a ``repro.telemetry.Telemetry``, optional) spans the
    phases: ``resolve.prep`` / ``targeted`` / ``anneal`` / ``select`` /
    ``polish`` / ``result``.
    """
    pick = lambda v, sv, d: (v if v is not None
                             else (sv if sv is not None else d))
    sweeps = pick(sweeps, getattr(spec, "sweeps", None), 2)
    anneal_steps = pick(anneal_steps, getattr(spec, "anneal_steps", None), 600)
    anneal_chains = pick(anneal_chains,
                         getattr(spec, "anneal_chains", None), 8)
    anneal_t0 = pick(anneal_t0, getattr(spec, "anneal_t0", None), 5.0)
    anneal_t1 = pick(anneal_t1, getattr(spec, "anneal_t1", None), 0.05)
    polish_sweeps = pick(polish_sweeps,
                         getattr(spec, "polish_sweeps", None), 2)
    key = jax.random.PRNGKey(0) if key is None else key
    tel = telemetry
    with span(tel, "resolve.prep"):
        if eligible is None and spec is not None:
            eligible = spec.masks(problem)
        aux = build_aux(problem)
        if state is None:
            if prev_X is None:
                raise ValueError("resolve_incremental needs prev_X or state")
            state = init_state(problem, jnp.asarray(prev_X, jnp.int32))
        # else: the caller-carried state (power.warm_state) is trusted
        # as-is -- that's the O(V*(N+P)) event path; candidates are
        # re-scored exactly below, so carried float32 load drift cannot
        # corrupt the result
        changed_rows = [] if changed_rows is None else list(changed_rows)
        free = np.asarray(aux.free_pos)
        if free.shape[0] == 0:  # everything pinned: nothing to re-place
            return _result(problem, state.X, "incremental")
        el_np, cnt_np, cand_np = _eligible_np(eligible)
        el_j = None if el_np is None else jnp.asarray(el_np)
        if el_np is not None:
            # the warm incumbent may predate the mask (a substrate fault
            # can arrive after placement): project it first, so a
            # mask-violating placement can never win the exact-objective
            # argmin below
            X0, moved = _project_eligible(problem, state.X, el_np)
            if moved:
                state = init_state(problem, apply_pins(problem, X0))
        cands = [state.X]
        pos_changed = free[np.isin(free[:, 0], changed_rows)]
        # wave axis bucketing: pad the changed-position list so the
        # targeted sweep (and the Metropolis target set below -- duplicate
        # targets are a harmless proposal bias) compiles once per
        # wave-shape bucket
        pos_changed = _pad_positions(pos_changed, pad_changed_to)

    # phase 1: greedy placement of the changed VMs
    if pos_changed.shape[0]:
        with span(tel, "resolve.targeted"):
            pc = jnp.asarray(pos_changed)
            for _ in range(max(1, sweeps)):
                state, _ = _sweep(problem, aux, state, pc, el_j)
            cands.append(state.X)

    # phase 2: short Metropolis refinement
    hist = None
    if anneal_steps > 0 and anneal_chains > 0:
        with span(tel, "resolve.anneal"):
            # the changed positions, else every free VM; padded past the
            # largest wave bucket, so no wave size compiles a new program
            target = pos_changed if pos_changed.shape[0] else free
            pad = max(_pow2(problem.R * problem.V), target.shape[0])
            bX, hist = _resolve_anneal(
                problem, aux, key, _pad_positions(target, pad),
                target.shape[0], state.X, anneal_t0, anneal_t1 / anneal_t0,
                cnt_np, cand_np, n_steps=anneal_steps,
                n_chains=anneal_chains)
            if tel is not None:
                tel.inc("solver.anneal_programs", site="resolve",
                        backend="delta")
            cands.append(bX)

    # pick the exact-objective best (one batched call), then polish
    with span(tel, "resolve.select"):
        objs = [float(o) for o in
                objective_batch(problem, jnp.stack(cands))]
        k = int(np.argmin(objs))
        best_obj, best_X = objs[k], cands[k]
        history: List[float] = objs + [best_obj]
    if polish_sweeps > 0:
        with span(tel, "resolve.polish"):
            state = init_state(problem, best_X)
            pa = jnp.asarray(_pad_positions(free, pad_positions_to))
            for _ in range(polish_sweeps):
                state, _ = _sweep(problem, aux, state, pa, el_j)
            obj = float(objective(problem, state.X))
            if obj < best_obj:
                best_obj, best_X = obj, state.X
            history.append(best_obj)
    with span(tel, "resolve.result"):
        res = _result(problem, best_X, "incremental", history)
        if record_conv and hist is not None:
            # fixed length anneal_steps (static per effort bucket): the
            # telemetry plane's quality-vs-steps trace for this solve, read
            # after the objectives, so the anneal has finished and the
            # read adds no wait
            res.conv = {"best_obj": np.asarray(hist[0]),
                        "accept_rate": np.asarray(hist[1])}
    return res


def resolve_wave(problem: PlacementProblem,
                 state: PlacementState,
                 changed_rows: Sequence[int],
                 key: Optional[jax.Array] = None,
                 pad_changed_to: Optional[int] = None,
                 spec=None, **kw) -> SolveResult:
    """Wave-batched incremental re-solve: ONE warm-start pass over a whole
    churn wave instead of one per event.

    The caller gathers a tick's arrivals/departures, applies
    ``power.detach_vsrs`` / the batch concat as one fused state update and
    builds ONE ``power.warm_state`` (``changed_rows`` = the arrival rows;
    departures need no changed rows -- survivors re-pack exactly as in the
    per-event remove path).  This then runs the three
    ``resolve_incremental`` phases once for the whole wave: targeted sweeps
    over every changed row's free VMs, ONE restricted Metropolis refinement
    whose proposals range over the union of changed positions, and a single
    full-polish pass -- the polish that dominates per-event latency is paid
    once per wave.

    Compile-shape hygiene (the region-axis trick of
    ``federation.solve_portfolio_batched``): the changed-position list is
    padded to a power-of-two bucket (``pad_changed_to``; default
    ``_pow2`` of the wave's free-position count), so the jitted ``_sweep``
    / ``_anneal_scan_delta`` kernels -- both ``@count_traces``-covered --
    compile once per wave-shape bucket, not once per wave size.
    """
    changed_rows = list(changed_rows)
    if pad_changed_to is None and changed_rows:
        fixed = np.asarray(problem.fixed_mask)[changed_rows]
        n_pos = int((~fixed).sum())
        if n_pos:
            pad_changed_to = _pow2(n_pos)
    res = resolve_incremental(problem, key=key, changed_rows=changed_rows,
                              state=state, spec=spec,
                              pad_changed_to=pad_changed_to, **kw)
    return SolveResult(X=res.X, breakdown=res.breakdown, method="wave",
                       history=res.history, conv=res.conv)


# ---------------------------------------------------------------------------
# Portfolio solver: the "CFN (MILP)" stand-in
# ---------------------------------------------------------------------------

def solve_portfolio(problem: PlacementProblem, topo: CFNTopology,
                    spec=None, key: Optional[jax.Array] = None,
                    eligible: Optional[np.ndarray] = None,
                    telemetry=None) -> SolveResult:
    """Best-of portfolio driven by a ``repro.api.PlacementSpec``: effort
    tier, anneal backend, and constraint masks all come from the spec
    (``eligible`` overrides ``spec.masks(problem)`` when given explicitly),
    so a full-portfolio solve -- including the online engine's defrag --
    enforces exactly the constraint set every other path enforces.

    On instances small enough for `exhaustive` the unconstrained portfolio
    is provably optimal; tests pin it to the exhaustive optimum.

    ``telemetry`` (optional) spans ``portfolio.warm_starts`` (both
    coordinate descents, one program) and ``portfolio.anneal``, and
    counts ``solver.warm_start_programs{site=portfolio}`` and the sweeps
    run, ``solver.coordinate_sweeps{site=portfolio}``.
    """
    key = jax.random.PRNGKey(0) if key is None else key
    effort = getattr(spec, "effort", "standard")
    backend = getattr(spec, "backend", "auto")
    if eligible is None and spec is not None:
        eligible = spec.masks(problem)
    cdc = topo.layer_indices("cdc")[0]
    # warm starts: CDC-everything and IoT-first-fit, both descended in one
    # program (the masked sweeps move both onto the eligible set in their
    # first pass)
    with span(telemetry, "portfolio.warm_starts"):
        aux = build_aux(problem)
        base_cdc = np.full((problem.R, problem.V), cdc, dtype=np.int32)
        layers, cap = _layer_inputs(topo, "iot", "cdc")
        candidates = _warm_starts(problem, aux, base_cdc, eligible, layers,
                                  cap, (12, 12))  # coordinate's max_sweeps
        if telemetry is not None:
            telemetry.inc("solver.warm_start_programs", site="portfolio")
            telemetry.inc("solver.coordinate_sweeps",
                          sum(len(c.history) for c in candidates),
                          site="portfolio")
    if effort in ("standard", "high"):
        k1, k2 = jax.random.split(key)
        warm = min(candidates, key=lambda r: r.objective).X
        n_steps = 4000 if effort == "standard" else 12000
        with span(telemetry, "portfolio.anneal"):
            candidates.append(anneal(problem, k1, warm, n_steps=n_steps,
                                     backend=backend, eligible=eligible,
                                     telemetry=telemetry, aux=aux))
        if effort == "high":
            candidates.append(genetic(problem, k2, warm, eligible=eligible))
    best = min(candidates, key=lambda r: r.objective)
    return SolveResult(X=best.X, breakdown=best.breakdown,
                       method=f"cfn-milp({best.method})", history=best.history)


def _pow2(n: int, lo: int = 2) -> int:
    """Next power-of-two bucket >= max(n, 1) (compile-shape hygiene): the
    ONE bucketing policy, shared by the online engine's row/column padding
    (``core.dynamic._bucket_rows``) and the federated batch path
    (``core.federation.solve_portfolio_batched``)."""
    n = max(n, 1)
    b = lo
    while b < n:
        b *= 2
    return b


# The batched (federated) portfolio -- stack_problems / stack_auxes /
# solve_portfolio_batched, the vmapped-over-regions lift of the jitted
# primitives above -- lives in core.federation, its only consumer.  Lazy
# aliases keep the old ``solvers.solve_portfolio_batched`` imports working.
_FEDERATION_MOVED = ("solve_portfolio_batched", "stack_problems",
                     "stack_auxes", "_pad_links", "_solve_regions_impl",
                     "_solve_regions_jit", "_BATCH_EFFORT")


def __getattr__(name: str):
    if name in _FEDERATION_MOVED:
        from . import federation
        return getattr(federation, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def solve_cfn(problem: PlacementProblem, topo: CFNTopology,
              key: Optional[jax.Array] = None,
              effort: str = "standard") -> SolveResult:
    """Deprecated shim: constructs a ``PlacementSpec`` and routes through
    ``solve_portfolio`` (use ``repro.api.CFNSession`` / ``solve_portfolio``
    directly).  Results are identical to the pre-spec portfolio."""
    from . import api
    warnings.warn(
        "solve_cfn() is deprecated; build a repro.api.PlacementSpec and "
        "call solve_portfolio() (or use repro.api.CFNSession)",
        DeprecationWarning, stacklevel=2)
    return solve_portfolio(problem, topo, api.PlacementSpec(effort=effort),
                           key)


def repair_to_eligible(problem: PlacementProblem, res: SolveResult,
                       eligible: np.ndarray) -> SolveResult:
    """Force a solved placement onto an [R, P] eligibility mask.

    Free VMs already inside their row's eligible set are untouched; each
    violator is moved to its masked ``delta_sweep`` argmin (live state kept
    consistent so later repairs see earlier ones).  The safety net that
    makes every ``spec.masks`` consumer -- including solvers with no native
    masking, like the fixed-layer baselines -- end on an eligible
    placement.  A no-op (the input result is returned as-is, history and
    all) when nothing violates.
    """
    el_np, _, _ = _eligible_np(eligible)
    X = np.asarray(res.X).copy()
    fixed = np.asarray(problem.fixed_mask)
    rows = np.arange(X.shape[0])[:, None]
    if not np.any(~el_np[rows, X] & ~fixed):
        return res
    aux = build_aux(problem)
    state = init_state(problem, jnp.asarray(X))
    for r in range(X.shape[0]):
        mask_r = jnp.asarray(el_np[r])
        for v in range(X.shape[1]):
            if fixed[r, v] or el_np[r, X[r, v]]:
                continue
            obj_all = delta_sweep(problem, aux, state, r, v)
            best = int(jnp.argmin(jnp.where(mask_r, obj_all, jnp.inf)))
            state = apply_move(problem, aux, state, r, v, best)
            X[r, v] = best
    return _result(problem, X, res.method, res.history)
