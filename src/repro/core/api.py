"""Unified placement API: one declarative constraint object, one session.

The paper's MILP is a single optimization with one constraint set (Eq. 1/2
power, hop/latency bounds, capacity).  The repo grew five entry points --
``solve_cfn``, ``embed``, ``embed_latency_bounded``, ``resolve_incremental``
and ``OnlineEmbedder``/``EnergyAwareScheduler`` -- each threading SLA masks,
pinning, budgets and portfolio knobs through different ad-hoc kwargs, which
is exactly how the defrag-ignores-``max_hops`` hole crept in.  This module
replaces the kwarg sprawl with two objects:

  * **PlacementSpec** -- a frozen, declarative bundle of everything that
    constrains or configures a solve: per-service ``max_hops`` /
    eligibility masks, admission budgets, R- and V-shape bucketing policy,
    portfolio method/effort, and the anneal backend.  ``spec.masks(problem)``
    builds the [R, P] eligibility mask in ONE place; every solver path
    (coordinate sweeps, all three Metropolis backends' proposal streams,
    the full-portfolio defrag, the incremental re-solve) consumes that same
    mask, so a constraint declared once is enforced everywhere.  The spec
    is registered as a jax pytree (array-valued constraints are leaves,
    config is static aux data) and survives flatten/unflatten.

  * **CFNSession** -- the facade owning topology + spec + warm state:
    ``solve()`` embeds a whole VSR batch (or re-packs the live set),
    ``add``/``remove`` are warm-start churn events, ``defrag()`` re-packs
    under the SAME spec (closing the ROADMAP's defrag/SLA hole
    structurally), ``attribute()`` splits fleet watts per tenant, and
    ``replay()`` drives a churn timeline.

The legacy entry points remain as deprecated shims that construct a
``PlacementSpec`` internally, so old call sites keep working while new code
declares constraints once:

    from repro.api import CFNSession, PlacementSpec
    spec = PlacementSpec(max_hops=2, power_budget_w=500.0)
    session = CFNSession(topo, spec)
    session.solve(vsrs)                      # batch embedding
    session.add(service); session.defrag()   # online churn, masked defrag
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Union

import jax
import numpy as np

from . import dynamic, embed as embed_mod, vsr as vsr_mod
from .embed import METHODS
from .power import PlacementProblem, SubstrateHealth
from .solvers import SolveResult, solve_portfolio
from .topology import CFNTopology

__all__ = ["PlacementSpec", "CFNSession", "SolveResult", "solve_portfolio",
           "FederatedSession", "RegionPartition", "SubstrateHealth"]

_EFFORTS = ("quick", "standard", "high")
_BACKENDS = ("auto", "delta", "fused", "full")


@dataclass(frozen=True, eq=False)
class PlacementSpec:
    """Declarative constraint + configuration bundle for CFN placement.

    Constraint fields (pytree leaves):
      * ``max_hops`` -- SLA hop bound: every VM of a service must sit within
        this many network hops of the service's source node.  A scalar
        applies to all services; a length-n sequence constrains the first n
        rows (rows beyond it -- e.g. shape-bucket padding -- are
        unconstrained).  ``None`` disables.
      * ``eligible`` -- explicit [R, P] bool mask ANDed on top of the hop
        mask (rows beyond its length are unconstrained).
      * ``health`` -- ``power.SubstrateHealth`` up/down state of the
        physical substrate (fault plane).  Dead nodes and nodes behind dead
        network elements are ANDed out of the mask for EVERY row, and the
        online engine additionally zeroes dead capacities on the problem
        (``health.degrade``).  Column-wise and shape-preserving, so it
        composes with churn: unlike row-positional constraints it never
        binds to batch rows.

    Row-positional forms (sequence ``max_hops``, explicit ``eligible``)
    bind to BATCH rows and are rejected by the churn path (``add`` /
    ``remove`` raise: a removal shifts row indices, which would silently
    re-assign SLAs across services); scalar ``max_hops`` is the online
    contract.

    Federation fields (consumed by ``core.federation.FederatedSession``;
    flat sessions ignore them):
      * ``region_affinity`` -- per-service target region index (-1 = the
        service's home region, i.e. the region owning its source node).  A
        scalar applies to all services; a length-n sequence binds to the
        first n batch rows.
      * ``region_anti_affinity`` -- per-service FORBIDDEN region index
        (-1 = none); a service homed in its forbidden region is migrated
        out at admission.
      * ``region_power_budget_w`` -- per-region total-watts budget (scalar
        = every region; sequence = per region).  The federation coordinator
        migrates services out of a region whose exact attributed watts
        exceed its budget.
      * ``inter_region_hops`` -- cap on shared-core hops a cross-region
        (migrated) service may traverse between its home and host regions.

    Admission budgets (online path; ``None`` disables each):
      * ``power_budget_w`` -- reject an arrival whose incremental fleet
        power draw exceeds this many watts.
      * ``violation_tol`` -- reject an arrival that increases capacity
        violation by more than this.
      * ``queue_rejected`` -- park rejected arrivals and retry after each
        capacity-increasing event (departure, recovery, brownout_end)
        instead of dropping them.
      * ``priority_classes`` -- number of admission priority classes (class
        0 is the most important).  Services carry a class at ``add()`` /
        ``apply_wave()`` time; the rejection queue drains class-by-class
        (FIFO within a class).
      * ``preempt`` -- under power-budget pressure, let an arrival park a
        strictly lower-class live service into the queue to free budget
        (lowest class first, newest first), instead of rejecting.
      * ``defrag_rows_per_tick`` -- amortized background defrag: every
        ``defrag_tick()`` delta-sweeps this many live rows (round-robin
        cursor carried across ticks, never-regressing).  > 0 REPLACES the
        periodic full-portfolio defrag (``defrag_every`` stops firing), so
        defrag cost leaves the per-event latency path entirely.

    Shape-bucketing policy (compile-count hygiene; see power.build_problem):
      * ``bucket_rows``/``bucket_cols`` -- pad the service count R and the
        VM width V to power-of-two buckets (zero-demand fully-pinned pads).
      * ``row_bucket_lo``/``col_bucket_lo`` -- smallest bucket.

    Portfolio / solver configuration:
      * ``method`` -- solver for full solves (one of ``embed.METHODS``).
      * ``effort`` -- portfolio tier: "quick" (coordinate warm starts only),
        "standard" (+4000-step anneal), "high" (+12000 steps and genetic).
      * ``backend`` -- anneal backend ("auto"/"delta"/"fused"/"full").
      * ``defrag_every`` -- full-portfolio re-pack cadence in churn events
        (0 disables periodic defrag).
      * ``sweeps``/``anneal_steps``/``anneal_chains``/``anneal_t0``/
        ``anneal_t1``/``remove_anneal_t0``/``polish_sweeps`` -- the
        incremental re-solve knobs (``solvers.resolve_incremental``);
        departures re-pack survivors from the hotter ``remove_anneal_t0``.
    """

    # constraints --------------------------------------------------------
    max_hops: Optional[Union[int, Sequence[int], np.ndarray]] = None
    eligible: Optional[np.ndarray] = None
    # substrate health (fault plane; see power.SubstrateHealth) -----------
    health: Optional[SubstrateHealth] = None
    # federation (core.federation.FederatedSession; ignored by flat paths) -
    region_affinity: Optional[Union[int, Sequence[int], np.ndarray]] = None
    region_anti_affinity: Optional[Union[int, Sequence[int],
                                         np.ndarray]] = None
    region_power_budget_w: Optional[Union[float, Sequence[float],
                                          np.ndarray]] = None
    inter_region_hops: Optional[int] = None
    # admission budgets ---------------------------------------------------
    power_budget_w: Optional[float] = None
    violation_tol: Optional[float] = None
    queue_rejected: bool = False
    priority_classes: int = 1
    preempt: bool = False
    defrag_rows_per_tick: int = 0
    # bucketing policy ----------------------------------------------------
    bucket_rows: bool = True
    bucket_cols: bool = True
    row_bucket_lo: int = 2
    col_bucket_lo: int = 2
    # portfolio / solver config ------------------------------------------
    method: str = "cfn-milp"
    effort: str = "standard"
    backend: str = "auto"
    defrag_every: int = 16
    sweeps: int = 2
    anneal_steps: int = 600
    anneal_chains: int = 8
    anneal_t0: float = 5.0
    anneal_t1: float = 0.05
    remove_anneal_t0: float = 20.0
    polish_sweeps: int = 2

    def __post_init__(self):
        if self.method not in METHODS:
            raise ValueError(f"unknown method {self.method!r}; "
                             f"choose from {METHODS}")
        if self.effort not in _EFFORTS:
            raise ValueError(f"unknown effort {self.effort!r}; "
                             f"choose from {_EFFORTS}")
        if self.backend not in _BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}; "
                             f"choose from {_BACKENDS}")
        if self.row_bucket_lo < 1 or self.col_bucket_lo < 1:
            raise ValueError("bucket floors must be >= 1")
        if self.priority_classes < 1:
            raise ValueError("priority_classes must be >= 1")
        if self.defrag_rows_per_tick < 0:
            raise ValueError("defrag_rows_per_tick must be >= 0")

    def replace(self, **changes) -> "PlacementSpec":
        """A copy with ``changes`` applied (validation re-runs)."""
        return dataclasses.replace(self, **changes)

    # -- the one place constraint masks are built -------------------------
    def masks(self, problem: PlacementProblem) -> Optional[np.ndarray]:
        """The [R, P] node-eligibility mask this spec imposes on a problem,
        or ``None`` when unconstrained.

        Hop counts come from the problem's own padded-CSR route table
        (``route_len[b, e]`` == number of non-sentinel ids), and each
        service's source from its pinned input VM, so the mask is a pure
        function of (spec, problem) -- every consumer (coordinate sweep
        argmins, Metropolis destination sampling across all three anneal
        backends, the portfolio defrag, incremental re-solves) sees the
        identical constraint set.
        """
        h_active = self.health is not None and not self.health.all_up
        if self.max_hops is None and self.eligible is None and not h_active:
            return None
        R, P = problem.R, problem.P
        el = np.ones((R, P), dtype=bool)
        if h_active:
            el &= self.health.eligibility(problem)
        if self.max_hops is not None:
            hops = (np.asarray(problem.route_idx) < problem.N).sum(axis=-1)
            fixed_mask = np.asarray(problem.fixed_mask)
            fixed_node = np.asarray(problem.fixed_node)
            src_of = fixed_node[np.arange(R), fixed_mask.argmax(axis=1)]
            mh = np.asarray(self.max_hops)
            lim = np.full(R, np.iinfo(np.int64).max)
            if mh.ndim == 0:
                lim[:] = int(mh)
            else:
                n = min(R, mh.shape[0])
                lim[:n] = mh[:n]
            el &= hops[src_of] <= lim[:, None]
        if self.eligible is not None:
            ex = np.asarray(self.eligible, bool)
            n = min(R, ex.shape[0])
            el[:n] &= ex[:n]
        return el

    # -- pytree protocol --------------------------------------------------
    _LEAF_FIELDS = ("max_hops", "eligible", "health", "region_affinity",
                    "region_anti_affinity", "region_power_budget_w")

    def tree_flatten(self):
        aux_fields = tuple(f for f in self.__dataclass_fields__
                           if f not in self._LEAF_FIELDS)
        children = tuple(getattr(self, f) for f in self._LEAF_FIELDS)
        aux = tuple((f, getattr(self, f)) for f in aux_fields)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        kw = dict(aux)
        kw.update(dict(zip(cls._LEAF_FIELDS, children)))
        return cls(**kw)


jax.tree_util.register_pytree_node(
    PlacementSpec,
    lambda s: s.tree_flatten(),
    PlacementSpec.tree_unflatten)


def _split_services(vsrs: vsr_mod.VSRBatch) -> List[vsr_mod.VSRBatch]:
    """A VSRBatch as a list of R=1 services (session/engine row granularity;
    concat pad columns, if any, ride along as zero-demand VMs)."""
    return [vsr_mod.VSRBatch(F=vsrs.F[i:i + 1], H=vsrs.H[i:i + 1],
                             src=vsrs.src[i:i + 1],
                             input_vm=vsrs.input_vm[i:i + 1])
            for i in range(vsrs.R)]


class CFNSession:
    """The CFN placement facade: topology + spec + warm state, one object.

    All five legacy entry points collapse onto this: batch embedding
    (``solve(vsrs)``), online churn (``add``/``remove``), the masked
    full-portfolio re-pack (``defrag``), per-tenant power accounting
    (``attribute``), and timeline replay (``replay``).  The session's
    engine (``core.dynamic.OnlineEmbedder``) carries the placement and the
    incremental load state between events; every solve -- incremental or
    full -- enforces ``spec.masks`` identically.
    """

    def __init__(self, topo: CFNTopology,
                 spec: Optional[PlacementSpec] = None,
                 key: Optional[jax.Array] = None,
                 monitor=None, telemetry=None):
        self.topo = topo
        self._engine = dynamic.OnlineEmbedder(
            topo, spec=spec if spec is not None else PlacementSpec(),
            key=key, monitor=monitor, telemetry=telemetry)
        if monitor is not None and telemetry is not None:
            monitor.attach_telemetry(telemetry)

    # -- configuration / introspection ------------------------------------
    def attach_monitor(self, monitor) -> None:
        """Attach (or replace) the ``fault.monitor.PlacementMonitor``
        receiving this session's admission/budget events."""
        self._engine.monitor = monitor
        if monitor is not None and self.telemetry is not None:
            monitor.attach_telemetry(self.telemetry)

    def attach_telemetry(self, telemetry) -> None:
        """Attach (or replace) the ``repro.telemetry.Telemetry`` receiving
        this session's spans, energy ledger, and compile attribution; an
        attached monitor mirrors its counters there too."""
        self._engine.attach_telemetry(telemetry)
        if self._engine.monitor is not None and telemetry is not None:
            self._engine.monitor.attach_telemetry(telemetry)

    @property
    def telemetry(self):
        return self._engine.telemetry

    @property
    def spec(self) -> PlacementSpec:
        return self._engine.spec

    @property
    def engine(self) -> "dynamic.OnlineEmbedder":
        """The underlying online engine (escape hatch for benchmarks)."""
        return self._engine

    @property
    def n_live(self) -> int:
        return self._engine.n_live

    @property
    def sids(self) -> List[int]:
        return self._engine.sids

    @property
    def problem(self) -> Optional[PlacementProblem]:
        return self._engine.problem

    @property
    def X(self) -> Optional[np.ndarray]:
        return self._engine.X

    @property
    def result(self) -> Optional[SolveResult]:
        return self._engine.result

    @property
    def stats(self) -> list:
        return self._engine.stats

    @property
    def admission(self) -> Dict[str, int]:
        return self._engine.admission

    def service_vms(self, row: int) -> int:
        return self._engine.service_vms(row)

    def power_w(self) -> float:
        return self._engine.power_w()

    def objective(self) -> float:
        return self._engine.objective()

    def masks(self) -> Optional[np.ndarray]:
        """The live problem's eligibility mask under this spec."""
        return (None if self.problem is None
                else self.spec.masks(self.problem))

    # -- solving ----------------------------------------------------------
    def solve(self, vsrs: Optional[vsr_mod.VSRBatch] = None
              ) -> Optional[SolveResult]:
        """Embed a whole VSR batch under the spec, or re-pack the live set.

        With ``vsrs`` (empty session only): the batch becomes the session's
        live services -- one full solve with ``spec.method``/``effort``,
        constraint masks applied.  Without ``vsrs``: a full re-pack of the
        current live set (identical to ``defrag()``).
        """
        if vsrs is None:
            if self._engine.problem is None:
                raise ValueError("empty session: pass a VSRBatch to solve()")
            return self._engine.defrag()
        if self._engine.n_live:
            raise ValueError(
                "session already has live services; use add()/remove() for "
                "churn or solve() with no batch to re-pack")
        return self._engine.bootstrap(_split_services(vsrs))

    def add(self, service: vsr_mod.VSRBatch, sid: Optional[int] = None,
            priority: Optional[int] = None) -> Optional[SolveResult]:
        """Admit one service (R=1) as a wave of one: warm-start
        re-embedding under the spec's masks and admission budgets.  ``priority`` is the
        admission class (0 = highest; < ``spec.priority_classes``).
        ``None`` = rejected."""
        return self._engine.add(service, sid=sid, priority=priority)

    def remove(self, sid: int) -> Optional[SolveResult]:
        """Retire a service as a wave of one: detach its loads, re-settle
        survivors."""
        return self._engine.remove(sid)

    def apply_wave(self, arrivals: Sequence = (),
                   departures: Sequence[int] = ()) -> "dynamic.WaveResult":
        """Apply one churn wave (a tick's arrivals + departures) as a
        single batched re-solve (``OnlineEmbedder.apply_wave``): one fused
        detach, one warm-started ``solvers.resolve_wave``, one polish pass,
        priority-ordered admission, queue drain.  ``add`` and ``remove``
        run this same path as waves of one."""
        return self._engine.apply_wave(arrivals, departures)

    def defrag_tick(self, rows: Optional[int] = None) -> Optional[SolveResult]:
        """One amortized background-defrag step (``spec.defrag_rows_per_tick``
        rows, round-robin, never-regressing); see
        ``OnlineEmbedder.defrag_tick``."""
        return self._engine.defrag_tick(rows)

    def defrag(self) -> Optional[SolveResult]:
        """Full-portfolio re-pack of the live set under ``spec.masks`` --
        a hop-constrained service can never be defragged out of its
        radius.  Keeps the live placement when the portfolio can't beat
        it."""
        return self._engine.defrag()

    # -- fault plane ------------------------------------------------------
    @property
    def health(self) -> Optional[SubstrateHealth]:
        return self._engine.spec.health

    def tick(self, t: float) -> None:
        """Advance the session clock (availability timestamps)."""
        self._engine.tick(t)

    def fail_node(self, node: int) -> Optional[SolveResult]:
        """Fail a processing node: strand services sourced there, mass
        re-embed displaced VMs on the degraded substrate."""
        return self._engine.fail_node(node)

    def recover_node(self, node: int) -> Optional[SolveResult]:
        return self._engine.recover_node(node)

    def fail_link(self, n: int) -> Optional[SolveResult]:
        """Fail a network element: traffic routed across it is re-embedded
        around the cut."""
        return self._engine.fail_link(n)

    def recover_link(self, n: int) -> Optional[SolveResult]:
        return self._engine.recover_link(n)

    def brownout(self, budget_w: float) -> None:
        """Tighten the admission power budget mid-run (restore with
        ``brownout_end``)."""
        self._engine.brownout(budget_w)

    def brownout_end(self) -> None:
        self._engine.brownout_end()

    def apply_fault(self, ev: "dynamic.FaultEvent"):
        """Dispatch one ``core.dynamic.FaultEvent`` to the handlers above."""
        return self._engine.apply_fault(ev)

    def attribute(self) -> Dict[int, float]:
        """Per-tenant watts {sid: W}, summing exactly to the fleet total."""
        return self._engine.per_service_power_w()

    def replay(self, events: Sequence["dynamic.ServiceEvent"],
               make_vsr: Callable[[int], vsr_mod.VSRBatch],
               on_event: Optional[Callable] = None,
               waves: bool = False) -> list:
        """Drive the session through a churn timeline
        (``core.dynamic.replay`` on this session's engine).  ``waves=True``
        batches same-tick events through ``apply_wave`` and runs the
        amortized background defrag tick after each wave."""
        return dynamic.replay(self._engine, events, make_vsr, on_event,
                              waves=waves)

    # -- reporting --------------------------------------------------------
    def savings_vs_baseline(self, baseline: str = "cdc") -> dict:
        """Paper headline metric for the live set: power saving vs a
        fixed-layer baseline, BOTH solved under this spec's constraints
        (masks, effort, backend) so the reported saving is achievable
        within the declared SLA."""
        vsrs = self._engine.vsr_batch()
        if vsrs is None:
            raise ValueError("empty session")
        from .power import build_problem
        problem = build_problem(self.topo, vsrs)
        base = embed_mod._embed(self.topo, vsrs,
                                self.spec.replace(method=baseline),
                                problem=problem)
        opt = embed_mod._embed(self.topo, vsrs, self.spec, problem=problem)
        saving = 1.0 - opt.power / max(base.power, 1e-9)
        return dict(baseline_w=base.power, optimized_w=opt.power,
                    saving_frac=saving, baseline=base, optimized=opt)


# Federation layer (bottom import: federation builds on PlacementSpec /
# CFNSession defined above; the lazy `from . import api` inside it resolves
# against this module mid-initialization without a cycle).
from .federation import FederatedSession, RegionPartition  # noqa: E402
