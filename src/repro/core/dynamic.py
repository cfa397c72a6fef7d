"""Dynamic service churn: event timelines + the online embedding engine.

The paper evaluates static VSR sets (1-20 VSRs placed once).  A serving
system sees services *arrive and depart* continuously -- the regime studied
by Yosuf et al. ("Energy Efficient Service Distribution in IoT", diurnal
demand profiles) and named the core open problem for fog AI by Tuli et al.
This module supplies both halves of that regime:

  * **Timelines** -- non-homogeneous Poisson arrivals (thinning) under a
    24 h diurnal rate profile, exponential service lifetimes, and scenario
    presets (`steady`, `diurnal24`, `burst`).
  * **OnlineEmbedder** -- the live placement state machine: every churn
    event is a wave (`apply_wave`; `add` / `remove` are waves of one) that
    carries the previous embedding through `power.detach_vsrs` /
    `power.warm_state` and re-solves with `solvers.resolve_wave` (only the
    arrivals' VMs are re-placed; survivors polish in place).  Every
    `defrag_every` events a full portfolio solve
    (`solvers.solve_portfolio`) re-packs the substrate and bounds the drift of
    purely local re-optimization.

Times are in hours throughout; rates in services/hour.
"""
from __future__ import annotations

import functools
import heapq
import warnings
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, Iterator, List, Optional, \
    Sequence, Tuple, Union

import jax
import numpy as np

from . import embed as embed_mod
from . import power, solvers, vsr
from .topology import CFNTopology
from ..telemetry import span


# ---------------------------------------------------------------------------
# Rate profiles and event timelines
# ---------------------------------------------------------------------------

def diurnal_rate(t_h, base_rate: float, peak_rate: float,
                 peak_hour: float = 20.0):
    """24 h-periodic arrival rate (services/h): a raised cosine between
    ``base_rate`` (quietest, 12 h off-peak) and ``peak_rate`` at
    ``peak_hour`` -- the evening-peak shape of Yosuf et al.'s demand
    profiles.  Accepts scalars or arrays.
    """
    phase = 2.0 * np.pi * (np.asarray(t_h, np.float64) - peak_hour) / 24.0  # tracelint: allow[CFN102]
    return base_rate + (peak_rate - base_rate) * 0.5 * (1.0 + np.cos(phase))


@dataclass(frozen=True)
class ServiceEvent:
    """One churn event: service ``sid`` arrives or departs at hour ``t``."""
    t: float
    kind: str          # "arrive" | "depart"
    sid: int


def poisson_timeline(duration_h: float,
                     rate_fn: Callable[[float], float],
                     mean_lifetime_h: float,
                     rng: np.random.Generator | int = 0,
                     max_services: Optional[int] = None
                     ) -> List[ServiceEvent]:
    """Arrival/departure events over ``[0, duration_h)``.

    Arrivals are a non-homogeneous Poisson process with intensity
    ``rate_fn(t)`` sampled by thinning; each arrival draws an Exp(mean)
    lifetime and emits a matching departure if it falls inside the horizon.
    Events are returned time-sorted (departures before arrivals on exact
    ties, so the live set stays minimal).
    """
    rng = np.random.default_rng(rng) if isinstance(rng, int) else rng
    grid = np.linspace(0.0, duration_h, 512)
    lam_max = float(np.max([rate_fn(t) for t in grid]))
    if lam_max <= 0:
        return []
    events: List[ServiceEvent] = []
    t, sid = 0.0, 0
    while True:
        t += rng.exponential(1.0 / lam_max)
        if t >= duration_h:
            break
        if rng.random() <= rate_fn(t) / lam_max:
            events.append(ServiceEvent(t, "arrive", sid))
            t_dep = t + rng.exponential(mean_lifetime_h)
            if t_dep < duration_h:
                events.append(ServiceEvent(t_dep, "depart", sid))
            sid += 1
            if max_services is not None and sid >= max_services:
                break
    events.sort(key=lambda e: (e.t, e.kind == "arrive"))
    return events


def churn_trace(n_steady: int, n_events: int,
                rng: np.random.Generator | int = 0) -> List[ServiceEvent]:
    """The benchmark trace: a steady state of ``n_steady`` live services
    perturbed by alternating single departure / arrival events (depart a
    uniformly random live service, then admit a fresh one), so every event
    is a one-service change at paper scale."""
    rng = np.random.default_rng(rng) if isinstance(rng, int) else rng
    events = [ServiceEvent(0.0, "arrive", sid) for sid in range(n_steady)]
    live = list(range(n_steady))
    sid = n_steady
    for i in range(n_events):
        t = 1.0 + i
        if i % 2 == 0:
            victim = live.pop(int(rng.integers(0, len(live))))
            events.append(ServiceEvent(t, "depart", victim))
        else:
            events.append(ServiceEvent(t, "arrive", sid))
            live.append(sid)
            sid += 1
    return events


def flash_crowd_trace(n_steady: int, n_waves: int, wave_size: int,
                      rng: np.random.Generator | int = 0,
                      replace: bool = True) -> List[ServiceEvent]:
    """A flash-crowd timeline: churn arrives in correlated same-tick WAVES
    instead of one event at a time (the regime ``apply_wave`` /
    ``replay(..., waves=True)`` batches).

    ``n_steady`` services arrive at t=0 (the bootstrap burst), then
    ``n_waves`` bursts land at t = 1, 2, ...:

      * ``replace=True`` (the steady benchmark shape): each wave departs
        ``wave_size // 2`` uniformly random live services and admits
        ``wave_size - wave_size // 2`` fresh ones IN THE SAME TICK, so the
        live count -- and the solver's compile bucket -- never moves.
      * ``replace=False`` (the classic flash crowd): ``n_waves`` pure
        arrival bursts ramp the crowd up, then equal departure bursts drain
        it in LIFO order.

    Within every tick the departures sort before the arrivals
    (``merge_timelines`` tie order), so a same-tick replace never
    double-counts capacity."""
    rng = np.random.default_rng(rng) if isinstance(rng, int) else rng
    events = [ServiceEvent(0.0, "arrive", sid) for sid in range(n_steady)]
    live = list(range(n_steady))
    sid = n_steady
    t = 0.0
    if replace:
        n_dep = wave_size // 2
        for _ in range(n_waves):
            t += 1.0
            for _ in range(n_dep):
                victim = live.pop(int(rng.integers(0, len(live))))
                events.append(ServiceEvent(t, "depart", victim))
            for _ in range(wave_size - n_dep):
                events.append(ServiceEvent(t, "arrive", sid))
                live.append(sid)
                sid += 1
    else:
        crowd: List[int] = []
        for _ in range(n_waves):
            t += 1.0
            for _ in range(wave_size):
                events.append(ServiceEvent(t, "arrive", sid))
                crowd.append(sid)
                sid += 1
        while crowd:
            t += 1.0
            for _ in range(min(wave_size, len(crowd))):
                events.append(ServiceEvent(t, "depart", crowd.pop()))
    return merge_timelines(events)


@dataclass(frozen=True)
class ChurnScenario:
    """A named workload regime: rate profile + lifetimes + VSR shape."""
    name: str
    duration_h: float
    base_rate: float           # services/h (off-peak)
    peak_rate: float           # services/h (at peak_hour)
    peak_hour: float
    mean_lifetime_h: float
    n_vms: int = 3
    vm_gflops: Tuple[float, float] = (3.0, 10.0)
    link_mbps: Tuple[float, float] = (5.0, 50.0)
    source_nodes: Tuple[int, ...] = (0,)

    def rate_fn(self) -> Callable[[float], float]:
        return lambda t: float(diurnal_rate(t, self.base_rate,
                                            self.peak_rate, self.peak_hour))

    def timeline(self, rng: np.random.Generator | int = 0
                 ) -> List[ServiceEvent]:
        return poisson_timeline(self.duration_h, self.rate_fn(),
                                self.mean_lifetime_h, rng=rng)

    def sample_vsr(self, rng: np.random.Generator | int) -> vsr.VSRBatch:
        """One fresh service (R=1 VSR) drawn from the scenario's shape."""
        return vsr.random_vsrs(1, rng=rng, n_vms=self.n_vms,
                               source_nodes=list(self.source_nodes),
                               vm_gflops=self.vm_gflops,
                               link_mbps=self.link_mbps)


SCENARIOS: Dict[str, ChurnScenario] = {
    # flat arrival rate; ~8 concurrent services in expectation
    "steady": ChurnScenario("steady", duration_h=24.0, base_rate=2.0,
                            peak_rate=2.0, peak_hour=12.0,
                            mean_lifetime_h=4.0),
    # paper-scale diurnal day: ~4 services overnight, ~20 at the peak
    "diurnal24": ChurnScenario("diurnal24", duration_h=24.0, base_rate=1.0,
                               peak_rate=5.0, peak_hour=20.0,
                               mean_lifetime_h=4.0),
    # short sharp evening burst of small services
    "burst": ChurnScenario("burst", duration_h=6.0, base_rate=0.5,
                           peak_rate=12.0, peak_hour=3.0,
                           mean_lifetime_h=1.0, vm_gflops=(1.0, 4.0)),
}


# ---------------------------------------------------------------------------
# Fault timelines: substrate failures composable with service churn
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class FaultEvent:
    """One substrate fault at hour ``t``.

    Kinds on flat engines: ``fail_node`` / ``recover_node`` (``target`` =
    processing-node id), ``fail_link`` / ``recover_link`` (``target`` =
    network-element id), ``brownout`` / ``brownout_end`` (``value`` = the
    tightened fleet admission budget in watts).  Federated sessions take
    region granularity instead: ``fail_region`` / ``recover_region``
    (``target`` = region index) and ``brownout`` / ``brownout_end`` with
    ``target`` = the region whose power budget tightens to ``value``.
    """
    t: float
    kind: str
    target: int = -1
    value: Optional[float] = None


# Tie-break order at equal t: departures free capacity first, failures land
# before recoveries (a same-instant fail/recover pair nets to a clean
# recover), and arrivals admit last, onto the settled substrate.
_EVENT_ORDER = {"depart": 0,
                "fail_node": 1, "fail_link": 1, "fail_region": 1,
                "brownout": 1,
                "recover_node": 2, "recover_link": 2, "recover_region": 2,
                "brownout_end": 2,
                "arrive": 3}


def merge_timelines(*streams) -> List:
    """Merge churn (``ServiceEvent``) and fault (``FaultEvent``) streams
    into one time-sorted list, stable within the tie-break order above;
    feed the result to ``replay``."""
    events = [e for s in streams for e in s]
    events.sort(key=lambda e: (e.t, _EVENT_ORDER.get(e.kind, 9)))
    return events


def iter_waves(events: Iterable) -> Iterator[List]:
    """Group a time-sorted event stream (``merge_timelines`` output) into
    same-tick waves: maximal runs of ``ServiceEvent``s sharing one
    timestamp.  Because ``merge_timelines`` sorts departures before
    arrivals on ties, every yielded wave carries its departures first -- a
    same-tick replace inside one wave never double-counts capacity.
    ``FaultEvent``s are barriers: each is yielded as its own single-element
    wave (the churn before it must land on the pre-fault substrate)."""
    wave: List = []
    for ev in events:
        if wave and (isinstance(ev, FaultEvent) or ev.t != wave[0].t):
            yield wave
            wave = []
        if isinstance(ev, FaultEvent):
            yield [ev]
        else:
            wave.append(ev)
    if wave:
        yield wave


def _storm_nodes(topo: CFNTopology, n: int) -> List[int]:
    """The first ``n`` fog-tier nodes to fail in a storm preset: mini-fog
    servers first (the tier the paper calls "limited ... and highly
    distributed"), then access fog, then anything non-source."""
    pool: List[int] = []
    for layer in ("mf", "af", "cdc"):
        pool += [p for p in topo.layer_indices(layer) if p not in pool]
    if len(pool) < n:
        pool += [p for p in range(topo.P) if p not in pool]
    return pool[:n]


def single_node(topo: CFNTopology, node: Optional[int] = None,
                t_fail: float = 20.0, outage_h: float = 2.0
                ) -> List[FaultEvent]:
    """One fog node dies at the diurnal peak and recovers ``outage_h``
    later."""
    if node is None:
        node = _storm_nodes(topo, 1)[0]
    return [FaultEvent(t_fail, "fail_node", node),
            FaultEvent(t_fail + outage_h, "recover_node", node)]


def rack_storm(topo: CFNTopology, nodes: Optional[Sequence[int]] = None,
               n_nodes: int = 4, t_fail: float = 20.0,
               stagger_h: float = 0.05, outage_h: float = 1.0
               ) -> List[FaultEvent]:
    """A cascading rack outage: ``n_nodes`` fog nodes fail in quick
    succession (``stagger_h`` apart) and recover in the same order after
    ``outage_h``."""
    if nodes is None:
        nodes = _storm_nodes(topo, n_nodes)
    ev: List[FaultEvent] = []
    for k, p in enumerate(nodes):
        ev.append(FaultEvent(t_fail + k * stagger_h, "fail_node", int(p)))
        ev.append(FaultEvent(t_fail + outage_h + k * stagger_h,
                             "recover_node", int(p)))
    return merge_timelines(ev)


def brownout_day(topo: CFNTopology, region: int = 0,
                 budget_w: float = 500.0, t0: float = 10.0,
                 t1: float = 16.0) -> List[FaultEvent]:
    """A mid-day brownout: the power budget tightens to ``budget_w`` over
    ``[t0, t1)`` (region-targeted on a FederatedSession; a flat engine
    applies it fleet-wide)."""
    return [FaultEvent(t0, "brownout", region, value=budget_w),
            FaultEvent(t1, "brownout_end", region)]


FAULT_SCENARIOS: Dict[str, Callable] = {
    "single_node": single_node,
    "rack_storm": rack_storm,
    "brownout_day": brownout_day,
}


def fault_preset(name: str, topo: CFNTopology, **kw) -> List[FaultEvent]:
    """Build a named storm preset on a topology (see FAULT_SCENARIOS)."""
    if name not in FAULT_SCENARIOS:
        raise ValueError(f"unknown fault preset {name!r}; choose from "
                         f"{sorted(FAULT_SCENARIOS)}")
    return FAULT_SCENARIOS[name](topo, **kw)


# ---------------------------------------------------------------------------
# The online embedding engine
# ---------------------------------------------------------------------------

@dataclass
class OnlineStats:
    """Bookkeeping for one engine event (exposed to benchmarks/examples)."""
    event: str                 # "add" | "remove" | "wave" | "reject" | ...
    method: str
    objective: float
    power_w: float
    n_live: int


@dataclass
class WaveResult:
    """Outcome of one ``apply_wave`` call.

    ``sids`` maps the call's arrivals (input order) to their assigned
    service ids; each of those sids lands in exactly one of ``admitted`` /
    ``rejected`` / ``queued``.  ``result`` is the engine's committed fleet
    ``SolveResult`` after the wave (``None`` once the engine is empty);
    ``n_preempted`` counts live services parked to make room."""
    result: Optional[solvers.SolveResult]
    sids: List[int] = field(default_factory=list)
    admitted: List[int] = field(default_factory=list)
    rejected: List[int] = field(default_factory=list)
    queued: List[int] = field(default_factory=list)
    departed: List[int] = field(default_factory=list)
    n_preempted: int = 0


def _bucket_rows(n: int, lo: int = 2) -> int:
    """Shape bucket for a live-service count: the next power of two (>= lo).

    Each distinct problem shape compiles its own `_sweep` /
    `_anneal_scan_delta` variants (~3 s each on a 2-core box), so the online
    engine pads the service dimension to these buckets -- the compile set
    is O(log R) instead of O(distinct R), which kills the p90 latency
    spikes in examples/online_day.py.  The ONE bucketing policy, shared
    with the federated batch path (``solvers._pow2``)."""
    return solvers._pow2(n, lo=lo)


def _traced(name: str):
    """Wrap an engine entry point in a telemetry span (``span``: a shared
    no-op context when no ``Telemetry`` is attached, so the disabled path
    stays bit-identical and allocates nothing)."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(self, *args, **kwargs):
            with span(self.telemetry, name):
                return fn(self, *args, **kwargs)
        return wrapper
    return deco


class OnlineEmbedder:
    """Live CFN embedding under service churn.

    Keeps the current VSR set, placement, and incremental
    ``PlacementState``; every churn event is a wave (``apply_wave``, with
    ``add`` / ``remove`` as waves of one) re-solved warm by
    ``solvers.resolve_wave``, and every ``spec.defrag_every`` events -- or
    on demand via ``defrag()`` -- runs the full portfolio to re-pack the
    substrate.  Service identity is the caller's ``sid``; internally rows
    are dense [0, R).

    Configuration lives in one declarative ``repro.api.PlacementSpec``
    (pass ``spec=``; the legacy kwarg signature is a deprecated shim that
    builds a spec internally and keeps working via the property aliases
    below).  The spec governs:

    **Shape bucketing** (``spec.bucket_rows`` / ``spec.bucket_cols``): the
    tensor problem is padded to power-of-two service counts AND VM widths
    with zero-demand fully-pinned dummy rows/columns (power.build_problem),
    and sweep position lists are padded to the bucket, so the jitted solver
    kernels compile once per bucket instead of once per live count -- and a
    single wide service no longer recompiles the whole concat batch.

    **SLA admission control**: with ``spec.max_hops`` set, every service
    may only be placed within that many network hops of its source --
    ``spec.masks(problem)`` is rebuilt per event and threaded through every
    incremental re-solve AND through the full-portfolio defrag
    (``solvers.solve_portfolio``), so no path can move a hop-constrained
    service out of its radius; with ``spec.power_budget_w`` and/or
    ``spec.violation_tol`` set, arrivals whose incremental power draw or
    capacity-violation increase exceeds the budget are rejected -- or, with
    ``spec.queue_rejected``, parked and retried after each departure.
    Counters in ``admission`` (surfaced by ``replay``).
    """

    def __init__(self, topo: CFNTopology, defrag_every: int = 16,
                 key: Optional[jax.Array] = None, sweeps: int = 2,
                 anneal_steps: int = 600, anneal_chains: int = 8,
                 polish_sweeps: int = 2, method: str = "cfn-milp",
                 bucket_rows: bool = True,
                 max_hops: Optional[int] = None,
                 admit_power_budget_w: Optional[float] = None,
                 admit_violation_tol: Optional[float] = None,
                 queue_rejected: bool = False,
                 spec=None, monitor=None, telemetry=None):
        if spec is None:
            from . import api
            warnings.warn(
                "OnlineEmbedder(defrag_every=..., max_hops=..., ...) kwargs "
                "are deprecated; build a repro.api.PlacementSpec and pass "
                "spec= (or use repro.api.CFNSession)",
                DeprecationWarning, stacklevel=2)
            spec = api.PlacementSpec(
                method=method, defrag_every=defrag_every, max_hops=max_hops,
                power_budget_w=admit_power_budget_w,
                violation_tol=admit_violation_tol,
                queue_rejected=queue_rejected,
                bucket_rows=bucket_rows, bucket_cols=bucket_rows,
                sweeps=sweeps, anneal_steps=anneal_steps,
                anneal_chains=anneal_chains, polish_sweeps=polish_sweeps)
        self.topo = topo
        self.spec = spec
        # a fault.monitor.PlacementMonitor (optional): admission rejections
        # and budget violations are counted there instead of being dropped
        self.monitor = monitor
        # a repro.telemetry.Telemetry (optional): spans on the entry
        # points, energy-ledger ticks + convergence traces on commits,
        # compile attribution via the count_traces hook.  None (default)
        # keeps every instrumented path a strict no-op.
        self.telemetry = None
        self._commits_since_attr = 0
        if telemetry is not None:
            self.attach_telemetry(telemetry)
        self._key = jax.random.PRNGKey(1) if key is None else key
        self._add_kw = dict(sweeps=spec.sweeps,
                            anneal_steps=spec.anneal_steps,
                            anneal_chains=spec.anneal_chains,
                            anneal_t0=spec.anneal_t0,
                            anneal_t1=spec.anneal_t1,
                            polish_sweeps=spec.polish_sweeps)
        # departures re-pack the survivors: random-restart chains over all
        # free VMs need a hotter start to escape the vacated layout
        self._remove_kw = dict(self._add_kw, sweeps=0,
                               anneal_t0=spec.remove_anneal_t0)
        self.admission = dict(admitted=0, rejected=0, queued=0, preempted=0)
        # the rejection queue is a priority heap of (class, seq, sid,
        # service): class 0 drains first, FIFO (seq) within a class
        self._queue: List[tuple] = []
        self._qseq = 0
        self._vsrs: List[vsr.VSRBatch] = []    # one R=1 batch per service
        self._sids: List[int] = []
        self._prio: List[int] = []             # admission class per live row
        self._next_sid = 0
        # amortized background defrag: round-robin row cursor carried
        # across defrag_tick() calls
        self._defrag_cursor = 0
        # per-event cost hygiene: the concatenated batch is maintained
        # incrementally (concat/delete-row, never a 20-way re-concat) and
        # the substrate tensors are built once per topology
        self._batch_cache: Optional[vsr.VSRBatch] = None
        self._substrate: Optional[dict] = None
        self._problem: Optional[power.PlacementProblem] = None
        self._X: Optional[np.ndarray] = None
        self._state: Optional[power.PlacementState] = None
        self._result: Optional[solvers.SolveResult] = None
        self._events_since_defrag = 0
        self.stats: List[OnlineStats] = []
        # fault plane: engine clock (hours; availability timestamps) and the
        # pre-brownout admission budget to restore on brownout_end
        self._now = 0.0
        self._brownout_saved: Optional[tuple] = None

    # -- legacy attribute aliases (read/write through the spec) -----------
    def _spec_alias(name):  # noqa: N805 -- descriptor factory, not a method
        def get(self):
            return getattr(self.spec, name)

        def set_(self, v):
            self.spec = self.spec.replace(**{name: v})
        return property(get, set_)

    defrag_every = _spec_alias("defrag_every")
    method = _spec_alias("method")
    bucket_rows = _spec_alias("bucket_rows")
    max_hops = _spec_alias("max_hops")
    admit_power_budget_w = _spec_alias("power_budget_w")
    admit_violation_tol = _spec_alias("violation_tol")
    queue_rejected = _spec_alias("queue_rejected")
    del _spec_alias

    # -- introspection ----------------------------------------------------
    @property
    def n_live(self) -> int:
        return len(self._vsrs)

    @property
    def sids(self) -> List[int]:
        return list(self._sids)

    @property
    def problem(self) -> Optional[power.PlacementProblem]:
        return self._problem

    @property
    def X(self) -> Optional[np.ndarray]:
        return None if self._X is None else self._X.copy()

    @property
    def result(self) -> Optional[solvers.SolveResult]:
        return self._result

    def service_vms(self, row: int) -> int:
        """The row's OWN VM count (columns beyond it are concat padding)."""
        return self._vsrs[row].V

    def attach_telemetry(self, tel) -> None:
        """Attach (or replace) a ``repro.telemetry.Telemetry``: spans,
        energy ledger, convergence traces, and compile attribution start
        flowing from the next event.  Pass ``None`` to detach."""
        self.telemetry = tel
        if tel is not None:
            if tel.ledger.tiers is None:
                from ..telemetry import tiers_of
                tel.ledger.set_tiers(tiers_of(self.topo))
            tel.attach_traces()

    def clone(self) -> "OnlineEmbedder":
        """A detached copy sharing the (immutable) arrays: events applied to
        the clone leave this engine untouched.  Used by benchmarks to replay
        one event several times for min-of-reps timing."""
        other = OnlineEmbedder(self.topo, spec=self.spec, key=self._key)
        other._add_kw = dict(self._add_kw)
        other._remove_kw = dict(self._remove_kw)
        other.admission = dict(self.admission)
        other._queue = list(self._queue)
        other._qseq = self._qseq
        other._vsrs = list(self._vsrs)
        other._sids = list(self._sids)
        other._prio = list(self._prio)
        other._next_sid = self._next_sid
        other._defrag_cursor = self._defrag_cursor
        other._batch_cache = self._batch_cache
        other._substrate = self._substrate
        other._problem = self._problem
        other._X = self._X
        other._state = self._state
        other._result = self._result
        other._events_since_defrag = self._events_since_defrag
        other.stats = list(self.stats)
        other._now = self._now
        other._brownout_saved = self._brownout_saved
        return other

    def objective(self) -> float:
        return float("nan") if self._result is None \
            else self._result.objective

    def power_w(self) -> float:
        return 0.0 if self._result is None else self._result.power

    def per_service_power_w(self) -> Dict[int, float]:
        """Per-tenant watts (sums to the total; power.attribute_power)."""
        if self._problem is None or not self._sids:
            return {}
        per = power.attribute_power(self._problem, self._X,
                                    self._result.breakdown,
                                    n_rows=self.n_live)
        return {sid: float(w) for sid, w in zip(self._sids, per)}

    def vsr_batch(self) -> Optional[vsr.VSRBatch]:
        """The live service set as one concatenated VSRBatch (may carry
        zero-demand pad columns from departed wider services)."""
        return self._batch_cache

    # -- internals --------------------------------------------------------
    def _split_key(self) -> jax.Array:
        self._key, k = jax.random.split(self._key)
        return k

    def _pad_rows(self) -> Optional[int]:
        return (_bucket_rows(len(self._vsrs), lo=self.spec.row_bucket_lo)
                if self.spec.bucket_rows else None)

    def _pad_cols(self) -> Optional[int]:
        """V-width bucket: a wide arrival only widens the problem up to the
        next power of two, so jitted solver shapes stay on O(log V) buckets
        instead of one per distinct concat width."""
        if not self.spec.bucket_cols or self._batch_cache is None:
            return None
        return _bucket_rows(self._batch_cache.V, lo=self.spec.col_bucket_lo)

    def _rebuild_problem(self) -> None:
        if self._substrate is None:
            self._substrate = power.substrate_arrays(self.topo)
        self._problem = power.build_problem(self.topo, self._batch_cache,
                                            substrate=self._substrate,
                                            pad_to_rows=self._pad_rows(),
                                            pad_to_cols=self._pad_cols())
        h = self.spec.health
        if h is not None and not h.all_up:
            # value-only substitution: dead capacities zero, same shapes,
            # so jitted solver kernels stay on their compile buckets
            self._problem = h.degrade(self._problem)

    def _resolve_kw(self, base: dict) -> dict:
        """Per-event solver kwargs: bucket-stable sweep padding, plus the
        attached telemetry (the solver's phase spans) and convergence-trace
        recording when telemetry wants it (host-side materialization only
        -- the jitted scans always compute the trace, so this flag can
        never retrace)."""
        kw = dict(base)
        if self.bucket_rows and self._problem is not None:
            kw["pad_positions_to"] = int(
                self._problem.R * (self._problem.V - 1))
        if self.telemetry is not None:
            kw["telemetry"] = self.telemetry
            if self.telemetry.convergence:
                kw["record_conv"] = True
        return kw

    def _drop_row(self, row: int) -> None:
        """Delete one service's row from the cached batch, KEEPING the VM
        width (stable shapes keep the jit caches warm; pad VMs are free)."""
        b = self._batch_cache
        self._batch_cache = vsr.VSRBatch(
            F=np.delete(b.F, row, axis=0), H=np.delete(b.H, row, axis=0),
            src=np.delete(b.src, row), input_vm=np.delete(b.input_vm, row))

    def _commit(self, res: solvers.SolveResult, event: str) -> None:
        self._X = np.asarray(res.X)
        self._state = power.init_state(self._problem, self._X)
        self._result = res
        self.stats.append(OnlineStats(
            event=event, method=res.method, objective=res.objective,
            power_w=res.power, n_live=self.n_live))
        if self.telemetry is not None:
            self._telemetry_commit(res, event)

    def _telemetry_commit(self, res: solvers.SolveResult,
                          event: str) -> None:
        """Record one commit into the attached telemetry: a solve event
        (with the convergence trace when recorded), an energy-ledger tick
        from the commit's already-computed breakdown, and -- every
        ``telemetry.attribution_every``-th commit -- the exact per-tenant
        ``power.attribute_power`` split (an O(R) host loop, so it runs on
        a cadence, never per commit by default)."""
        tel = self.telemetry
        per_tenant = None
        every = tel.attribution_every
        if every:
            self._commits_since_attr += 1
            if self._commits_since_attr >= every:
                self._commits_since_attr = 0
                per = power.attribute_power(self._problem, self._X,
                                            res.breakdown,
                                            n_rows=self.n_live)
                per_tenant = {int(s): float(w)
                              for s, w in zip(self._sids, per)}
        tel.record_commit(event=event, res=res, t=self._now,
                          n_live=self.n_live, per_tenant=per_tenant)

    def _full_solve(self, event: str,
                    incumbent: Optional[solvers.SolveResult] = None
                    ) -> solvers.SolveResult:
        """``_repack`` and commit its result."""
        res = self._repack(incumbent)
        self._commit(res, event)
        return res

    def _repack(self, incumbent: Optional[solvers.SolveResult] = None
                ) -> solvers.SolveResult:
        """Spec-driven full solve (``spec.method``, ``spec.masks`` applied
        -- a defrag can no longer move a hop-constrained service out of its
        radius); an ``incumbent`` result for the SAME problem (the
        incremental solution, or the live placement on an explicit defrag)
        is kept when the portfolio fails to beat it, so defrags never
        regress."""
        res = embed_mod._embed(self.topo, self._batch_cache, self.spec,
                               key=self._split_key(), problem=self._problem,
                               telemetry=self.telemetry)
        if incumbent is not None and incumbent.objective < res.objective:
            res = solvers.SolveResult(
                X=incumbent.X, breakdown=incumbent.breakdown,
                method=f"defrag-kept({incumbent.method})",
                history=incumbent.history)
        self._events_since_defrag = 0
        return res

    # -- the priority rejection queue -------------------------------------
    @property
    def queued_sids(self) -> List[int]:
        """Parked service ids in drain order (class, then FIFO)."""
        return [e[2] for e in sorted(self._queue)]

    def _park(self, service: vsr.VSRBatch, sid: int, prio: int = 0,
              seq: Optional[int] = None) -> None:
        """Push one service onto the priority rejection heap.  ``seq``
        re-parks a drained entry at its original within-class position
        (a failed retry keeps its place at the head of its class)."""
        if seq is None:
            seq = self._qseq
            self._qseq += 1
        heapq.heappush(self._queue, (int(prio), seq, sid, service))

    def _priority_of(self, priority: Optional[int]) -> int:
        prio = 0 if priority is None else int(priority)
        if not 0 <= prio < self.spec.priority_classes:
            raise ValueError(
                f"priority {prio} out of range for "
                f"{self.spec.priority_classes} priority class(es)")
        return prio

    # -- the online API ---------------------------------------------------
    @_traced("bootstrap")
    def bootstrap(self, services: Sequence[vsr.VSRBatch],
                  sids: Optional[Sequence[int]] = None,
                  X0: Optional[np.ndarray] = None,
                  priorities: Optional[Sequence[int]] = None
                  ) -> solvers.SolveResult:
        """Cold-start with a whole service set in ONE full-portfolio solve
        (serving restart / benchmark steady state) instead of N incremental
        admissions.

        ``X0`` [len(services), V0] (optional) ADOPTS a placement computed
        elsewhere (a checkpoint, or the federation's vmapped batch solve)
        instead of solving: pins are applied, missing columns fill from each
        row's source, and the engine commits the exact evaluation of that
        placement as its live state -- churn events then warm-start from it.
        """
        if self._vsrs:
            raise RuntimeError("bootstrap() requires an empty engine")
        if not services:
            raise ValueError("bootstrap() needs at least one service")
        if sids is not None and len(sids) != len(services):
            raise ValueError(f"{len(sids)} sids for {len(services)} services")
        if priorities is not None and len(priorities) != len(services):
            raise ValueError(f"{len(priorities)} priorities for "
                             f"{len(services)} services")
        for k, s in enumerate(services):
            if s.R != 1:
                raise ValueError(f"service {k} must be R=1, got R={s.R}")
        self._vsrs = list(services)
        self._sids = (list(range(len(services))) if sids is None
                      else list(sids))
        self._prio = ([0] * len(services) if priorities is None
                      else [self._priority_of(p) for p in priorities])
        self._next_sid = max(self._sids, default=-1) + 1
        out = services[0]
        for b in services[1:]:
            out = out.concat(b)
        self._batch_cache = out
        self._rebuild_problem()
        self.admission["admitted"] += len(services)
        if X0 is not None:
            X0 = np.asarray(X0)
            if X0.shape[0] != len(services):
                raise ValueError(f"X0 has {X0.shape[0]} rows for "
                                 f"{len(services)} services")
            # shape-map only (no state rebuild here: _commit re-derives the
            # incremental state and _result scores the placement exactly):
            # adopted rows fill the leading block, extra columns / bucket
            # pad rows fall back to each row's pinned source
            p = self._problem
            fixed_node = np.asarray(p.fixed_node)
            src_of = fixed_node[np.arange(p.R),
                                np.asarray(p.fixed_mask).argmax(axis=1)]
            X = np.tile(src_of[:, None], (1, p.V)).astype(np.int32)
            k = min(p.V, X0.shape[1])
            X[:X0.shape[0], :k] = X0[:, :k]
            res = solvers._result(p, X, "bootstrap(adopted)")
            self._events_since_defrag = 0
            self._commit(res, "bootstrap")
            return res
        return self._full_solve("bootstrap")

    @property
    def _positional_constraints(self) -> bool:
        """True when the spec carries ROW-positional constraints (sequence
        ``max_hops`` or an explicit ``eligible`` matrix).  Those bind to
        batch rows; churn shifts row indices on removal, which would
        silently re-assign SLAs to the wrong services -- so churn events
        reject them (scalar ``max_hops`` is the online contract)."""
        return (self.spec.eligible is not None
                or (self.spec.max_hops is not None
                    and np.ndim(self.spec.max_hops) > 0))

    def _check_churn_constraints(self, event: str) -> None:
        if self._positional_constraints:
            raise ValueError(
                f"{event}() with row-positional constraints (sequence "
                "max_hops / explicit eligible) is unsupported: removal "
                "shifts row indices, mis-assigning per-service SLAs.  Use "
                "a scalar max_hops for churn, or positional constraints "
                "with the static batch path (CFNSession.solve/defrag).")

    @property
    def _admission_active(self) -> bool:
        return (self.max_hops is not None
                or self.admit_power_budget_w is not None
                or self.admit_violation_tol is not None)

    @_traced("add")
    def add(self, service: vsr.VSRBatch, sid: Optional[int] = None,
            priority: Optional[int] = None) -> Optional[solvers.SolveResult]:
        """Admit one service (an R=1 VSRBatch) as a wave of one arrival.

        ``priority`` is the service's admission class (0 = most important;
        must be < ``spec.priority_classes``).  Returns the committed result,
        or ``None`` when the arrival is not admitted: refused by admission
        control (the engine state is rolled back; with ``queue_rejected``
        the service is parked and retried after the next
        capacity-increasing event) or parked because its source node is
        down.  With ``spec.preempt``, a power-budget refusal may instead
        park a strictly lower-class live service (lowest class, newest
        first) and retry."""
        if service.R != 1:
            raise ValueError(f"add() takes one service, got R={service.R}")
        wr = self._apply_wave([(service, sid, priority)], (), "add")
        return wr.result if wr.admitted else None

    def _preempt_victim(self, prio: int) -> Optional[int]:
        """Park the lowest-class live service strictly below ``prio``
        (newest first on class ties) to free admission budget; returns its
        sid, or ``None`` when no live service may be preempted."""
        victims = [r for r in range(self.n_live) if self._prio[r] > prio]
        if not victims:
            return None
        r = max(victims, key=lambda i: (self._prio[i], i))
        vsid, vsvc, vprio = self._sids[r], self._vsrs[r], self._prio[r]
        # no drain: the arrival that triggered this retries first, before
        # any parked arrival can take the freed budget
        self._wave([], [vsid], WaveResult(self._result, departed=[vsid]),
                   drain=False)
        self._park(vsvc, vsid, vprio)
        self.admission["preempted"] += 1
        if self.monitor is not None:
            self.monitor.count("preempted", detail=f"sid={vsid}")
        self.stats.append(OnlineStats(
            event="preempt", method="admission", objective=self.objective(),
            power_w=self.power_w(), n_live=self.n_live))
        return vsid

    @_traced("remove")
    def remove(self, sid: int) -> Optional[solvers.SolveResult]:
        """Retire a service as a wave of one departure: its loads detach,
        the survivors re-settle, and freed capacity re-admits queued
        arrivals.  Returns the committed result, or ``None`` once the
        engine is empty."""
        return self._apply_wave((), [sid], "remove").result

    # -- wave-batched churn ------------------------------------------------
    @_traced("apply_wave")
    def apply_wave(self, arrivals: Sequence = (),
                   departures: Sequence[int] = ()) -> WaveResult:
        """Apply one churn WAVE -- a tick's worth of arrivals and
        departures -- as a single batched engine event.

        ``arrivals``: R=1 ``VSRBatch``es, or ``(service, sid)`` /
        ``(service, sid, priority)`` tuples (``sid=None`` auto-assigns).
        ``departures``: live sids.  Lifecycle: departures detach first in
        ONE fused ``detach_vsrs`` (a same-tick replace never double-counts
        capacity), arrivals join the batch in one concat + problem rebuild,
        ``solvers.resolve_wave`` re-solves the whole wave with ONE targeted
        sweep / Metropolis / polish pass (the polish that dominates
        per-event latency is paid once per wave), admission verdicts land
        per arrival in priority order, and a departure-carrying wave drains
        the rejection queue.

        This is the engine's one churn path: ``add`` and ``remove`` are
        waves of one, and queue and preemption retries are waves of one
        too.  Stats record a one-arrival wave as ``"add"``, a
        one-departure wave as ``"remove"``, any other as ``"wave"``."""
        return self._apply_wave(arrivals, departures, "apply_wave")

    def _apply_wave(self, arrivals: Sequence, departures: Sequence[int],
                    caller: str) -> WaveResult:
        """Validate a wave's input and run it (``caller`` names the entry
        point in errors)."""
        self._check_churn_constraints(caller)
        arr: List[tuple] = []
        seen: set = set()
        for a in arrivals:
            if isinstance(a, (tuple, list)):
                svc = a[0]
                sid = a[1] if len(a) > 1 else None
                prio = self._priority_of(a[2] if len(a) > 2 else 0)
            else:
                svc, sid, prio = a, None, 0
            if svc.R != 1:
                raise ValueError(
                    f"wave arrivals must be R=1, got R={svc.R}")
            if sid is None:
                sid = self._next_sid
            if sid in self._sids or sid in seen:
                raise ValueError(f"sid {sid} is already live")
            seen.add(sid)
            self._next_sid = max(self._next_sid, sid + 1)
            arr.append((svc, int(sid), prio, None))
        deps = [int(s) for s in departures]
        if len(deps) != len(set(deps)):
            raise ValueError("duplicate departure sid in wave")
        for s in deps:
            if s not in self._sids:
                raise KeyError(f"no live service {s}")
        wr = WaveResult(result=self._result,
                        sids=[a[1] for a in arr], departed=deps)
        if arr or deps:
            pre_preempted = self.admission["preempted"]
            self._wave(arr, deps, wr)
            wr.n_preempted = self.admission["preempted"] - pre_preempted
        return wr

    def _wave_of_one(self, a: tuple) -> WaveResult:
        """Run one arrival record as its own wave (queue and preemption
        retries)."""
        return self._wave([a], [], WaveResult(self._result, sids=[a[1]]))

    def _move_rows(self, dep_rows: List[int], arr: Sequence[tuple]
                   ) -> Tuple[List[tuple], Optional[power.PlacementState]]:
        """The engine's row bookkeeping, shared by waves and faults: detach
        the sorted ``dep_rows`` as ONE fused state update, drop their rows,
        append the arrival records, rebuild the problem, and carry the
        previous placement onto the new rows (``warm_state``; arrival rows
        start unplaced).  Returns the dropped rows' ``(service, sid,
        priority)`` in descending row order, and the warm state -- ``None``
        when there was no placement to carry, or when no service is left
        (the engine's state is then cleared)."""
        tel = self.telemetry
        state, prev_X, n0 = self._state, self._X, self.n_live
        dropped: List[tuple] = []
        if dep_rows:
            with span(tel, "wave.detach"):
                state = power.detach_vsrs(self._problem, state, dep_rows)
                for r in sorted(dep_rows, reverse=True):
                    dropped.append((self._vsrs.pop(r), self._sids.pop(r),
                                    self._prio.pop(r)))
                    self._drop_row(r)
        with span(tel, "wave.rebuild"):
            for svc, sid, prio, _ in arr:
                self._vsrs.append(svc)
                self._sids.append(sid)
                self._prio.append(prio)
                self._batch_cache = (svc if self._batch_cache is None
                                     else self._batch_cache.concat(svc))
            if self._vsrs:
                self._rebuild_problem()
        if not self._vsrs:
            self._problem = self._X = self._state = self._result = None
            self._batch_cache = None
        if not self._vsrs or prev_X is None:
            return dropped, None
        surv = sorted(set(range(n0)) - set(dep_rows))
        row_map = surv + [-1] * (self._problem.R - len(surv))
        with span(tel, "wave.warm_state"):
            st = power.warm_state(
                self._problem, prev_X,
                prev_loads=(state.omega, state.tm, state.theta, state.lam),
                row_map=row_map)
        return dropped, st

    def _wave(self, arr: List[tuple], deps: List[int], wr: WaveResult,
              deferred: Optional[List[tuple]] = None,
              drain: bool = True) -> WaveResult:
        """One attempt at a wave; admission refusals roll the whole attempt
        back and recurse without the refused arrivals.

        Arrival records are ``(service, sid, priority, qseq)``; ``qseq`` is
        the queue position of a parked arrival's re-attempt (``None`` when
        fresh).  A re-attempt that is refused, or whose source is down,
        goes back to that position and is not counted again as rejected,
        queued or stranded.  ``drain=False`` leaves the rejection queue
        alone after the wave's departures (a preemption victim's)."""
        deferred = [] if deferred is None else deferred
        event = ("add" if len(wr.sids) == 1 and not wr.departed
                 else "remove" if len(wr.departed) == 1 and not wr.sids
                 else "wave")
        # source-down arrivals park immediately: a fault is not an SLA
        # rejection (recursive attempts see only the already-filtered list)
        h = self.spec.health
        if h is not None and arr:
            up = []
            for a in arr:
                svc, sid, prio, qseq = a
                if bool(h.node_up[int(svc.src[0])]):
                    up.append(a)
                    continue
                self._park(svc, sid, prio, seq=qseq)
                if qseq is None:
                    self.admission["queued"] += 1
                    if self.monitor is not None:
                        self.monitor.strand(sid, self._now,
                                            detail=f"sid={sid} source down")
                self.stats.append(OnlineStats(
                    event="strand", method="fault",
                    objective=self.objective(), power_w=self.power_w(),
                    n_live=self.n_live))
                wr.queued.append(sid)
            arr = up
        if not arr and not deps:
            wr.result = self._result
            return self._wave_deferred(wr, deferred)
        tel = self.telemetry
        prev = (self._vsrs[:], self._sids[:], self._prio[:],
                self._batch_cache, self._problem, self._X, self._state,
                self._result, self._events_since_defrag)
        # phase 1-2: departures detach BEFORE any arrival lands
        # (merge_timelines tie order; capacity is never double-counted
        # inside a wave), then arrivals join the batch in one pass
        dep_rows = sorted(self._sids.index(s) for s in deps)
        _, st = self._move_rows(dep_rows, arr)
        if not self._vsrs:
            self.stats.append(OnlineStats(event, "empty", 0.0, 0.0, 0))
            wr.result = None
            if drain:
                with span(tel, "wave.drain"):
                    self._drain_queue()
            return self._wave_deferred(wr, deferred)
        self._events_since_defrag += len(arr) + len(dep_rows)
        new_rows = list(range(self.n_live - len(arr), self.n_live))
        if st is None and not self._admission_active:
            # empty engine and nothing to admit against: one full solve
            with span(tel, "wave.full_solve"):
                res = self._repack()
        else:
            if st is None:
                # cold wave under admission control: every arrival starts
                # at its pinned source, so the masked re-solve and the
                # budget test below still apply
                st = power.init_state(self._problem,
                                      np.asarray(self._problem.fixed_node))
            res = self._wave_solve(st, new_rows, len(deps))
            # phase 4: admission, per arrival in priority order
            if new_rows and self._admission_active:
                with span(tel, "wave.admission"):
                    refused = self._wave_refusals(res, arr, new_rows, prev[7])
                if refused:
                    (self._vsrs, self._sids, self._prio, self._batch_cache,
                     self._problem, self._X, self._state, self._result,
                     self._events_since_defrag) = prev
                    (i, reason), = refused.items()
                    keep = arr[:i] + arr[i + 1:]
                    if reason == "power_budget_exceeded" and self.spec.preempt:
                        if keep or deps:
                            # retried as a wave of one after this one commits
                            deferred.append(arr[i])
                            return self._wave(keep, deps, wr, deferred, drain)
                        # a wave of one: park a lower-class victim and retry
                        if self._preempt_victim(arr[i][2]) is not None:
                            return self._wave(arr, deps, wr, deferred, drain)
                    self._refuse(arr[i], reason, res, wr)
                    return self._wave(keep, deps, wr, deferred, drain)
        # phase 5: commit, then drain freed capacity into queued arrivals
        for _, sid, _, _ in arr:
            wr.admitted.append(sid)
            self.admission["admitted"] += 1
            if self.monitor is not None:
                self.monitor.unstrand(sid, self._now)
        if self._defrag_due():
            with span(tel, "wave.full_solve"):
                res = self._repack(incumbent=res)
        with span(tel, "wave.commit"):
            self._commit(res, event)
        wr.result = res
        if deps and drain:
            with span(tel, "wave.drain"):
                self._drain_queue()
        return self._wave_deferred(wr, deferred)

    def _wave_solve(self, st: power.PlacementState, new_rows: List[int],
                    n_depart: int) -> solvers.SolveResult:
        """Phase 3: ONE batched re-solve for the whole wave."""
        tel = self.telemetry
        kw = self._add_kw if new_rows else self._remove_kw
        wave_bucket = 0
        if tel is not None and new_rows:
            n_pos = int((~np.asarray(
                self._problem.fixed_mask)[new_rows]).sum())
            wave_bucket = solvers._pow2(n_pos) if n_pos else 0
        with span(tel, "resolve_wave", n_arrive=len(new_rows),
                  n_depart=n_depart, wave_bucket=wave_bucket,
                  r_bucket=int(self._problem.R)) as sp:
            res = solvers.resolve_wave(
                self._problem, st, new_rows, key=self._split_key(),
                spec=self.spec, **self._resolve_kw(kw))
            if tel is not None:
                # _result already materialized res.X/breakdown on host, so
                # this reads no device value
                sp.attrs["objective"] = float(res.objective)
        return res

    def _refuse(self, a: tuple, reason: str, res: solvers.SolveResult,
                wr: WaveResult) -> None:
        """Record one arrival record's admission refusal: reject it, or
        park it with ``queue_rejected`` (a queue re-attempt always goes back
        to its old position and is not counted again)."""
        svc, sid, prio, qseq = a
        if qseq is None:
            self.admission["rejected"] += 1
            if self.monitor is not None:
                self.monitor.count("admission_rejected", detail=f"sid={sid}")
                self.monitor.count(reason, detail=f"sid={sid}")
            if self.queue_rejected:
                self.admission["queued"] += 1
        if self.queue_rejected or qseq is not None:
            self._park(svc, sid, prio, seq=qseq)
            wr.queued.append(sid)
        else:
            wr.rejected.append(sid)
        self.stats.append(OnlineStats(
            event="reject", method="admission", objective=res.objective,
            power_w=res.power, n_live=self.n_live))

    def _wave_deferred(self, wr: WaveResult,
                       deferred: List[tuple]) -> WaveResult:
        """Retry power-refused arrivals (``spec.preempt``) after the wave
        commits, each as a wave of one, which may park a lower-class
        victim to free budget."""
        for a in deferred:
            one = self._wave_of_one(a)
            wr.admitted += one.admitted
            wr.rejected += one.rejected
            wr.queued += one.queued
            wr.result = one.result
        return wr

    def _wave_refusals(self, res: solvers.SolveResult, arr: List[tuple],
                       new_rows: List[int],
                       prev: Optional[solvers.SolveResult]
                       ) -> Dict[int, str]:
        """Admission verdicts for one solved wave attempt against the
        engine's ``prev`` result: {arr index -> reason}.  The wave's
        budgets are the per-arrival budgets linearly extended to the wave
        (mean marginal power / violation increase per arrival); when
        exceeded, ONE victim is refused per attempt -- lowest priority
        class first, and within it the arrival with the highest exact
        attributed watts (``power.attribute_power``) when a power budget is
        set, else the newest -- and the remaining wave is re-solved, so
        higher classes keep their seats."""
        budget, tol = self.admit_power_budget_w, self.admit_violation_tol
        prev_power = 0.0 if prev is None else prev.power
        prev_viol = 0.0 if prev is None else float(prev.breakdown.violation)
        over_power = (budget is not None
                      and res.power - prev_power > budget * len(new_rows))
        over_viol = (tol is not None
                     and float(res.breakdown.violation) - prev_viol
                     > tol * len(new_rows))
        if not over_power and not over_viol:
            return {}
        reason = ("power_budget_exceeded" if over_power
                  else "violation_budget_exceeded")
        lowest = max(a[2] for a in arr)
        cls = [j for j in range(len(arr)) if arr[j][2] == lowest]
        if budget is not None and len(cls) > 1:
            per = power.attribute_power(self._problem, np.asarray(res.X),
                                        res.breakdown, n_rows=self.n_live)
            i = max(cls, key=lambda j: (float(per[new_rows[j]]), j))
        else:
            i = max(cls)
        return {i: reason}

    def _drain_queue(self) -> None:
        """Retry parked arrivals class-by-class (FIFO within a class), each
        as a wave of one; stop at the first re-refusal.  Runs after EVERY
        capacity-increasing event: departures, node/link recoveries, and
        brownout_end."""
        while self._queue:
            prio, seq, sid, service = heapq.heappop(self._queue)
            if not self._wave_of_one((service, sid, prio, seq)).admitted:
                break  # re-parked at its original position (seq)

    def cancel_queued(self, sid: int) -> bool:
        """Drop a parked arrival (its lifetime ended while queued)."""
        n0 = len(self._queue)
        self._queue = [e for e in self._queue if e[2] != sid]
        removed = len(self._queue) < n0
        if removed:
            heapq.heapify(self._queue)
            if self.monitor is not None:
                # a stranded service departing from the queue closes its
                # availability window without counting as re-embedded
                self.monitor.unstrand(sid, self._now, re_embedded=False)
        return removed

    @_traced("defrag")
    def defrag(self) -> Optional[solvers.SolveResult]:
        """Force a full-portfolio re-pack of the current service set (keeps
        the live placement when the portfolio cannot beat it)."""
        if self._problem is None:
            return None
        return self._full_solve("defrag", incumbent=self._result)

    @_traced("defrag_tick")
    def defrag_tick(self, rows: Optional[int] = None
                    ) -> Optional[solvers.SolveResult]:
        """Amortized background defrag: ONE targeted delta-sweep over the
        free VMs of ``rows`` live services (default
        ``spec.defrag_rows_per_tick``), round-robin from a cursor carried
        across ticks -- over ceil(R / K) ticks every service gets
        re-considered, without a full-portfolio solve ever landing on the
        event path.

        Never-regressing: the swept placement is committed only when its
        exact objective improves on the incumbent.  Bucket-stable: the
        position list is padded to a power-of-two, so steady-state ticks
        replay ONE compiled ``_sweep`` per (K, V) bucket.  Returns the
        committed result, or ``None`` when the tick found no improvement
        (or there is nothing to defrag)."""
        k = self.spec.defrag_rows_per_tick if rows is None else int(rows)
        if k <= 0 or self._problem is None or self._result is None:
            return None
        n = self.n_live
        sel = [(self._defrag_cursor + i) % n for i in range(min(k, n))]
        self._defrag_cursor = (self._defrag_cursor + len(sel)) % n
        aux = power.build_aux(self._problem)
        free = np.asarray(aux.free_pos)
        pos = free[np.isin(free[:, 0], sel)]
        if pos.shape[0] == 0:
            return None
        bucket = solvers._pow2(int(pos.shape[0]))
        pos_j = jax.numpy.asarray(solvers._pad_positions(pos, bucket))
        el = self.spec.masks(self._problem)
        el_np, _, _ = solvers._eligible_np(el)
        el_j = None if el_np is None else jax.numpy.asarray(el_np)
        st, _ = solvers._sweep(self._problem, aux, self._state, pos_j, el_j)
        res = solvers._result(self._problem, st.X, "defrag_tick")
        if res.objective >= self._result.objective - 1e-9:
            return None  # never-regressing: keep the incumbent
        self._commit(res, "defrag_tick")
        return res

    def _defrag_due(self) -> bool:
        # amortized mode (defrag_rows_per_tick > 0) REPLACES the periodic
        # full-portfolio defrag: re-packing happens K rows per tick in
        # defrag_tick(), off the event latency path
        return (self.spec.defrag_rows_per_tick == 0
                and self.defrag_every > 0
                and self._events_since_defrag >= self.defrag_every)

    # -- fault plane ------------------------------------------------------
    def tick(self, t: float) -> None:
        """Advance the engine clock (hours).  Strand / unstrand timestamps
        -- the availability integral -- come from this clock."""
        self._now = float(t)

    def _health(self) -> "power.SubstrateHealth":
        h = self.spec.health
        return power.SubstrateHealth.fresh(self.topo) if h is None else h

    def _fault_rows(self) -> Tuple[List[int], List[int]]:
        """(stranded, moved) row indices for the live placement under the
        just-updated ``spec.health``: stranded rows lost their pinned
        source -- or every admissible node -- and are parked; moved rows
        have VMs on dead nodes or traffic routed over dead elements and
        get mass re-embedded."""
        h = self.spec.health
        el = self.spec.masks(self._problem)
        pair_ok = h.pair_alive(self._problem)
        all_links = bool(h.link_up.all())
        X = self._X
        stranded: List[int] = []
        moved: List[int] = []
        for r in range(self.n_live):
            svc = self._vsrs[r]
            if not bool(h.node_up[int(svc.src[0])]):
                stranded.append(r)
                continue
            nodes = X[r, :svc.V]
            hit = bool((~h.node_up[nodes]).any())
            if not hit and not all_links:
                H = np.asarray(svc.H)[0]
                uu, vv = np.nonzero(H > 0)
                if uu.size:
                    hit = bool((~pair_ok[nodes[uu], nodes[vv]]).any())
            if not hit:
                continue
            if el is not None and not bool(el[r].any()):
                # nowhere admissible left: the solvers' best-effort
                # all-True fallback must never see this row
                stranded.append(r)
            else:
                moved.append(r)
        return stranded, moved

    def _apply_fault_impl(self, event: str) -> Optional[solvers.SolveResult]:
        """Shared fail/recover re-embedding: strand rows that lost their
        source (parked in the retry queue -- never silently dropped), mass
        re-embed displaced rows through ``warm_state`` +
        ``resolve_incremental`` on the degraded problem."""
        if self._X is None:
            return None  # nothing placed; _rebuild_problem degrades later
        recovery = event.startswith("recover")
        stranded, moved = ([], []) if recovery else self._fault_rows()
        moved_new = [r - sum(s < r for s in stranded) for r in moved]
        dropped, st = self._move_rows(stranded, ())
        for svc, sid, prio in dropped:
            self._park(svc, sid, prio)
            if self.monitor is not None:
                self.monitor.strand(sid, self._now,
                                    detail=f"sid={sid} {event}")
        if st is None:
            self.stats.append(OnlineStats(event, "empty", 0.0, 0.0, 0))
            return None
        self._events_since_defrag += 1
        if not recovery and not moved_new and not stranded:
            # the dead element hosted nothing: re-score the same placement
            # on the degraded problem, no solver work
            res = solvers._result(self._problem, st.X, "untouched")
            self._commit(res, event)
            return res
        kw = self._add_kw if moved_new else self._remove_kw
        res = solvers.resolve_incremental(
            self._problem, key=self._split_key(),
            changed_rows=moved_new, state=st, spec=self.spec,
            **self._resolve_kw(kw))
        if self._defrag_due():
            res = self._full_solve(event, incumbent=res)
        else:
            self._commit(res, event)
        if moved_new and self.monitor is not None:
            self.monitor.count("re_embedded", n=len(moved_new),
                               detail=f"{event}: {len(moved_new)} displaced")
        return res

    def fail_node(self, node: int) -> Optional[solvers.SolveResult]:
        """Fail a processing node: services sourced there are stranded
        (queued for recovery), services with VMs there are mass
        re-embedded on the degraded substrate."""
        self._check_churn_constraints("fail_node")
        h = self._health()
        if not bool(h.node_up[node]):
            return None
        self.spec = self.spec.replace(health=h.fail_node(node))
        if self.monitor is not None:
            self.monitor.count("node_failed", detail=f"node={node}")
        return self._apply_fault_impl("fail_node")

    def recover_node(self, node: int) -> Optional[solvers.SolveResult]:
        """Recover a node: survivors re-settle onto the restored capacity
        and stranded / parked services retry admission."""
        self._check_churn_constraints("recover_node")
        h = self._health()
        if bool(h.node_up[node]):
            return None
        self.spec = self.spec.replace(health=h.recover_node(node))
        if self.monitor is not None:
            self.monitor.count("node_recovered", detail=f"node={node}")
        res = self._apply_fault_impl("recover_node")
        self._drain_queue()
        return res

    def fail_link(self, n: int) -> Optional[solvers.SolveResult]:
        """Fail a network element: traffic routed across it is re-embedded
        around the cut (zero C_net penalizes any load left there)."""
        self._check_churn_constraints("fail_link")
        h = self._health()
        if not bool(h.link_up[n]):
            return None
        self.spec = self.spec.replace(health=h.fail_link(n))
        if self.monitor is not None:
            self.monitor.count("link_failed", detail=f"link={n}")
        return self._apply_fault_impl("fail_link")

    def recover_link(self, n: int) -> Optional[solvers.SolveResult]:
        self._check_churn_constraints("recover_link")
        h = self._health()
        if bool(h.link_up[n]):
            return None
        self.spec = self.spec.replace(health=h.recover_link(n))
        if self.monitor is not None:
            self.monitor.count("link_recovered", detail=f"link={n}")
        res = self._apply_fault_impl("recover_link")
        self._drain_queue()
        return res

    def brownout(self, budget_w: Optional[float]) -> None:
        """Tighten the fleet admission power budget mid-run (arrivals
        beyond it reject/queue through the existing admission path);
        ``brownout_end`` restores the previous budget."""
        if self._brownout_saved is None:
            self._brownout_saved = (self.spec.power_budget_w,)
        self.spec = self.spec.replace(power_budget_w=budget_w)
        if self.monitor is not None:
            self.monitor.count("brownout", detail=f"budget_w={budget_w}")

    def brownout_end(self) -> None:
        if self._brownout_saved is None:
            return
        (prev_budget,) = self._brownout_saved
        self._brownout_saved = None
        self.spec = self.spec.replace(power_budget_w=prev_budget)
        if self.monitor is not None:
            self.monitor.count("brownout_end",
                               detail=f"budget_w={prev_budget}")
        self._drain_queue()

    @_traced("apply_fault")
    def apply_fault(self, ev: FaultEvent):
        """Dispatch one ``FaultEvent`` to the handlers above (region kinds
        belong to ``FederatedSession``; a flat engine rejects them)."""
        if ev.kind == "fail_node":
            return self.fail_node(int(ev.target))
        if ev.kind == "recover_node":
            return self.recover_node(int(ev.target))
        if ev.kind == "fail_link":
            return self.fail_link(int(ev.target))
        if ev.kind == "recover_link":
            return self.recover_link(int(ev.target))
        if ev.kind == "brownout":
            return self.brownout(ev.value)
        if ev.kind == "brownout_end":
            return self.brownout_end()
        raise ValueError(f"flat engine cannot apply fault kind {ev.kind!r} "
                         "(region faults need a FederatedSession)")


def replay(engine: OnlineEmbedder, events: Sequence[ServiceEvent],
           make_vsr: Callable[[int], vsr.VSRBatch],
           on_event: Optional[Callable] = None,
           waves: bool = False) -> List[OnlineStats]:
    """Drive an engine through a timeline.  ``make_vsr(sid)`` materializes
    the service for each arrival; departures of services neither live in
    the engine (e.g. bootstrapped) nor admitted by this replay are skipped.
    ``on_event(event, result)`` observes each step (``result`` is None for
    an SLA-rejected arrival).  Admission counters accumulate in
    ``engine.admission`` (admitted / rejected / queued).

    The timeline may interleave ``FaultEvent``s (``merge_timelines``):
    those dispatch through ``engine.apply_fault``, and the engine clock is
    ticked to each event's time so strand/unstrand availability windows
    are measured on the timeline's clock.

    ``waves=True`` batches each same-tick run of churn events
    (``iter_waves``) through ``engine.apply_wave`` -- one fused re-solve
    per tick instead of one per event -- and, when the engine carries an
    amortized defrag budget (``spec.defrag_rows_per_tick``), runs one
    background ``defrag_tick()`` after each wave, OFF the event path.
    ``on_event`` then observes ``(event, WaveResult)`` for every event of
    the wave."""
    if waves:
        return _replay_waves(engine, events, make_vsr, on_event)
    live = set(engine.sids)
    for ev in events:
        tick = getattr(engine, "tick", None)
        if tick is not None:
            tick(ev.t)
        if isinstance(ev, FaultEvent):
            res = engine.apply_fault(ev)
            # faults strand (sids leave the engine for the retry queue) and
            # recoveries re-admit: re-sync the live set either way
            live = set(engine.sids)
            if on_event is not None:
                on_event(ev, res)
            continue
        if ev.kind == "arrive":
            res = engine.add(make_vsr(ev.sid), sid=ev.sid)
            if res is not None:
                live.add(ev.sid)
        else:
            if ev.sid not in live:
                # not live -- but it may be parked in the retry queue
                # (stranded by a fault): a departure cancels the retry
                engine.cancel_queued(ev.sid)
                if on_event is not None:
                    on_event(ev, None)
                continue
            res = engine.remove(ev.sid)
            live.discard(ev.sid)
            live.update(s for s in engine.sids)  # queue re-admissions
        if on_event is not None:
            on_event(ev, res)
    return engine.stats


def _replay_waves(engine, events, make_vsr, on_event) -> List[OnlineStats]:
    """The ``replay(..., waves=True)`` loop: collect -> apply_wave ->
    background defrag tick, one pass per same-tick wave."""
    defrag_budget = getattr(engine.spec, "defrag_rows_per_tick", 0)
    for group in iter_waves(events):
        tick = getattr(engine, "tick", None)
        if tick is not None:
            tick(group[-1].t)
        if isinstance(group[0], FaultEvent):
            res = engine.apply_fault(group[0])
            if on_event is not None:
                on_event(group[0], res)
            continue
        live = set(engine.sids)
        arrivals, departures = [], []
        for ev in group:
            if ev.kind == "arrive":
                arrivals.append((make_vsr(ev.sid), ev.sid))
            elif ev.sid in live:
                departures.append(ev.sid)
            else:
                engine.cancel_queued(ev.sid)
        wres = engine.apply_wave(arrivals, departures)
        if defrag_budget:
            engine.defrag_tick()
        if on_event is not None:
            for ev in group:
                on_event(ev, wres)
    return engine.stats
