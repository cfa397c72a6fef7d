"""Flow-sensitive tracelint rules (CFN106-CFN109) over ``dataflow``.

These are ``ProjectRule``s: one shared dataflow run per analysis
(``dataflow.analyze_dataflow``, memoized on the Project) feeds all four
families, and findings land on whichever module/line they belong to.

  CFN106  PRNG-key discipline -- a key consumed by two draws, a key
          defined outside a loop consumed inside it without a
          per-iteration split, a split output silently dropped.
  CFN107  donation & aliasing -- args at ``donate_argnums`` slots read
          (or written, incl. ``.at[].set`` / subscript stores) after the
          jitted call, and a donated buffer aliased by another argument
          slot of the same call.
  CFN108  compile-cache cardinality -- the statically bounded jit-cache
          key-space of every ``@count_traces`` entry; unbounded
          provenance reaching an entry, or a bound above the declared
          cap, is a finding.  ``compute_cache_bounds`` is the public API
          the runtime contract test cross-checks against TRACE_COUNTS.
  CFN109  dead device compute -- device arrays computed and never
          consumed (the ``np.asarray(st.X)`` bug class of PR 7).

Findings deliberately carry NO line numbers in their messages: the
baseline fingerprint is ``rule::context::message`` and must survive both
line shifts and a function moving across files.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .engine import Finding, Project, ProjectRule
from .dataflow import CacheAxis, EntryCall, analyze_dataflow

# ---------------------------------------------------------------------------
# CFN106: PRNG-key discipline
# ---------------------------------------------------------------------------

class PrngKeyDiscipline(ProjectRule):
    """Every ``jax.random`` draw must own its key.

    Three defects: (1) one key definition consumed by two or more draws
    (correlated streams -- the paper's Metropolis acceptance must be
    independent of its proposal stream); (2) a key defined outside a
    loop consumed inside it with no per-iteration ``split`` and no
    reassignment of the key in the loop body (every iteration replays
    the same stream); (3) a ``split`` output that is never read (a
    silently dropped stream -- usually a refactoring leftover).
    ``fold_in`` derives an independent stream without consuming its
    argument, so ``uniform(fold_in(k, 1))`` after ``randint(k, ...)``
    is the sanctioned two-stream idiom.  Consumption is counted
    path-insensitively: two branch-exclusive draws from one key are
    still flagged, because nothing ties the branches' streams apart.
    """

    id = "CFN106"
    title = "PRNG-key discipline"

    def check_project(self, project: Project) -> Iterable[Finding]:
        an = analyze_dataflow(project)
        for key in sorted(an.functions):
            facts = an.functions[key]
            mod = project.by_path.get(facts.path)
            if mod is None:
                continue
            # (1) multi-consumption of one definition (a merged binding --
            # `k = PRNGKey(0) if k is None else k` -- holds several def
            # sites, so dedupe by rendered message and line)
            emitted: Set[Tuple] = set()
            for site in sorted(facts.consumes,
                               key=lambda s: (s[1], s[2])):
                uses = facts.consumes[site]
                distinct = sorted({(u.line, u.col) for u in uses})
                if len(distinct) < 2:
                    continue
                var = uses[0].var
                hows = ", ".join(sorted({u.how for u in uses}))
                msg = (f"PRNG key `{var}` is consumed by {len(distinct)} "
                       f"draws ({hows}); split it (or fold_in) so every "
                       "draw owns an independent stream")
                if (distinct[1][0], msg) in emitted:
                    continue
                emitted.add((distinct[1][0], msg))
                yield self.finding(mod, distinct[1][0], msg)
            # (2) loop fan-out without a per-iteration split
            seen: Set[Tuple] = set()
            for site in sorted(facts.consumes,
                               key=lambda s: (s[1], s[2])):
                def_loops = facts.site_loops.get(site, frozenset())
                for u in facts.consumes[site]:
                    for loop_id in sorted(set(u.loops) - set(def_loops)):
                        stores = facts.loop_stores.get(loop_id, set())
                        if u.var in stores:
                            continue   # carry idiom: key, k = split(key)
                        k = (site, loop_id)
                        if k in seen:
                            continue
                        seen.add(k)
                        yield self.finding(
                            mod, u.line,
                            f"PRNG key `{u.var}` defined outside the loop "
                            "is consumed inside it without a per-iteration "
                            "split (every iteration replays the same "
                            "stream)")
            # (3) dropped split outputs
            for line, names, _loops in facts.split_assigns:
                for nm in names:
                    if nm.startswith("_") or nm == "<unpack>":
                        continue
                    if nm not in facts.loads:
                        yield self.finding(
                            mod, line,
                            f"split output `{nm}` is never used (a "
                            f"dropped stream; rename it to `_{nm}` if "
                            "that is intentional)")


# ---------------------------------------------------------------------------
# CFN107: donation & aliasing
# ---------------------------------------------------------------------------

class DonationDiscipline(ProjectRule):
    """``donate_argnums`` invalidates the caller's buffer: any later read
    (or subscript / ``.at[].set`` write) of a name still bound to the
    donated value is a use-after-free on the device, and passing the
    same buffer to a donated slot AND another slot of one call aliases
    input and output storage.  Rebinding (``x = step(x)``) is the clean
    idiom and is not flagged -- the new binding is a new definition."""

    id = "CFN107"
    title = "donation & aliasing"

    def check_project(self, project: Project) -> Iterable[Finding]:
        an = analyze_dataflow(project)
        for key in sorted(an.functions):
            facts = an.functions[key]
            mod = project.by_path.get(facts.path)
            if mod is None:
                continue
            seen: Set[Tuple] = set()
            for ev in facts.donation_events:
                k = (ev.kind, ev.var, ev.entry, ev.line)
                if k in seen:
                    continue
                seen.add(k)
                if ev.kind == "alias":
                    yield self.finding(
                        mod, ev.line,
                        f"`{ev.var}` is passed both to a donated slot of "
                        f"`{ev.entry}` and to another argument slot of the "
                        "same call (the donated buffer aliases a live "
                        "input)")
                else:
                    yield self.finding(
                        mod, ev.line,
                        f"`{ev.var}` is used after being donated to "
                        f"`{ev.entry}` (donate_argnums invalidates the "
                        "buffer; rebind the result instead)")


# ---------------------------------------------------------------------------
# CFN108: compile-cache cardinality
# ---------------------------------------------------------------------------

# Declared per-entry jit-cache caps: how many distinct cache keys the
# shape-bucket discipline is allowed to produce for each @count_traces
# entry at the documented deployment scale.  The runtime contract test
# (tests/test_cache_contract.py) cross-checks the static bound against
# measured TRACE_COUNTS.
CACHE_CAPS: Dict[str, int] = {
    "sweep": 64,
    "anneal_delta": 64,
    "anneal_full": 32,
    "solve_regions": 32,
    # one program per anneal: the re-solve's and anneal()'s per problem
    # shape (and backend), the fused kernel's best-chain pick
    "resolve_anneal": 64,
    "anneal_program": 32,
    "fused_pick": 32,
    # the warm starts' one program: per problem shape, mask or none, and
    # history bucket (coordinate, fixed_layer and the portfolio share it)
    "warm_start_program": 32,
}
DEFAULT_CACHE_CAP = 64

# default axis cardinalities for the STATIC bound: a pow-2 bucket axis
# can realize at most ~log2(R*V) distinct buckets at the documented max
# scale; a param axis is one compile per caller-supplied shape family.
STATIC_BUCKET_CARD = 8
STATIC_PARAM_CARD = 1


@dataclasses.dataclass
class EntryBound:
    """Static jit-cache key-space of one ``@count_traces`` entry.

    ``sites`` are its project-wide call sites; each carries the cache
    axes (provenance roots) of the values reaching the entry there.
    The bound is the sum over call sites of the product of axis
    cardinalities -- ``evaluate`` lets a runtime scenario substitute
    realized cardinalities (and drop unexercised sites) to compare
    against measured TRACE_COUNTS."""

    entry: str
    sites: List[EntryCall] = dataclasses.field(default_factory=list)

    def axes(self) -> Dict[str, CacheAxis]:
        out: Dict[str, CacheAxis] = {}
        for s in self.sites:
            for ax in s.axes:
                out.setdefault(ax.name, ax)
        return out

    @staticmethod
    def _card(ax: CacheAxis, axis_cards: Optional[Dict[str, int]],
              default_bucket: int, default_param: int) -> Optional[int]:
        if axis_cards and ax.name in axis_cards:
            return axis_cards[ax.name]
        if ax.kind == "finite":
            return ax.card
        if ax.kind == "param":
            return default_param
        if ax.kind == "bucket":
            return default_bucket
        if ax.kind == "unbounded":
            return None
        return 1

    def evaluate(self, sites: Optional[Sequence[str]] = None,
                 axis_cards: Optional[Dict[str, int]] = None,
                 default_bucket: int = 1,
                 default_param: int = 1) -> Optional[int]:
        """Bound under a scenario: ``sites`` restricts to call sites in
        the named enclosing functions (None = all); ``axis_cards`` maps
        axis names to realized cardinalities.  Returns None when an
        included axis is statically unbounded and not overridden."""
        total = 0
        for s in self.sites:
            if sites is not None and s.context not in sites:
                continue
            prod = 1
            for ax in s.axes:
                c = self._card(ax, axis_cards, default_bucket,
                               default_param)
                if c is None:
                    return None
                prod *= max(int(c), 1)
            total += prod
        return total

    def static_bound(self) -> Optional[int]:
        return self.evaluate(default_bucket=STATIC_BUCKET_CARD,
                             default_param=STATIC_PARAM_CARD)


def compute_cache_bounds(project: Project) -> Dict[str, EntryBound]:
    """Per-entry static jit-cache bounds over the whole project (the
    CFN108 substrate and the contract-test API)."""
    an = analyze_dataflow(project)
    out: Dict[str, EntryBound] = {
        name: EntryBound(name) for name in an.index.entry_defs}
    for c in an.entry_calls:
        out.setdefault(c.entry, EntryBound(c.entry)).sites.append(c)
    for eb in out.values():
        eb.sites.sort(key=lambda s: (s.path, s.line))
    return out


class CacheCardinality(ProjectRule):
    """Every ``@count_traces`` entry must have a statically BOUNDED
    jit-cache key-space under the declared caps: a value of unbounded
    provenance (I/O, wall clock, unresolved call with no rooted inputs)
    reaching an entry's static or shape-determining slots means every
    new value is a fresh compile -- exactly the regression the
    TRACE_COUNTS assertions exist to catch, caught at PR time."""

    id = "CFN108"
    title = "compile-cache cardinality"

    def check_project(self, project: Project) -> Iterable[Finding]:
        bounds = compute_cache_bounds(project)
        an = analyze_dataflow(project)
        for entry in sorted(bounds):
            eb = bounds[entry]
            unbounded = False
            for site in eb.sites:
                mod = project.by_path.get(site.path)
                if mod is None:
                    continue
                for ax in site.axes:
                    if ax.kind != "unbounded":
                        continue
                    unbounded = True
                    root = ax.name.split("@")[0]
                    slot = "a static arg slot" if ax.static \
                        else "a shape-determining slot"
                    yield self.finding(
                        mod, site.line,
                        f"entry `{entry}`: value of statically unbounded "
                        f"provenance ({root}) reaches {slot} of the "
                        "jitted call -- its jit-cache key-space is "
                        "unbounded (every new value is a fresh compile)")
            if unbounded:
                continue
            b = eb.static_bound()
            cap = CACHE_CAPS.get(entry, DEFAULT_CACHE_CAP)
            if b is not None and b > cap:
                ed = an.index.entry_defs.get(entry)
                if ed is None:
                    continue
                yield self.finding(
                    ed.mod, ed.fn.lineno,
                    f"entry `{entry}`: static jit-cache bound {b} exceeds "
                    f"the declared cap {cap} (tighten the shape bucketing "
                    "or raise CACHE_CAPS with justification)")


# ---------------------------------------------------------------------------
# CFN109: dead device compute
# ---------------------------------------------------------------------------

class DeadDeviceCompute(ProjectRule):
    """A device-producing call assigned to a name that is never read is
    wasted device compute -- and for ``np.asarray``/``np.array`` on
    device values, a dead device->host transfer that blocks the
    dispatch stream (the exact bug PR 7 had to find by hand).  Names
    prefixed ``_`` are exempt (the documented discard idiom)."""

    id = "CFN109"
    title = "dead device compute"

    def check_project(self, project: Project) -> Iterable[Finding]:
        an = analyze_dataflow(project)
        for key in sorted(an.functions):
            facts = an.functions[key]
            mod = project.by_path.get(facts.path)
            if mod is None:
                continue
            for line, name, call in sorted(facts.dead_assigns):
                yield self.finding(
                    mod, line,
                    f"device array `{name}` ({call}) is computed but "
                    "never consumed (dead compute / dead transfer; "
                    f"delete it or rename to `_{name}`)")


def flow_rules() -> List[ProjectRule]:
    return [PrngKeyDiscipline(), DonationDiscipline(), CacheCardinality(),
            DeadDeviceCompute()]
