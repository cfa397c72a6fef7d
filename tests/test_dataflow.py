"""Flow-sensitive tracelint rules (CFN106-CFN109) and the dataflow engine.

Pure-AST (no jax import): each rule family gets violation fixtures with
the exact rule id asserted and clean twins that must produce nothing --
including the sanctioned idioms the engine must NOT flag (the
``key, k = split(key)`` loop carry, ``fold_in`` stream derivation,
split-array indexing, rebinding after donation).  Also covers pragma
suppression for the new ids, and the move-stability contract: a baseline
fingerprint survives the offending function moving to another file.
"""
import json
import textwrap
from pathlib import Path

from repro.analysis import (CACHE_CAPS, analyze_paths, analyze_source,
                            apply_baseline, baseline_payload,
                            compute_cache_bounds)
from repro.analysis.engine import load_project

REPO = Path(__file__).resolve().parents[1]


def findings_for(src, path="<string>"):
    return analyze_source(textwrap.dedent(src), path=path)


def hits(findings, rule):
    return [f for f in findings if f.rule == rule]


# ---------------------------------------------------------------------------
# CFN106: PRNG-key discipline
# ---------------------------------------------------------------------------

def test_cfn106_key_consumed_by_two_draws():
    fs = findings_for("""\
        import jax

        def f(key):
            a = jax.random.uniform(key, (4,))
            b = jax.random.normal(key, (4,))
            return a + b
    """)
    got = hits(fs, "CFN106")
    assert got and got[0].line == 5 and "2 draws" in got[0].message


def test_cfn106_branch_exclusive_double_use_still_flagged():
    # path-insensitive by design: nothing ties the branches' streams apart
    fs = findings_for("""\
        import jax

        def f(key, masked):
            if masked:
                u = jax.random.uniform(key, (4,))
            else:
                u = jax.random.normal(key, (4,))
            return u
    """)
    assert hits(fs, "CFN106")


def test_cfn106_split_then_draw_clean():
    fs = findings_for("""\
        import jax

        def f(key):
            k1, k2 = jax.random.split(key)
            a = jax.random.uniform(k1, (4,))
            b = jax.random.normal(k2, (4,))
            return a + b
    """)
    assert not hits(fs, "CFN106")


def test_cfn106_fold_in_two_stream_idiom_clean():
    # fold_in derives an independent stream WITHOUT consuming its argument
    fs = findings_for("""\
        import jax

        def f(key):
            a = jax.random.randint(key, (4,), 0, 10)
            b = jax.random.uniform(jax.random.fold_in(key, 1), (4,))
            return a + b
    """)
    assert not hits(fs, "CFN106")


def test_cfn106_loop_fanout_without_split():
    fs = findings_for("""\
        import jax

        def f(key, xs):
            out = []
            for x in xs:
                out.append(jax.random.uniform(key, (4,)))
            return out
    """)
    got = hits(fs, "CFN106")
    assert got and "loop" in got[0].message


def test_cfn106_loop_carry_split_clean():
    # key, k = split(key): the canonical per-iteration carry
    fs = findings_for("""\
        import jax

        def f(key, xs):
            out = []
            for x in xs:
                key, k = jax.random.split(key)
                out.append(jax.random.uniform(k, (4,)))
            return out
    """)
    assert not hits(fs, "CFN106")


def test_cfn106_dropped_split_output():
    fs = findings_for("""\
        import jax

        def f(key):
            k1, k2 = jax.random.split(key)
            return jax.random.uniform(k1, (4,))
    """)
    got = hits(fs, "CFN106")
    assert got and "`k2`" in got[0].message and "never used" in got[0].message


def test_cfn106_underscore_split_output_clean():
    fs = findings_for("""\
        import jax

        def f(key):
            k1, _k2 = jax.random.split(key)
            return jax.random.uniform(k1, (4,))
    """)
    assert not hits(fs, "CFN106")


def test_cfn106_split_array_index_reuse_flagged_distinct_clean():
    # ks = split(key, 3) is an ARRAY of keys: ks[0] twice is a double
    # draw, ks[0]/ks[1] is clean
    bad = findings_for("""\
        import jax

        def f(key):
            ks = jax.random.split(key, 3)
            a = jax.random.uniform(ks[0], (4,))
            b = jax.random.normal(ks[0], (4,))
            return a + b
    """)
    assert hits(bad, "CFN106")
    clean = findings_for("""\
        import jax

        def f(key):
            ks = jax.random.split(key, 3)
            a = jax.random.uniform(ks[0], (4,))
            b = jax.random.normal(ks[1], (4,))
            return a + b
    """)
    assert not hits(clean, "CFN106")


def test_cfn106_interprocedural_consumption_through_helper():
    # the second consumption happens INSIDE a callee: still two draws
    fs = findings_for("""\
        import jax

        def helper(k):
            return jax.random.normal(k, (4,))

        def f(key):
            a = jax.random.uniform(key, (4,))
            return a + helper(key)
    """)
    assert hits(fs, "CFN106")


# ---------------------------------------------------------------------------
# CFN107: donation & aliasing
# ---------------------------------------------------------------------------

_DONATE = textwrap.dedent("""\
    import jax

    def update(state, x):
        return state + x

    step = jax.jit(update, donate_argnums=(0,))

""")


def test_cfn107_read_after_donation():
    fs = findings_for(_DONATE + textwrap.dedent("""\
        def run(state, x):
            new = step(state, x)
            return state + new
    """))
    got = hits(fs, "CFN107")
    assert got and "donated" in got[0].message


def test_cfn107_rebind_idiom_clean():
    fs = findings_for(_DONATE + textwrap.dedent("""\
        def run(state, x):
            state = step(state, x)
            return state
    """))
    assert not hits(fs, "CFN107")


def test_cfn107_donated_buffer_aliased_in_same_call():
    fs = findings_for(_DONATE + textwrap.dedent("""\
        def run(state):
            return step(state, state)
    """))
    got = hits(fs, "CFN107")
    assert got and "alias" in got[0].message


# ---------------------------------------------------------------------------
# CFN108: compile-cache cardinality
# ---------------------------------------------------------------------------

_ENTRY = textwrap.dedent("""\
    import jax
    import jax.numpy as jnp
    from .solvers import count_traces

    def _pow2(n, lo=2):
        b = lo
        while b < n:
            b *= 2
        return b

    @jax.jit
    @count_traces("kern")
    def kern(x):
        return x * 2

""")


def test_cfn108_unbounded_provenance_reaching_entry():
    fs = findings_for(_ENTRY + textwrap.dedent("""\
        def run():
            import time
            n = time.time()
            return kern(jnp.zeros(int(n)))
    """), path="src/repro/core/mymod.py")
    got = hits(fs, "CFN108")
    assert got and "unbounded" in got[0].message


def test_cfn108_bucketed_shapes_clean():
    fs = findings_for(_ENTRY + textwrap.dedent("""\
        def run(xs):
            return kern(jnp.zeros(_pow2(len(xs))))
    """), path="src/repro/core/mymod.py")
    assert not hits(fs, "CFN108")


def test_cfn108_static_bound_over_cap():
    # three independent pow-2 bucket axes: 8^3 = 512 > the default cap
    fs = findings_for(_ENTRY + textwrap.dedent("""\
        def run(a, b, c):
            x = jnp.zeros((_pow2(a), _pow2(b), _pow2(c)))
            return kern(x)
    """), path="src/repro/core/mymod.py")
    got = hits(fs, "CFN108")
    assert got and "exceeds" in got[0].message


def test_cfn108_shipped_bounds_under_caps():
    """The committed tree's entries all sit under their declared caps."""
    project, errs = load_project([str(REPO / "src")])
    assert not errs
    bounds = compute_cache_bounds(project)
    for entry in ("sweep", "anneal_delta", "anneal_full", "solve_regions",
                  "warm_start_program"):
        eb = bounds[entry]
        b = eb.static_bound()
        assert b is not None, f"{entry}: unbounded static provenance"
        assert b <= CACHE_CAPS[entry], f"{entry}: {b} > cap"


# ---------------------------------------------------------------------------
# CFN109: dead device compute
# ---------------------------------------------------------------------------

def test_cfn109_dead_device_array():
    fs = findings_for("""\
        import jax.numpy as jnp

        def f(x):
            y = jnp.sum(x * x)
            return x
    """)
    got = hits(fs, "CFN109")
    assert got and "`y`" in got[0].message


def test_cfn109_dead_host_transfer():
    # the PR 7 bug class: np.asarray(device_value) never consumed
    fs = findings_for("""\
        import numpy as np

        def f(state):
            snapshot = np.asarray(state)
            return state
    """)
    assert hits(fs, "CFN109")


def test_cfn109_consumed_and_underscore_clean():
    fs = findings_for("""\
        import jax.numpy as jnp

        def f(x):
            y = jnp.sum(x * x)
            _warm = jnp.ones((4,))
            return y
    """)
    assert not hits(fs, "CFN109")


# ---------------------------------------------------------------------------
# suppression + fingerprint stability
# ---------------------------------------------------------------------------

def test_flow_rule_pragma_right_id_suppresses_wrong_id_does_not():
    src = """\
        import jax

        def f(key):
            a = jax.random.uniform(key, (4,))
            b = jax.random.normal(key, (4,))  # tracelint: allow[CFN106]
            return a + b
    """
    assert not hits(findings_for(src), "CFN106")
    wrong = src.replace("allow[CFN106]", "allow[CFN104]")
    assert hits(findings_for(wrong), "CFN106")


def test_baseline_fingerprint_survives_cross_file_move(tmp_path):
    body = textwrap.dedent("""\
        import jax

        def correlated(key):
            a = jax.random.uniform(key, (4,))
            b = jax.random.normal(key, (4,))
            return a + b
    """)
    (tmp_path / "alpha.py").write_text(body)
    (tmp_path / "beta.py").write_text("import jax\n")
    fs = analyze_paths([str(tmp_path)])
    assert hits(fs, "CFN106")
    baseline = set(json.loads(json.dumps(
        baseline_payload(fs)))["suppressions"])
    # move the function (with extra padding lines) to the OTHER file
    (tmp_path / "alpha.py").write_text("import jax\n")
    (tmp_path / "beta.py").write_text("import jax\n\n\n" + body[len("import jax\n"):])
    moved = analyze_paths([str(tmp_path)])
    assert hits(moved, "CFN106")
    assert apply_baseline(moved, baseline) == []
