"""Analyzer self-tests (DESIGN.md §14).

Every rule gets fixture snippets it must fire on (golden findings) and
clean snippets it must stay silent on; plus framework behavior —
suppression comments, rule toggles, baseline diffing, stable keys — and
positive controls for the runtime layer (compile tracking, sync-region
counting, stray-pull interception).
"""
import json
import textwrap
from pathlib import Path

import pytest

from repro.analysis import lint
from repro.analysis.rules import all_rules, rule_names

REPO_ROOT = Path(__file__).resolve().parents[1]


def _scan(tmp_path, source, enabled=None):
    """Lint one fixture module; returns (findings, inline_suppressed)."""
    f = tmp_path / "fixture.py"
    f.write_text(textwrap.dedent(source))
    index = lint.build_index(tmp_path, [tmp_path])
    enabled_set = {enabled} if isinstance(enabled, str) else enabled
    return lint.run_rules(index, all_rules(), enabled=enabled_set)


def _rules_hit(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# host-sync
# ---------------------------------------------------------------------------

def test_host_sync_in_trace_reachable_from_hot_root(tmp_path):
    """`.item()` two calls below lm_prefill is flagged via reachability."""
    findings, _ = _scan(tmp_path, """
        import jax.numpy as jnp

        def _helper(x):
            return _inner(x)

        def _inner(x):
            return x.item()

        def lm_prefill(params, caches, batch, cfg):
            return _helper(jnp.ones(3))
        """, enabled="host-sync")
    assert len(findings) == 1
    assert findings[0].symbol == "_inner"
    assert ".item()" in findings[0].message


def test_host_sync_driver_loop_flags_and_coercion_heuristic(tmp_path):
    """np.asarray + int() on jit results inside a driver loop are flagged;
    int() on config scalars is not."""
    findings, _ = _scan(tmp_path, """
        import jax, numpy as np

        @jax.jit
        def fwd(x):
            return x * 2

        def drive(xs, cfg):
            out = []
            for x in xs:
                y = fwd(x)
                out.append(np.asarray(y))       # flagged
                n = int(y[0])                   # flagged
                m = int(cfg.d_model * 4)        # static python: silent
            return out
        """, enabled="host-sync")
    assert len(findings) == 2
    assert all(f.symbol == "drive" for f in findings)


def test_host_sync_declared_sync_region_is_exempt(tmp_path):
    findings, _ = _scan(tmp_path, """
        import jax, numpy as np
        from repro.analysis.runtime import sync_region

        @jax.jit
        def fwd(x):
            return x * 2

        def drive(xs):
            out = []
            for x in xs:
                y = fwd(x)
                with sync_region("drive"):
                    out.append(np.asarray(y))   # declared: exempt
            return out
        """, enabled="host-sync")
    assert findings == []


def test_host_sync_static_argnames_not_device(tmp_path):
    """Params declared static in the jit decorator are python values."""
    findings, _ = _scan(tmp_path, """
        import functools, jax

        @functools.partial(jax.jit, static_argnames=("ticks",))
        def _decode_chunk(tok, ticks):
            n = int(ticks) + 1        # static: silent
            m = float(tok)            # traced param: flagged
            return tok * n * m
        """, enabled="host-sync")
    assert len(findings) == 1
    assert "`float()`" in findings[0].message


# ---------------------------------------------------------------------------
# prng-reuse
# ---------------------------------------------------------------------------

def test_prng_consumed_twice(tmp_path):
    findings, _ = _scan(tmp_path, """
        import jax

        def sample(key):
            a = jax.random.normal(key, (3,))
            b = jax.random.uniform(key, (3,))
            return a + b
        """, enabled="prng-reuse")
    assert len(findings) == 1
    assert "consumed twice" in findings[0].message


def test_prng_consume_then_derive(tmp_path):
    findings, _ = _scan(tmp_path, """
        import jax

        def init(key):
            w = my_init(key, 16)
            k2 = jax.random.fold_in(key, 1)
            return w, my_init(k2, 16)
        """, enabled="prng-reuse")
    assert len(findings) == 1
    assert "split/fold_in parent" in findings[0].message


def test_prng_loop_consumption(tmp_path):
    findings, _ = _scan(tmp_path, """
        import jax

        def roll(key, n):
            outs = []
            for i in range(n):
                outs.append(jax.random.normal(key, (2,)))
            return outs
        """, enabled="prng-reuse")
    assert len(findings) >= 1
    assert "inside a loop" in findings[0].message


def test_prng_clean_patterns_stay_silent(tmp_path):
    """split-reassign, per-iteration fold_in, exclusive return branches,
    and keys passed through jnp selectors are all fine."""
    findings, _ = _scan(tmp_path, """
        import jax, jax.numpy as jnp

        def good_split(key):
            key, sub = jax.random.split(key)
            a = jax.random.normal(sub, (3,))
            b = jax.random.normal(key, (3,))
            return a + b

        def good_fold_loop(key, n):
            return [jax.random.normal(jax.random.fold_in(key, i), (2,))
                    for i in range(n)]

        def good_branches(key, kind):
            if kind == "a":
                return init_a(key)
            if kind == "b":
                return init_b(key)
            raise ValueError(kind)

        def good_select(key, t):
            k2, sub = jax.random.split(key)
            tok = jax.random.categorical(sub, t)
            return tok, jnp.where(t > 0, k2, key)
        """, enabled="prng-reuse")
    assert findings == []


# ---------------------------------------------------------------------------
# recompile-hazard
# ---------------------------------------------------------------------------

def test_recompile_jit_in_loop_and_immediate(tmp_path):
    findings, _ = _scan(tmp_path, """
        import jax

        def bench(xs):
            for x in xs:
                f = jax.jit(lambda v: v * 2)    # flagged: jit in loop
                f(x)
            return jax.jit(lambda v: v + 1)(xs[0])   # flagged: immediate
        """, enabled="recompile-hazard")
    msgs = sorted(f.message for f in findings)
    assert len(findings) == 2
    assert any("inside a loop" in m for m in msgs)
    assert any("invoked immediately" in m for m in msgs)


def test_recompile_static_arg_hazards(tmp_path):
    findings, _ = _scan(tmp_path, """
        import functools, jax

        @functools.partial(jax.jit, static_argnames=("cfg", "start"))
        def prefill(tokens, cfg, start=0):
            return tokens[start:]

        def admit(reqs, tokens):
            prefill(tokens, cfg=[1, 2, 3])            # unhashable static
            prefill(tokens, cfg=lambda: 3)            # fresh lambda static
            for r in reqs:
                start = r.hit_len
                prefill(tokens, cfg=(), start=start)  # loop-varying static
        """, enabled="recompile-hazard")
    msgs = sorted(f.message for f in findings)
    assert len(findings) == 3
    assert any("unhashable literal" in m for m in msgs)
    assert any("fresh lambda" in m for m in msgs)
    assert any("reassigned inside the enclosing loop" in m for m in msgs)


def test_recompile_stable_static_calls_stay_silent(tmp_path):
    findings, _ = _scan(tmp_path, """
        import functools, jax

        @functools.partial(jax.jit, static_argnames=("ticks",))
        def chunk(tok, ticks):
            return tok * ticks

        def drive(tok, n):
            f = jax.jit(lambda v: v * 2)   # bound outside any loop
            for _ in range(n):
                tok = chunk(tok, ticks=4)  # constant static: one compile
                tok = f(tok)
            return tok
        """, enabled="recompile-hazard")
    assert findings == []


def test_recompile_naive_adaptive_driver_antipattern(tmp_path):
    """The DESIGN.md §15 hazard the AdaptiveChunkPolicy exists to avoid:
    a serving loop that feeds an unbounded load signal straight into the
    static chunk-length argument compiles one XLA variant per distinct
    load level — the rule must flag the loop-varying static."""
    findings, _ = _scan(tmp_path, """
        import functools, jax

        @functools.partial(jax.jit, static_argnames=("ticks",))
        def decode_chunk(tok, ticks):
            return tok * ticks

        def serve(engine, tok):
            while engine.pending:
                ticks = engine.queue_depth        # unbounded load signal
                tok = decode_chunk(tok, ticks=ticks)
            return tok
        """, enabled="recompile-hazard")
    assert len(findings) == 1
    assert "reassigned inside the enclosing loop" in findings[0].message


def test_recompile_sweep_clean_over_adaptive_serving_path():
    """The real adaptive code path (serving/slo.py + the engine's
    _next_ticks -> step wiring) must carry zero NEW recompile-hazard
    findings: the policy's frozen level ladder, not a loop-varying
    static, feeds the ``ticks`` static of ``_decode_chunk``.  (The one
    baselined finding — the justified per-prefix-bucket ``start`` static
    of ``_paged_prefill_step`` — is allowed to survive, nothing else.)"""
    serving = REPO_ROOT / "src" / "repro" / "serving"
    index = lint.build_index(REPO_ROOT, [serving])
    findings, _ = lint.run_rules(index, all_rules(),
                                 enabled={"recompile-hazard"})
    stray = [f.format() for f in findings
             if not ("_paged_prefill_step" in f.message
                     and "`start`" in f.message)]
    assert stray == []
    # and nothing — baselined or not — implicates the adaptive path
    assert not [f for f in findings
                if "slo" in f.path or "`ticks`" in f.message]


# ---------------------------------------------------------------------------
# pallas-constraints
# ---------------------------------------------------------------------------

def test_pallas_missing_interpret_path(tmp_path):
    findings, _ = _scan(tmp_path, """
        import jax.experimental.pallas as pl
        import jax.numpy as jnp

        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def run(x):
            return pl.pallas_call(
                kernel, grid=(4,),
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            )(x)
        """, enabled="pallas-constraints")
    assert len(findings) == 1
    assert "interpret" in findings[0].message


def test_pallas_index_map_arity_and_coords(tmp_path):
    findings, _ = _scan(tmp_path, """
        import jax.experimental.pallas as pl

        def run(x, *, interpret=False):
            return pl.pallas_call(
                kern, grid=(4, 2),
                in_specs=[pl.BlockSpec((8, 8), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 8), lambda i, j: (i, j, 0)),
                interpret=interpret,
            )(x)
        """, enabled="pallas-constraints")
    msgs = sorted(f.message for f in findings)
    assert len(findings) == 2
    assert any("takes 1 args but grid rank 2" in m for m in msgs)
    assert any("returns 3 coords but block_shape has 2 dims" in m for m in msgs)


def test_pallas_traced_capture_flagged_and_static_capture_not(tmp_path):
    findings, _ = _scan(tmp_path, """
        import jax.experimental.pallas as pl
        import jax.numpy as jnp

        def run(x, table, *, bm: int = 8, interpret=False):
            ps = x.shape[1]                  # provably static
            live = jnp.sum(table)            # traced!
            def pool_map(i, j):
                return (live + i * bm, ps)
            return pl.pallas_call(
                kern, grid=(4, 2),
                in_specs=[pl.BlockSpec((bm, ps), pool_map)],
                interpret=interpret,
            )(x)
        """, enabled="pallas-constraints")
    assert len(findings) == 1
    assert "captures `live`" in findings[0].message


def test_pallas_prefetch_grid_spec_arity(tmp_path):
    """index_map params = grid rank + num_scalar_prefetch, resolved
    through a local grid_spec binding."""
    findings, _ = _scan(tmp_path, """
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def run(x, tbl, *, interpret=False):
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(4, 2),
                in_specs=[pl.BlockSpec((8, 8), lambda i, j, t: (i, j))],
                out_specs=pl.BlockSpec((8, 8), lambda i, j: (i, j)),
            )
            return pl.pallas_call(
                kern, grid_spec=grid_spec, interpret=interpret)(x, tbl)
        """, enabled="pallas-constraints")
    # out_specs map misses the prefetch ref: 2 params != 2 + 1
    assert len(findings) == 1
    assert "takes 2 args but grid rank 2 + 1" in findings[0].message


def test_pallas_partial_index_maps_in_a_spread_spec_list(tmp_path):
    """One index map per page slot -- ``functools.partial`` of a nested
    def binding a keyword-only slot, spread into the grid spec from a
    local list -- is checked like a plain one: its arity counts the
    positional parameters, the bound slot is no capture, a traced
    capture is flagged once however often the list is spread."""
    findings, _ = _scan(tmp_path, """
        import functools
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
        import jax.numpy as jnp

        def run(x, tbl, *, interpret=False):
            live = jnp.sum(tbl)              # traced!
            def pool_map(i, j, t, *, p):
                return (live + i + p, j)
            pages = [pl.BlockSpec((8, 8), functools.partial(pool_map, p=p))
                     for p in range(2)]
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(4, 2),
                in_specs=[*pages, *pages],
                out_specs=pl.BlockSpec((8, 8), lambda i, j, t: (i, j)),
            )
            return pl.pallas_call(
                kern, grid_spec=grid_spec, interpret=interpret)(x, tbl)
        """, enabled="pallas-constraints")
    assert len(findings) == 1
    assert "`pool_map` captures `live`" in findings[0].message


# ---------------------------------------------------------------------------
# framework: suppressions, toggles, baseline, keys
# ---------------------------------------------------------------------------

SUPPRESSED_SRC = """
    import jax

    def sample(key):
        a = jax.random.normal(key, (3,))
        b = jax.random.uniform(key, (3,))  # lint: ignore[prng-reuse]
        return a + b
    """


def test_inline_suppression_comment(tmp_path):
    findings, suppressed = _scan(tmp_path, SUPPRESSED_SRC)
    assert [f for f in findings if f.rule == "prng-reuse"] == []
    assert suppressed == 1


def test_inline_suppression_is_rule_scoped(tmp_path):
    findings, suppressed = _scan(tmp_path, SUPPRESSED_SRC.replace(
        "ignore[prng-reuse]", "ignore[host-sync]"))
    assert len([f for f in findings if f.rule == "prng-reuse"]) == 1
    assert suppressed == 0


def test_rule_toggles(tmp_path):
    src = """
        import jax

        def bad(key, xs):
            a = jax.random.normal(key, (3,))
            b = jax.random.normal(key, (3,))
            for x in xs:
                f = jax.jit(lambda v: v)
                f(x)
            return a + b
        """
    both, _ = _scan(tmp_path, src)
    only_prng, _ = _scan(tmp_path, src, enabled="prng-reuse")
    assert _rules_hit(both) == {"prng-reuse", "recompile-hazard"}
    assert _rules_hit(only_prng) == {"prng-reuse"}


def test_baseline_diff_and_stale(tmp_path):
    findings, _ = _scan(tmp_path, SUPPRESSED_SRC.replace(
        "  # lint: ignore[prng-reuse]", ""))
    base_path = tmp_path / "baseline.json"
    lint.write_baseline(base_path, findings)
    baseline = lint.load_baseline(base_path)
    # same findings: all known, none new
    diff = lint.diff_baseline(findings, baseline)
    assert diff.new == [] and len(diff.known) == 1 and diff.stale == []
    # a new violation shows up as new without touching known
    diff2 = lint.diff_baseline(findings + [lint.Finding(
        rule="prng-reuse", path="other.py", line=3, col=0,
        symbol="g", message="key `k` consumed twice without an interleaving split/fold_in")],
        baseline)
    assert len(diff2.new) == 1 and len(diff2.known) == 1
    # fixed finding -> stale baseline entry
    diff3 = lint.diff_baseline([], baseline)
    assert len(diff3.stale) == 1


def test_finding_keys_are_line_number_free(tmp_path):
    src = SUPPRESSED_SRC.replace("  # lint: ignore[prng-reuse]", "")
    f1, _ = _scan(tmp_path, src)
    f2, _ = _scan(tmp_path, "import os\nimport sys\n\n" + textwrap.dedent(src))
    assert [f.key() for f in f1] == [f.key() for f in f2]
    assert f1[0].line != f2[0].line


def test_repo_sweep_is_clean_against_checked_in_baseline():
    """The gate check.sh runs: the tree must lint clean vs the baseline,
    with no stale entries left behind either."""
    report = lint.run_project(REPO_ROOT)
    assert [f.format() for f in report.diff.new] == []
    assert report.diff.stale == []
    # every baselined suppression carries a real justification
    baseline = lint.load_baseline(REPO_ROOT / lint.BASELINE_NAME)
    assert len(baseline) == len(report.diff.known)
    for key, entry in baseline.items():
        assert entry.get("note") and "TODO" not in entry["note"], key


def test_every_rule_has_a_baselined_or_fixed_real_finding():
    """Acceptance: each rule produced at least one real finding in the
    sweep — surviving ones must be baselined (the fixed ones are gone)."""
    baseline = lint.load_baseline(REPO_ROOT / lint.BASELINE_NAME)
    rules_in_baseline = {e["rule"] for e in baseline.values()}
    assert rules_in_baseline == set(rule_names())


# ---------------------------------------------------------------------------
# runtime layer: positive controls
# ---------------------------------------------------------------------------

def test_runtime_compile_tracker_sees_fresh_compile():
    import jax
    import jax.numpy as jnp

    from repro.analysis import runtime as art

    f = jax.jit(lambda x: x * 3 + 1)
    tracker = art.CompileTracker(f=f)
    before = tracker.snapshot()
    f(jnp.ones((4,)))                        # first call compiles
    mid = tracker.snapshot()
    f(jnp.ones((4,)))                        # cache hit
    after = tracker.snapshot()
    assert art.CompileTracker.new_compiles(before, mid)["f"] == 1
    assert art.CompileTracker.new_compiles(mid, after)["f"] == 0
    assert art.CompileTracker.new_compiles(mid, after)["_events"] == 0


def test_runtime_sync_region_counts_and_pull_attribution():
    import jax.numpy as jnp
    import numpy as np

    from repro.analysis import runtime as art

    x = jnp.arange(8)
    before_regions = art.region_counts().get("unit-test", 0)
    with art.measure_pulls() as pulls:
        with art.sync_region("unit-test"):
            np.asarray(x)
    assert art.region_counts()["unit-test"] == before_regions + 1
    assert pulls.get("unit-test", 0) >= 1


def test_runtime_no_host_sync_raises_on_stray_pull():
    import jax.numpy as jnp
    import numpy as np

    from repro.analysis import runtime as art

    x = jnp.arange(8)
    with pytest.raises(art.HostSyncError):
        with art.no_host_sync(strict=True):
            np.asarray(x)                    # undeclared pull
    # declared pulls pass, and the patch is removed afterwards
    with art.no_host_sync(strict=True):
        with art.sync_region("declared"):
            assert int(np.asarray(x)[3]) == 3
    assert np.asarray(x).shape == (8,)
