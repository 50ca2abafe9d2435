"""Tests for the device-purity auditor (``repro.analysis``).

Three layers: golden jaxpr snapshots of the registered Pallas entry points
(a changed primitive histogram means the lowering changed -- bump the
snapshot deliberately, not accidentally), unit tests of each AST lint rule
on synthetic snippets, and the end-to-end contracts the CI gate stands on
(repo is finding-free vs the checked-in baseline, the 3-segment adaptive
rerun compiles nothing).
"""
import ast
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import Finding, load_baseline, new_findings, run_all
from repro.analysis.ast_rules import (
    _RingViewLinter,
    _TaintLinter,
    discover_contexts,
)
from repro.analysis.jaxpr_audit import (
    PALLAS_COVERAGE,
    REGISTRY,
    TIER_DEVICE,
    VMEM_HEADROOM,
    VMEM_LIMIT_BYTES,
    audit_entry,
    get_entry,
    primitive_counts,
)
from repro.analysis.retrace import CompileCacheGuard, run_retrace_audit


# -- golden jaxpr snapshots ----------------------------------------------------
# Full recursive primitive histograms of the three consolidation-loop Pallas
# entries, traced at the registry's production shapes (T = 230). These are
# *snapshots*: a diff here is not necessarily a bug, but it is always a
# lowering change on a hot path and must be reviewed (then re-recorded).

GOLDEN_PRIMITIVES = {
    "kernels.consolidation.consolidation_scores": {
        "add": 4, "broadcast_in_dim": 9, "concatenate": 2,
        "convert_element_type": 5, "div": 1, "dot_general": 1, "eq": 1,
        "gather": 1, "get": 7, "gt": 1, "iota": 3, "jit": 5, "lt": 2,
        "max": 1, "min": 1, "mul": 2, "pad": 1, "pallas_call": 1,
        "reduce_max": 1, "reduce_sum": 2, "reshape": 4, "select_n": 3,
        "slice": 2, "squeeze": 2, "sub": 1, "swap": 2, "transpose": 2,
    },
    "kernels.telemetry.pair_scatter": {
        "add": 3, "broadcast_in_dim": 5, "cond": 1, "convert_element_type": 2,
        "dot_general": 3, "eq": 2, "get": 6, "iota": 1, "jit": 1, "mul": 2,
        "pallas_call": 1, "program_id": 1, "reshape": 1, "slice": 2,
        "squeeze": 2, "swap": 5, "transpose": 1,
    },
    "engine.make_scorer[pallas]": {
        "add": 4, "broadcast_in_dim": 10, "concatenate": 2,
        "convert_element_type": 5, "div": 1, "dot_general": 1, "eq": 1,
        "gather": 1, "get": 7, "gt": 1, "iota": 3, "jit": 6, "lt": 2,
        "max": 1, "min": 1, "mul": 3, "pad": 1, "pallas_call": 1,
        "reduce_max": 1, "reduce_sum": 2, "reshape": 4, "select_n": 3,
        "slice": 2, "squeeze": 2, "sub": 1, "swap": 2, "transpose": 2,
    },
}


@pytest.mark.parametrize("name", sorted(GOLDEN_PRIMITIVES))
def test_golden_primitive_counts(name):
    entry = get_entry(name)
    closed, _ = entry.trace()
    assert primitive_counts(closed.jaxpr) == GOLDEN_PRIMITIVES[name], (
        f"the lowering of {name} changed -- review the diff, then update "
        "GOLDEN_PRIMITIVES")


def test_registry_is_clean():
    """Every registered hot entry audits with zero findings."""
    for entry in REGISTRY:
        findings, info = audit_entry(entry)
        assert findings == [], [f.render() for f in findings]
        if entry.pallas:
            assert info["pallas_sites"], f"{entry.name}: no pallas_call traced"


def test_pallas_sites_under_budget():
    budget = int(VMEM_LIMIT_BYTES * VMEM_HEADROOM)
    seen_files = set()
    for entry in REGISTRY:
        if not entry.pallas:
            continue
        _, info = audit_entry(entry)
        for site in info["pallas_sites"]:
            assert 0 < site["resident_bytes"] <= budget, site
        seen_files.add(entry.name)
    # the coverage list that gates new pallas_call sites is non-trivial
    assert len(PALLAS_COVERAGE) >= 5


def test_device_tier_rejects_callback():
    """A host callback inside a device-tier entry is flagged."""
    from repro.analysis.jaxpr_audit import HotEntry, _check_eqns

    def leaky(x):
        jax.debug.print("x = {}", x)  # lowers to debug_print
        return x * 2.0

    entry = HotEntry("test.leaky", TIER_DEVICE,
                     lambda: (leaky, (jnp.ones((4,), jnp.float32),)))
    closed, _ = entry.trace()
    rules = {f.rule for f in _check_eqns(entry, closed)}
    assert "host-callback" in rules


def test_donation_missing_flagged():
    """An entry registered as donating whose trace never donates is flagged."""
    from repro.analysis.jaxpr_audit import HotEntry, _check_donation

    entry = HotEntry("test.nodonate", TIER_DEVICE,
                     lambda: (jax.jit(lambda x: x + 1.0),
                              (jnp.ones((4,), jnp.float32),)),
                     donated=True)
    closed, _ = entry.trace()
    rules = {f.rule for f in _check_donation(entry, closed)}
    assert "donation-missing" in rules


# -- AST rules on synthetic snippets -------------------------------------------

def _lint(src: str) -> list[Finding]:
    tree = ast.parse(textwrap.dedent(src))
    contexts = discover_contexts(tree)
    traced = {id(c.fn) for c in contexts}
    findings = []
    for ctx in contexts:
        findings += _TaintLinter(ctx, "snippet.py", traced).run()
    ring = _RingViewLinter("snippet.py")
    ring.visit(tree)
    return findings + ring.findings


def _rules(src: str) -> set:
    return {f.rule for f in _lint(src)}


def test_ast_traced_branch():
    assert "traced-branch" in _rules("""
        @jax.jit
        def f(x):
            if x > 0:
                return x
            return -x
    """)


def test_ast_traced_branch_static_ok():
    """Branching on static_argnames or shape metadata never flags."""
    assert _rules("""
        @partial(jax.jit, static_argnames=("mode",))
        def f(x, mode):
            if mode == "fast":
                return x
            m, n = x.shape
            if m > n:
                return x.T
            return x
    """) == set()


def test_ast_np_on_traced():
    assert "np-on-traced" in _rules("""
        @jax.jit
        def f(x):
            return np.asarray(x).sum()
    """)


def test_ast_host_item_and_coercion():
    rules = _rules("""
        @jax.jit
        def f(x):
            a = x.sum().item()
            b = float(x[0])
            return a + b
    """)
    assert "host-item" in rules and "host-coercion" in rules


def test_ast_loop_body_context():
    """while_loop bodies are traced contexts even without a jit decorator."""
    assert "traced-branch" in _rules("""
        def body(carry):
            if carry > 0:
                carry = carry - 1
            return carry
        def run(x):
            return jax.lax.while_loop(lambda c: c > 0, body, x)
    """)


def test_ast_pallas_kernel_context():
    """pallas_call kernels are traced contexts; kwargs stay static config."""
    findings = _rules("""
        def kernel(x_ref, o_ref, *, causal):
            if causal:
                o_ref[...] = x_ref[...]
            v = float(x_ref[0])
        def launch(x):
            return pl.pallas_call(kernel, out_shape=x)(x)
    """)
    # `if causal:` is partial-bound config (kw-only) -- not flagged;
    # float(x_ref[0]) syncs a traced ref -- flagged
    assert "traced-branch" not in findings
    assert "host-coercion" in findings


def test_ast_taint_propagates_through_assignment():
    assert "traced-branch" in _rules("""
        @jax.jit
        def f(x):
            y = x * 2
            z = y + 1
            if z > 0:
                return z
            return -z
    """)


def test_ast_stale_ring_view():
    assert "stale-ring-view" in _rules("""
        def f(ring, block):
            v = ring.view()
            ring.push(block)
            return v.co.sum()
    """)


def test_ast_ring_view_before_push_ok():
    assert "stale-ring-view" not in _rules("""
        def f(ring, block):
            v = ring.view()
            total = v.co.sum()
            ring.push(block)
            return total
    """)


# -- pair_scatter index-space contract -----------------------------------------

def test_pair_scatter_bounds_assert():
    from repro.kernels.telemetry import pair_scatter

    T = 16
    cbar = jnp.ones((3, T), jnp.float32)
    vals = jnp.ones((3,), jnp.float32)
    # negative types are the padding/eviction contract: accepted, dropped
    p, b = pair_scatter(jnp.array([0, -1, 5], jnp.int32), cbar, vals,
                        interpret=True)
    assert float(b.sum()) == 2.0
    # >= T is a misrouted index: debug mode (default under interpret) raises
    with pytest.raises(ValueError, match="index-space contract"):
        pair_scatter(jnp.array([0, T, 5], jnp.int32), cbar, vals,
                     interpret=True)
    # ... but the kernel's silent-drop semantics stay reachable
    p, b = pair_scatter(jnp.array([0, T, 5], jnp.int32), cbar, vals,
                        interpret=True, debug=False)
    assert float(b.sum()) == 2.0
    # under an enclosing trace the host check self-disables
    f = jax.jit(lambda t: pair_scatter(t, cbar, vals, interpret=True))
    f(jnp.array([0, T, 5], jnp.int32))


# -- compile-cache guard -------------------------------------------------------

def test_compile_cache_guard_counts_traces():
    @jax.jit
    def f(x):
        return x * 2.0

    with CompileCacheGuard({"f": f}) as g:
        f(jnp.ones((4,)))          # one trace
        f(jnp.ones((4,)))          # cache hit
    assert g.deltas == {"f": 1}

    with CompileCacheGuard({"f": f}) as g:
        f(jnp.ones((8,)))          # new shape: one more trace
    assert g.new_traces() == {"f": 1}
    with pytest.raises(AssertionError, match="compile-cache guard"):
        g.assert_max(0)

    with CompileCacheGuard({"f": f}) as g:
        f(jnp.ones((4,)))          # warm
    assert g.new_traces() == {}
    g.assert_max(0)


def test_adaptive_rerun_zero_recompiles():
    """The acceptance criterion: a 3-segment AdaptiveEngine stream run,
    rerun on the same engine, triggers zero new traces anywhere in the
    tracked per-segment hot loop."""
    stats = {}
    findings = run_retrace_audit(stats, segments=3)
    assert findings == [], [f.render() for f in findings]
    r = stats["retrace"]
    assert r["rerun_total"] == 0, r
    # warm run: at most one trace per tracked function (shared segment shape)
    assert all(v == 1 for v in r["warm_traces"].values()), r


# -- the CI contract -----------------------------------------------------------

def test_repo_is_finding_free():
    """The full static audit (jaxpr + AST) vs the checked-in baseline: zero
    unbaselined findings. This is exactly what the CI static-analysis job
    enforces via ``python -m repro.analysis``."""
    findings, stats = run_all(retrace=False)
    fresh = new_findings(findings, load_baseline())
    assert fresh == [], [f.render() for f in fresh]
    assert len(stats["jaxpr"]) == len(REGISTRY)
    assert stats["ast"]["files"] > 50


def test_finding_key_ignores_detail():
    """Baseline keys must survive rewording: detail is excluded."""
    a = Finding("ast", "traced-branch", "x.py:3", "old wording")
    b = Finding("ast", "traced-branch", "x.py:3", "new wording")
    assert a.key() == b.key()
