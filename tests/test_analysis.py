"""Tests for the dittolint analysis subsystem (DESIGN.md §12).

Covers all three passes: per-rule AST fixtures + the disable escape,
jaxpr-audit fixtures, the recompile-count regression sweep, and one
mutation test per sanitizer invariant (each corruption must fire with
its rule id; clean traces must pass; ``sanitize=False`` must stay
bit-identical).
"""

import dataclasses
import functools
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import all_rules, astlint, jaxpr_audit, sanitize
from repro.core.cache import access_group, run_trace
from repro.core.types import (CacheConfig, init_cache, init_clients,
                              init_stats)
from repro.workloads.plan import GroupPlan, plan_groups

ROOT = Path(__file__).resolve().parent.parent

pytestmark = pytest.mark.fast


# ----------------------------------------------------------------------
# Pass 1: AST lint
# ----------------------------------------------------------------------

def _rules_of(findings):
    return {f.rule for f in findings}


class TestAstLint:
    def test_dl001_traced_branch(self):
        src = ("import jax.numpy as jnp\n"
               "def f(x):\n"
               "    if jnp.sum(x) > 0:\n"
               "        return 1\n"
               "    return 0\n")
        assert "DL001" in _rules_of(
            astlint.lint_source(src, "src/repro/core/x.py"))
        # Out of scope (not a traced module): silent.
        assert "DL001" not in _rules_of(
            astlint.lint_source(src, "src/repro/workloads/x.py"))

    def test_dl002_key_reuse(self):
        src = ("import jax\n"
               "def f(key):\n"
               "    a = jax.random.uniform(key)\n"
               "    b = jax.random.normal(key)\n"
               "    return a + b\n")
        fs = astlint.lint_source(src, "src/repro/core/x.py")
        assert "DL002" in _rules_of(fs)
        # The canonical re-threading idiom is clean: split rebinds the
        # name on the same line that consumes it.
        ok = ("import jax\n"
              "def f(key):\n"
              "    key, sub = jax.random.split(key)\n"
              "    a = jax.random.uniform(sub)\n"
              "    b = jax.random.normal(key)\n"
              "    return a + b\n")
        assert "DL002" not in _rules_of(
            astlint.lint_source(ok, "src/repro/core/x.py"))

    def test_dl002_nested_def_own_scope(self):
        src = ("import jax\n"
               "def outer(key):\n"
               "    a = jax.random.uniform(key)\n"
               "    def inner(key):\n"
               "        return jax.random.normal(key)\n"
               "    return a\n")
        assert "DL002" not in _rules_of(
            astlint.lint_source(src, "src/repro/core/x.py"))

    def test_dl003_hot_path_sort(self):
        src = "import jax.numpy as jnp\ndef f(x):\n    return jnp.argsort(x)\n"
        assert "DL003" in _rules_of(
            astlint.lint_source(src, "src/repro/kernels/x.py"))
        # Cold-path module: allowed (elastic drain, models, ...).
        assert "DL003" not in _rules_of(
            astlint.lint_source(src, "src/repro/models/x.py"))

    def test_dl004_wide_dtypes(self):
        for snippet in ("x.astype(jnp.float64)", "x.astype(float)",
                        "jnp.zeros((2,), dtype=jnp.int64)"):
            src = f"import jax.numpy as jnp\ndef f(x):\n    return {snippet}\n"
            assert "DL004" in _rules_of(
                astlint.lint_source(src, "src/repro/core/x.py")), snippet

    def test_dl005_interpret_true(self):
        sig = "def f(x, interpret=True):\n    return x\n"
        call = ("import jax.numpy as jnp\n"
                "def f(x):\n"
                "    return pl.pallas_call(k, interpret=True)(x)\n")
        assert "DL005" in _rules_of(
            astlint.lint_source(sig, "src/repro/kernels/x.py"))
        assert "DL005" in _rules_of(
            astlint.lint_source(call, "src/repro/kernels/x.py"))
        # Tests may hard-pin the interpreter.
        assert "DL005" not in _rules_of(
            astlint.lint_source(sig, "tests/test_x.py"))

    def test_dl006_mutable_defaults(self):
        fn = "def f(x, acc=[]):\n    return acc\n"
        dc = ("import dataclasses\n"
              "@dataclasses.dataclass\n"
              "class C:\n"
              "    xs: list = []\n")
        assert "DL006" in _rules_of(astlint.lint_source(fn, "src/a.py"))
        assert "DL006" in _rules_of(astlint.lint_source(dc, "src/a.py"))

    def test_disable_comment_same_line_and_next_line(self):
        same = ("import jax.numpy as jnp\n"
                "def f(x):\n"
                "    return jnp.argsort(x)  # dittolint: disable=DL003\n")
        prev = ("import jax.numpy as jnp\n"
                "def f(x):\n"
                "    # segment packing, not ranking. dittolint: disable=DL003\n"
                "    return jnp.argsort(x)\n")
        wrong_rule = ("import jax.numpy as jnp\n"
                      "def f(x):\n"
                      "    return jnp.argsort(x)  # dittolint: disable=DL004\n")
        p = "src/repro/kernels/x.py"
        assert not astlint.lint_source(same, p)
        assert not astlint.lint_source(prev, p)
        assert "DL003" in _rules_of(astlint.lint_source(wrong_rule, p))

    def test_shipped_tree_clean(self):
        assert astlint.lint_paths([str(ROOT / "src" / "repro")]) == []

    def test_syntax_error_reported(self):
        fs = astlint.lint_source("def f(:\n", "src/broken.py")
        assert [f.rule for f in fs] == ["DL000"]


# ----------------------------------------------------------------------
# Pass 2: jaxpr audit
# ----------------------------------------------------------------------

class TestJaxprAudit:
    def test_jx001_wide_dtype(self):
        with jax.enable_x64(True):
            closed = jax.make_jaxpr(
                lambda x: x.astype(jnp.float64) * 2)(
                    jnp.ones((4,), jnp.float32))
        assert "JX001" in {f.rule for f in
                           jaxpr_audit.audit_closed(closed, "fx")}

    def test_jx002_round_trip(self):
        closed = jax.make_jaxpr(
            lambda x: x.astype(jnp.float32).astype(jnp.uint32))(
                jnp.ones((4,), jnp.uint32))
        assert "JX002" in {f.rule for f in
                           jaxpr_audit.audit_closed(closed, "fx")}

    def test_jx002_budget(self):
        closed = jax.make_jaxpr(
            lambda x: x.astype(jnp.float32))(jnp.ones((4,), jnp.uint32))
        over = jaxpr_audit.audit_closed(closed, "fx", convert_budget=0)
        under = jaxpr_audit.audit_closed(closed, "fx", convert_budget=10)
        assert "JX002" in {f.rule for f in over}
        assert "JX002" not in {f.rule for f in under}

    def test_jx003_callback(self):
        def f(x):
            jax.debug.print("x={x}", x=x)
            return x * 2
        closed = jax.make_jaxpr(f)(jnp.ones((4,)))
        assert "JX003" in {f.rule for f in
                           jaxpr_audit.audit_closed(closed, "fx")}

    def test_jx004_dead_output(self):
        closed = jax.make_jaxpr(
            lambda x: (x * 2, jnp.zeros((2,), jnp.float32)))(jnp.ones((4,)))
        assert "JX004" in {f.rule for f in
                           jaxpr_audit.audit_closed(closed, "fx")}
        clean = jax.make_jaxpr(lambda x: (x * 2, x + 1))(jnp.ones((4,)))
        assert not jaxpr_audit.audit_closed(clean, "fx")

    def test_jx005_weak_type_flap(self):
        n = jaxpr_audit.count_retraces(
            lambda x: x * 2, [(1.0,), (jnp.float32(1.0),)])
        assert n == 2  # one shape signature, two compiles: the bug class

    def test_core_entry_points_clean(self):
        # The in-tests subset of the full audit (the CLI runs the rest):
        # both backends, 1 and 2 tenants, widths 1 and 8, no dm/retrace.
        fs = jaxpr_audit.audit_entry_points(
            widths=(1, 8), tenants=(1, 2), include_dm=False,
            retrace_widths=())
        assert fs == []


class TestRecompileRegression:
    @pytest.mark.parametrize("backend", ["reference", "fused"])
    def test_access_group_one_trace_per_width(self, backend):
        """Satellite: widths 1/8/32/128 x both backends — each entry
        point traces at most once per shape signature."""
        widths = (1, 8, 32, 128)
        cfg = CacheConfig(n_buckets=64, assoc=4, capacity=64, hist_len=64,
                          backend=backend)
        st = init_cache(cfg)
        cl = init_clients(cfg, 4)
        sa = init_stats()
        calls = [(st, cl, sa, jnp.ones((g, 4), jnp.uint32))
                 for g in widths]
        n = jaxpr_audit.count_retraces(
            functools.partial(access_group, cfg), calls)
        assert n == len(widths), (
            f"{backend}: {n} compiles for {len(widths)} width signatures")


# ----------------------------------------------------------------------
# Pass 3: sanitizer mutation tests
# ----------------------------------------------------------------------

def _seeded(n_tenants=1, backend="reference", steps=1):
    kw = dict(n_buckets=64, assoc=4, capacity=64, hist_len=64,
              backend=backend, n_tenants=n_tenants)
    if n_tenants > 1:
        kw["tenant_budget_blocks"] = tuple([32] * n_tenants)
    cfg = CacheConfig(**kw)
    st, cl, sa = init_cache(cfg), init_clients(cfg, 4), init_stats()
    keys = (jnp.arange(1, 33, dtype=jnp.uint32).reshape(8, 4) % 7) + 1
    ten = (keys % n_tenants).astype(jnp.uint32) if n_tenants > 1 else None
    for _ in range(steps):
        st, cl, sa, _ = access_group(
            cfg, st, cl, sa, keys, is_write=jnp.ones((8, 4), bool),
            tenant=ten)
    return cfg, st, cl, sa


def _fires(rule, probe):
    with pytest.raises(Exception, match=rule):
        probe()


class TestSanitizerMutations:
    def test_clean_state_passes(self):
        cfg, st, cl, _ = _seeded(n_tenants=2)
        scfg = dataclasses.replace(cfg, sanitize=True)
        sanitize.check_state(scfg, st)     # eager: raises on failure
        sanitize.check_clients(scfg, cl)

    def test_san001_byte_drift(self):
        cfg, st, _, _ = _seeded()
        bad = st._replace(bytes_cached=st.bytes_cached + 5)
        _fires("SAN001", lambda: sanitize.check_state(
            cfg, bad, rules=["SAN001"]))

    def test_san002_tenant_overshoot(self):
        cfg, st, _, _ = _seeded(n_tenants=2)
        over = st._replace(tenant_bytes=st.tenant_budget + 1,
                           bytes_cached=jnp.sum(st.tenant_budget + 1))
        _fires("SAN002", lambda: sanitize.check_step(
            cfg, st, over, rules=["SAN002"]))

    def test_san002_column_sum_drift(self):
        cfg, st, _, _ = _seeded(n_tenants=2)
        bad = st._replace(tenant_bytes=st.tenant_bytes.at[0].add(3))
        _fires("SAN002", lambda: sanitize.check_state(
            cfg, bad, rules=["SAN002"]))

    def test_san002_shrunk_budget_is_legal(self):
        # Occupancy above a freshly shrunken budget must NOT fire — only
        # *growing* while over budget does.
        cfg, st, _, _ = _seeded(n_tenants=2)
        shrunk = st._replace(tenant_budget=jnp.zeros_like(st.tenant_budget))
        sanitize.check_step(cfg, shrunk, shrunk, rules=["SAN002"])

    def test_san003_duplicate_key(self):
        cfg, st, _, _ = _seeded()
        bad = st._replace(key=st.key.at[0].set(7).at[1].set(7),
                          size=st.size.at[0].set(1).at[1].set(1))
        _fires("SAN003", lambda: sanitize.check_state(
            cfg, bad, rules=["SAN003"]))

    def test_san004_off_simplex(self):
        cfg, st, cl, _ = _seeded()
        bad = st._replace(weights=st.weights * 0 + 2.0)
        _fires("SAN004", lambda: sanitize.check_state(
            cfg, bad, rules=["SAN004"]))
        badc = cl._replace(local_weights=cl.local_weights - 1.0)
        _fires("SAN004", lambda: sanitize.check_clients(
            cfg, badc, rules=["SAN004"]))

    def test_san005_timestamp(self):
        cfg, st, _, _ = _seeded()
        bad = st._replace(size=st.size.at[0].set(1),
                          last_ts=st.last_ts.at[0].set(st.clock + 5))
        _fires("SAN005", lambda: sanitize.check_state(
            cfg, bad, rules=["SAN005"]))
        back = st._replace(clock=st.clock - 1)
        _fires("SAN005", lambda: sanitize.check_step(
            cfg, st, back, rules=["SAN005"]))

    def test_san006_overlapping_plan(self):
        k = np.full((1, 2, 1), 7, np.uint32)
        plan = GroupPlan(k, np.zeros_like(k, bool), np.ones_like(k),
                         np.zeros_like(k, np.int32), batch=2,
                         scope="strict")
        fs = sanitize.check_plan(plan, 64)
        assert fs and all(f.rule == "SAN006" for f in fs)
        with pytest.raises(ValueError, match="SAN006"):
            sanitize.assert_plan_ok(plan, 64)

    def test_san006_lane_write_reuse(self):
        k = np.full((1, 2, 1), 7, np.uint32)
        w = np.zeros_like(k, bool)
        w[0, 1, 0] = True           # second visit writes: not read-read
        plan = GroupPlan(k, w, np.ones_like(k), np.zeros_like(k, np.int32),
                         batch=2, scope="lane")
        assert sanitize.check_plan(plan, 64)
        ok = GroupPlan(k, np.zeros_like(k, bool), np.ones_like(k),
                       np.zeros_like(k, np.int32), batch=2, scope="lane")
        assert not sanitize.check_plan(ok, 64)  # read-read reuse is legal

    def test_san006_program_order(self):
        k = np.full((2, 1, 1), 7, np.uint32)
        src = np.array([[[5]], [[2]]], np.int32)   # row 5 before row 2
        plan = GroupPlan(k, np.zeros_like(k, bool), np.ones_like(k), src,
                         batch=1, scope="strict")
        assert "SAN006" in {f.rule for f in sanitize.check_plan(plan, 64)}

    def test_planner_output_validates(self):
        rng = np.random.RandomState(1)
        keys = (rng.zipf(1.3, size=(40, 8)) % 61 + 1).astype(np.uint32)
        wr = rng.rand(40, 8) < 0.3
        for scope in ("strict", "lane"):
            plan = plan_groups(keys, 64, 4, scope=scope, is_write=wr,
                               validate=True)
            assert sanitize.check_plan(plan, 64) == []


class TestSanitizedExecution:
    @pytest.mark.parametrize("backend", ["reference", "fused"])
    def test_clean_trace_passes_and_bit_identical(self, backend):
        cfg = CacheConfig(n_buckets=64, assoc=4, capacity=64, hist_len=64,
                          backend=backend)
        scfg = dataclasses.replace(cfg, sanitize=True)
        st, cl = init_cache(cfg), init_clients(cfg, 4)
        keys = (jnp.arange(1, 121, dtype=jnp.uint32).reshape(30, 4) % 19) + 1
        wr = jnp.ones_like(keys, dtype=bool).at[15:].set(False)
        res_s = sanitize.checked(
            lambda: run_trace(scfg, st, cl, keys, wr))()
        res_p = run_trace(cfg, st, cl, keys, wr)
        for a, b in zip(jax.tree.leaves(res_s), jax.tree.leaves(res_p)):
            assert bool((a == b).all())

    def test_sanitized_step_catches_corrupt_carry(self):
        # The step recomputes byte counters and renormalizes weights, so
        # those corruptions cannot survive it.  Duplicate live keys in a
        # bucket the step does not touch DO persist — the post-step hook
        # must catch them (consistent byte counters keep SAN001 quiet, so
        # the duplicate itself is what fires).
        cfg, st, cl, sa = _seeded()
        scfg = dataclasses.replace(cfg, sanitize=True)
        bad = st._replace(
            key=st.key.at[0].set(999).at[1].set(999),
            size=st.size.at[0].set(1).at[1].set(1),
            bytes_cached=st.bytes_cached + 2,
            n_cached=st.n_cached + 2)
        keys = jnp.ones((1, 4), jnp.uint32) * 3
        with pytest.raises(Exception, match="SAN003"):
            access_group(scfg, bad, cl, sa, keys)


# ----------------------------------------------------------------------
# CLI exit codes
# ----------------------------------------------------------------------

class TestCli:
    def _run(self, *args):
        return subprocess.run(
            [sys.executable, str(ROOT / "scripts" / "dittolint.py"), *args],
            capture_output=True, text=True, timeout=300)

    def test_clean_tree_exits_zero(self):
        r = self._run(str(ROOT / "src" / "repro"))
        assert r.returncode == 0, r.stdout + r.stderr

    def test_demo_fires_nonzero(self):
        r = self._run("--demo", "DL003")
        assert r.returncode == 1, r.stdout + r.stderr
        assert "DL003" in r.stdout

    def test_unknown_rule_usage_error(self):
        r = self._run("--demo", "DL999")
        assert r.returncode == 2

    def test_finding_exits_one(self, tmp_path):
        bad = tmp_path / "src" / "repro" / "kernels"
        bad.mkdir(parents=True)
        f = bad / "x.py"
        f.write_text("import jax.numpy as jnp\n"
                     "def f(x):\n"
                     "    return jnp.argsort(x)\n")
        r = self._run(str(f))
        assert r.returncode == 1
        assert "DL003" in r.stdout

    def test_all_rules_catalogued(self):
        cat = all_rules()
        assert len(cat) == 19
        assert {r[:2] for r in cat} == {"DL", "JX", "SA"}
