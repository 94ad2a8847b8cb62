"""The static-analysis pass is itself under test: every lint rule has a
good/bad fixture pair (including the pragma escapes), the compile-budget
sentinel must catch an artificially injected re-trace, and the HLO
checker must flag a seeded f64 leak / host callback.

The dynamic sentinel tests run REAL tiny plans (seconds, CPU) — the same
canonical world `python -m repro.analysis` uses.
"""
import json

import pytest

from repro.analysis import compile_budget, hlo_lint
from repro.analysis.lint import lint_source


def rules_of(violations):
    return sorted({v.rule for v in violations})


# ---------------------------------------------------------------------------
# R1 — PRNG key discipline
# ---------------------------------------------------------------------------

class TestR1KeyReuse:
    def test_key_used_twice_flagged(self):
        src = """
import jax

def f(key):
    a = jax.random.normal(key, (3,))
    b = jax.random.normal(key, (3,))
    return a + b
"""
        vs = lint_source(src, rules=["R1"])
        assert rules_of(vs) == ["R1"]
        assert "key" in vs[0].message

    def test_split_then_use_clean(self):
        src = """
import jax

def f(key):
    k1, k2 = jax.random.split(key)
    a = jax.random.normal(k1, (3,))
    b = jax.random.normal(k2, (3,))
    return a + b
"""
        assert lint_source(src, rules=["R1"]) == []

    def test_rebinding_resets_consumption(self):
        src = """
import jax

def f(key):
    for _ in range(3):
        key, sub = jax.random.split(key)
        x = jax.random.normal(sub, (3,))
    return x
"""
        assert lint_source(src, rules=["R1"]) == []

    def test_loop_reuse_without_rebind_flagged(self):
        src = """
import jax

def f(key):
    out = []
    for _ in range(3):
        out.append(jax.random.normal(key, (3,)))
    return out
"""
        assert rules_of(lint_source(src, rules=["R1"])) == ["R1"]

    def test_exclusive_branches_not_flagged(self):
        # the engine's dropout split: both branches consume `key`, but
        # only one executes
        src = """
import jax

def f(key, dropout: float):
    if dropout:
        k1, k2, k3 = jax.random.split(key, 3)
    else:
        k1, k2 = jax.random.split(key, 2)
    return jax.random.normal(k1, (3,))
"""
        assert lint_source(src, rules=["R1"]) == []

    def test_fold_in_loop_is_blessed(self):
        src = """
import jax

def f(key):
    return [jax.random.normal(jax.random.fold_in(key, i), (3,))
            for i in range(4)]
"""
        assert lint_source(src, rules=["R1"]) == []

    def test_fold_in_same_constant_twice_flagged(self):
        src = """
import jax

def f(key):
    a = jax.random.fold_in(key, 7)
    b = jax.random.fold_in(key, 7)
    return a, b
"""
        assert rules_of(lint_source(src, rules=["R1"])) == ["R1"]

    def test_seed_ladder_flagged_and_fold_in_clean(self):
        ladder = """
import jax

def bench():
    p = jax.random.normal(jax.random.key(0), (3,))
    q = jax.random.normal(jax.random.key(1), (3,))
    return p, q
"""
        vs = lint_source(ladder, rules=["R1"])
        assert rules_of(vs) == ["R1"]
        assert "fold_in" in vs[0].message

        fixed = """
import jax

def bench():
    base = jax.random.key(0)
    p = jax.random.normal(jax.random.fold_in(base, 0), (3,))
    q = jax.random.normal(jax.random.fold_in(base, 1), (3,))
    return p, q
"""
        assert lint_source(fixed, rules=["R1"]) == []

    def test_pragma_suppresses(self):
        src = """
import jax

def f(key):
    a = jax.random.normal(key, (3,))
    b = jax.random.normal(key, (3,))  # lint: key-reuse-ok
    return a + b
"""
        assert lint_source(src, rules=["R1"]) == []


# ---------------------------------------------------------------------------
# R2 — host syncs reachable from jit roots
# ---------------------------------------------------------------------------

class TestR2HostSync:
    def test_item_in_jitted_function_flagged(self):
        src = """
import jax

@jax.jit
def step(x):
    return x * x.sum().item()
"""
        vs = lint_source(src, rules=["R2"])
        assert rules_of(vs) == ["R2"]
        assert ".item()" in vs[0].message

    def test_reachability_through_call_chain(self):
        src = """
import jax
import numpy as np

def helper(x):
    return np.asarray(x)

@jax.jit
def step(x):
    return helper(x) * 2
"""
        vs = lint_source(src, rules=["R2"])
        assert rules_of(vs) == ["R2"]
        assert "helper" in vs[0].message

    def test_unreachable_host_code_not_flagged(self):
        src = """
import numpy as np

def host_only(x):
    return float(np.asarray(x).mean())
"""
        assert lint_source(src, rules=["R2"]) == []

    def test_float_on_static_shape_not_flagged(self):
        src = """
import jax

@jax.jit
def step(x):
    scale = float(x.shape[0])
    return x / scale
"""
        assert lint_source(src, rules=["R2"]) == []

    def test_float_on_traced_value_flagged(self):
        src = """
import jax

@jax.jit
def step(x):
    return x / float(x)
"""
        assert rules_of(lint_source(src, rules=["R2"])) == ["R2"]

    def test_pragma_suppresses(self):
        src = """
import jax
import numpy as np

@jax.jit
def step(x):
    c = np.asarray([1.0, 2.0])  # lint: host-sync-ok
    return x * c[0]
"""
        assert lint_source(src, rules=["R2"]) == []


# ---------------------------------------------------------------------------
# R3 — traced-value branching in engine/kernels modules
# ---------------------------------------------------------------------------

class TestR3StaticBranch:
    PATH = "src/repro/kernels/fixture.py"

    def test_branch_on_traced_value_flagged(self):
        src = """
import jax.numpy as jnp

def f(x):
    if jnp.sum(x) > 0:
        return x
    return -x
"""
        vs = lint_source(src, path=self.PATH, rules=["R3"])
        assert rules_of(vs) == ["R3"]
        assert "static-branch" in vs[0].message

    def test_shape_and_config_branches_clean(self):
        src = """
def f(x, cfg, causal: bool = True, block: int = 128):
    if x.ndim != 2:
        raise ValueError(f"bad rank {x.shape}")
    if cfg.use_masks:
        block = block * 2
    if causal and x.shape[0] % block == 0:
        return x
    return -x
"""
        assert lint_source(src, path=self.PATH, rules=["R3"]) == []

    def test_propagated_config_scalar_clean(self):
        # the PR 6 `if alpha > 0:` pattern — static via assignment from a
        # config attribute chain
        src = """
def f(state, cfg):
    alpha = cfg.feddyn.alpha
    if alpha > 0:
        return state
    return None
"""
        assert lint_source(src, path=self.PATH, rules=["R3"]) == []

    def test_pragma_allows_static_branch(self):
        src = """
def f(x, flags):
    if flags[0]:  # lint: static-branch
        return x
    return -x
"""
        assert lint_source(src, path=self.PATH, rules=["R3"]) == []

    def test_out_of_scope_module_not_checked(self):
        src = """
import jax.numpy as jnp

def f(x):
    if jnp.sum(x) > 0:
        return x
    return -x
"""
        assert lint_source(src, path="src/repro/launch/fixture.py",
                           rules=["R3"]) == []


# ---------------------------------------------------------------------------
# R4 / R5
# ---------------------------------------------------------------------------

class TestR4R5:
    def test_bare_assert_in_kernels_flagged(self):
        src = """
def kernel(x, block: int = 128):
    assert x.shape[0] % block == 0
    return x
"""
        vs = lint_source(src, path="src/repro/kernels/fixture.py",
                         rules=["R4"])
        assert rules_of(vs) == ["R4"]
        assert "ValueError" in vs[0].message
        # same snippet outside kernels/ is fine (pytest-style asserts etc.)
        assert lint_source(src, path="src/repro/core/fixture.py",
                           rules=["R4"]) == []

    def test_mutable_default_flagged(self):
        src = """
def f(x, acc=[]):
    acc.append(x)
    return acc
"""
        assert rules_of(lint_source(src, rules=["R5"])) == ["R5"]

    def test_module_level_jnp_flagged_and_pragma(self):
        src = """
import jax.numpy as jnp

TABLE = jnp.arange(16)
"""
        vs = lint_source(src, rules=["R5"])
        assert rules_of(vs) == ["R5"]
        assert "import time" in vs[0].message

        src_ok = """
import jax.numpy as jnp

TABLE = jnp.arange(16)  # lint: import-time-ok

def f(x):
    y = jnp.zeros_like(x)
    return y
"""
        assert lint_source(src_ok, rules=["R5"]) == []


# ---------------------------------------------------------------------------
# Compile-budget sentinel
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def world():
    return compile_budget.make_world()


class TestCompileBudget:
    def test_budget_file_is_source_of_truth(self):
        budget = compile_budget.load_budget()
        names = {sc.name for sc in compile_budget.scenarios()}
        assert names == set(budget["scenarios"])
        for name, entry in budget["scenarios"].items():
            assert entry["programs"] >= 1, name
        # the specific counts the repo's tests rely on
        assert compile_budget.expected_programs("local/prune_mask") == 1
        assert compile_budget.expected_programs("mesh/prune_mask") == 1
        assert compile_budget.expected_programs("mesh/mask_then_shrink") == 2

    def test_canonical_scenario_within_budget(self, world):
        sc = next(s for s in compile_budget.scenarios()
                  if s.name == "local/scan_eval")
        errors = compile_budget.check(scenario_list=[sc], world=world)
        assert errors == []

    def test_injected_retrace_is_caught(self, world):
        """Negative proof: a plan with TWO distinct chunk lengths against
        a budget that promises ONE program must fail, naming the plan
        event after which the count jumped."""
        from repro.core import Eval, Scan, Snapshot, TrainPlan

        sc = compile_budget.Scenario(
            "local/injected_retrace", "local",
            lambda: TrainPlan(Scan(1), Snapshot(), Scan(2), Eval()))
        budget = {"scenarios": {"local/injected_retrace": {"programs": 1}}}
        errors = compile_budget.check(budget=budget, scenario_list=[sc],
                                      world=world)
        assert len(errors) == 1
        assert "local/injected_retrace" in errors[0]
        assert "Scan(rounds=2)" in errors[0]   # the event that re-traced

    def test_missing_scenario_is_reported(self, world):
        sc = next(s for s in compile_budget.scenarios()
                  if s.name == "local/scan_eval")
        errors = compile_budget.check(budget={"scenarios": {}},
                                      scenario_list=[sc], world=world)
        assert len(errors) == 1 and "--update" in errors[0]


# ---------------------------------------------------------------------------
# HLO invariant checker
# ---------------------------------------------------------------------------

class TestHloLint:
    def test_f64_leak_detected(self):
        leaky = """
HloModule leak

ENTRY %main (p0: f32[4]) -> f64[4] {
  %p0 = f32[4]{0} parameter(0)
  ROOT %c = f64[4]{0} convert(f32[4]{0} %p0)
}
"""
        assert hlo_lint.f64_ops(leaky) > 0

    def test_clean_f32_program_has_no_f64(self):
        import jax
        import jax.numpy as jnp

        txt = jax.jit(lambda x: jnp.sin(x) * 2.0).lower(
            jnp.zeros((4,), jnp.float32)).compile().as_text()
        assert hlo_lint.f64_ops(txt) == 0
        assert hlo_lint.host_callbacks(txt) == []

    def test_host_callback_detected(self):
        txt = """
HloModule cb

ENTRY %main (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %cb = f32[4]{0} custom-call(f32[4]{0} %p0), custom_call_target="xla_python_cpu_callback"
  %tok = token[] after-all()
  %inf = (f32[4]{0}, token[]) infeed(token[] %tok)
  ROOT %r = f32[4]{0} add(f32[4]{0} %cb, f32[4]{0} %p0)
}
"""
        found = hlo_lint.host_callbacks(txt)
        assert any("callback" in f for f in found)
        assert any("infeed" in f for f in found)

    def test_local_chunk_invariants(self, world):
        """The real local chunk: no f64, no collectives, no callbacks."""
        from repro.launch import hlo_cost

        txt, _ = hlo_lint._lower_chunk("local", world)
        assert hlo_lint.f64_ops(txt) == 0
        assert hlo_lint.host_callbacks(txt) == []
        cm = hlo_cost.HloCostModel(txt)
        assert dict(cm.entry_cost().collective_counts) == {}


# ---------------------------------------------------------------------------
# Layering: the model, kernel and round layers never reach up into launch/
# ---------------------------------------------------------------------------

def _imported_modules(path, package):
    """Every module an ``import`` or ``from ... import`` in ``path`` names
    (``from a import b`` yields ``a.b``, which may name an attribute of
    ``a``; relative imports resolved against ``package``)."""
    import ast

    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                parts = package.split(".")
                parent = parts[:len(parts) + 1 - node.level]
                base = ".".join(parent + ([base] if base else []))
            yield from (f"{base}.{a.name}" for a in node.names)


def test_lower_layers_do_not_import_launch():
    import pathlib

    src = pathlib.Path(__file__).resolve().parents[1] / "src"
    bad = []
    for layer in ("models", "kernels", "core"):
        for path in sorted((src / "repro" / layer).rglob("*.py")):
            package = ".".join(path.relative_to(src).parent.parts)
            for mod in _imported_modules(path, package):
                if (mod == "repro.launch" or mod.startswith("repro.launch.")) \
                        and not (mod == "repro.launch.mesh"
                                 or mod.startswith("repro.launch.mesh.")):
                    bad.append(f"{path.relative_to(src)}: {mod}")
    assert bad == [], bad
