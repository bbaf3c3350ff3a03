"""Compile-contract checker + lint gate (repro.analysis, DESIGN.md §9).

Each pass is held to "catches the seeded violation": a planted psum for
the collective census, a dtype-mismatched donation for the donation
audit, a shape-varying loop for the retrace guard, and lint fixture
snippets (with and without waiving pragmas) for each lint rule. The
roofline cost model is locked bit-identically to a saved-HLO golden so
the parser refactor (roofline -> analysis.hlo) stays observationally
invisible.
"""
import json
import os
import warnings

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import (Contract, ContractViolation, RetraceViolation,
                            assert_contract, audit_donation, check_hlo,
                            check_lowered, collective_census, compile_count,
                            contract, contract_of, guard_jit, parse_hlo,
                            parse_io_aliases, reset_guards)
from repro.analysis.lint import lint_source
from repro.analysis.retrace import GuardRecord

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
MIXED_HLO = os.path.join(FIXTURES, "roofline_mixed.hlo")
MIXED_GOLDEN = os.path.join(FIXTURES, "roofline_mixed.golden.json")


def _mixed_text():
    with open(MIXED_HLO, encoding="utf-8") as fh:
        return fh.read()


# ---------------------------------------------------------------------------
# shared HLO parser + roofline bit-identity
# ---------------------------------------------------------------------------

def test_roofline_cost_bit_identical_to_golden():
    """The saved 4-device mixed compile (dot + psum + scan + DUS) costs
    exactly what the pre-refactor parser computed — the parser move into
    analysis/hlo.py changed imports, not numbers."""
    from repro.roofline.analysis import hlo_cost, roofline_terms
    with open(MIXED_GOLDEN, encoding="utf-8") as fh:
        golden = json.load(fh)
    cost = hlo_cost(_mixed_text())
    assert cost.flops == golden["flops"]
    assert cost.bytes_accessed == golden["bytes_accessed"]
    assert dict(cost.collective_bytes) == golden["collective_bytes"]
    terms = roofline_terms(cost, n_chips=4)
    assert terms == golden["terms"]


def test_roofline_reexports_shared_parser():
    """roofline.analysis re-exports the moved parser (back-compat)."""
    import repro.analysis.hlo as hlo
    import repro.roofline.analysis as ra
    assert ra.parse_hlo is hlo.parse_hlo
    assert ra.COLLECTIVES is hlo.COLLECTIVES


def test_parse_hlo_finds_entry_and_scan_trip_count():
    comps, entry = parse_hlo(_mixed_text())
    assert entry in comps
    assert any(i.op == "all-reduce" for c in comps.values()
               for i in c.instrs)


# ---------------------------------------------------------------------------
# collective census
# ---------------------------------------------------------------------------

def test_census_counts_planted_psum():
    census = collective_census(_mixed_text())
    assert census["all-reduce"].count == 1
    assert census["all-reduce"].bytes > 0


def test_census_clean_module_is_empty():
    text = jax.jit(lambda x: x @ x).lower(
        jnp.ones((8, 8))).compile().as_text()
    assert collective_census(text) == {}


def test_contract_catches_planted_psum():
    """collectives=0 must reject the module with the planted psum; the
    exact per-family count must accept it and reject a wrong family."""
    text = _mixed_text()
    viol = check_hlo(text, collectives=0, name="planted")
    assert viol and "all-reduce" in viol[0]
    assert check_hlo(text, collectives={"all-reduce": 1}) == []
    viol = check_hlo(text, collectives={"all-gather": 1})
    assert len(viol) == 2           # missing all-gather AND extra all-reduce
    with pytest.raises(ContractViolation):
        assert_contract(text, Contract(name="planted", collectives=0))


# ---------------------------------------------------------------------------
# donation audit
# ---------------------------------------------------------------------------

def test_donation_audit_accepts_real_aliasing():
    f = jax.jit(lambda x: x + 1.0, donate_argnums=(0,))
    x = jnp.ones((16, 16))
    assert check_lowered(f, x, con=Contract(name="ok", donated=(0,))) == []


def test_donation_audit_catches_dtype_mismatch():
    """Donating an f32 input to a program whose only output is bf16 (no
    output of the donated buffer's byte size): JAX drops the donation with
    a warning most callers never see — the audit reads the alias table and
    fails loudly. (A same-size s32 output is no longer enough: XLA now
    aliases a donated f32[16,16] to an s32[16,16] result.)"""
    f = jax.jit(lambda x: x.astype(jnp.bfloat16), donate_argnums=(0,))
    x = jnp.ones((16, 16))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        compiled = f.lower(x).compile()
    assert parse_io_aliases(compiled.as_text()) == []
    viol = check_hlo(compiled.as_text(), donated=(0,), example_args=(x,),
                     name="dropped")
    assert viol and "not aliased" in viol[0]


def test_donation_audit_maps_pytree_args():
    """Donated pytree arg: every leaf must alias; one mismatched leaf in
    the donated tree is caught, leaves of undonated args are ignored."""
    def g(state, y):
        return {"a": state["a"] * 2, "b": state["b"].astype(jnp.bfloat16)}, y

    f = jax.jit(g, donate_argnums=(0,))
    state = {"a": jnp.ones((4, 4)), "b": jnp.ones((3,))}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        compiled = f.lower(state, jnp.ones((2,))).compile()
    viol = audit_donation(compiled.as_text(), (0,),
                          example_args=(state, jnp.ones((2,))), name="tree")
    assert viol and "1/2" in viol[0]


def test_contract_decorator_attaches_metadata():
    @contract(collectives=0, donated=(1,), notes="n")
    def fn(a, b):
        return b

    con = contract_of(fn)
    assert con.collectives == 0 and con.donated == (1,)


# ---------------------------------------------------------------------------
# retrace guard
# ---------------------------------------------------------------------------

def test_retrace_guard_trips_on_shape_varying_loop():
    """A loop feeding growing shapes through a budget-1 jit is exactly
    the silent-recompile bug the guard exists for; strict mode (active
    under pytest) raises on the second trace."""
    reset_guards("t.shape_loop")
    g = guard_jit(lambda x: x * 2.0, name="t.shape_loop", max_traces=1)
    g(jnp.ones((4,)))
    assert compile_count("t.shape_loop") == 1
    with pytest.raises(RetraceViolation):
        g(jnp.ones((5,)))


def test_retrace_guard_cache_hits_are_free():
    reset_guards("t.stable")
    g = guard_jit(lambda x: x + 1.0, name="t.stable", max_traces=1)
    for _ in range(5):
        g(jnp.ones((8,)))
    assert compile_count("t.stable") == 1


def test_retrace_per_signature_allows_distinct_shapes():
    reset_guards("t.sweep")
    g = guard_jit(lambda x: x.sum(), name="t.sweep", per_signature=True)
    for n in (4, 8, 16):
        g(jnp.ones((n,)))
    assert compile_count("t.sweep") == 3


def test_retrace_per_signature_flags_repeat_trace():
    """jit never re-traces a cached signature, so the repeat branch is
    exercised on the record directly (it fires on cache thrash)."""
    rec = GuardRecord("t.thrash", per_signature=True)
    assert rec.note_trace(("sig",)) is None
    msg = rec.note_trace(("sig",))
    assert msg and "thrash" in msg


def test_runtime_decode_guard_is_registered():
    """The serve runtime's decode budget is declared where the jit is
    built — one compile per Runtime (the CLI gate asserts the count
    across a real mixed/staggered run)."""
    import inspect

    from repro.serve import runtime
    src = inspect.getsource(runtime.Runtime.__init__)
    assert 'name="serve.decode_step", max_traces=1' in src


# ---------------------------------------------------------------------------
# lint
# ---------------------------------------------------------------------------

_HOT_SYNC_SRC = '''
class Runtime:
    def step(self):
        toks = jax.device_get(self._decode(x))
        return toks
'''

_HOT_SYNC_PRAGMA_SRC = '''
class Runtime:
    def step(self):
        # comq: allow(host-sync) streaming tokens is a sync by design
        toks = jax.device_get(self._decode(x))
        return toks
'''


def test_lint_flags_host_sync_in_hot_zone():
    finds = lint_source(_HOT_SYNC_SRC, "serve/runtime.py")
    assert [f.rule for f in finds] == ["host-sync"]
    # same code outside a hot zone: silent
    assert lint_source(_HOT_SYNC_SRC, "serve/other.py") == []


def test_lint_pragma_waives_host_sync():
    assert lint_source(_HOT_SYNC_PRAGMA_SRC, "serve/runtime.py") == []


_TIME_IN_JIT_SRC = '''
import time, jax

def step(x):
    t0 = time.time()
    return x * t0

step_j = jax.jit(step)
lam = jax.jit(lambda x: x + time.perf_counter())
part = partial(jax.jit, static_argnames=("n",))(step)
'''


def test_lint_flags_time_in_jit():
    finds = lint_source(_TIME_IN_JIT_SRC, "core/whatever.py")
    rules = {f.rule for f in finds}
    assert rules == {"time-in-jit"}
    assert len(finds) == 2          # step body + the lambda


def test_lint_time_ok_outside_jit():
    src = "import time\n\ndef wall():\n    return time.time()\n"
    assert lint_source(src, "core/whatever.py") == []


_REPLACE_SRC = '''
import os

def publish(tmp, dst):
    os.replace(tmp, dst)

def publish_durable(tmp, dst, fh):
    os.fsync(fh.fileno())
    os.replace(tmp, dst)
'''


def test_lint_fsync_before_replace_scoped_to_durable_dirs():
    finds = lint_source(_REPLACE_SRC, "ft/journal.py")
    assert [f.rule for f in finds] == ["fsync-before-replace"]
    assert "publish" in finds[0].message
    # outside ft/ and ckpt/ the durability rule does not apply
    assert lint_source(_REPLACE_SRC, "serve/engine.py") == []


def test_lint_repo_tree_is_clean():
    """The shipped source passes its own gate (pragmas included)."""
    from repro.analysis.lint import lint_paths
    root = os.path.join(os.path.dirname(__file__), os.pardir)
    finds = lint_paths([os.path.join(root, "src", "repro")], root=root)
    assert finds == [], [str(f) for f in finds]


# ---------------------------------------------------------------------------
# registry + CLI gate
# ---------------------------------------------------------------------------

def test_registry_solver_entry_passes():
    from repro.analysis.registry import ENTRIES
    assert ENTRIES["solver.comq_blocked"].run() == []


def test_registry_skips_dist_entries_without_devices():
    from repro.analysis.registry import run_gate
    results = {r.name: r for r in run_gate(["dist.solve", "dist.gram"])}
    for name, res in results.items():
        if jax.device_count() < 2:
            assert res.skipped and not res.violations
        else:
            assert res.ok, res.violations


def test_cli_retrace_smoke_one_decode_compile():
    """Acceptance: exactly one decode-step compile across a mixed-length,
    staggered serve run (the CLI gate's --retrace section)."""
    from repro.analysis.cli import run_retrace_smoke
    assert run_retrace_smoke(quiet=True) == 0
    assert compile_count("serve.decode_step") == 1
