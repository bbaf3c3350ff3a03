"""The program's spans on the profiler's clock (repro.obs.Tracer): a
2-layer quantize_model and three Runtime.step() calls run under the JAX
profiler on the CPU. Every span is a TraceMe on the one host thread that
drives the work, nested per phase, and the profiler holds exactly the
spans the Tracer's JSON events hold; a disabled tracer adds none. The
serve programs and the Pallas kernels carry stable names."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_smoke_config
from repro.core import QuantSpec, quantize_model
from repro.models import BuildPlan, init_params
from repro.obs import Tracer
from repro.serve import Runtime, ServeConfig

KEY = jax.random.PRNGKey(0)

SERVE = ("serve.admit", "serve.pages", "decode_step", "serve.dispatch",
         "serve.pull", "serve.emit")
WALK = ("layer", "quant.gram", "leaf_solve", "quant.sync.tap_finite",
        "quant.sync.gram_health", "quant.sync.solve_verdict",
        "quant.sync.trace_block")
PROGRAM = SERVE + WALK + ("quant.sync.results_finite",)

# per layer of the tiny dense walk (per-channel comq_blocked, greedy order:
# no fused solves): 4 tap groups, 7 leaves, every solve healthy
SYNCS_PER_LAYER = {"quant.sync.tap_finite": 4, "quant.sync.gram_health": 4,
                   "quant.sync.solve_verdict": 7,
                   "quant.sync.trace_block": 4}


def _runtime(tracer=None):
    cfg = get_smoke_config("h2o-danube-1.8b").replace(
        compute_dtype="float32")
    plan = BuildPlan(remat=False, cache_dtype=jnp.float32)
    params = init_params(KEY, cfg, plan)
    sc = ServeConfig(max_slots=2, block_size=8, num_blocks=16,
                     buckets=(16,), max_blocks_per_slot=4)
    rt = Runtime(params, cfg, plan, sc, tracer=tracer)
    rs = np.random.RandomState(3)
    for n in (9, 12):
        rt.submit(rs.randint(0, cfg.vocab_size, (n,)).astype(np.int32),
                  max_new_tokens=8)
    return rt


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(host thread lines of the profile, the Tracer's span events). An
    untraced runtime steps under the same profiler: it must add nothing."""
    tr = Tracer(run="spans")
    cfg = get_smoke_config("qwen2-7b")
    plan = BuildPlan(remat=False)
    params = init_params(KEY, cfg, plan)
    tokens = jax.random.randint(KEY, (4, 64), 0, cfg.vocab_size)
    spec = QuantSpec(bits=4, granularity="per_channel", lam=0.9, sweeps=1,
                     order="greedy")
    rt, plain = _runtime(tr), _runtime()
    out = tmp_path_factory.mktemp("profile")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        _, report = quantize_model(params, cfg, plan, tokens, spec,
                                   method="comq_blocked", tracer=tr)
        for _ in range(3):
            rt.step()
            plain.step()
    finally:
        jax.profiler.stop_trace()
    assert not report.guard_events
    pd = ProfileData.from_file(str(sorted(out.rglob("*.xplane.pb"))[-1]))
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for e in line.events]
             for plane in pd.planes if plane.name == "/host:CPU"
             for line in plane.lines]
    spans = [e for e in tr.events if e["ph"] == "X"]
    return lines, spans


def _program_line(lines):
    held = [i for i, line in enumerate(lines)
            if any(name in PROGRAM for name, _, _ in line)]
    assert len(held) == 1, f"program spans on {len(held)} thread lines"
    return [e for e in lines[held[0]] if e[0] in PROGRAM]


def _by_name(events, name):
    return [e for e in events if e[0] == name]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_spans_on_one_thread_match_the_json_events(traced):
    lines, spans = traced
    line = _program_line(lines)
    for name in PROGRAM:
        assert len(_by_name(line, name)) == \
            sum(1 for s in spans if s["name"] == name), name
    # the sharded and expert paths' sync is the one this walk lacks
    assert {s["name"] for s in spans} == \
        set(PROGRAM) - {"quant.sync.results_finite"}


def test_serve_spans_nest_per_step(traced):
    line = _program_line(traced[0])
    steps = [_by_name(line, n) for n in ("serve.admit", "serve.pages",
                                         "decode_step", "serve.emit")]
    assert [len(s) for s in steps] == [3, 3, 3, 3]
    for admit, pages, decode, emit in zip(*steps):
        assert admit[2] <= pages[1] and pages[2] <= decode[1] \
            and decode[2] <= emit[1]
        inner = [e for e in line if _inside(e, decode) and e is not decode]
        assert [e[0] for e in inner] == ["serve.dispatch", "serve.pull"]


def test_serve_admit_args(traced):
    # the pass itself carries no args: each admission's own `admit`
    # request event says whether it resumed (obs/timeline.py reads it)
    admits = [s["args"] for s in traced[1] if s["name"] == "serve.admit"]
    assert admits == [{}, {}, {}]
    steps = [s["args"] for s in traced[1] if s["name"] == "decode_step"]
    assert [a["slots"] for a in steps] == [2, 2, 2]


def test_walk_spans_nest(traced):
    line = _program_line(traced[0])
    layers = _by_name(line, "layer")
    solves = _by_name(line, "leaf_solve")
    assert len(layers) == 2 and len(solves) == 8
    for name in WALK[1:]:
        for e in _by_name(line, name):
            assert any(_inside(e, l) for l in layers), name
    for name in ("quant.sync.gram_health", "quant.sync.solve_verdict",
                 "quant.sync.trace_block"):
        for e in _by_name(line, name):
            assert any(_inside(e, s) for s in solves), name
    for e in _by_name(line, "quant.sync.tap_finite") + \
            _by_name(line, "quant.gram"):
        assert not any(_inside(e, s) for s in solves)


def test_walk_syncs_per_layer(traced):
    line = _program_line(traced[0])
    for layer in _by_name(line, "layer"):
        within = [e[0] for e in line if _inside(e, layer)]
        assert {n: within.count(n) for n in SYNCS_PER_LAYER} == \
            SYNCS_PER_LAYER


def test_decode_programs_are_named():
    rt = _runtime()
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    B = rt.serve_cfg.max_slots
    args = (jax.tree_util.tree_map(sds, rt.params),
            jax.tree_util.tree_map(sds, rt.pool),
            jax.ShapeDtypeStruct((B, rt.maxb), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32))
    hlo = rt._decode.lower(*args).compile().as_text()
    assert hlo.startswith("HloModule jit_decode_step,")
    logits = jax.ShapeDtypeStruct((B, rt.cfg.vocab_size), jnp.float32)
    assert "module @jit_argmax " in rt._argmax.lower(logits).as_text()
    vec = lambda dt: jax.ShapeDtypeStruct((B,), dt)  # noqa: E731
    assert "module @jit_sample " in rt._sample.lower(
        logits, vec(jnp.uint32), vec(jnp.int32), vec(jnp.float32),
        vec(jnp.int32), vec(jnp.float32)).as_text()


def _pallas_names(fn, *args):
    eqns = jax.make_jaxpr(fn)(*args).eqns
    return [e.params["name"] for e in eqns
            if e.primitive.name == "pallas_call"]


def test_pallas_kernels_are_named():
    """The traced programs name the kernels (interpret mode on the CPU;
    tests/test_tpu_compile.py sees the name in the v5e compile)."""
    from repro.kernels.comq_panel import comq_panel_dq_pallas
    from repro.kernels.flash_attention import flash_attention_pallas
    B, n = 8, 16
    panel = (jnp.eye(B), jnp.ones((B, n)), jnp.ones((B, n)),
             jnp.ones((n,)), jnp.zeros((n,)), jnp.full((n,), 7.0),
             jnp.ones((B,)))
    assert _pallas_names(lambda *a: comq_panel_dq_pallas(
        *a, interpret=True), *panel) == ["comq_panel"]
    q = jnp.ones((4, 16, 8), jnp.float32)
    kv = jnp.ones((2, 16, 8), jnp.float32)
    assert _pallas_names(lambda q, k, v: flash_attention_pallas(
        q, k, v, interpret=True), q, kv, kv) == ["flash_attention"]
