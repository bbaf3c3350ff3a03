"""Device idle time pinned on the program's spans (bench/attribution.py)
and the seven readers over it: on a hand-built trace with known idle in each
phase, and on a small trace of two serve steps recorded on a TPU v5e.
Every reader reads None where the window holds none of its spans (a
program without them)."""
from pathlib import Path

import pytest

from bench import attribution as at
from bench import readers
from bench import spec
from bench import trace as tr

SERVE = ("sched.pull_idle.offline", "sched.host_idle.offline",
         "prefill.admit_idle.offline")
QUANT = ("quant.sync_idle", "quant.syncs_per_layer", "quant.dispatch_idle",
         "quant.gram_idle")

# one device, window [1000, 2000): busy [1000, 1100), [1150, 1450),
# [1500, 1700), [1900, 1950); idle [1100, 1150), [1450, 1500),
# [1700, 1900), [1950, 2000): 350 ns of 1000
OPS = [("fusion", 1000, 100), ("decode", 1150, 300), ("x", 1500, 200),
       ("y", 1900, 50), ("late", 2100, 50)]
HOST = [("bench.window", 1000, 1000), ("bench.step", 1000, 1000),
        ("ReadSyncFlag", 1100, 50),
        # serve: pull idle 50; emit 20 + pages 20 + dispatch 10 = 50;
        # admit 150 (of 200 idle there, the rest in no phase)
        ("serve.pull", 1090, 70), ("serve.emit", 1440, 30),
        ("serve.pages", 1470, 20), ("serve.dispatch", 1490, 20),
        ("serve.admit", 1700, 150), ("serve.pull", 2050, 10),
        # walk: two layers over the window; syncs idle 30 + 50 + 10 = 90,
        # trace_block idle 30 (a sync only tracing makes); a Gram between
        # syncs idle 60, part of the dispatch idle (230)
        ("layer", 1000, 600), ("layer", 1600, 400),
        ("quant.sync.tap_finite", 1100, 30),
        ("quant.sync.trace_block", 1440, 40),
        ("quant.sync.gram_health", 1700, 50), ("quant.gram", 1750, 60),
        ("quant.sync.solve_verdict", 1960, 10),
        ("quant.sync.tap_finite", 2010, 10)]


def _ctx(host=HOST, ops=OPS, run=None, reduced=True):
    red = tr.Reduced({"/device:TPU:0": tr.DeviceTrace(ops=list(ops))},
                     sorted(host, key=lambda e: e[1]))
    return readers.Ctx(None, red if reduced else None, 1000.0, 2000.0,
                       {}, run=run if run is not None else {"layers": 2})


def _read(name, ctx):
    return spec.load_module(spec.BENCH_DIR / "metrics" / f"{name}.py",
                            "test_metric_" + name.replace(".", "_")
                            ).read(ctx)


def test_interval_algebra():
    a = [(0, 10), (20, 30), (40, 50)]
    b = [(5, 25), (45, 60)]
    assert at.intersect(a, b) == [(5, 10), (20, 25), (45, 50)]
    assert at.subtract(a, b) == [(0, 5), (25, 30), (40, 45)]
    assert at.subtract(a, []) == a and at.intersect(a, []) == []
    assert at.subtract([(0, 10)], [(2, 3), (5, 6)]) == [(0, 2), (3, 5),
                                                        (6, 10)]
    assert at.idle(_ctx()) == [(1100, 1150), (1450, 1500), (1700, 1900),
                               (1950, 2000)]


@pytest.mark.parametrize("name,want", [
    ("sched.pull_idle.offline", 5.0), ("sched.host_idle.offline", 5.0),
    ("prefill.admit_idle.offline", 15.0), ("quant.sync_idle", 9.0),
    ("quant.syncs_per_layer", 1.5), ("quant.dispatch_idle", 23.0),
    ("quant.gram_idle", 6.0)])
def test_readers_on_known_idle(name, want):
    assert _read(name, _ctx()) == pytest.approx(want)


def test_serve_shares_within_device_idle():
    ctx = _ctx()
    idle = 100.0 * (1.0 - ctx.busy_s() / ctx.window_s)
    assert idle == pytest.approx(35.0)
    assert sum(_read(n, ctx) for n in SERVE) == pytest.approx(25.0)
    walk = _read("quant.sync_idle", ctx) + _read("quant.dispatch_idle", ctx)
    # the rest of the idle lies in quant.sync.trace_block
    assert walk + 3.0 == pytest.approx(idle)
    assert _read("quant.gram_idle", ctx) <= _read("quant.dispatch_idle", ctx)


@pytest.mark.parametrize("name", SERVE + QUANT)
def test_readers_none_without_their_spans(name):
    bench_only = [e for e in HOST if e[0].startswith(("bench.", "Read"))]
    assert _read(name, _ctx(host=bench_only)) is None
    assert _read(name, _ctx(reduced=False)) is None


def test_syncs_per_layer_needs_the_layer_count():
    assert _read("quant.syncs_per_layer", _ctx(run={})) is None
    # only trace_block (a traced walk's own sync) is no sync of the walk
    only_block = [e for e in HOST if not at.is_sync(e[0])]
    assert _read("quant.sync_idle", _ctx(host=only_block)) is None


# Two Runtime.step() calls on one TPU v5e (h2o-danube-1.8b widths cut to
# 2 layers, 4 slots, packed 4-bit weights from bench/weights.py), each in
# a `bench.step` span inside `bench.window` (bench.harness.profiled): the
# first admits two requests beside two running, the second decodes all
# four. Kept of the recording: the TPU plane's "XLA Modules" and "XLA
# Ops" lines and the /host:CPU plane, without per-event stats and with op
# names cut at " = " as trace.op_name reads them (58 KB of 1 MB).
SPANS = Path(__file__).parent / "data" / "spans_v5e.xplane.pb"
STEP_SPANS = ("serve.admit", "serve.pages", "decode_step",
              "serve.dispatch", "serve.pull", "serve.emit")


@pytest.fixture(scope="module")
def v5e():
    red = tr.load(SPANS)
    lo, hi = tr.host_window(red.host, "bench.window")
    return readers.Ctx(None, red, lo, hi,
                       readers.peaks_for("TPU v5 lite"), run={"slots": 4})


def test_v5e_spans_and_program_names(v5e):
    host = [e[0] for e in v5e.reduced.host if v5e.lo <= e[1] < v5e.hi]
    assert [host.count(n) for n in STEP_SPANS + ("bench.step",)] == \
        [2] * 7
    names = {m[0].split("(", 1)[0] for m in v5e.modules}
    assert {"jit_decode_step", "jit_argmax", "jit_prefill_full",
            "jit_write"} <= names


def test_v5e_spans_on_the_driving_thread():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(SPANS))
    lines = [{e.name for e in line.events} for plane in pd.planes
             if plane.name == "/host:CPU" for line in plane.lines]
    held = [i for i, names in enumerate(lines)
            if names & set(STEP_SPANS)]
    assert len(held) == 1 and "bench.window" in lines[held[0]]


def test_v5e_serve_readers(v5e):
    got = {n: _read(n, v5e) for n in SERVE}
    assert got == pytest.approx({"sched.pull_idle.offline": 14.378878,
                                 "sched.host_idle.offline": 3.874352,
                                 "prefill.admit_idle.offline": 61.029366})
    idle = _read("device.idle.offline", v5e)
    assert idle == pytest.approx(79.515584)
    assert 0.9 * idle <= sum(got.values()) <= idle
    for n in QUANT:
        assert _read(n, v5e) is None


# A trace with the harness's spans only, as the parent program records:
# three executions of one small jitted program, each in a `bench.step`.
SMALL = Path(__file__).parent / "data" / "small_v5e.xplane.pb"


def test_idle_as_trace_counts_it_and_readers_none_without_spans():
    red = tr.load(SMALL)
    lo, hi = tr.host_window(red.host, "bench.window")
    ctx = readers.Ctx(None, red, lo, hi, readers.peaks_for("TPU v5 lite"),
                      run={"layers": 4})
    gaps = tr.idle_gaps(red.device.ops, red.host, lo, hi)
    assert sum(b - a for a, b in at.idle(ctx)) == \
        pytest.approx(sum(s for _, s in gaps) * 1e9)
    for n in SERVE + QUANT:
        assert _read(n, ctx) is None, n
