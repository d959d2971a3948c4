"""The program-trace reduction (``lib/program_trace.py``) and the metric
readers on top of it, on small synthetic traces: idle time put down to
the innermost ``fed.*`` span, scope self time with nested ops, no number
where the program carries no spans or scopes, and no share above 100 when
an event is counted twice."""

import jax
import pytest

import bench_toy  # noqa: F401  (puts the checkout on sys.path)
from bench.lib import program_trace as pt
from bench.lib import registry
from bench.lib import trace as tr

MS = 1e6  # ns
CHIP = "/device:TPU:0"


def op(scope, a, b):
    return (scope, a * MS, b * MS)


def span(name, a, b, **args):
    return (name, a * MS, b * MS, dict(window=0, **args))


# one window of 20 ms: staging, a dispatch, the device busy 6-16 ms, a
# sync, an unpack; the scatter's row loop carries no scope of its own
SPANS = [span("fed.run", 0, 19, rounds=4),
         span("fed.sample", 1, 3, bytes=64), span("fed.h2d", 3, 4, bytes=64),
         span("fed.dispatch", 4, 5), span("fed.sync", 5, 17),
         span("fed.unpack", 17, 18)]
OPS = [op("fed.fakes", 6, 7), op("fed.d_update", 7, 9),
       op("fed.select", 9, 10), op("fed.fold", 10, 11),
       op("fed.store_scatter", 11, 14), op("", 11.5, 12.5),
       op("", 12.5, 13.5), op("fed.g_update", 14, 15), op("", 15, 16)]
COMPILES = [("backend_compile_and_load", 2 * MS, 2.5 * MS),
            ("backend_compile_and_load", 25 * MS, 26 * MS)]
NEW = ("stage_idle_share.train", "driver_idle_share.train", "compiles.train",
       "store_roofline", "fold_roofline", "select_roofline",
       "model_mfu.train")


class _R:
    """A Readings stand-in: the harness's trace plus the program trace."""

    def __init__(self, program, chips=1, facts=None, codec="topk_int8"):
        devices = {name: [(s or "op", a, b) for s, a, b in ops]
                   for name, ops in program.devices.items()}
        self.trace = tr.Trace(devices, [], (0.0, 20 * MS))
        self.program_trace = program
        self.chips = chips
        self.facts = facts or {"rounds": 4, "flops_per_round": 1e6,
                               "cohort": 2, "d_params": 1000}
        self.peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
        self.traffic = {"codec": codec, "error_feedback": True}
        self.time = tr.device_time(self.trace)
        self.window_s = self.time["window_s"]


def read(name, r):
    return registry.metric_reader(name)(r)


HLO = """
ENTRY %main.2 (x.1: f32[4,128]) -> f32[128] {
  %while.7 = (s32[]) while(%t), body=%b, metadata={op_name="jit(chunk)/while/body/closed_call/fed.select/jit(topk_mask)/while"}
  %fusion.3 = f32[4] fusion(%x), calls=%c, metadata={op_name="jit(chunk)/while/body/transpose(jvp(fed.d_update))/dot_general" stack_frame_id=3}
  %copy.9 = f32[4] copy(%x)
  ROOT %add.1 = f32[4] add(%x, %y), metadata={op_name="jit(chunk)/add"}
}
"""


def test_an_instruction_s_scope_is_the_innermost_fed_name():
    assert pt.scopes_of_module(HLO) == {"while.7": "fed.select",
                                        "fusion.3": "fed.d_update"}


def test_ops_take_the_scope_of_their_program_s_instruction():
    scopes = {"jit_chunk(12)": {"fusion.3": "fed.fold"},
              "jit_slice(3)": {"fusion.3": "fed.select"},
              "jit_slice(4)": {}}
    modules = [("jit_chunk(12)", 0, 10 * MS), ("jit_chunk", 20 * MS, 30 * MS),
               ("jit_slice(99)", 40 * MS, 50 * MS)]
    ops = [("%fusion.3 = f32[4] fusion(%x)", 1 * MS, 2 * MS),
           ("%copy.9 = f32[4] copy(%x)", 2 * MS, 3 * MS),
           ("%fusion.3 = f32[4] fusion(%x)", 12 * MS, 13 * MS),  # no program
           ("%fusion.3 = f32[4] fusion(%x)", 21 * MS, 22 * MS),  # by name
           ("%fusion.3 = f32[4] fusion(%x)", 41 * MS, 42 * MS)]  # ambiguous
    assert [sc for sc, _, _ in pt.scoped_ops(ops, modules, scopes)] == [
        "fed.fold", "", "", "fed.fold", ""]


def test_idle_goes_to_the_innermost_open_span():
    p = pt.ProgramTrace(SPANS, [], {CHIP: OPS})
    idle = pt.idle_ns_by_span(p, 0.0, 20 * MS)
    # busy 6-16: idle 0-6 and 16-20 ms; the run's own 0-1, 18-19
    assert idle == {"fed.run": 2 * MS, "fed.sample": 2 * MS,
                    "fed.h2d": 1 * MS, "fed.dispatch": 1 * MS,
                    "fed.sync": 2 * MS, "fed.unpack": 1 * MS}
    r = _R(p)
    assert read("stage_idle_share.train", r) == pytest.approx(15.0)
    assert read("driver_idle_share.train", r) == pytest.approx(30.0)
    # 19-20 ms lies outside every span: idle, but no program span's
    assert read("idle_share.train", r) == pytest.approx(50.0)


def test_scope_self_time_takes_in_nested_ops():
    inherited = pt.inherit(OPS)
    assert [s for s, _, _ in inherited] == [
        "fed.fakes", "fed.d_update", "fed.select", "fed.fold",
        "fed.store_scatter", "fed.store_scatter", "fed.store_scatter",
        "fed.g_update", ""]
    p = pt.ProgramTrace(SPANS, [], {CHIP: OPS})
    lo, hi = 0.0, 20 * MS
    # the loop op's self time leaves out its body; the body is its scope's
    assert pt.scope_ns(p, lo, hi, ("fed.store_scatter",)) == 3 * MS
    assert pt.scope_ns(p, lo, hi, pt.MODEL) == 4 * MS
    # the unscoped op after the G update stays unscoped
    assert pt.scope_ns(p, lo, hi, ("",)) == 1 * MS
    r = _R(p)
    n, c, rounds = 1000, 2, 4
    assert read("fold_roofline", r) == pytest.approx(
        100 * 4 * n * (c + 1) * rounds / 1e9 / 1e-3)
    assert read("select_roofline", r) == pytest.approx(
        100 * c * (4 * n + n / 8) * rounds / 1e9 / 1e-3)
    assert read("store_roofline", r) == pytest.approx(
        100 * (8 * c * (n + 2 * n + 1 + n) + 8 * c) * rounds / 1e9 / 3e-3)
    assert read("store_roofline", _R(p, codec="none")) == pytest.approx(
        100 * (8 * c * (n + 2 * n + 1) + 8 * c) * rounds / 1e9 / 3e-3)
    assert read("model_mfu.train", r) == pytest.approx(
        100 * 1e6 * rounds / (4e-3 * 1e12))


def test_compiles_count_only_inside_the_window():
    r = _R(pt.ProgramTrace(SPANS, COMPILES, {CHIP: OPS}))
    assert read("compiles.train", r) == 1.0
    assert read("compiles.train", _R(pt.ProgramTrace(
        SPANS, [], {CHIP: OPS}))) == 0.0


def test_no_spans_or_scopes_read_nothing():
    # a program without the marks, as the parent of this change is
    bare = [op("", a, b) for _, a, b in OPS]
    for name in NEW:
        assert read(name, _R(pt.ProgramTrace([], COMPILES, {CHIP: bare}))) \
            is None, name
    # no chip in the trace (the CPU): nothing at all
    for name in NEW:
        assert read(name, _R(pt.ProgramTrace(SPANS, COMPILES, {}))) \
            is None, name
    # a program with spans but no scopes: the span readers still read
    r = _R(pt.ProgramTrace(SPANS, [], {CHIP: bare}))
    assert read("stage_idle_share.train", r) == pytest.approx(15.0)
    for name in ("store_roofline", "fold_roofline", "select_roofline",
                 "model_mfu.train"):
        assert read(name, r) is None, name


def test_a_double_count_stays_within_the_window():
    # every span and every op recorded twice, on two chips
    p = pt.ProgramTrace(SPANS + SPANS, [], {CHIP: OPS + OPS,
                                            "/device:TPU:1": OPS})
    r = _R(p, chips=2)
    once = _R(pt.ProgramTrace(SPANS, [], {CHIP: OPS, "/device:TPU:1": OPS}),
              chips=2)
    stage = read("stage_idle_share.train", r)
    driver = read("driver_idle_share.train", r)
    assert 0 < stage + driver <= read("idle_share.train", r) <= 100
    assert (stage, driver) == (pytest.approx(15.0), pytest.approx(30.0))
    for name in ("store_roofline", "fold_roofline", "select_roofline",
                 "model_mfu.train"):
        assert read(name, r) == pytest.approx(read(name, once)), name


def test_load_reads_spans_compiles_and_scopes_from_a_real_profile(
        tmp_path):
    @jax.jit
    def fold(x):
        with jax.named_scope("fed.fold"):
            return jax.numpy.max(jax.numpy.abs(x), axis=0) * 3

    x = jax.numpy.ones((4, 128))
    fold(x).block_until_ready()              # compiled before the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("fed.run", window=16, rounds=16):
            fold(x).block_until_ready()
            jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(7.0))
    finally:
        jax.profiler.stop_trace()
    p = pt.load(str(tmp_path))
    (name, s, e, args), = p.spans
    assert (name, args) == ("fed.run", {"window": 16, "rounds": 16})
    assert p.compiles and all(s <= c[1] <= e for c in p.compiles)
    assert p.devices == {}               # no chip plane on the CPU
    path, = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    scopes = pt.hlo_scopes(memoryview(path.read_bytes()))
    fold_scopes, = [v for k, v in scopes.items() if k.startswith("jit_fold(")]
    assert set(fold_scopes.values()) == {"fed.fold"}
    assert pt.load(str(tmp_path / "none")) == pt.ProgramTrace([], [], {})


def test_the_optimizer_row_is_two_moments_and_a_count():
    from repro.core.approaches import DistGANConfig, d_opt_flat_layout
    for name in ("mlp784", "convgan64"):
        cfg, mod = registry.config(name)
        pair = mod.program_pair(cfg)
        assert d_opt_flat_layout(pair, DistGANConfig()).n == \
            2 * cfg["d_params"] + 1

