"""The reduction from trace events to per-layer numbers, on small
synthetic event lists, and the metric readers on top of it."""

import pytest

import bench_toy  # noqa: F401  (puts the checkout on sys.path)
from bench.lib import registry
from bench.lib import trace as tr

MS = 1e6  # ns


def ev(name, a, b):
    return (name, a * MS, b * MS)


TOPK = "%topk_mask.3 = s32[264,1024] custom-call []"
OPS = [ev("fusion.1", 0, 2), ev("fusion.2", 1, 3),       # overlap: 0-3
       ev(TOPK, 5, 6), ev("all-reduce.1", 6, 9),
       ev("fusion.3", 7, 8), ev("fusion.1", 12, 13)]


def test_union_busy_and_idle_gaps():
    assert tr.merged(OPS, 0, 20 * MS) == [(0, 3 * MS), (5 * MS, 9 * MS),
                                          (12 * MS, 13 * MS)]
    assert tr.busy_ns(OPS, 0, 20 * MS) == 8 * MS
    assert tr.idle_gaps(OPS, 0, 20 * MS) == [(3 * MS, 5 * MS),
                                             (9 * MS, 12 * MS),
                                             (13 * MS, 20 * MS)]
    # clipping to the window
    assert tr.busy_ns(OPS, 1 * MS, 12.5 * MS) == 6.5 * MS


def test_kernel_sums_by_name():
    topk = tr.matching(OPS, registry.kernels()["topk"])
    assert [e[0] for e in topk] == [TOPK]
    assert tr.summed_ns(OPS, 0, 20 * MS) == 10 * MS      # overlaps count
    assert tr.summed_ns(topk, 5.5 * MS, 20 * MS) == 0.5 * MS


def test_kernel_names_match_only_the_pallas_calls():
    names = ["%quantize_rows.12 = f32[264] custom-call []",
             "%quantize_rows.13 = s8[8,264,1024] custom-call []",
             "%dequantize_rows = f32[8,264,1024] custom-call []",
             "%topk_mask = s32[33] custom-call []",
             "%fusion.9 = f32[8,267009] fusion",
             "%quantize_rows_fusion = f32[8] fusion"]
    evs = [(n, 0, 1) for n in names]
    k = registry.kernels()
    assert [e[0] for e in tr.matching(evs, k["int8"])] == names[:3]
    assert [e[0] for e in tr.matching(evs, k["topk"])] == [names[3]]


def test_gaps_are_labelled_by_the_innermost_open_span():
    spans = [("window", 0, 20 * MS), ("session.run", 2 * MS, 11 * MS),
             ("stage.user_batch", 2.5 * MS, 4 * MS)]
    t = tr.Trace({"/device:TPU:0": OPS}, spans, (0, 20 * MS))
    assert tr.label((3 * MS, 5 * MS), spans) == "stage.user_batch"
    assert tr.label((9 * MS, 12 * MS), spans) == "session.run"
    assert tr.label((13 * MS, 20 * MS), spans) == "no_span"
    gaps = tr.top_gaps(t, n=2)
    assert gaps == [["no_span", 7e-3], ["session.run", 3e-3]]
    assert tr.top_ops(t, n=1) == [["fusion.1", 3e-3]]


def test_self_time_leaves_out_nested_ops():
    # all-reduce.1 (6-9 ms) holds fusion.3 (7-8 ms) on the same line
    got = tr.self_times(OPS, 0, 20 * MS)
    assert got == {"fusion.1": 3 * MS, "fusion.2": 2 * MS,
                   TOPK: 1 * MS, "all-reduce.1": 2 * MS,
                   "fusion.3": 1 * MS}
    loop = [ev("while", 0, 10), ev("body.1", 1, 3), ev("body.2", 4, 9),
            ev("leaf", 5, 6)]
    assert tr.self_times(loop, 0, 20 * MS) == {
        "while": 3 * MS, "body.1": 2 * MS, "body.2": 4 * MS, "leaf": 1 * MS}


def test_op_names_drop_operands_and_layouts():
    text = ("%copy.300 = f32[1024,534019]{1,0:T(8,128)} copy(f32[1024,534019]"
            "{1,0:T(8,128)} %get-tuple-element.2401)")
    assert tr.op_name(text) == "%copy.300 = f32[1024,534019] copy"
    cc = tr.op_name("%custom-call.7 = f32[8,1024]{1,0} custom-call(%p)",
                    lambda: [("tf_op",
                              "jit(chunk)/pallas_call[_mask_ge_bits_kernel]"),
                             ("flops", 12)])
    assert cc.startswith("%custom-call.7 = f32[8,1024] custom-call [")
    assert "_mask_ge_bits_kernel" in cc


def test_device_time_averages_over_chips():
    t = tr.Trace({"/device:TPU:0": OPS, "/device:TPU:1": [ev("f", 0, 4)]},
                 [], (0, 20 * MS))
    d = tr.device_time(t)
    assert d["window_s"] == pytest.approx(0.02)
    assert d["per_device_busy_s"] == {"/device:TPU:0": pytest.approx(8e-3),
                                      "/device:TPU:1": pytest.approx(4e-3)}
    assert d["busy_s"] == pytest.approx(6e-3)


class _R:
    """A Readings stand-in for the metric readers."""

    def __init__(self, trace, facts, chips=1):
        self.trace, self.facts, self.chips = trace, facts, chips
        self.peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
        self.kernels = registry.kernels()
        self.time = tr.device_time(trace)
        self.window_s = self.time["window_s"]


def test_idle_share_reader():
    name = "idle_share.train"
    r = _R(tr.Trace({"/device:TPU:0": OPS}, [], (0, 20 * MS)), {})
    assert registry.metric_reader(name)(r) == pytest.approx(60.0)
    empty = _R(tr.Trace({}, [], (0, 20 * MS)), {})
    assert registry.metric_reader(name)(empty) is None


def test_mfu_reader():
    r = _R(tr.Trace({"/device:TPU:0": OPS}, [], (0, 1e9)),
           {"rounds": 10, "flops_per_round": 1e9})
    assert registry.metric_reader("mfu.train")(r) == pytest.approx(1.0)


def test_a_kernel_with_no_events_reads_nothing():
    r = _R(tr.Trace({"/device:TPU:0": [ev("fusion", 0, 1)]}, [],
                    (0, 1e9)), {"rounds": 3, "cohort": 2, "d_params": 10})
    for name in ("topk_roofline", "int8_roofline"):
        assert registry.metric_reader(name)(r) is None
