"""The trace reduction on the small trace recorded on a TPU v5e
(benchmark/tests/record_trace.py): 18 runs of one jitted step in 6 groups of
3, each group inside a `bench.fit` span and after a 4 ms sleep inside a
`bench.input_next` span."""
import os

import pytest

from benchmark import trace

RECORDED = os.path.join(os.path.dirname(trace.__file__), "tests", "data",
                        "recorded.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return trace.Trace.from_file(RECORDED)


def test_interval_arithmetic():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.total([(0, 3), (5, 8)]) == 6
    assert trace.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]
    assert trace.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]


def test_op_names():
    hlo = ("%fusion.61 = bf16[50272,2048]{1,0:T(8,128)(2,1)} fusion(bf16[4096,2048]"
           "{1,0} %x), kind=kOutput, calls=%fused_computation.1")
    assert trace.op_name(hlo) == ("fusion.61", "bf16[50272,2048]", "fusion")
    assert trace.label(hlo) == "fusion.61_bf16_50272_2048"
    assert trace.op_name("jit_step(123)")[0] == "jit_step(123)"


def test_planes_and_spans(recorded):
    assert list(recorded.devices) == ["/device:TPU:0"]
    dev = recorded.devices["/device:TPU:0"]
    assert len(dev["modules"]) == 18 and len(dev["ops"]) == 54
    names = sorted({s[0] for s in recorded.spans})
    assert names == ["bench.fit", "bench.input_next"]
    assert len(recorded.spans) == 12


def test_busy_union_and_idle_share(recorded):
    # 18 fusions of ~94 us; the copies overlap them and add nothing
    busy = recorded.busy_s()
    assert busy == pytest.approx(0.001699197, rel=1e-6)
    assert recorded.window_s() == pytest.approx(0.027872483, rel=1e-6)
    assert recorded.idle_share() == pytest.approx(1 - busy / 0.027872483)
    by_op = sum(t for _, t in recorded.top_ops(10))
    assert by_op >= busy              # overlapping copies are counted per op
    assert by_op == pytest.approx(busy, rel=1e-3)


def test_idle_gaps_are_named_by_the_span_the_host_was_in(recorded):
    gaps = recorded.idle_gaps(5)
    assert [name for name, _ in gaps] == ["bench.input_next"] * 5
    assert all(0.004 < s < 0.006 for _, s in gaps)       # the 4 ms sleeps
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    assert recorded.span_at(0) == trace.OUTSIDE


def test_per_kernel_and_per_program_time(recorded):
    seconds, runs = recorded.op_time(r"fusion")
    assert runs == 18 and seconds == pytest.approx(0.001698912, rel=1e-6)
    assert recorded.op_time(r"tpu_custom_call") == (0.0, 0)
    name, per_run, n = recorded.module_time()
    assert name.startswith("jit_step(") and n == 17   # the first began before the first op
    assert per_run == pytest.approx(94.4e-6, rel=0.01)
    assert recorded.module_time(r"jit_other") is None
    assert recorded.module_time(by="runs")[2] == 17


def test_busy_union_and_gaps_of_overlapping_ops():
    ops = [("%fusion.1 = bf16[8]{0} fusion()", 0, 10),
           ("%fusion.3 = bf16[8]{0} fusion()", 5, 25),
           ("%fusion.2 = bf16[8]{0} fusion()", 20, 30)]
    t = trace.Trace({"/device:TPU:0": {"ops": ops, "modules": []}},
                    [(trace.WINDOW_SPAN, 0, 40)])
    assert t.busy_s() == pytest.approx(30e-9)
    assert t.idle_gaps(1) == [[trace.OUTSIDE, 10e-9]]
