"""The reduction from a profiler trace to the benchmark's numbers:
busy and idle share, device time by op, exposed collective time, and
idle gaps labelled by the host span around them."""
import glob
import os
from types import SimpleNamespace as NS

import pytest

from bench import trace

HERE = os.path.dirname(__file__)


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


def test_interval_arithmetic():
    assert trace.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert trace.measure([(0, 2), (1, 3), (10, 11)]) == 4
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]


def test_op_names_drop_the_hlo_suffix():
    assert trace.op_name("%fused_update.1 = (f32[8]) custom-call(x)") == \
        "fused_update"
    assert trace.op_name("%copy-done = s32[8] copy-done(x)") == "copy-done"
    assert trace.op_name(
        "%bitcast_dynamic-update-slice_fusion.2 = bf16[2] fusion()") == \
        "bitcast_dynamic-update-slice_fusion"


def test_self_time_takes_children_out_of_their_loop():
    evs = [("while", 0, 100), ("fusion", 10, 30), ("fused_update", 40, 90)]
    own = trace.self_times(evs)
    assert own["while"] == pytest.approx(30e-9)
    assert own["fusion"] == pytest.approx(20e-9)
    assert own["fused_update"] == pytest.approx(50e-9)
    leaves = trace.leaf_intervals(evs)
    assert [n for n, _, _ in leaves] == ["fusion", "fused_update"]


def _fake_trace():
    host = plane("/host:CPU", python3=[
        ev("bench.window", 0, 1000), ev("bench.dispatch", 0, 40),
        ev("bench.sync", 600, 400)])
    dev = plane("/device:TPU:0",
                XLA_Modules=[ev("jit_train_step(1)", 100, 400),
                             ev("jit_train_step(2)", 900, 200)],
                XLA_Ops=[ev("%while.1 = ()", 100, 400),
                         ev("%fused_update.3 = ()", 150, 100),
                         ev("%all-reduce.2 = ()", 300, 50),
                         ev("%fusion.5 = ()", 350, 100),
                         ev("%all-gather.1 = ()", 950, 20)])
    return NS(planes=[host, dev, plane("/device:CUSTOM:Megascale Trace")])


def test_reduce_on_a_constructed_trace():
    red = trace.reduce(_fake_trace(), 1)
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx(500e-9)      # 100..500, 900..1000
    # the second run is cut in half by the window's end
    assert red["modules"]["jit_train_step"] == pytest.approx(1.5)
    # device seconds of the program inside the window: 400 + 100
    assert red["module_s"]["jit_train_step"] == pytest.approx(500e-9)
    assert red["ops_s"]["fused_update"] == pytest.approx(100e-9)
    # the core's time in collectives: all-reduce 50, all-gather 20
    assert red["exposed_collective_s"] == pytest.approx(70e-9)
    # gaps 0..100 (its middle, 50, is after the dispatch span) and
    # 500..900 (middle 700 is inside the sync)
    assert red["idle_gaps"][0] == ["bench.sync", pytest.approx(400e-9)]
    assert red["idle_gaps"][1] == ["none", pytest.approx(100e-9)]
    b = trace.breakdown(red)
    assert b["device_ops"][0][0] == "while"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_reduce_needs_the_window_span():
    pd = _fake_trace()
    pd.planes[0].lines[0].events = pd.planes[0].lines[0].events[1:]
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(pd, 1)


RECORDED = sorted(glob.glob(os.path.join(HERE, "data", "*.xplane.pb")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_reduce_a_recorded_chip_trace(path):
    from jax.profiler import ProfileData
    assert os.path.getsize(path) < 1 << 20
    red = trace.reduce(ProfileData.from_file(path), 1)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["modules"]["jit_train_step"] > 0
    # the optimizer engine's two Pallas kernels, by the names the trace
    # gives them today
    assert red["ops_s"]["fused_update"] > 0
    assert red["ops_s"]["chunk_sumsq"] > 0
    assert sum(red["ops_s"].values()) == pytest.approx(red["busy_s"],
                                                       rel=0.05)


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_traced_run_reads_its_layers_from_the_trace(path, cpu_bench, capsys,
                                                    monkeypatch):
    """A --trace 1 run of the training cell on the CPU, its profiler
    replaced by a trace recorded on the chip: the per-layer metrics, busy
    and window seconds, and the breakdown come out in the result."""
    import contextlib

    from jax.profiler import ProfileData

    from bench import peaks
    from bench_cpu import last_result, run_cell
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(trace, "traced",
                        lambda d: contextlib.nullcontext())
    monkeypatch.setattr(trace, "load", lambda d: ProfileData.from_file(path))
    run_cell("train-yi9b-8k", trace=1)
    res = last_result(capsys)
    bench = cpu_bench.load_benchmark()
    want = {m["name"] for m in cpu_bench.per_layer_for(bench,
                                                       "train-yi9b-8k")}
    assert set(res["metrics"]) == want
    for name, m in res["metrics"].items():
        if m["unit"] == "%":
            assert 0 < m["value"] <= 100, (name, m)
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert res["correct"] is True
