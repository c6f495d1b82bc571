"""The program's spans and scopes read from a trace (``bench/phases.py``)
and the per-layer metrics that read them: phases of op paths, spans
nested per thread, idle time split by scheduler phase, device self time
by scope, each reader on hand-built numbers, and the scheduler's own
counters against the benchmark's wrapper."""
import os
import shutil
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import phases, trace

HERE = os.path.dirname(__file__)
FIXTURE = os.path.join(HERE, "data", "train-yi9b-8k.xplane.pb")


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur),
              stats=list(stats.items()))


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


@pytest.mark.parametrize("path,phase", [
    ("jit(train_step)/while/body/fwd_bwd/transpose(jvp(mlp))/dot_general",
     "fwd_bwd"),
    ("jit(train_step)/fwd_bwd/jvp(params_view)/convert_element_type",
     "params_view"),
    ("jit(train_step)/while/body/grad_pack/concatenate", "grad_pack"),
    ("jit(train_step)/sngm_update/jit(_chunk_sumsq)/pallas_call",
     "sngm_update"),
    ("jit(chunk)/while/body/attention/dot_general", "attention"),
    ("jit(prefill)/unembed/dot_general", "unembed"),
    ("jit(train_step)/while", "other"),
    ("", "other"),
])
def test_phase_of_an_op_path(path, phase):
    assert phases.phase_of(path) == phase


def test_names_of_ops_and_programs():
    assert phases.hlo_name("%fusion.72 = s32[8] fusion(x), kind=kLoop") == \
        "fusion.72"
    assert phases.hlo_name("%copy-start.3 = (f32[8]) copy-start(x)") == \
        "copy-start.3"
    assert phases.program("jit_train_step(3608465511902068072)") == \
        ("jit_train_step", "3608465511902068072")


def test_own_times_are_self_times_per_event():
    evs = [("while", 0, 100), ("fusion", 10, 30), ("fused_update", 40, 90),
           ("fusion", 95, 99)]
    own = phases.own_times(evs)
    assert own == [30 - 4, 20, 50, 4]
    by_name = trace.self_times(evs)
    assert by_name["fusion"] == pytest.approx(24e-9)
    assert sum(own) * 1e-9 == pytest.approx(sum(by_name.values()))


def _serve_trace():
    """Window 0..1000; two rounds; the device runs at 0..100, 300..350
    and 700..900."""
    host = plane("/host:CPU", python3=[
        ev("bench.window", 0, 1000),
        ev("serve.round", 50, 450),                  # 50..500
        ev("serve.admit", 60, 20),
        ev("serve.prefill", 80, 200, bucket=64, rows=2, real_tokens=70),
        ev("serve.sync", 150, 100, what="first"),    # inside the prefill
        ev("serve.grow_blocks", 280, 100),           # 280..380
        ev("serve.sync", 290, 10, what="pos"),
        ev("serve.chunk", 380, 10),
        ev("serve.sync", 390, 50, what="toks"),
        ev("serve.emit", 440, 60),                   # 440..500
        ev("serve.round", 600, 350),                 # 600..950
        ev("serve.grow_blocks", 600, 100),
        ev("serve.emit", 900, 50),
        ev("bench.decode", 600, 350),
        ev("serve.round", 1100, 50)])                # after the window
    dev = plane("/device:TPU:0",
                XLA_Modules=[ev("jit_prefill(7)", 0, 100),
                             ev("jit_chunk(9)", 300, 50),
                             ev("jit_chunk(9)", 700, 200)],
                XLA_Ops=[ev("%fusion.1 = ()", 0, 100),
                         ev("%while.2 = ()", 700, 200),
                         ev("%custom-call.3 = ()", 710, 100),
                         ev("%fusion.4 = ()", 820, 40)])
    return NS(planes=[host, dev])


def test_spans_nest_per_thread_inside_the_window():
    pd = _serve_trace()
    spans = phases.program_spans(pd, 0, 1000)
    names = [sp["name"] for sp in spans]
    assert names.count("serve.round") == 2        # the third is outside
    assert "bench.decode" not in names
    by = {(sp["name"], sp["start"]): sp for sp in spans}
    assert by[("serve.sync", 150.0)]["args"] == {"what": "first"}
    assert spans[by[("serve.sync", 150.0)]["parent"]]["name"] == \
        "serve.prefill"
    assert spans[by[("serve.emit", 900.0)]["parent"]]["start"] == 600.0
    assert by[("serve.round", 50.0)]["parent"] is None


def test_idle_split_by_round_phase_sums_to_the_idle_time():
    pd = _serve_trace()
    spans = phases.program_spans(pd, 0, 1000)
    gaps = trace.subtract([(0, 1000)], [(0, 100), (300, 350), (700, 900)])
    idle = phases.idle_phases(gaps, spans)
    ns = {k: round(v * 1e9) for k, v in idle.items()}
    # round 1 (idle 100..300, 350..500) and round 2 (600..700, 900..950)
    assert ns["serve.admit"] == 0                     # before the gap
    assert ns["serve.prefill"] == 180                 # 100..280
    assert ns["serve.grow_blocks"] == 20 + 30 + 100   # 280..300, 350..380
    assert ns["serve.chunk"] == 10
    assert ns["serve.sync"] == 50                     # the toks read
    assert ns["serve.emit"] == 60 + 50                # 440..500, 900..950
    assert ns["serve.round"] == 0                     # phases tile it
    assert ns["none"] == 100 + 50                     # 500..600, 950..1000
    assert sum(idle.values()) == pytest.approx(trace.measure(gaps) * 1e-9)


def test_device_self_time_by_scope():
    pd = _serve_trace()
    paths = {("7", "fusion.1"): "jit(prefill)/embed/gather",
             ("9", "while.2"): "jit(chunk)/while",
             ("9", "custom-call.3"): "jit(chunk)/while/body/attention/x",
             ("9", "fusion.4"): "jit(chunk)/while/body/mlp/dot"}
    got = phases.scope_seconds(pd, paths, 0, 1000, 1)
    assert got["jit_prefill"] == {"embed": pytest.approx(100e-9)}
    assert got["jit_chunk"]["attention"] == pytest.approx(100e-9)
    assert got["jit_chunk"]["mlp"] == pytest.approx(40e-9)
    assert got["jit_chunk"]["other"] == pytest.approx(60e-9)


def _got(**kw):
    spans = [{"name": "serve.round"}] * 4 + [{"name": "serve.sync"}] * 110
    base = {"window_s": 2.0, "spans": spans, "scope_s": {},
            "idle_by_phase": {"serve.grow_blocks": 0.1, "serve.emit": 0.02,
                              "serve.prefill": 0.2, "none": 0.01}}
    return dict(base, **kw)


@pytest.mark.parametrize("metric,want", [
    ("scheduler.syncs_per_round", 110 / 4),
    ("scheduler.programs_per_round", 300 / 4),
    ("scheduler.upkeep_idle_share", 100 * 0.12 / 2.0),
])
def test_scheduler_readers(metric, want, cpu_bench, monkeypatch):
    run = {"trace": {"window_s": 2.0, "modules": {"jit_chunk": 250.5,
                                                  "jit_fold_in": 49.5}}}
    reader = cpu_bench.reader(metric)
    monkeypatch.setattr(phases, "find", lambda r: _got())
    assert reader.read(run) == pytest.approx(want)
    # a program without the scheduler's spans (no round) reads nothing
    monkeypatch.setattr(phases, "find", lambda r: _got(spans=[]))
    assert reader.read(run) is None
    monkeypatch.setattr(phases, "find", lambda r: None)
    assert reader.read(run) is None


def test_flat_buffer_reader(cpu_bench, monkeypatch):
    reader = cpu_bench.reader("train_step.flat_buffers.ms_per_step")
    run = {"trace": {"window_s": 4.0, "modules": {"jit_train_step": 10.0},
                     "ops_s": {"fused_update": 0.22, "chunk_sumsq": 0.08,
                               "fusion": 2.0}}}
    scope = {"params_view": 0.2, "fwd_bwd": 2.4, "grad_pack": 0.17,
             "grad_accum": 0.3, "sngm_update": 0.7, "other": 0.05}
    monkeypatch.setattr(phases, "find", lambda r: _got(
        scope_s={"jit_train_step": scope}))
    # (0.2 + 0.17 + 0.3 + 0.7 - 0.22 - 0.08) s over 10 steps
    assert reader.read(run) == pytest.approx(107.0)
    # a program without the phase scopes: every op under "other"
    monkeypatch.setattr(phases, "find", lambda r: _got(
        scope_s={"jit_train_step": {"other": 3.8}}))
    assert reader.read(run) is None


def test_find_reads_the_newest_trace_of_the_window(cpu_bench, monkeypatch,
                                                   tmp_path):
    """A trace taken on the CPU: spans and window found, and a window of
    another length refused."""
    import jax
    import jax.numpy as jnp
    monkeypatch.setattr(cpu_bench, "OUT_DIR", str(tmp_path))
    with trace.traced(str(tmp_path / "traces" / "cell-1")):
        with jax.profiler.TraceAnnotation("serve.round"):
            with jax.profiler.TraceAnnotation("serve.sync", what="toks"):
                jax.block_until_ready(jnp.ones(4) + 1)
    lo, hi = phases._window(trace.load(str(tmp_path / "traces" / "cell-1")))
    w = (hi - lo) * 1e-9
    got = phases.find({"trace": {"window_s": w}})
    assert [sp["name"] for sp in got["spans"]] == ["serve.round",
                                                   "serve.sync"]
    assert got["spans"][1]["parent"] == 0
    assert got["spans"][1]["args"] == {"what": "toks"}
    assert 0 <= got["spans"][0]["start"] <= got["spans"][1]["start"]
    assert got["scope_s"] == {}                     # no device plane
    assert phases.find({"trace": {"window_s": w + 1e-3}}) is None


def test_fixture_reduces_as_before_and_stays_untouched(tmp_path):
    """``trace.reduce`` on the recorded chip trace returns the keys and
    values it did; reading its spans and scopes from a copy writes
    nothing beside the fixture or the copy."""
    from jax.profiler import ProfileData
    before = sorted(os.listdir(os.path.dirname(FIXTURE)))
    red = trace.reduce(ProfileData.from_file(FIXTURE), 1)
    assert sorted(red) == ["busy_s", "exposed_collective_s", "idle_gaps",
                           "module_s", "modules", "ops_s", "window_s"]
    assert red["window_s"] == pytest.approx(0.389996842, abs=1e-12)
    assert red["busy_s"] == pytest.approx(0.38433676, abs=1e-12)
    assert red["module_s"]["jit_train_step"] == pytest.approx(
        0.383446209, abs=1e-12)
    assert red["ops_s"]["fused_update"] == pytest.approx(0.022167192,
                                                         abs=1e-12)
    assert red["idle_gaps"][0] == ["bench.dispatch",
                                   pytest.approx(0.005655342, abs=1e-12)]
    copy = tmp_path / "t.xplane.pb"
    shutil.copyfile(FIXTURE, copy)
    got = phases.read(str(copy))
    assert got["window_s"] == pytest.approx(red["window_s"])
    # a trace of a program without spans: all idle time outside a round
    assert got["spans"] == []
    assert got["idle_by_phase"] == {"none": pytest.approx(
        red["window_s"] - red["busy_s"])}
    assert sorted(os.listdir(tmp_path)) == ["t.xplane.pb"]
    assert sorted(os.listdir(os.path.dirname(FIXTURE))) == before


def test_scheduler_counters_equal_the_wrapper_counts(cpu_bench):
    """The scheduler's own prefill and decode counters count what the
    benchmark's wrappers round its calls count, in the same run."""
    from repro.serving.scheduler import ServeRequest

    drv = cpu_bench.driver("serve_open_loop")
    _, config, mix = cpu_bench.cell_files(cpu_bench.load_benchmark(),
                                          "serve-ds7b-chat")
    server = drv.Server(config, mix, 11)
    s = server.sched
    before = dict(s.stats)
    server.record = {"prefill_real": 0, "prefill_rows": 0, "keys": []}
    rng = np.random.default_rng(5)
    for i in range(9):
        s.submit(ServeRequest(rid=i, max_new=int(rng.integers(3, 12)),
                              prompt=rng.integers(0, 512, int(rng.integers(
                                  8, 60)), dtype=np.int32)))
    s.run()
    rec = server.record

    def delta(k):
        return s.stats[k] - before[k]
    assert delta("prefill_real_tokens") == rec["prefill_real"] > 0
    assert delta("prefill_slot_tokens") == rec["prefill_rows"]
    assert delta("decode_tokens") == len(rec["keys"]) > 0
    assert delta("rounds") > 0 and delta("host_syncs") > delta("rounds")


SCOPED = os.path.join(HERE, "data", "scopes", "train-yi9b-8k.xplane.pb")


def test_scopes_of_a_recorded_chip_trace(cpu_bench, monkeypatch, tmp_path):
    """One train step recorded on the chip, its programs' metadata kept:
    every op of the step's device time gets a phase or 'other', the five
    phases take most of it, and the flat-buffer reader reads it."""
    from jax.profiler import ProfileData
    assert os.path.getsize(SCOPED) < 2 << 20
    copy = tmp_path / "t.xplane.pb"
    shutil.copyfile(SCOPED, copy)
    got = phases.read(str(copy))
    assert sorted(os.listdir(tmp_path)) == ["t.xplane.pb"]
    red = trace.reduce(ProfileData.from_file(str(copy)), 1)
    step = got["scope_s"]["jit_train_step"]
    assert set(phases.STEP_PHASES) <= set(step)
    total = sum(step.values())
    assert total == pytest.approx(sum(red["ops_s"].values()), rel=1e-6)
    assert 1 - step["other"] / total > 0.9
    assert step["fwd_bwd"] > step["grad_accum"] > step["sngm_update"] > 0
    monkeypatch.setattr(phases, "find", lambda r: got)
    ms = cpu_bench.reader("train_step.flat_buffers.ms_per_step").read(
        {"trace": red})
    assert ms == pytest.approx(108.32543, rel=1e-5)
