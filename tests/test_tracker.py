"""Tracker-layer tests: backend fan-out order, callback ordering, JSONL
round-trip, scalarization, and the shared run_steps loop."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from repro.tracker import (CompositeTracker, JsonlTracker, MemoryTracker,
                           StdoutTracker, Tracker, read_jsonl, scalarize)
from repro.tracker.callbacks import (Callback, CallbackRunner, MetricsBuffer,
                                     StepTimer)


# --- scalarization -----------------------------------------------------

def test_scalarize_accepts_scalars_and_device_scalars():
    assert scalarize(3) == 3
    assert scalarize(1.5) == 1.5
    assert scalarize("x") == "x"
    assert scalarize(None) is None
    assert scalarize(True) is True
    v = scalarize(jnp.float32(2.5))
    assert v == 2.5 and isinstance(v, float)
    v = scalarize(np.int32(7))
    assert v == 7 and isinstance(v, int)
    assert scalarize({"a": jnp.int32(1), "b": [np.float64(2.0)]}) == \
        {"a": 1, "b": [2.0]}


def test_scalarize_rejects_nonscalar_arrays():
    with pytest.raises(TypeError, match="scalar"):
        scalarize(jnp.zeros((3,)))
    with pytest.raises(TypeError, match="scalar"):
        scalarize(np.zeros((2, 2)))


# --- backends ----------------------------------------------------------

def test_memory_tracker_records_and_series():
    t = MemoryTracker()
    t.log(0, {"loss": jnp.float32(2.0), "lr": 0.1})
    t.log(1, {"loss": 1.0})
    t.log_summary({"final_loss": 1.0})
    t.finish()
    assert t.steps == [(0, {"loss": 2.0, "lr": 0.1}), (1, {"loss": 1.0})]
    assert t.series("loss") == [2.0, 1.0]
    assert t.series("lr") == [0.1]
    assert t.summary == {"final_loss": 1.0}
    assert t.finished


def test_composite_fans_out_in_registration_order():
    order = []

    class Probe(Tracker):
        def __init__(self, name):
            self.name = name

        def _log(self, step, metrics):
            order.append((self.name, "log", step))

        def _log_summary(self, metrics):
            order.append((self.name, "summary"))

        def finish(self):
            order.append((self.name, "finish"))

    comp = CompositeTracker([Probe("a"), Probe("b"), Probe("c")])
    comp.log(0, {"x": 1})
    comp.log_summary({"y": 2})
    comp.finish()
    assert order == [("a", "log", 0), ("b", "log", 0), ("c", "log", 0),
                     ("a", "summary"), ("b", "summary"), ("c", "summary"),
                     ("a", "finish"), ("b", "finish"), ("c", "finish")]


def test_composite_backends_see_identical_records():
    a, b = MemoryTracker(), MemoryTracker()
    comp = CompositeTracker([a, b])
    comp.log(3, {"loss": jnp.float32(0.5)})
    assert a.steps == b.steps == [(3, {"loss": 0.5})]


def test_jsonl_round_trip(tmp_path):
    path = str(tmp_path / "m.jsonl")
    t = JsonlTracker(path)
    t.log(0, {"loss": 2.5, "lr": jnp.float32(0.1), "tag": "warmup"})
    t.log(1, {"loss": 1.25})
    t.log_summary({"final_loss": 1.25, "diverged": False})
    t.finish()
    recs = read_jsonl(path)
    assert recs == [
        {"step": 0, "loss": 2.5, "lr": pytest.approx(0.1), "tag": "warmup"},
        {"step": 1, "loss": 1.25},
        {"summary": True, "final_loss": 1.25, "diverged": False},
    ]
    # append mode: a resumed run extends its own stream
    t2 = JsonlTracker(path)
    t2.log(2, {"loss": 1.0})
    t2.finish()
    assert len(read_jsonl(path)) == 4
    with pytest.raises(ValueError, match="finished"):
        t2.log(3, {"loss": 0.9})


def test_stdout_tracker_rate_limits(capsys):
    t = StdoutTracker(every=2)
    for s in range(4):
        t.log(s, {"loss": float(s)})
    t.log_summary({"final_loss": 3.0})
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 3             # steps 0, 2 + summary
    assert "step     0" in lines[0] and "step     2" in lines[1]
    assert lines[2].startswith("summary")


# --- callbacks ---------------------------------------------------------

def test_callback_runner_ordering_and_merge():
    """Callbacks run in registration order; each sees the metrics the
    previous one produced; derived metrics land in the tracker record."""
    calls = []

    class A(Callback):
        def on_step(self, step, metrics):
            calls.append(("A", step))
            assert "derived_b" not in metrics     # A runs before B
            return {"derived_a": step * 10}

        def on_end(self):
            calls.append(("A", "end"))
            return {"sum_a": 1}

    class B(Callback):
        def on_step(self, step, metrics):
            calls.append(("B", step))
            assert metrics["derived_a"] == step * 10   # B sees A's output
            return {"derived_b": True}

        def on_end(self):
            calls.append(("B", "end"))
            return {"sum_b": 2}

    mem = MemoryTracker()
    runner = CallbackRunner(mem, [A(), B()], flush_every=2)
    for s in range(3):
        runner.push(s, {"loss": float(s)})
    runner.close({"explicit": 3})
    assert calls == [("A", 0), ("B", 0), ("A", 1), ("B", 1),
                     ("A", 2), ("B", 2), ("A", "end"), ("B", "end")]
    assert [s for s, _ in mem.steps] == [0, 1, 2]
    assert mem.steps[1][1]["derived_a"] == 10
    assert mem.steps[1][1]["derived_b"] is True
    # internal _t_* plumbing never reaches the tracker
    assert not any(k.startswith("_") for _, m in mem.steps for k in m)
    assert mem.summary == {"sum_a": 1, "sum_b": 2, "explicit": 3}
    assert mem.finished


def test_callback_runner_buffers_until_flush_boundary():
    mem = MemoryTracker()
    runner = CallbackRunner(mem, flush_every=3)
    runner.push(0, {"loss": 1.0})
    runner.push(1, {"loss": 0.9})
    assert mem.steps == []            # still buffered (device scalars live)
    runner.push(2, {"loss": 0.8})
    assert [s for s, _ in mem.steps] == [0, 1, 2]
    runner.push(3, {"loss": 0.7})
    runner.close()
    assert [s for s, _ in mem.steps] == [0, 1, 2, 3]
    runner.close()                    # idempotent


def test_metrics_buffer_defers_conversion():
    buf = MetricsBuffer()
    buf.push(0, {"loss": jnp.float32(1.5)})
    buf.push(1, {"loss": jnp.float32(0.5)})
    assert len(buf) == 2
    drained = buf.drain()
    assert len(buf) == 0 and buf.drain() == []
    assert [(s, m["loss"]) for s, m in drained] == [(0, 1.5), (1, 0.5)]
    assert all(isinstance(m["loss"], float) for _, m in drained)
    # wall-time stamps are monotone across pushes
    assert drained[0][1]["_t_wall"] <= drained[1][1]["_t_wall"]


def test_step_timer_throughput():
    timer = StepTimer(tokens_per_step=100)
    m0 = timer.on_step(0, {"_t_wall": 10.0, "_t_loop_start": 9.0})
    assert m0["step_time_s"] == pytest.approx(1.0)
    assert m0["tokens_per_s"] == pytest.approx(100.0)
    m1 = timer.on_step(1, {"_t_wall": 10.5})
    assert m1["step_time_s"] == pytest.approx(0.5)
    assert m1["tokens_per_s"] == pytest.approx(200.0)
    end = timer.on_end()
    assert end["wall_time_s"] == pytest.approx(1.5)
    assert end["tokens_per_s"] == pytest.approx(200 / 1.5)


# --- the shared loop ---------------------------------------------------

def test_run_steps_threads_state_and_logs():
    from repro.training import run_steps

    def step_fn(state, batch):
        return state + batch, {"loss": jnp.float32(10 - state)}

    mem = MemoryTracker()
    final = run_steps(step_fn, 0, lambda t: 1, 5, tracker=mem, log_every=2,
                      summary={"done": True})
    assert final == 5
    assert mem.series("loss") == [10.0, 9.0, 8.0, 7.0, 6.0]
    assert mem.summary["done"] is True
    assert mem.finished


def test_run_steps_start_offset():
    from repro.training import run_steps

    mem = MemoryTracker()
    run_steps(lambda s, b: (s, {"loss": 0.0}), 0, lambda t: t, 6,
              start=4, tracker=mem)
    assert [s for s, _ in mem.steps] == [4, 5]
