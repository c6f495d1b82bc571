"""A training run with the timed path broken underneath it comes out
not correct, once for each fault a one-chip training cell can have; and
the control (the reference in a lower precision) separates from the
program at a size a test can hold."""
import pytest

from bench_cpu import last_result, run_cell

CELL = "train-yi9b-8k"


def _broken(monkeypatch, wrap):
    import repro.training as training
    real = training.make_train_step

    def make(*a, **kw):
        return wrap(real(*a, **kw))
    monkeypatch.setattr(training, "make_train_step", make)


def test_step_that_returns_its_state_unchanged(cpu_bench, capsys,
                                               monkeypatch):
    def wrap(step):
        def unchanged(state, batch):
            _, stats = step(state, batch)
            return state, stats
        return unchanged
    _broken(monkeypatch, wrap)
    run_cell(CELL)
    res = last_result(capsys)
    assert res["correct"] is False
    assert res["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_step_that_leaves_half_the_batch_out(cpu_bench, capsys,
                                             monkeypatch):
    def wrap(step):
        def half(state, batch):
            n = batch["tokens"].shape[0] // 2
            return step(state, {k: v[:n] for k, v in batch.items()})
        return half
    _broken(monkeypatch, wrap)
    run_cell(CELL)
    res = last_result(capsys)
    assert res["correct"] is False
    over = [k for k, c in res["checks"].items() if c["value"] > c["limit"]]
    assert "grad_gap" in over


def test_fp8_control_fails_where_the_program_passes(cpu_bench):
    from bench.drivers import train
    bench = cpu_bench.load_benchmark()
    _, config, mix = cpu_bench.cell_files(bench, CELL)
    seed = 11
    ref = train.reference_steps(config, mix, seed)
    job = train.Job(config, mix, seed)
    got = train.compare(job.first_steps(), ref)
    job.free()
    ctl = train.compare(train.reference_steps(config, mix, seed, "fp8"),
                        ref)
    limits = mix["limits"]
    assert all(got[k] <= limits[k] for k in limits), got
    assert any(ctl[k] > limits[k] for k in limits), ctl
