"""``bench/control.py`` judges the program, the control and each fault
against the cell's own limits, and fails where any of them comes out
on the wrong side."""
import json

import pytest

CELL = "train-yi9b-8k"


def test_judged_and_sound():
    from bench import control
    limits = {"loss_gap": 1e-3, "grad_gap": 0.03}
    res = control.judged({
        "program": {"loss_gap": 1e-4, "grad_gap": 1e-3},
        "control_fp8": {"loss_gap": 1e-4, "grad_gap": 0.5},
        "half_batch": {"loss_gap": 2e-3, "grad_gap": 1e-3}}, limits)
    assert [res[k]["correct"] for k in res] == [True, False, False]
    assert res["control_fp8"]["checks"]["grad_gap"] == {"value": 0.5,
                                                        "limit": 0.03}
    assert control.sound(res)
    # a control that passes, or a program that fails, is not sound
    assert not control.sound(dict(res, control_fp8=res["program"]))
    assert not control.sound(dict(res, program=res["half_batch"]))


def test_training_control_on_the_cpu(cpu_bench, capsys):
    from bench import control
    control.main(["--workload", CELL, "--seeds", "5"])
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    seed, last = lines[0], lines[-1]
    assert seed["program"]["correct"] is True, seed
    assert seed["control_fp8"]["correct"] is False, seed
    assert seed["half_batch"]["correct"] is False, seed
    assert last["sound"] is True


def test_control_that_passes_exits_nonzero(cpu_bench, capsys, monkeypatch):
    """With the control computed in the reference's own precision it
    passes, and the tool says the cell's limits do not separate it."""
    from bench import control
    from bench.drivers import train
    real = train.reference_steps

    def fp32_only(config, mix, seed, prec="fp32", rows=None):
        return real(config, mix, seed, "fp32", rows)
    monkeypatch.setattr(train, "reference_steps", fp32_only)
    monkeypatch.setattr(control.common, "driver", lambda kind: train)
    with pytest.raises(SystemExit, match="control or a fault passed"):
        control.main(["--workload", CELL, "--seeds", "5"])
    assert json.loads(capsys.readouterr().out.splitlines()[-1])["sound"] \
        is False
