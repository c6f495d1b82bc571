"""chip_smoke.py rehearsed on the CPU backend at a tiny size.

The script's device check is steered from here (``PLATFORM``), never by
a program option; on TPU the same code runs at yi-9b widths.
"""
import json
import os
import subprocess
import sys
import textwrap

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

TINY_TRAIN = dict(arch="yi-9b", n_layers=1, batch=4, seq=32, n_micro=2,
                  steps=3, lr=1.6, reduced=True)
TINY_SERVE = dict(arch="yi-9b", n_layers=2, slots=2, requests=3,
                  prompt_len=8, max_new=4, block_size=16, reduced=True)


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_refuses_without_tpu():
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=_env(), capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs 'tpu'" in r.stderr, r.stderr[-2000:]


def test_one_chip_phases_on_cpu(monkeypatch, tmp_path, capsys):
    import chip_smoke
    monkeypatch.setattr(chip_smoke, "PLATFORM", "cpu")
    monkeypatch.setattr(chip_smoke, "OUT", str(tmp_path / "out"))
    monkeypatch.setattr(chip_smoke, "CKPT", str(tmp_path / "ckpt"))
    monkeypatch.setattr(chip_smoke, "TRAIN", TINY_TRAIN)
    monkeypatch.setattr(chip_smoke, "SERVE", TINY_SERVE)
    chip_smoke.main([])
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu",
                               "count": 1}}
    text = "\n".join(out)
    assert "depth cut to 1 of 2 layers" in text
    assert "[train] largest gaps: loss" in text
    assert "[train:resumed] step 1: loss" in text
    assert "[train:update] largest per-leaf gap" in text
    assert "train step module holds 0 tpu_custom_call(s)" in text
    assert not os.path.exists(tmp_path / "ckpt")
    assert "kernel vs gather largest gap" in text


FOUR_CHIPS = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, {root!r})
    import chip_smoke
    chip_smoke.PLATFORM = "cpu"
    chip_smoke.OUT = {out!r}
    chip_smoke.CKPT = {ckpt!r}
    chip_smoke.FOUR_CHIP_TRAIN = {train!r}
    chip_smoke.main(["--four-chips"])
""")


def test_four_chip_phase_on_cpu_devices(tmp_path):
    script = FOUR_CHIPS.format(root=ROOT, out=str(tmp_path / "out"),
                               ckpt=str(tmp_path / "ckpt"), train=TINY_TRAIN)
    r = subprocess.run([sys.executable, "-c", script], env=_env(),
                       capture_output=True, text=True, timeout=560)
    lines = r.stdout.strip().splitlines()
    assert r.returncode == 0 and lines, r.stdout[-2000:] + r.stderr[-3000:]
    assert json.loads(lines[-1])["device"]["count"] == 4
    assert "mesh={'data': 2, 'model': 2}" in r.stdout
    assert "[mesh2x2] largest gaps: loss" in r.stdout
    assert "[mesh2x2:resumed] step 1: loss" in r.stdout
    assert "[mesh2x2:update] largest per-leaf gap" in r.stdout
