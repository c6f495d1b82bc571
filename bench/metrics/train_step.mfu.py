"""The whole train step's share of the chips' bf16 peak: train steps run
in the traced window (pro rata at its edges) times the model FLOPs of a
step, over the window's seconds times the chips' peak."""
from bench import peaks


def read(run):
    steps = run["trace"]["modules"].get("jit_train_step", 0.0)
    if not steps:
        return None
    mix = run["mix"]
    flops = (peaks.train_flops_per_token(run["dims"], mix["seq"])
             * mix["batch"] * mix["seq"] * steps)
    peak = peaks.peaks(run["kind"])["bf16_flops"] * run["chips"]
    return 100.0 * flops / (run["trace"]["window_s"] * peak)
