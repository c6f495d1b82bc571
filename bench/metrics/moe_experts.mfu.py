"""The held experts' grouped products' share of the chip's bf16 peak
(they are compute-bound at these shapes): the FLOPs that forward and
backward of the three expert products need for the tokens a step routes
to the held experts at an even load (6 * 3 d f * tokens * k * held / E,
every MoE layer), over the device self time per train step of the ops
under the ``moe_experts`` scope, times the peak."""
from bench import common, peaks

SCOPE = "moe_experts"


def read(run):
    from bench import scope_time
    steps = run["trace"]["modules"].get("jit_train_step", 0.0)
    arch = common.arch_named(run["dims"]["arch"])
    if not steps or not hasattr(arch, "moe_experts_flops_per_token"):
        return None
    secs = scope_time.seconds(run, SCOPE)
    if not secs:
        return None
    mix = run["mix"]
    flops = (arch.moe_experts_flops_per_token(run["dims"])
             * mix["batch"] * mix["seq"])
    peak = peaks.peaks(run["kind"])["bf16_flops"] * run["chips"]
    return 100.0 * flops / (secs / steps * peak)
