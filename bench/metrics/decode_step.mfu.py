"""The serve decode step's share of the chip's bf16 peak: the model FLOPs
of every token the traced window decoded (weights, and attention over
the positions each token attends) over the device seconds of the decode
programs in the window (the scheduler's ``chunk``, from the trace's
"XLA Modules") times the peak.  Prefill and idle time are not counted."""
from bench import peaks

PROGRAMS = ("jit_chunk",)


def read(run):
    rec = run.get("record") or {}
    secs = sum(run["trace"].get("module_s", {}).get(p, 0.0)
               for p in PROGRAMS)
    if not rec.get("keys") or not secs:
        return None
    flops = sum(peaks.decode_flops(run["dims"], n) for n in rec["keys"])
    peak = peaks.peaks(run["kind"])["bf16_flops"] * run["chips"]
    return 100.0 * flops / (secs * peak)
