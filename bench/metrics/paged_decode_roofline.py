"""The paged decode kernel's share of its HBM roofline: the bytes that
decode attention must move for every live sequence's token in the traced
window (K and V of the positions it attends, at the cache's bf16, plus
query and output, every layer) at the HBM peak, over the kernel's device
time in the window.  Counted from the work, not from the kernel's grid."""
from bench import peaks

KERNELS = ("paged_decode_attention",)


def read(run):
    from bench import trace
    rec = run.get("record") or {}
    secs = trace.kernel_seconds(run["trace"], KERNELS)
    if not rec.get("keys") or not secs:
        return None
    moved = sum(peaks.paged_decode_bytes(run["dims"], n) for n in
                rec["keys"])
    least = moved / peaks.peaks(run["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / secs
