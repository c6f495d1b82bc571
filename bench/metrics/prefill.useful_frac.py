"""Share of the rows x bucket tokens that the traced window's prefill
calls computed which were real prompt tokens (the rest is padding to the
slot count and to the bucket length), from the benchmark's span round
each prefill group."""


def read(run):
    rec = run.get("record") or {}
    if not rec.get("prefill_rows"):
        return None
    return 100.0 * rec["prefill_real"] / rec["prefill_rows"]
