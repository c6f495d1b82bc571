"""Device self time by ``jax.named_scope`` anywhere on an op's path.

``bench/phases.py`` files each op under the innermost train-step phase on
its path, so the model's own scopes inside a phase (``fwd_bwd`` holds the
whole forward and backward) have no time of their own there.  ``seconds``
sums, over the first device, the self time of every op of a program that
ran in the traced window whose framework op name has the scope as one of
its parts, also where a transformation wraps it
(``transpose(jvp(moe_experts))``).  The trace is the one that
``phases.find`` would take: the newest under the benchmark's trace
directory, taken only if its window is the one the run reduced."""
from __future__ import annotations

import bisect
import functools
import glob
import os
import re

from bench import common, phases, trace


def parts(path: str):
    """'a/transpose(jvp(moe_experts))/dot' -> ['a', 'transpose(jvp(...))',
    'jvp(moe_experts)', 'moe_experts', 'dot']."""
    out = []
    for part in path.split("/"):
        while True:
            out.append(part)
            m = phases._WRAPPED.match(part)
            if not m:
                break
            part = m.group(1)
    return out


def by_scope(pd, paths, lo, hi, scope: str):
    """{program: device self seconds of its ops under ``scope``} on the
    first device, ops that start in [lo, hi)."""
    devices = sorted((p for p in pd.planes
                      if re.match(r"/device:(TPU|GPU):\d+$", p.name)),
                     key=lambda p: int(p.name.rsplit(":", 1)[1]))
    out = {}
    if not devices:
        return out
    lines = {ln.name: trace._events(ln) for ln in devices[0].lines}
    mods = sorted(lines.get("XLA Modules", []), key=lambda x: x[1])
    xops = [x for x in lines.get("XLA Ops", []) if lo <= x[1] < hi]
    starts = [s for _, s, _ in mods]
    for (n, s, e), own in zip(xops, phases.own_times(xops)):
        k = bisect.bisect_right(starts, s) - 1
        if k < 0 or s >= mods[k][2]:
            continue
        name, pid = phases.program(mods[k][0])
        if scope in parts(paths.get((pid, phases.hlo_name(n)), "")):
            out[name] = out.get(name, 0.0) + own * 1e-9
    return out


@functools.lru_cache(maxsize=4)
def _read(path: str, mtime: float):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    try:
        paths = phases.op_paths(path)
    except Exception as exc:         # the tool's failure costs the metric
        common.log(f"[scope_time] hlo_stats failed: {exc!r}")
        paths = {}
    return pd, phases._window(pd), paths


def seconds(run, scope: str, program: str = "jit_train_step"):
    """Device self seconds of ``program``'s ops under ``scope`` in the
    run's traced window; None where the trace is not found or no op of
    the program carries the scope."""
    found = glob.glob(os.path.join(common.OUT_DIR, "traces", "**",
                                   "*.xplane.pb"), recursive=True)
    if not found:
        return None
    newest = max(found, key=os.path.getmtime)
    pd, (lo, hi), paths = _read(newest, os.path.getmtime(newest))
    if abs((hi - lo) * 1e-9 - run["trace"]["window_s"]) > 1e-9:
        return None
    return by_scope(pd, paths, lo, hi, scope).get(program)
