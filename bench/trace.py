"""The profiler trace of a traced window, and its reduction to numbers.

A traced run wraps its window in the host span ``bench.window`` and its
calls into the program in spans of its own (``bench.*``).  The reduction
reads the ``.xplane.pb`` with ``jax.profiler.ProfileData`` and gives:

  * the window and, per device, the union of the programs it ran
    (line "XLA Modules"): busy and idle; and how many runs of each
    program fell in the window, a run cut by its edge counted pro rata,
    and (first device) each program's device seconds inside it;
  * device time by operation (line "XLA Ops"), as self time: an op that
    encloses others (a while loop) keeps only what its children do not
    cover;
  * exposed collective time: the device's time in collective ops (a
    synchronous collective, or the wait of an asynchronous one's
    "-done") that no other op on it overlaps;
  * the longest idle gaps of the first device, each labelled with the
    innermost ``bench.*`` span the host was in at the gap's middle.

Host and device events share one clock in these traces (nanoseconds
from the start of the trace)."""
from __future__ import annotations

import collections
import contextlib
import glob
import os
import re

WINDOW = "bench.window"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all")


@contextlib.contextmanager
def traced(log_dir: str):
    """Profile the block; only the host spans that the benchmark writes
    and the runtime's own are kept (no Python function events)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield
    finally:
        jax.profiler.stop_trace()


def load(log_dir: str):
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return ProfileData.from_file(paths[-1])


def op_name(event_name: str) -> str:
    """'%fused_update.1 = (f32[..]) custom-call(..)' -> 'fused_update'."""
    m = re.match(r"%?([\w\-.]+?)(\.\d+)?\s*=", event_name)
    return m.group(1) if m else event_name.split(" ")[0]


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def subtract(a, b):
    """Parts of the merged intervals ``a`` that no interval of ``b``
    covers."""
    out = []
    b = union(b)
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events):
    """[(name, start, end)] -> {op: self seconds}.  Events of one line
    nest; a child's time is taken from its parent's."""
    evs = sorted(events, key=lambda x: (x[1], -(x[2] - x[1])))
    own = collections.Counter()
    stack = []                       # [(end, name)]
    for name, s, e in evs:
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= (min(e, stack[-1][0]) - s)
        own[name] += e - s
        stack.append((e, name))
    return {k: v * 1e-9 for k, v in own.items()}


def leaf_intervals(events):
    """Intervals of ops that enclose no other op (compute, not loops)."""
    evs = sorted(events, key=lambda x: (x[1], -(x[2] - x[1])))
    parents = set()
    stack = []
    for i, (name, s, e) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            parents.add(stack[-1])
        stack.append(i)
    return [(n, s, e) for i, (n, s, e) in enumerate(evs) if i not in parents]


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def reduce(pd, n_devices: int):
    """The numbers of one traced window (see the module docstring)."""
    host_spans = []
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                host_spans += [x for x in _events(line)
                               if x[0].startswith("bench.")]
    wins = [(s, e) for n, s, e in host_spans if n == WINDOW]
    if not wins:
        raise ValueError(f"no {WINDOW} span in the trace")
    lo, hi = wins[0]
    devices = sorted((p for p in pd.planes
                      if re.match(r"/device:(TPU|GPU):\d+$", p.name)),
                     key=lambda p: int(p.name.rsplit(":", 1)[1]))
    devices = devices[:n_devices]
    busy, ops, modules, exposed, gaps = [], collections.Counter(), [], [], []
    module_s = collections.Counter()       # program -> seconds inside
    for i, plane in enumerate(devices):
        lines = {ln.name: _events(ln) for ln in plane.lines}
        mods = [x for x in lines.get("XLA Modules", [])
                if x[2] > lo and x[1] < hi]
        runs = collections.Counter()       # program -> runs inside, pro rata
        for n, s, e in mods:
            runs[n.split("(")[0]] += (min(e, hi) - max(s, lo)) / max(e - s, 1)
            if i == 0:
                module_s[n.split("(")[0]] += (min(e, hi) - max(s, lo)) * 1e-9
        modules.append(runs)
        busy_iv = union(clip([(s, e) for _, s, e in mods], lo, hi))
        busy.append(measure(busy_iv) * 1e-9)
        xops = [(op_name(n), s, e) for n, s, e in lines.get("XLA Ops", [])
                if lo <= s < hi]
        for k, v in self_times(xops).items():
            ops[k] += v / len(devices)
        leaves = leaf_intervals(xops)
        coll = [(s, e) for n, s, e in leaves if COLLECTIVE.search(n)]
        comp = [(s, e) for n, s, e in leaves if not COLLECTIVE.search(n)]
        exposed.append(measure(subtract(coll, comp)) * 1e-9)
        if i == 0:
            gaps = subtract([(lo, hi)], busy_iv)
    if not devices:
        raise ValueError("no device plane in the trace")
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": sum(busy) / len(busy),
            "ops_s": dict(ops),
            "modules": modules[0],
            "module_s": dict(module_s),
            "exposed_collective_s": sum(exposed) / len(exposed),
            "idle_gaps": label_gaps(gaps, host_spans)}


def label_gaps(gaps, host_spans, top: int = 10):
    """The longest gaps, each [label, seconds]; the label is the
    innermost bench span around the gap's middle ("none" outside all)."""
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        inside = [(n, hs, he) for n, hs, he in host_spans
                  if hs <= mid <= he and n != WINDOW]
        label = min(inside, key=lambda x: x[2] - x[1])[0] if inside \
            else "none"
        out.append([label, (e - s) * 1e-9])
    return out


def kernel_seconds(red, names) -> float:
    return sum(v for k, v in red["ops_s"].items() if k in names)


def breakdown(red, top: int = 10):
    ops = sorted(red["ops_s"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": red["idle_gaps"][:top]}
