"""The program's own spans and scopes in a traced window, on the clock
that ``bench/trace.py`` reads.

The program marks its work in two ways, and this reads both:

  * host spans (``jax.profiler.TraceAnnotation``) named ``train.*`` and
    ``serve.*``: ``spans`` lists those in the window (name, start and
    end in seconds from the window's opening, args, and the index of the
    innermost program span around it on the same thread), and
    ``idle_by_phase`` splits the first device's idle time by the phase
    of ``serve.round`` the host was in: a direct child of the round,
    ``serve.round`` itself between its phases, ``none`` outside any
    round;
  * device scopes (``jax.named_scope``), which reach each XLA op's
    framework op name (its ``op_name`` metadata): ``scope_s`` gives,
    per program, device self seconds by phase, an op's phase being the
    innermost train-step phase on its path, else the innermost model
    scope, else ``other``.  Self time is ``trace.reduce``'s: ops that
    start in the window, a child's time taken from its parent's.  A
    fusion's time goes to the scope of the op its metadata names.

The map from op to framework op name comes from the profiler's
``hlo_stats`` tool (the ``xprof`` package), run on a copy of the trace
in a temporary directory, since the tool writes beside what it reads.

A metric reader is handed only the numbers ``trace.reduce`` gave, so
``find`` looks up the trace those came from: the newest under the
benchmark's trace directory, taken only if its window is the one the
run reduced."""
from __future__ import annotations

import bisect
import collections
import glob
import json
import os
import re
import shutil
import tempfile

from bench import common, trace

STEP_PHASES = ("params_view", "fwd_bwd", "grad_pack", "grad_accum",
               "sngm_update")
MODEL_SCOPES = ("embed", "attention", "mlp", "unembed", "loss")
PREFIXES = ("train.", "serve.")
ROUND = "serve.round"
OTHER = "other"
# a scope wrapped by a transformation, e.g. 'transpose(jvp(attention))'
_WRAPPED = re.compile(r"^[\w\-]+\((.*)\)$")


def phase_of(path: str) -> str:
    """'jit(train_step)/while/body/fwd_bwd/transpose(jvp(mlp))/dot'
    -> 'fwd_bwd'; a path with no train-step phase gives its innermost
    model scope, and one with neither 'other'."""
    names = []
    for part in path.split("/"):
        while True:
            names.append(part)
            m = _WRAPPED.match(part)
            if not m:
                break
            part = m.group(1)
    for known in (STEP_PHASES, MODEL_SCOPES):
        hits = [n for n in names if n in known]
        if hits:
            return hits[-1]
    return OTHER


def hlo_name(event_name: str) -> str:
    """'%fusion.72 = s32[8] fusion(..)' -> 'fusion.72'."""
    m = re.match(r"%?([\w\-.]+)\s*=", event_name)
    return m.group(1) if m else event_name.split(" ")[0].lstrip("%")


def program(module_event_name: str):
    """'jit_train_step(3608465511902068072)' -> ('jit_train_step',
    '3608465511902068072')."""
    m = re.match(r"(.*?)\((\d+)\)$", module_event_name)
    return (m.group(1), m.group(2)) if m else (module_event_name, "")


def own_times(events):
    """[(name, start, end)] -> each event's self nanoseconds, in the
    events' order (``trace.self_times`` per event)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], events[i][1] - events[i][2]))
    own = [e - s for _, s, e in events]
    stack = []                       # [(end, index)]
    for i in order:
        _, s, e = events[i]
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= min(e, stack[-1][0]) - s
        stack.append((e, i))
    return own


def op_paths(path: str):
    """{(program id, HLO op name): framework op name} from the
    ``hlo_stats`` tool; empty where the tool is missing or finds no
    device ops."""
    try:
        from xprof.convert import raw_to_tool_data
    except ImportError:
        return {}
    with tempfile.TemporaryDirectory() as tmp:
        copy = os.path.join(tmp, os.path.basename(path))
        shutil.copyfile(path, copy)
        data, _ = raw_to_tool_data.xspace_to_tool_data([copy], "hlo_stats",
                                                       {})
    if not data:
        return {}
    table = json.loads(data)
    cols = [c["id"] for c in table.get("cols", [])]
    out = {}
    for row in table.get("rows", []):
        r = dict(zip(cols, (c.get("v") if c else None for c in row["c"])))
        if r.get("hlo_op_name"):
            out[(str(r.get("program_id") or ""),
                 r["hlo_op_name"].lstrip("%"))] = r.get("tf_op_name") or ""
    return out


def _window(pd):
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for n, s, e in trace._events(line):
                    if n == trace.WINDOW:
                        return s, e
    raise ValueError(f"no {trace.WINDOW} span in the trace")


def program_spans(pd, lo, hi):
    """The program's spans that overlap the window, each with the index
    of its innermost enclosing program span on the same thread."""
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            evs = sorted((e for e in line.events
                          if e.name.startswith(PREFIXES)
                          and e.start_ns + e.duration_ns > lo
                          and e.start_ns < hi),
                         key=lambda e: (e.start_ns, -e.duration_ns))
            stack = []               # [(end, index in spans)]
            for e in evs:
                s, end = e.start_ns, e.start_ns + e.duration_ns
                while stack and stack[-1][0] <= s:
                    stack.pop()
                spans.append({"name": e.name, "start": s, "end": end,
                              "args": {k: v for k, v in e.stats},
                              "parent": stack[-1][1] if stack else None})
                stack.append((end, len(spans) - 1))
    return spans


def idle_phases(gaps, spans):
    """{phase: seconds} of the idle intervals ``gaps`` (ns)."""
    rounds = [i for i, sp in enumerate(spans) if sp["name"] == ROUND]
    out = collections.Counter()
    left = list(gaps)
    for r in rounds:
        rs = (spans[r]["start"], spans[r]["end"])
        inside = trace.clip(left, *rs)
        if not inside:
            continue
        kids = [(sp["start"], sp["end"], sp["name"]) for sp in spans
                if sp["parent"] == r]
        covered = []
        for s, e, name in kids:
            part = trace.clip(inside, s, e)
            out[name] += trace.measure(part) * 1e-9
            covered += part
        out[ROUND] += trace.measure(trace.subtract(inside, covered)) * 1e-9
        left = trace.subtract(left, [rs])
    out["none"] += trace.measure(left) * 1e-9
    return dict(out)


def scope_seconds(pd, paths, lo, hi, n_devices):
    """{program: {phase: device self seconds}} over the first
    ``n_devices`` devices, averaged."""
    devices = sorted((p for p in pd.planes
                      if re.match(r"/device:(TPU|GPU):\d+$", p.name)),
                     key=lambda p: int(p.name.rsplit(":", 1)[1]))
    devices = devices[:n_devices]
    out = collections.defaultdict(collections.Counter)
    for plane in devices:
        lines = {ln.name: trace._events(ln) for ln in plane.lines}
        mods = sorted(lines.get("XLA Modules", []), key=lambda x: x[1])
        xops = [x for x in lines.get("XLA Ops", []) if lo <= x[1] < hi]
        starts = [s for _, s, _ in mods]
        for (n, s, e), own in zip(xops, own_times(xops)):
            # the program run the op belongs to: the last to start by it
            k = bisect.bisect_right(starts, s) - 1
            if k < 0 or s >= mods[k][2]:
                continue
            name, pid = program(mods[k][0])
            tf = paths.get((pid, hlo_name(n)), "")
            out[name][phase_of(tf)] += own * 1e-9 / len(devices)
    return {k: dict(v) for k, v in out.items()}


def read(path: str, n_devices: int = 1):
    """``window_s``, ``spans``, ``idle_by_phase`` and ``scope_s`` of the
    trace at ``path`` (module docstring)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    lo, hi = _window(pd)
    spans = program_spans(pd, lo, hi)
    dev = sorted((p for p in pd.planes
                  if re.match(r"/device:(TPU|GPU):\d+$", p.name)),
                 key=lambda p: int(p.name.rsplit(":", 1)[1]))
    gaps = []
    if dev:
        mods = [(s, e) for ln in dev[0].lines if ln.name == "XLA Modules"
                for _, s, e in trace._events(ln)]
        gaps = trace.subtract([(lo, hi)], trace.clip(mods, lo, hi))
    try:
        paths = op_paths(path) if dev else {}
    except Exception as exc:         # the tool's failure costs one key
        common.log(f"[phases] hlo_stats failed: {exc!r}")
        paths = {}
    out = {"window_s": (hi - lo) * 1e-9,
           "spans": [dict(sp, start=(sp["start"] - lo) * 1e-9,
                          end=(sp["end"] - lo) * 1e-9) for sp in spans],
           "idle_by_phase": idle_phases(gaps, spans),
           "scope_s": scope_seconds(pd, paths, lo, hi, n_devices)
           if paths else {}}
    return out


def find(run):
    """What ``read`` gives for the trace that ``run['trace']`` was
    reduced from, or None where it cannot be found."""
    paths = glob.glob(os.path.join(common.OUT_DIR, "traces", "**",
                                   "*.xplane.pb"), recursive=True)
    if not paths:
        return None
    newest = max(paths, key=os.path.getmtime)
    got = read(newest, run.get("chips", 1))
    if abs(got["window_s"] - run["trace"]["window_s"]) > 1e-9:
        return None
    return got


def rounds(got) -> int:
    return sum(sp["name"] == ROUND for sp in got["spans"])
