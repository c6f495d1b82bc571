"""What every driver shares: the device claim, the compile cache, the
compile clock, seeds, files found by name, and the result line."""
from __future__ import annotations

import functools
import importlib.util
import json
import math
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
OUT_DIR = os.path.join(ROOT, "chiprun_out", "bench")

# The platform a run must find.  Tests rehearse the harness on the CPU
# backend by setting this (and SHRINK) on the imported module.
PLATFORM = "tpu"
# Test hook: a function (config, mix) -> (config, mix) that cuts a cell
# to a size the CPU runs in seconds.  None on the chip.
SHRINK = None


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_benchmark():
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"bench: no {what} named {name!r} in BENCHMARK.json")


def cell_files(bench, workload: str):
    """(cell, config, mix) for a workload name, each read from its own
    file: the configuration from ``configs[].file``, the traffic mix from
    ``bench/traffic/<traffic>.json``."""
    cell = find(bench["workloads"], workload, "workload")
    conf_entry = find(bench["configs"], cell["config"], "config")
    config = load_json(os.path.join(ROOT, conf_entry["file"]))
    mix = load_json(os.path.join(BENCH_DIR, "traffic",
                                 cell["traffic"] + ".json"))
    if SHRINK is not None:
        config, mix = SHRINK(config, mix)
    return cell, config, mix


def load_by_path(path: str, modname: str):
    """Import a file whose name need not be a Python identifier (a metric
    reader is named after its metric, dots and all)."""
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(kind: str):
    return load_by_path(os.path.join(BENCH_DIR, "drivers", kind + ".py"),
                        f"bench_driver_{kind}")


def reader(metric: str):
    return load_by_path(os.path.join(BENCH_DIR, "metrics", metric + ".py"),
                        "bench_metric_" + metric.replace(".", "_"))


def arch(config):
    """The module of a configuration's architecture, found by the Hugging
    Face class name in its ``architectures[0]``."""
    return arch_named(config["architectures"][0])


def arch_named(name: str):
    """``bench/archs/<name>.py``: the architecture's sizes, weights,
    program mapping, reference layers, costs and CPU cut."""
    path = os.path.join(BENCH_DIR, "archs", name + ".py")
    if not name.isidentifier() or not os.path.exists(path):
        raise SystemExit(f"bench: no module for the architecture {name!r}; "
                         f"add bench/archs/{name}.py")
    return _load_arch(path, name)


@functools.lru_cache(maxsize=None)
def _load_arch(path: str, name: str):
    return load_by_path(path, "bench_arch_" + name)


def claim_devices(chips: int):
    """The first ``chips`` devices of the platform the run must find;
    exits non-zero (before any result is printed) otherwise."""
    import jax
    devs = jax.devices()
    if devs[0].platform != PLATFORM:
        raise SystemExit(f"bench: found {devs[0].platform!r} devices "
                         f"({len(devs)}), needs {PLATFORM!r}")
    if len(devs) < chips:
        raise SystemExit(f"bench: found {len(devs)} devices, the cell "
                         f"needs {chips}")
    return devs[:chips]


def device_info(devs):
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes(devs):
    """Peak bytes in use on the fullest chip since the process started."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else 0


def enable_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where JAX_COMPILATION_CACHE_DIR says), every program
    cached however small, so that only a cell's first run compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    if jax.default_backend() == "cpu":
        return "off"
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Counts backend compiles (a persistent-cache hit counts its load)
    and sums their seconds, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.secs, self.n, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.n += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return (self.n, self.secs, self.hits)


def seed_key(seed: int, stream: int = 0):
    """A PRNG key from a whole number of any size: PRNGKey keeps only 32
    bits, so the rest is folded in."""
    import jax
    k = jax.random.PRNGKey(seed % (2 ** 31))
    k = jax.random.fold_in(k, (seed // 2 ** 31) % (2 ** 31))
    return jax.random.fold_in(k, stream)


def nearest_rank(values, q: float) -> float:
    """The q-quantile of all values by nearest rank (no interpolation)."""
    v = sorted(values)
    return float(v[max(0, math.ceil(q * len(v)) - 1)])


def check(name: str, value: float, limit: float, checks: dict) -> bool:
    """Record one compared number beside its limit; True when within."""
    checks[name] = {"value": float(value), "limit": float(limit)}
    return bool(math.isfinite(value) and value <= limit)


def emit(correct, attempted, failed, metrics, device, checks,
         breakdown=None):
    """The checks on standard error, then the result as the last line of
    standard output, with the checks under the last key."""
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    print(json.dumps(out), flush=True)


def metric(value, unit):
    return {"value": float(value), "unit": unit}


def per_layer_for(bench, workload: str):
    """The per-layer metrics that this cell reports."""
    ends = {m["name"] for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])}
    out = []
    for m in bench["per_layer"]:
        if workload in m.get("workloads", [workload]) and m["moves"] in ends:
            out.append(m)
    return out


def now() -> float:
    return time.monotonic()
