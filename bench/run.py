"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (a ``workloads`` entry of BENCHMARK.json) names a model
configuration (``bench/configs/``) and a traffic mix
(``bench/traffic/<name>.json``); the mix names the driver that runs it
(``bench/drivers/<driver>.py``), and each per-layer metric is read by
``bench/metrics/<metric>.py``.  All are found by name, so a new cell,
configuration, mix or metric is a new file.

With ``--trace 0`` the last line of standard output carries the cell's
end-to-end metrics; with ``--trace 1`` its per-layer metrics, read from a
profiler trace of a short window.  The run exits non-zero, printing no
result, where JAX finds no TPU or fewer chips than the cell needs."""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the TPU runtime's own logs would go to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import common  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t_start=None):
    args = parse(argv)
    bench = common.load_benchmark()
    cell, config, mix = common.cell_files(bench, args.workload)
    devs = common.claim_devices(cell["chips"])
    common.log(f"[bench] {args.workload} seed {args.seed} on "
               f"{devs[0].device_kind} x{len(devs)}; cache "
               f"{common.enable_cache()}")
    ctx = {"args": args, "cell": cell, "config": config, "mix": mix,
           "devs": devs, "clock": common.CompileClock(),
           "t_start": T_START if t_start is None else t_start,
           "per_layer": common.per_layer_for(bench, args.workload)}
    common.driver(mix["driver"]).run(ctx)


if __name__ == "__main__":
    main()
