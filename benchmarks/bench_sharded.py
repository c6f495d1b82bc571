"""Sharded resident-engine benchmark: the distributed scale-out gate.

Runs the multi-tensor engine on a 2x2 data x model host mesh (8 forced
CPU devices, the same lane the multidevice tests use) and records, for
sngm / msgd / lamb / clip->sngm:

  * kernel LAUNCHES per step with the resident state sharded over the
    mesh — the shard_map two-level norm must NOT add launches (the body
    traces once; the gather is a collective, not a kernel), so the
    counts are pinned to the single-device numbers (sngm 2, msgd 2,
    lamb 2, clip->sngm 3);
  * bitwise PARITY booleans: the donated sharded resident step against
    the undonated single-device canonical — fp32 bit-identity is the
    two-level norm's contract (per-shard Pallas partials + tiled gather
    + the canonical per-segment fold);
  * param-bytes RESIDENCY under sharding: the donated TrainState holds
    ~1x raw param bytes (flat buffers only; shard padding is the only
    overhead, bounded by the 1.5x gate);
  * DONATION warnings under sharding: the donated step must consume
    every sharded buffer (zero warnings).

CLI:  python -m benchmarks.bench_sharded [--quick] [--json OUT]
``--json`` writes the canonical schema-versioned BENCH artifact
(benchmarks/artifact.py envelope) that ``check_bench.py`` gates against
the ``sharded`` section of bench_thresholds.json.
"""
from __future__ import annotations

import os

# the mesh lane needs multiple host devices BEFORE jax initializes
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.artifact import make_envelope, validate_envelope
from benchmarks.common import csv_row
from repro.core import compile_chain, lamb, msgd, sngm
from repro.core import transform as T
from repro.core.multi_tensor import FlatOptState, mesh_shards, unflatten
from repro.core.schedules import constant
from repro.launch.mesh import make_mesh
from repro.tracker.counters import (capture_donation_warnings,
                                    launches_per_step, param_bytes_live)

SHAPES = [(512, 512)] * 6 + [(1024, 256)] * 2 + [(512,)] * 8
SHAPES_QUICK = [(256, 256)] * 6 + [(256,)] * 8


def make_tree(seed, shapes, scale=1.0):
    k = jax.random.PRNGKey(seed)
    return {f"p{i}": scale * jax.random.normal(jax.random.fold_in(k, i), s)
            for i, s in enumerate(shapes)}


def _state_tree(st: FlatOptState):
    slots = [st.p_flats, st.u_flats, st.m_flats, st.v_flats]
    return [unflatten(f, st.layout, keep_dtype=True) for f in slots if f]


def _bitwise(st_a: FlatOptState, st_b: FlatOptState) -> bool:
    for ta, tb in zip(_state_tree(st_a), _state_tree(st_b)):
        for a, b in zip(jax.tree.leaves(ta), jax.tree.leaves(tb)):
            if not bool(jnp.array_equal(a, b)):
                return False
    return True


def time_call(fn, *args, iters=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def run(quick: bool = False, json_path: str | None = None):
    shapes = SHAPES_QUICK if quick else SHAPES
    iters = 3 if quick else 5
    mesh = make_mesh((2, 2), ("data", "model"))
    assert mesh_shards(mesh) == 4, dict(mesh.shape)
    params = make_tree(0, shapes)
    grads = [make_tree(1 + t, shapes, 3.0) for t in range(2)]
    n_params = sum(int(np.prod(s)) for s in shapes)
    rows = []

    def clip_sngm(**kw):
        tx = T.chain(T.clip_by_global_norm(1.0), T.add_decayed_weights(1e-4),
                     T.normalize_by_global_norm(), T.trace(0.9),
                     T.scale_by_schedule(constant(0.1)))
        return compile_chain(tx, fused="multi_tensor", **kw)

    builders = {
        "sngm": lambda **kw: sngm(constant(0.1), beta=0.9,
                                  weight_decay=1e-4,
                                  fused="multi_tensor", **kw),
        "msgd": lambda **kw: msgd(constant(0.1), beta=0.9,
                                  weight_decay=1e-4,
                                  fused="multi_tensor", **kw),
        "lamb": lambda **kw: lamb(constant(0.1), weight_decay=1e-4,
                                  fused="multi_tensor", **kw),
        "clip_sngm": clip_sngm,
    }

    launches, parity, us = {}, {}, {}
    for name, mk in builders.items():
        opt_1, opt_s = mk(), mk(mesh=mesh)
        st_1, st_s = opt_1.init(params), opt_s.init(params)
        launches[f"{name}_single"] = launches_per_step(
            opt_1, grads[0], st_1, None)
        launches[name] = launches_per_step(opt_s, grads[0], st_s, None)
        # canonical single-device numerics (undonated) vs the production
        # configuration: sharded resident state, donated step
        step_1 = jax.jit(opt_1.step)
        step_s = jax.jit(opt_s.step, donate_argnums=(1,))
        for g in grads:
            _, st_1, _ = step_1(g, st_1, None)
            _, st_s, _ = step_s(g, st_s, None)
        parity[name] = _bitwise(st_1, st_s)
        us[name] = time_call(
            jax.jit(opt_s.step), grads[0], opt_s.init(params), None,
            iters=iters)
        rows.append(csv_row(
            f"sharded_{name}", us[name],
            f"launches/step={launches[name]} (single "
            f"{launches[f'{name}_single']}), bitwise_parity={parity[name]}"))
        print(f"  {rows[-1]}")

    # residency: the sharded resident TrainState still holds ~1x raw
    # param bytes — shard padding (buckets rounded up to shards*TILE) is
    # the only overhead, and the 1.5x gate bounds it
    opt_s = builders["sngm"](mesh=mesh)
    ts = opt_s.init_state(make_tree(0, shapes))
    pb_live = param_bytes_live(ts)
    param_bytes = sum(int(np.prod(s)) * 4 for s in shapes)
    rows.append(csv_row("sharded_param_bytes_live", pb_live,
                        f"raw={param_bytes} "
                        f"ratio={pb_live / param_bytes:.3f}"))
    print(f"  {rows[-1]}")

    # donation under sharding: every donated sharded buffer consumed
    _, warnings = capture_donation_warnings(
        opt_s.step_state, grads[0], ts, donate_argnums=(1,))
    for msg in warnings:
        print(f"  DONATION WARNING: {msg}")
    print(f"  donated sharded resident step: {len(warnings)} donation "
          f"warnings")

    out = {"rows": rows, "n_params": n_params,
           "mesh": {"data": 2, "model": 2, "shards": 4},
           "launches_per_step": launches,
           "parity_bitwise": parity,
           "us_per_step": us,
           "param_bytes_live": {"resident": int(pb_live),
                                "raw_params": int(param_bytes)},
           "donation_warnings": warnings}
    if json_path:
        import json

        envelope = make_envelope("sharded", out, quick=quick)
        assert not validate_envelope(envelope)
        d = os.path.dirname(json_path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(json_path, "w") as f:
            json.dump(envelope, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"  wrote {json_path}")
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small tree + few iters (CI smoke lane)")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="write results JSON to this path")
    args = ap.parse_args()
    run(quick=args.quick, json_path=args.json)
