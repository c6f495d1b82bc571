"""Compile a cell's programs for a described TPU, without the chip, and
print what each needs of the device's memory.

    JAX_PLATFORMS=cpu python3 bench/aot.py --workload <cell> [--topology v5e:2x2]

Training cells compile the train step at the cell's sizes; serving cells
compile the prefill of every bucket the traffic can reach and the decode
chunk.  Nothing runs: this is the compiler's verdict only."""
import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0:0] = [os.path.join(ROOT, "src"), ROOT]


def memory(compiled):
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "temp_size_in_bytes",
        "generated_code_size_in_bytes")}


def shapes_on(tree, sharding):
    import jax
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


def train(config, mix, dev):
    import jax
    import jax.numpy as jnp
    from repro.core.optim import OptimizerSpec, TrainState, make_optimizer
    from repro.models import model_defs
    from repro.models.param import abstract
    from repro.models.runtime import Runtime
    from repro.training import make_train_step
    from bench import common
    cfg = common.arch(config).program_config(config)
    params = abstract(model_defs(cfg))
    spec = OptimizerSpec("sngm", {
        "schedule": {"name": "poly_power", "kwargs": {
            "lr0": mix["lr"], "total_steps": mix["total_steps"],
            "power": 1.1}},
        "beta": mix["beta"], "weight_decay": mix["weight_decay"],
        "nesterov": False, "fused": mix["fused"]})
    opt = make_optimizer(spec)
    state = jax.eval_shape(lambda p: TrainState.wrap(p, opt.init(p)), params)
    rt = Runtime(mesh=None, data_axes=("data",),
                 remat=config["program"]["remat"])
    step = jax.jit(make_train_step(cfg, rt, opt, n_micro=mix["n_micro"]),
                   donate_argnums=(0,))
    B, S = mix["batch"], mix["seq"]
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
             "loss_mask": jax.ShapeDtypeStruct((B, S), jnp.float32)}
    c = step.lower(shapes_on(state, dev), shapes_on(batch, dev)).compile()
    return {"train_step": memory(c)}


def serve(config, mix, dev):
    import jax
    import jax.numpy as jnp
    from repro.models import model_defs
    from repro.models.param import abstract
    from repro.models.runtime import Runtime
    from repro.serving.paged_cache import n_blocks_for
    from repro.serving.scheduler import PagedScheduler
    from bench import common
    cfg = common.arch(config).program_config(config)
    params = shapes_on(abstract(model_defs(cfg)), dev)
    bs, ctx, n = mix["block_size"], mix["ctx_max"], mix["slots"]
    s = PagedScheduler(cfg, params, Runtime(mesh=None, remat=False),
                       n_slots=n, block_size=bs,
                       n_blocks=1 + n * n_blocks_for(ctx, bs), ctx_max=ctx,
                       decode_chunk=mix["decode_chunk"])
    out = {}
    lens = range(mix["prompt"]["min"], mix["prompt"]["max"] + 1)
    for b in sorted({s._bucket(x) for x in lens}):
        print(f"[aot] prefill {n}x{b}", file=sys.stderr, flush=True)
        c = s._prefill.lower(
            params, jax.ShapeDtypeStruct((n, b), jnp.int32, sharding=dev),
            last_pos=jax.ShapeDtypeStruct((n,), jnp.int32,
                                          sharding=dev)).compile()
        out[f"prefill_{n}x{b}"] = memory(c)
    k = mix["decode_chunk"]
    print(f"[aot] decode chunk {n}x{k}", file=sys.stderr, flush=True)
    c = s._chunk.lower(
        params, shapes_on(s.paged, dev), shapes_on(s.tok, dev),
        shapes_on(s.pos, dev),
        jax.ShapeDtypeStruct((k, n), jnp.bool_, sharding=dev),
        jax.ShapeDtypeStruct((k, 2), jnp.uint32, sharding=dev)).compile()
    out[f"decode_chunk_{n}x{k}"] = memory(c)
    pool = sum(int(x.size) * x.dtype.itemsize for x in
               jax.tree.leaves(s.paged))
    out["pool_bytes"] = pool
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--topology", default="v5e:2x2")
    args = ap.parse_args(argv)
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from bench import common
    bench = common.load_benchmark()
    cell, config, mix = common.cell_files(bench, args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    dev = SingleDeviceSharding(topo.devices[0])
    fn = train if mix["driver"] == "train" else serve
    print(json.dumps({args.workload: fn(config, mix, dev)}, indent=1))


if __name__ == "__main__":
    main()
