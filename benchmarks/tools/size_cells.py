#!/usr/bin/env python3
"""Ahead-of-time sizing: compile a cell's programs for a described v5e
(no chip attached) and print ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/size_cells.py <cell> [depth]

A script to run by hand when a cell is defined or its depth is chosen
(the third rehearsal of the on-chip-measurement guide); nothing runs, so
it says nothing about times.  ``ref:<cell>`` compiles the reference's
training step instead, to see that the check fits beside nothing.
"""

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmarks.lib import registry as registry_mod  # noqa: E402

GB = 1e9


def report(tag, compiled):
    ma = compiled.memory_analysis()
    args, out, alias, temp = (ma.argument_size_in_bytes,
                              ma.output_size_in_bytes,
                              ma.alias_size_in_bytes, ma.temp_size_in_bytes)
    print(json.dumps({"program": tag, "args_gb": args / GB,
                      "out_gb": out / GB, "alias_gb": alias / GB,
                      "temp_gb": temp / GB,
                      "total_gb": (args + out - alias + temp) / GB}),
          flush=True)
    return compiled


def abstract(tree, shardings):
    return jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        tree, shardings)


def main():
    from jax.experimental import topologies
    what = sys.argv[1]
    is_ref = what.startswith("ref:")
    workload = what[4:] if is_ref else what
    reg = registry_mod.Registry([ROOT])
    bench = reg.benchmark()
    cell = registry_mod.cell_of(bench, workload)
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.load(open(os.path.join(ROOT, conf["file"])))
    traffic = reg.data("traffic", cell["traffic"])
    if len(sys.argv) > 2:
        config["num_hidden_layers"][traffic["layout"]] = int(sys.argv[2])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devices = list(topo.devices)[:cell["chips"]]
    family = traffic["family"]
    # the default backend here is the CPU, where the program would
    # interpret its Pallas kernels: compile them for the described chip
    from horovod_tpu.ops import flash_attention as fa
    fa._auto_interpret = lambda: False
    adapter = reg.module("programs", family)
    ref = reg.module("reference", family)

    if is_ref:
        from benchmarks.lib import train_reference as tref
        layers = adapter.depth(config, traffic)
        shapes = ref.weight_shapes(config, layers)
        one = SingleDeviceSharding(devices[0])
        step, opt_init = tref.build_step(ref, config, layers, traffic, None)
        w = {k: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one)
             for k, s in shapes.items()}
        state = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            jax.eval_shape(opt_init, w))
        batch = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            jax.eval_shape(lambda k: ref.make_batch(k, traffic, config),
                           jax.random.PRNGKey(0)))
        with jax.default_matmul_precision("highest"):
            report(f"reference step {workload}",
                   jax.jit(step, donate_argnums=(0, 1)).lower(
                       w, state, batch).compile())
        return

    if traffic["generator"] == "train" and family == "baichuan":
        built = adapter.train_step(config, traffic, devices)
        from horovod_tpu import trainer
        params = abstract(built["abstract"], built["pshard"])
        opt_shapes = jax.eval_shape(built["tx"].init, built["abstract"])
        from horovod_tpu.parallel import mesh as mesh_lib
        oshard = mesh_lib.tree_shardings(trainer.opt_state_specs(
            built["tx"], built["abstract"], built["specs"]), built["mesh"])
        opt_state = abstract(opt_shapes, oshard)
        batch = jax.ShapeDtypeStruct(
            (traffic["global_batch"], traffic["seq_len"]), jnp.int32,
            sharding=built["bshard"])
        compiled = report(f"{workload} step, depth {built['layers']}",
                          built["step"].lower(params, opt_state,
                                              batch).compile())
        text = compiled.as_text()
        print(json.dumps({"collectives": {
            k: text.count(f" {k}(") + text.count(f" {k}-start(")
            for k in ("all-reduce", "all-gather", "all-to-all",
                      "collective-permute", "reduce-scatter")},
            "mosaic_calls": text.count("tpu_custom_call")}))
    elif traffic["generator"] == "train":
        raise SystemExit("resnet: size it on the chip (its step builds "
                         "through hvd.init(), which wants real devices)")
    else:
        from horovod_tpu.serving import engine as eng
        layers = adapter.depth(config, traffic)
        tcfg = adapter.transformer_config(config, layers)
        one = SingleDeviceSharding(devices[0])
        shapes = ref.weight_shapes(config, layers)
        params = adapter.to_tree(
            {k: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one)
             for k, s in shapes.items()}, layers)
        e = traffic["engine"]
        S, L = e["num_slots"], e["max_len"]
        kv = jax.ShapeDtypeStruct(
            (layers, S, L, tcfg.num_heads, tcfg.d_model // tcfg.num_heads),
            jnp.bfloat16, sharding=one)

        def arr(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
        key = arr((2,), jnp.uint32)
        report(f"{workload} decode, depth {layers}", eng._decode_jit.lower(
            tcfg, params, arr((S,), jnp.int32), arr((S,), jnp.int32), kv, kv,
            arr((S,), jnp.float32), key).compile())
        for s in (128, 1024):
            report(f"{workload} prefill {s}", eng._prefill_jit.lower(
                tcfg, params, arr((1, s), jnp.int32), arr((), jnp.int32),
                arr((), jnp.float32), key).compile())
        pk = arr((layers, 1, 1024, tcfg.num_heads, 128), jnp.bfloat16)
        report(f"{workload} write_slot 1024", eng._write_slot.lower(
            kv, kv, pk, pk, arr((), jnp.int32)).compile())


if __name__ == "__main__":
    main()
