"""A/B the TF frontend's compiled-graph collective routes across 2 real
processes — THREE legs, mirroring tools/torch_native_bench.py: the
single-tf.py_function fallback into the eager core, the native
AsyncOpKernel custom ops over the plane's default transport (shm for
same-host ring edges), and the native ops forced TCP-only
(HVD_PLANE_SHM=0). Single host, so what's measured is the per-step
seam: graph-node dispatch + negotiation round-trip + ring copy (shm or
loopback-TCP) for native, vs py_function + dlpack + core
enqueue/synchronize + device collective for the fallback.

The legs are INTERLEAVED round-robin so host load drift is common-mode
across every published ratio, and the result is one JSON line (same
schema as the torch bench) for docs/migration.md next to the
single-process py_function table (tools/tf_pyfunc_bench.py).

Usage: python tools/tf_native_bench.py [--steps 60] [--params 100352]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--params", type=int, default=100352,
                    help="model parameter count (~the MNIST CNN's 100k)")
    args = ap.parse_args()

    from horovod_tpu.run.launch import run

    def worker(steps, n_params, native_on):
        import os
        import time
        if not native_on:
            os.environ["HVD_TF_NATIVE"] = "0"
        import numpy as np
        import tensorflow as tf
        import horovod_tpu.tensorflow as hvd
        from horovod_tpu.tensorflow import native

        hvd.init()
        v = tf.Variable(np.random.RandomState(0).rand(n_params)
                        .astype(np.float32))
        opt = hvd.DistributedOptimizer(
            __import__("keras").optimizers.SGD(1e-6))

        @tf.function
        def step(x):
            with tf.GradientTape() as tape:
                loss = tf.reduce_sum(tf.square(v - x))
            opt.apply_gradients(zip(tape.gradient(loss, [v]), [v]))
            return loss

        x = tf.constant(0.5)
        float(step(x))  # trace + plane bring-up
        t0 = time.perf_counter()
        for _ in range(steps):
            out = step(x)
        float(out)
        dt = (time.perf_counter() - t0) / steps * 1e3
        used_native = native._state["plane_up"]
        hvd.shutdown()
        return dt, bool(used_native)

    import json

    import numpy as np

    env = {"JAX_PLATFORMS": "cpu"}
    # three legs interleaved round-robin (torch_native_bench protocol):
    # py_function bridge / native+shm (default) / native TCP-only
    bridge_s, shm_s, tcp_s = [], [], []
    legs = ((env, False, bridge_s),
            (env, True, shm_s),
            (dict(env, HVD_PLANE_SHM="0"), True, tcp_s))
    engaged = {id(shm_s): True, id(tcp_s): True, id(bridge_s): True}
    for _ in range(2):
        for env_over, native_on, sink in legs:
            results = run(worker,
                          args=(args.steps, args.params, native_on),
                          num_proc=2, env=env_over)
            sink.append(max(r[0] for r in results))
            used = (all(r[1] for r in results) if native_on
                    else not any(r[1] for r in results))
            engaged[id(sink)] = engaged[id(sink)] and used
    bridge_ms = float(np.median(bridge_s))
    native_shm = float(np.median(shm_s))
    native_tcp = float(np.median(tcp_s))
    out = {
        "pyfunc_ms_per_step": round(bridge_ms, 2),
        "native_ms_per_step": round(native_shm, 2),  # default route
        "native_tcp_ms_per_step": round(native_tcp, 2),
        "speedup": round(bridge_ms / native_shm, 2),
        "shm_over_tcp": round(native_tcp / native_shm, 2),
        "params": args.params,
        "procs": 2,
    }
    if not all(engaged.values()):
        out["warning"] = "a leg did not engage its intended route"
    print(json.dumps(out))


if __name__ == "__main__":
    main()
