#!/usr/bin/env python3
"""Record the small device trace that the trace reduction's self-test reads.

Run on the chip (it refuses anything else): a few iterations of a tiny
program holding one op of each class the reduction knows (matmul,
convolution, copy, elementwise fusion, a Mosaic flash kernel), with the
harness's host spans around dispatch and block.  Writes the raw
``.xplane.pb`` and a text dump of its planes, lines and event names under
``chiprun_out/fixture/``; the recorded file is then committed as
``benchmarks/fixtures/tiny.xplane.pb``.
"""

import glob
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def dump_structure(path, limit=12):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        out.append(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r} events={len(events)}")
            seen = {}
            for ev in events:
                if ev.name in seen:
                    seen[ev.name][0] += 1
                    continue
                stats = {}
                try:
                    for k, v in ev.stats:
                        stats[k] = v if isinstance(v, (int, float)) \
                            else str(v)[:80]
                except Exception as e:  # noqa: BLE001 - a dump, not a check
                    stats = {"stats_error": repr(e)}
                seen[ev.name] = [1, ev.start_ns, ev.duration_ns, stats]
            for name, (n, start, dur, stats) in list(seen.items())[:limit]:
                out.append(f"    {name[:100]!r} x{n} start={start} "
                           f"dur={dur} stats={json.dumps(stats)[:600]}")
    return "\n".join(out)


def main():
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        print("record_fixture: needs a TPU", file=sys.stderr)
        return 1
    from horovod_tpu.ops.flash_attention import flash_attention

    outdir = os.path.join("chiprun_out", "fixture")
    os.makedirs(outdir, exist_ok=True)
    tracedir = os.path.join(outdir, "trace")
    shutil.rmtree(tracedir, ignore_errors=True)

    @jax.jit
    def tiny_step(x, w, img, kern, q):
        y = jnp.dot(x, w, preferred_element_type=jnp.float32)
        z = jax.lax.conv_general_dilated(
            img, kern, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        a = flash_attention(q, q, q, causal=True)
        cache = jnp.zeros((4, 256, 256), jnp.bfloat16).at[1].set(
            y.astype(jnp.bfloat16))
        return (jnp.tanh(y).sum() + z.astype(jnp.float32).sum()
                + a.astype(jnp.float32).sum() + cache.sum())

    @jax.jit
    def write_row(cache, row):
        return cache.at[1].set(row)

    k = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(k[0], (256, 512), jnp.bfloat16)
    w = jax.random.normal(k[1], (512, 256), jnp.bfloat16)
    img = jax.random.normal(k[2], (2, 16, 16, 8), jnp.bfloat16)
    kern = jax.random.normal(k[3], (3, 3, 8, 16), jnp.bfloat16)
    q = jax.random.normal(k[4], (1, 256, 2, 128), jnp.bfloat16)
    cache = jnp.zeros((4, 256, 256), jnp.bfloat16)
    row = jnp.ones((256, 256), jnp.bfloat16)
    float(tiny_step(x, w, img, kern, q))
    cache = write_row(cache, row)
    jax.block_until_ready(cache)

    jax.profiler.start_trace(tracedir)
    t0 = time.perf_counter()
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            out = tiny_step(x, w, img, kern, q)
            cache = write_row(cache, row)
        with jax.profiler.TraceAnnotation("bench.block"):
            float(out)
        time.sleep(0.002)
    jax.block_until_ready(cache)
    window_s = time.perf_counter() - t0
    jax.profiler.stop_trace()

    pb = sorted(glob.glob(os.path.join(tracedir, "**", "*.xplane.pb"),
                          recursive=True))[-1]
    shutil.copy(pb, os.path.join(outdir, "tiny.xplane.pb"))
    with open(os.path.join(outdir, "structure.txt"), "w") as f:
        f.write(dump_structure(pb))
    stats = jax.devices()[0].memory_stats()
    print(json.dumps({"window_s": window_s, "xplane_bytes":
                      os.path.getsize(pb), "memory_stats": stats,
                      "device_kind": jax.devices()[0].device_kind}))
    shutil.rmtree(tracedir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
