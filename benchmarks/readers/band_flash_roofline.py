"""Roofline share of the banded flash forward (the Mosaic kernel
``args.kernel``, a window layer's prefill attention), in %: the least time
the chip could take for the calls in the trace (the larger of the band's
FLOPs over peak FLOP/s and the operands' bytes over peak bytes/s:
``counts.band_forward`` of each event's own shapes, q ``[bh, s, dh]`` and
k ``[bhk, s, dh]``) over the time the kernel took.  The FLOPs are those of
the band's (query, key) pairs, not of the tiles a kernel visits.  Nothing
where no such event ran or the family's counts have no ``band_forward``."""
from benchmarks.lib import xplane


def read(obs, args, run):
    trace = obs["trace"]
    counts = run.registry.module("counts", run.traffic["family"])
    if not hasattr(counts, "band_forward"):
        return None
    is_kernel = run.registry.module("readers",
                                    "decode_attn_roofline").is_kernel
    t0, t1 = xplane.window_of(trace)
    dev = sorted(trace.ops)[0]
    took = least = 0.0
    bound, calls = None, 0
    for e in trace.ops[dev]:
        if e.start < t0 or e.end > t1 or \
                not is_kernel(e.name, args["kernel"]):
            continue
        dims = [s for _, s in xplane.shapes(
            e.name.partition("custom-call(")[2]) if len(s) == 3]
        (bh, s, dh), (bhk, _, _) = dims[0], dims[1]
        flops, nbytes = counts.band_forward(
            bh, bhk, s, dh, run.config["sliding_window"])
        t_flops = flops / run.peaks["bf16_flops"]
        t_bytes = nbytes / run.peaks["hbm_bytes_per_s"]
        least += max(t_flops, t_bytes)
        bound = "compute" if t_flops >= t_bytes else "memory"
        took += e.end - e.start
        calls += 1
    if not calls:
        return None
    run.log("band_flash_roofline", calls=calls, kernel_seconds=took,
            least_seconds=least, bound=bound)
    return 100.0 * least / took
