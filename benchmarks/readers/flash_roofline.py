"""Roofline share of the Mosaic flash-attention kernels, in %: the least
time the chip could take for the calls in the trace (the larger of FLOPs
over peak FLOP/s and bytes over peak bytes/s, from the operand shapes in
each event) over the time the kernels took.  A forward call has three
operands (q, k, v), a backward call six (dq and dk/dv kernels together
are the backward).  Says which bound holds on an earlier line."""
from benchmarks.lib import xplane


def operands(text):
    inner = text.partition("custom-call(")[2].partition("), custom_call")[0]
    return inner.count("%")


def read(obs, args, run):
    trace = obs["trace"]
    counts = run.registry.module("counts", run.traffic["family"])
    count = {"fwd": counts.flash_forward, "bwd": counts.flash_backward}[
        args["direction"]]
    want = {"fwd": 3, "bwd": 6}[args["direction"]]
    t0, t1 = xplane.window_of(trace)
    dev = sorted(trace.ops)[0]
    took = least = 0.0
    bound, calls = None, 0
    for e in trace.ops[dev]:
        if e.start < t0 or e.end > t1 or \
                xplane.op_class(e.name) != "mosaic" or \
                operands(e.name) != want:
            continue
        dims = [s for _, s in xplane.shapes(
            e.name.partition("custom-call(")[2]) if len(s) == 3]
        bh, s, dh = dims[0]
        flops, nbytes = count(bh, s, dh)
        if args["direction"] == "bwd":
            # two kernels make one backward: each is charged its share
            flops, nbytes = flops / 2, nbytes / 2
        t_flops = flops / run.peaks["bf16_flops"]
        t_bytes = nbytes / run.peaks["hbm_bytes_per_s"]
        least += max(t_flops, t_bytes)
        bound = "compute" if t_flops >= t_bytes else "memory"
        took += e.end - e.start
        calls += 1
    if not calls:
        return None
    run.log("flash_roofline", direction=args["direction"], calls=calls,
            kernel_seconds=took, least_seconds=least, bound=bound)
    return 100.0 * least / took
