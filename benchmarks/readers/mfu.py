"""Model FLOP/s utilisation: the benchmark's own required-FLOP count per
item x ``train_rate`` (all items over all the window) per chip over the
chip's bf16 peak, in %."""


def read(obs, args, run):
    w = obs["window"]
    if "train_rate" not in w.get("end_to_end", {}):
        return None
    family = run.traffic["family"]
    counts = run.registry.module("counts", family)
    adapter = run.registry.module("programs", family)
    layers = adapter.depth(run.config, run.traffic)
    flops = counts.train_flops_per_item(run.config, layers, run.traffic)
    run.log("mfu", flops_per_item=flops,
            rate_per_chip=w["end_to_end"]["train_rate"],
            peak=run.peaks["bf16_flops"])
    return 100.0 * flops * w["end_to_end"]["train_rate"] \
        / run.peaks["bf16_flops"]
