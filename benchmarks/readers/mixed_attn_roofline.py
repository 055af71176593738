"""``decode_attn_roofline`` for a cache of two classes: the least time the
chip could take to read K and V of the live tokens in every full plane and
of each row's last window in every window plane
(``counts.decode_attention_bytes`` at the traced window's mean live tokens
AND mean decoding rows, over the HBM peak, a call) over the time of the
Mosaic kernel ``args.kernel``'s events inside ``args.module``, a call, in
%.  One kernel reads both classes, so its events are taken together.

Nothing where the kernel's events are absent or the family's counts have
no ``decode_attention_bytes``."""


def read(obs, args, run):
    steps = obs["traced"].get("steps")
    counts = run.registry.module("counts", run.traffic["family"])
    if not steps or not hasattr(counts, "decode_attention_bytes"):
        return None
    calls, events, took = run.registry.module(
        "readers", "decode_attn_roofline").kernel_events(obs, args)
    if not events:
        return None
    layers = obs["traced"]["model"]["layers"]
    live = sum(s[5] for s in steps) / len(steps)
    rows = sum(s[4] for s in steps) / len(steps)
    need = counts.decode_attention_bytes(run.config, layers, live, rows)
    least = need / run.peaks["hbm_bytes_per_s"]
    run.log("mixed_attn_roofline", bound="memory", calls=calls,
            events=events, live_tokens=live, rows=rows, bytes_per_call=need,
            least_ms=least * 1e3, kernel_ms_per_call=took / calls * 1e3)
    return 100.0 * least * calls / took
