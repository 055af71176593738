"""Share of a decode step's required bytes that is per-row state, in %:
the family's own count with the rows that decode against the same count
with none.  Nothing to read (no step, or a family whose count takes no
rows) gives nothing."""


def read(obs, args, run):
    steps = obs["window"].get("steps")
    if not isinstance(steps, list) or not steps:
        return None
    counts = run.registry.module("counts", run.traffic["family"])
    layers = obs["window"]["model"]["layers"]
    live = sum(s[5] for s in steps) / len(steps)
    rows = sum(s[4] for s in steps) / len(steps)
    with_rows = counts.decode_step_bytes(run.config, layers, live, rows=rows)
    without = counts.decode_step_bytes(run.config, layers, live, rows=0)
    if with_rows == without:
        return None
    return 100.0 * (with_rows - without) / with_rows
