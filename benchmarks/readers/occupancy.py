"""Mean share of the slots that hold a request after a step, in %."""


def read(obs, args, run):
    steps = obs["window"].get("steps")
    if not isinstance(steps, list) or not steps:
        return None
    return 100.0 * sum(s[4] for s in steps) / len(steps) \
        / obs["window"]["slots"]
