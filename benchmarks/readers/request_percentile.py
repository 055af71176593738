"""A percentile over the window's finished requests of one of their
fields; the sample count goes on an earlier line."""
from benchmarks.lib import stats


def read(obs, args, run):
    values = [r[args["field"]] for r in obs["window"].get("requests", [])
              if r.get(args["field"]) is not None]
    if not values:
        return None
    run.log("percentile", field=args["field"], q=args["q"],
            samples=len(values))
    return stats.percentile(values, args["q"])
