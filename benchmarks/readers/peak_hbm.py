"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip, in GB, read
when the windows have closed and before the reference runs."""


def read(obs, args, run):
    run.log("memory", peak_bytes=obs["memory_peak_bytes"],
            memory_analysis=obs.get("memory_analysis"))
    return obs["memory_peak_bytes"] / 1e9
