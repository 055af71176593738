"""Compiled programs of the timed entry points when the window closes."""


def read(obs, args, run):
    return obs["window"]["compiles"]
