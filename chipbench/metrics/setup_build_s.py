"""Seconds of set-up spent building SSTable filters on the host: every
filter build of the load, as the window opened (``filter_build_ns``,
span ``lsm.filter_build``). None for a store without the counter."""


def read(run):
    if not run.stats0 or "filter_build_ns" not in run.stats0:
        return None
    return run.stats0["filter_build_ns"] * 1e-9
