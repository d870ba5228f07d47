"""Time one write request spent merging its keys into the memtable under
the store's small lock (``merge_ns``, span ``lsm.memtable.merge``), per
``put_batch`` call over the window. None for a store without the
counter."""


def read(run):
    if not run.stats1 or "merge_ns" not in run.stats1:
        return None
    calls = run.stat_delta("put_calls")
    return run.stat_delta("merge_ns") * 1e-6 / calls if calls else None
