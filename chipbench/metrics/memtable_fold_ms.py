"""Time the writers spent folding a sealed delta run into the memtable's
base outside the store's small lock (``fold_ns``, span
``lsm.memtable.fold``), per ``put_batch`` call over the window. None for a
store without the counter."""


def read(run):
    if not run.stats1 or "fold_ns" not in run.stats1:
        return None
    calls = run.stat_delta("put_calls")
    return run.stat_delta("fold_ns") * 1e-6 / calls if calls else None
