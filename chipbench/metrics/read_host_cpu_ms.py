"""Host CPU time of one read request: the thread CPU time of the store's
``get_batch`` calls that took it (``get_cpu_ns`` over ``get_cpu_calls``,
one call in 16, span ``lsm.get_batch``) over the window. Wall time over
it is time spent waiting for a lock, the interpreter lock or the device.
None for a store without the counter."""


def read(run):
    if not run.stats1 or "get_cpu_calls" not in run.stats1:
        return None
    calls = run.stat_delta("get_cpu_calls")
    return run.stat_delta("get_cpu_ns") * 1e-6 / calls if calls else None
