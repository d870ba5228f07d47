"""SSTable reads that found nothing, per key read, over the window (the
store's own ``wasted_reads`` and ``gets`` counters)."""


def read(run):
    gets = run.stat_delta("gets")
    wasted = run.stat_delta("wasted_reads")
    if not gets:
        return None
    return wasted / gets
