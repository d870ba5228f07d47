"""Wall time of a probe's result pull, which waits for the kernel
(``probe_d2h_ns``, span ``gen.probe.d2h``), per probe launch over the
window. None for a store without the counter."""


def read(run):
    if not run.stats1 or "probe_d2h_ns" not in run.stats1:
        return None
    launches = run.stat_delta("probe_launches")
    return run.stat_delta("probe_d2h_ns") * 1e-6 / launches if launches else None
