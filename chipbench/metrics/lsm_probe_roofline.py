"""The fused LSM probe's share of its roofline over the traced stretch:
the least time its bytes allow at the chip's HBM bandwidth, over the
device time of its ``jit_lsm_probe`` runs. Bytes come from the real keys
probed and the tables of the generation they were probed against
(``kernels/lsm_probe.py``)."""


def read(run):
    t = run.trace
    if t is None or run.peaks is None or not run.probe_segments:
        return None
    seconds = t.kernel_seconds("lsm_probe")
    work = run.kernel("lsm_probe")
    nbytes = sum(work.bytes_moved(n, chains) for n, chains in run.probe_segments)
    if seconds <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / seconds
