"""Filter-bank bits per live key: the bytes of the published generation's
packed filter bank (the buffer the probe gathers from on the device) at
the window's end, times 8, over the live keys."""


def read(run):
    if not run.bank_bytes:
        return None
    return run.bank_bytes * 8.0 / run.live_keys
