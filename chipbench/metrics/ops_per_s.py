"""YCSB operations acknowledged inside the window, over the window's
seconds: keys read, keys written and scans, each request counted once it
returned within the window."""


def read(run):
    done = run.ok & (run.end_ns <= run.window_ns)
    return float(run.ops[done].sum()) / run.seconds
