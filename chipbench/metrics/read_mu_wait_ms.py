"""Time one read request waited to acquire the store's small lock
(``get_mu_wait_ns``, span ``lsm.get.mu_wait``), per ``get_batch`` call
over the window. None for a store without the counter."""


def read(run):
    if not run.stats1 or "get_mu_wait_ns" not in run.stats1:
        return None
    calls = run.stat_delta("get_calls")
    return run.stat_delta("get_mu_wait_ns") * 1e-6 / calls if calls else None
