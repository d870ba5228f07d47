"""Share of the probe's launched key slots that hold a real key: keys
probed over the slots of the tiles launched (``probed``, ``probe_slots``,
span ``gen.probe.split``) over the window. None for a store without the
counter."""


def read(run):
    if not run.stats1 or "probe_slots" not in run.stats1:
        return None
    slots = run.stat_delta("probe_slots")
    return 100.0 * run.stat_delta("probed") / slots if slots else None
