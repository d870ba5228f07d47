"""Reduce a JAX profiler trace to what the metrics read.

The harness traces a stretch of the window with ``record_options``:
Python function tracing off (it would trace every call of eight
clients). ``load_events`` reads the ``.xplane.pb`` the profiler wrote into
plain ``Event`` tuples, and ``reduce`` works only on those, so a test can
feed it a small recorded list:

- device busy time: the union of the intervals of the device's XLA ops
  (the ``XLA Ops`` line of each ``/device:`` plane), over the stretch;
- kernel time: the summed durations of one jitted module's runs (the
  ``XLA Modules`` line, module names ``jit_<name>(...)``);
- the device ops that took most time, and the longest idle gaps between
  device ops, each labelled by the client calls (``client.<call>`` host
  spans) that overlapped it, with their counts.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import NamedTuple

CLIENT_PREFIX = "client."
_MODULE_RE = re.compile(r"^(?:jit_)?([A-Za-z0-9_]+?)(?:\(.*\))?$")


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


def record_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def load_events(log_dir: str) -> list[Event]:
    """Every event of the newest ``.xplane.pb`` under ``log_dir``."""
    import jax
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return []
    data = jax.profiler.ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def module_name(event_name: str) -> str:
    """``jit_lsm_probe(123)`` -> ``lsm_probe``."""
    m = _MODULE_RE.match(event_name.strip())
    return m.group(1) if m else event_name


@dataclass
class TraceSummary:
    window_s: float                       # length of the traced stretch
    busy_s: float                         # device busy, mean over devices
    n_devices: int
    device_ops: list = field(default_factory=list)    # [[name, s], ...]
    idle_gaps: list = field(default_factory=list)     # [[label, s], ...]
    module_s: dict = field(default_factory=dict)      # module -> seconds
    module_runs: dict = field(default_factory=dict)   # module -> count

    def kernel_seconds(self, name: str) -> float:
        """Device seconds of one jitted module, summed over its runs and
        averaged over the devices."""
        return self.module_s.get(name, 0.0)


def op_name(event_name: str) -> str:
    """An HLO op event's name cut to its name and result type:
    ``%fusion.5 = u32[7168]{0:T(1024)} fusion(...)`` -> ``%fusion.5 = u32[7168]``."""
    name, eq, rest = event_name.partition(" = ")
    return f"{name} = {rest.split('{')[0].split(' ')[0]}" if eq else event_name


def _is_device(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane


def reduce(events: list[Event], window_s: float, top: int = 10) -> TraceSummary:
    """Summarise one traced stretch of ``window_s`` seconds."""
    dev_planes = sorted({e.plane for e in events if _is_device(e.plane)})
    per_dev_busy = []
    op_time: dict[str, float] = {}
    module_s: dict[str, float] = {}
    module_runs: dict[str, int] = {}
    all_busy: list[tuple[float, float]] = []
    for plane in dev_planes:
        evs = [e for e in events if e.plane == plane]
        ops = [e for e in evs if e.line == "XLA Ops"]
        if not ops:
            ops = [e for e in evs if e.line == "XLA Modules"]
        busy = _union([(e.start_ns, e.start_ns + e.dur_ns) for e in ops])
        per_dev_busy.append(sum(b - a for a, b in busy) * 1e-9)
        all_busy.extend(busy)
        for e in ops:
            name = op_name(e.name)
            op_time[name] = op_time.get(name, 0.0) + e.dur_ns * 1e-9
        for e in evs:
            if e.line == "XLA Modules":
                m = module_name(e.name)
                module_s[m] = module_s.get(m, 0.0) + e.dur_ns * 1e-9
                module_runs[m] = module_runs.get(m, 0) + 1
    n_dev = max(1, len(dev_planes))
    module_s = {k: v / n_dev for k, v in module_s.items()}
    ops_sorted = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    gaps = _idle_gaps(events, _union(all_busy), top)
    return TraceSummary(
        window_s=float(window_s),
        busy_s=(sum(per_dev_busy) / n_dev) if per_dev_busy else 0.0,
        n_devices=len(dev_planes),
        device_ops=[[n, s / n_dev] for n, s in ops_sorted],
        idle_gaps=gaps, module_s=module_s, module_runs=module_runs)


def _idle_gaps(events: list[Event], busy: list[tuple[float, float]],
               top: int) -> list:
    """The ``top`` longest gaps between device busy intervals, each
    labelled by the client spans that overlapped it (``name*count``)."""
    if len(busy) < 2:
        return []
    spans = sorted((e.start_ns, e.start_ns + e.dur_ns,
                    e.name[len(CLIENT_PREFIX):])
                   for e in events if e.name.startswith(CLIENT_PREFIX)
                   and not _is_device(e.plane))
    gaps = [(b0, a1) for (_, b0), (a1, _) in zip(busy, busy[1:]) if a1 > b0]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for g0, g1 in gaps[:top]:
        counts: dict[str, int] = {}
        for s0, s1, name in spans:
            if s0 >= g1:
                break
            if s1 > g0:
                counts[name] = counts.get(name, 0) + 1
        label = " ".join(f"{k}*{v}" for k, v in sorted(counts.items())) or "no client call"
        out.append([label, (g1 - g0) * 1e-9])
    return out
