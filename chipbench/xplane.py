"""From a profiler trace (``*.xplane.pb``) to intervals, and the arithmetic on
intervals every device metric is made of: the union of busy time, gaps, and
the part of one set of intervals that another does not cover.

The reader follows ``benchmarks/profile_step.collect_ops`` (device planes
``/device:TPU:n``, their "XLA Ops" and "XLA Modules" lines); that one sums
durations, which cannot give an idle share, so the union is new here.
Times are integer nanoseconds on the trace's own clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
#: host annotations the benchmark writes itself (``jax.profiler.TraceAnnotation``)
HOST_PREFIX = "chipbench."


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: int
    end: int


@dataclasses.dataclass
class Device:
    name: str
    ops: list       # the core's own line: one operation at a time
    modules: list   # whole executions of a compiled program
    flights: list   # asynchronous operations from their start to their done


def short(name: str) -> str:
    """``%fusion.12 = bf16[..] fusion(..)`` -> ``fusion.12``: a device event
    is named by its whole HLO instruction; the readers want its name."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


@dataclasses.dataclass
class Trace:
    devices: list   # Device, ordered by name
    host: list      # Event: the benchmark's own host annotations


def from_profile(profile) -> Trace:
    devices, host = [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            events = lambda name: sorted(
                (Event(short(e.name), int(e.start_ns),
                       int(e.start_ns + e.duration_ns))
                 for e in lines[name].events),
                key=lambda e: e.start) if name in lines else []
            devices.append(Device(plane.name, events(OPS_LINE),
                                  events(MODULES_LINE), events(ASYNC_LINE)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append(Event(e.name, int(e.start_ns),
                                          int(e.start_ns + e.duration_ns)))
    devices.sort(key=lambda d: d.name)
    host.sort(key=lambda e: e.start)
    return Trace(devices, host)


def load(path: str) -> Trace:
    """``path`` is an ``.xplane.pb`` file or a directory holding one."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no xplane.pb under {path}")
        path = found[-1]
    return from_profile(ProfileData.from_file(path))


# -- interval arithmetic -----------------------------------------------------


def spans(events) -> list:
    return [(e.start, e.end) for e in events]


def union(intervals) -> list:
    """Disjoint, ordered intervals covering the same instants."""
    out = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def total(intervals) -> int:
    return sum(hi - lo for lo, hi in intervals)


def clip(intervals, lo: int, hi: int) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(merged, lo: int, hi: int) -> list:
    """What ``[lo, hi]`` holds that the merged intervals do not."""
    out, at = [], lo
    for a, b in clip(merged, lo, hi):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def subtract(a, b) -> list:
    """The part of ``a`` that ``b`` does not cover (both any intervals)."""
    out = []
    for lo, hi in union(a):
        out.extend(gaps(union(b), lo, hi))
    return out


# -- what the readers share ---------------------------------------------------


def step_window(device: Device, whole_only: bool = True):
    """``(lo, hi, runs)``: from the start of the first whole execution of the
    step program in the trace to the end of the last, and how many there
    were. The step program is the module that took most of the time. A trace
    that starts and stops while the device is busy cuts the first and the
    last execution short, so those two are left out."""
    by_name = {}
    for m in device.modules:
        by_name[m.name] = by_name.get(m.name, 0) + m.end - m.start
    if not by_name:
        return None
    name = max(by_name, key=by_name.get)
    runs = [m for m in device.modules if m.name == name]
    if whole_only:
        runs = runs[1:-1]
    if not runs:
        return None
    return runs[0].start, runs[-1].end, len(runs)


def first_device(trace):
    """``(device, lo, hi, runs)`` of device 0 and its step window, or None
    where the trace holds no device or no whole step: a reader then returns
    nothing."""
    if trace is None or not trace.devices:
        return None
    window = step_window(trace.devices[0])
    return None if window is None else (trace.devices[0], *window)


def busy(device: Device, lo: int, hi: int) -> int:
    return total(clip(union(spans(device.ops)), lo, hi))
