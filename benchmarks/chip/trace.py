"""Reduce a profiler trace of the window to per-layer numbers.

Input is what the JAX profiler writes (``*.xplane.pb``), read with
``jax.profiler.ProfileData``. Two event lists matter, both in
nanoseconds on the profiler's one clock:

- device operations: the ``XLA Ops`` line of each ``/device:TPU:<k>``
  plane. Each event is one HLO instruction (``%wedge_fused.12 = ...``);
  control flow (``while``, ``conditional``) encloses its body's events.
- host spans: the host thread that holds the benchmark's job spans,
  with the Python calls the profiler records on it.

From them: busy time (the union of the device intervals), the window
(first job span's start to last job span's end), each kernel's time,
each job's host lead (span start to its first device operation), the
device operations that took most time by self time, and the longest
idle gaps labelled with the innermost host call running through them.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import os
import re
from typing import Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
_INSTR = re.compile(r"^%?([\w.\-]+?)(?:\s*=|$)")


def instruction(event_name: str) -> str:
    """The HLO instruction's name: ``%fusion.284 = s32[...] ...`` ->
    ``fusion.284``."""
    m = _INSTR.match(event_name.strip())
    return m.group(1) if m else event_name.split(" ", 1)[0]


def base_name(event_name: str) -> str:
    """The instruction without its number: ``wedge_fused.12`` ->
    ``wedge_fused``, the name a kernel is given."""
    return re.sub(r"\.\d+$", "", instruction(event_name))


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(events):
    """``{instruction: self ns}`` over properly nested events."""
    evs = sorted(events, key=lambda x: (x[1], -x[2]))
    child = [0.0] * len(evs)
    stack = []
    for i, (_n, s, d) in enumerate(evs):
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            child[stack[-1]] += d
        stack.append(i)
    out: dict = {}
    for (n, _s, d), c in zip(evs, child):
        k = instruction(n)
        out[k] = out.get(k, 0.0) + max(d - c, 0.0)
    return out


@dataclasses.dataclass
class Reduced:
    """The window's device and host events, and what is read from them.
    Events are ``[name, start_ns, duration_ns]``; ``device`` holds one
    list per chip."""

    device: list
    host: list
    jobs: list  # [(start_ns, end_ns)] of the job spans
    w0: float
    w1: float

    @classmethod
    def from_events(cls, device_events: list, host_events: list,
                    job_span: str) -> "Reduced":
        """``device_events`` holds one event list per chip."""
        jobs = sorted((s, s + d) for n, s, d in host_events if n == job_span)
        if not jobs:
            raise ValueError(f"no {job_span!r} span in the trace")
        return cls(device_events, host_events, jobs, jobs[0][0], jobs[-1][1])

    def _window(self, events):
        return [e for e in events if e[1] < self.w1 and e[1] + e[2] > self.w0]

    @functools.cached_property
    def _busy(self):
        """Merged busy intervals of each chip, clipped to the window."""
        return [_merge((max(s, self.w0), min(s + d, self.w1))
                       for _n, s, d in self._window(c)) for c in self.device]

    @property
    def window_s(self) -> float:
        return (self.w1 - self.w0) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        per = [sum(e - s for s, e in b) for b in self._busy]
        return sum(per) / len(per) * 1e-9 if per else 0.0

    def idle_pct(self) -> Optional[float]:
        if self.w1 <= self.w0 or not self.busy_s:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def kernel_s(self, name: str) -> float:
        """Seconds in the events of kernel ``name`` in the window,
        averaged over the chips."""
        per = [sum(d for n, _s, d in self._window(c) if base_name(n) == name)
               for c in self.device]
        return sum(per) / len(per) * 1e-9 if per else 0.0

    def host_lead_s(self) -> Optional[float]:
        """Mean seconds from a job span's start to the first device
        operation at or after it, over the jobs that had one."""
        starts = sorted(s for c in self.device for _n, s, _d in c)
        leads = []
        for js, je in self.jobs:
            i = bisect.bisect_left(starts, js)
            if i < len(starts) and starts[i] < je:
                leads.append(starts[i] - js)
        return sum(leads) / len(leads) * 1e-9 if leads else None

    def _host_at(self, t: float) -> str:
        """The innermost host span running at ``t``."""
        best = None
        for n, s, d in self.host:
            if s <= t < s + d and (best is None or s >= best[1]):
                best = (n, s)
        return best[0] if best else "no host span"

    def breakdown(self, top: int = 10) -> dict:
        """Device operations by self time, and the longest idle gaps of
        chip 0 labelled with what the host was doing."""
        self_ns: dict = {}
        for c in self.device:
            for k, v in _self_times(self._window(c)).items():
                self_ns[k] = self_ns.get(k, 0.0) + v / len(self.device)
        ops = sorted(self_ns.items(), key=lambda kv: -kv[1])[:top]
        gaps, t = [], self.w0
        for s, e in self._busy[0] if self._busy else []:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < self.w1:
            gaps.append((t, self.w1))
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
        return {
            "device_ops": [[k, v * 1e-9] for k, v in ops],
            "idle_gaps": [[self._host_at((s + e) / 2), (e - s) * 1e-9]
                          for s, e in gaps],
        }


def events_from_xspace(path: str, job_span: str, chips: int = 1):
    """``(device, host)`` event lists of one ``.xplane.pb`` file."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    device = [[] for _ in range(chips)]
    host = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < chips:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device[int(m.group(1))] = [
                        [e.name, e.start_ns, e.duration_ns] for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                evs = [[e.name, e.start_ns, e.duration_ns] for e in line.events]
                if any(n == job_span for n, _s, _d in evs):
                    host = evs
    return device, host


def reduce_dir(trace_dir: str, job_span: str, chips: int = 1) -> Reduced:
    """Reduce the newest trace the profiler wrote under ``trace_dir``."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    device, host = events_from_xspace(max(files, key=os.path.getmtime),
                                      job_span, chips)
    return Reduced.from_events(device, host, job_span)
