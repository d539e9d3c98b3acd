"""Reduction of a profiler trace to the benchmark's device numbers.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``load`` reads it with ``jax.profiler.ProfileData`` into three lists:

* device ops: the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane;
* device modules: its ``XLA Modules`` line (one event per program run,
  named ``jit_<function>(<id>)``);
* host events: every event with a duration on the ``/host:CPU`` plane
  (the benchmark's own ``TraceAnnotation`` spans among them).

From these: busy time (the union of device op intervals inside the window),
kernel time by name, module time by jit name, the device ops that took most
time, and the longest idle gaps attributed to what the host was doing.  A
name that matches nothing raises ``KeyError``: a missing kernel or module
never reads as 0.

    python bench/trace.py <trace dir>     # print a summary of a trace
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import sys
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"  # the host span that marks the measured window


@dataclass
class Event:
    name: str
    start: int  # ns, on the trace's common clock
    dur: int  # ns
    stats: dict = field(default_factory=dict)

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclass
class Trace:
    ops: dict  # device plane name -> [Event] (XLA Ops)
    modules: dict  # device plane name -> [Event] (XLA Modules)
    host: list  # [Event] host events with a duration

    @property
    def devices(self) -> list:
        return sorted(self.ops)


_DEVICE = re.compile(r"^/device:TPU:\d+$")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except (TypeError, ValueError):
        return {}


def load(path: str, *, op_stats: bool = True) -> Trace:
    """Read an ``.xplane.pb`` (or the trace directory holding one)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = [
                        Event(e.name, int(e.start_ns), int(e.duration_ns),
                              _stats(e) if op_stats else {})
                        for e in line.events
                    ]
                elif line.name == "XLA Modules":
                    modules[plane.name] = [
                        Event(e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events
                    ]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend(
                    Event(e.name, int(e.start_ns), int(e.duration_ns))
                    for e in line.events if e.duration_ns > 0
                )
    return Trace(ops=ops, modules=modules, host=host)


# ---------------------------------------------------------------------------
# window, busy and idle
# ---------------------------------------------------------------------------


def window(trace: Trace) -> tuple[int, int]:
    """(start, end) of the measured window: the ``bench.window`` host span."""
    spans = [e for e in trace.host if e.name == WINDOW_SPAN]
    if not spans:
        raise KeyError(f"no {WINDOW_SPAN!r} host span in the trace")
    return spans[0].start, spans[-1].end


def busy_intervals(events, lo: int, hi: int) -> list:
    """Union of the events' intervals, clipped to [lo, hi], merged."""
    iv = sorted((max(e.start, lo), min(e.end, hi)) for e in events
                if e.end > lo and e.start < hi)
    merged = []
    for a, b in iv:
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return merged


def busy_share(trace: Trace) -> tuple[float, float]:
    """(busy seconds averaged over the devices, window seconds)."""
    if not trace.ops:
        raise KeyError("no TPU device plane with an 'XLA Ops' line")
    lo, hi = window(trace)
    busy = [sum(b - a for a, b in busy_intervals(evs, lo, hi))
            for evs in trace.ops.values()]
    return sum(busy) / len(busy) / 1e9, (hi - lo) / 1e9


def idle_gaps(trace: Trace, device: str | None = None, top: int = 10) -> list:
    """The ``top`` longest idle gaps in the window, each as [what the host
    was doing, seconds]: the innermost of the benchmark's ``bench.*`` spans
    that covers the gap's middle, and the innermost host event there
    (``"no host span"`` where none does)."""
    device = device or trace.devices[0]
    lo, hi = window(trace)
    busy = busy_intervals(trace.ops[device], lo, hi)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    host = sorted(trace.host, key=lambda e: e.start)
    starts = [e.start for e in host]
    out = []
    for a, b in gaps[:top]:
        mid = (a + b) // 2
        cover = [e for e in host[: bisect.bisect_right(starts, mid)]
                 if e.end >= mid and e.name != WINDOW_SPAN]
        ours = [e for e in cover if e.name.startswith("bench.")]
        names = [min(c, key=lambda e: e.dur).name for c in (ours, cover) if c]
        label = ": ".join(dict.fromkeys(names)) or "no host span"
        out.append([label, (b - a) / 1e9])
    return out


# ---------------------------------------------------------------------------
# kernels, modules, top ops
# ---------------------------------------------------------------------------


def _in_window(trace: Trace, events) -> list:
    lo, hi = window(trace)
    return [e for e in events if e.start >= lo and e.end <= hi]


def kernel_events(trace: Trace, kernel: str) -> list:
    """Device op events of a kernel: ops named ``kernel`` or ``kernel.<n>``,
    or whose ``long_name`` stat names it, inside the window, all devices."""
    pat = re.compile(rf"(^|[^A-Za-z0-9_]){re.escape(kernel)}([^A-Za-z0-9_]|$)")
    found = []
    for evs in trace.ops.values():
        for e in _in_window(trace, evs):
            if pat.search(e.name) or pat.search(str(e.stats.get("long_name", ""))):
                found.append(e)
    if not found:
        raise KeyError(f"no device op named {kernel!r} in the window")
    return found


def kernel_seconds(trace: Trace, kernel: str) -> float:
    return sum(e.dur for e in kernel_events(trace, kernel)) / 1e9


_SHAPE = re.compile(r"f32\[(\d+),1,(\d+)\]")


def kernel_shape(event: Event) -> tuple[int, int]:
    """(B, C_pad) of a ``gather_distance`` call: its (B, 1, C_pad) float32
    output, as the op's HLO text spells it (the op's name on the TPU)."""
    for v in (event.name, *event.stats.values()):
        m = _SHAPE.search(str(v))
        if m:
            return int(m.group(1)), int(m.group(2))
    raise KeyError(f"no (B, 1, C) shape in the stats of {event.name!r}")


def module_name(event_name: str) -> str:
    """``jit_wave_core(123)`` -> ``jit_wave_core``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def module_events(trace: Trace, jit_name: str) -> list:
    """Runs of one jitted program (its XLA module), inside the window."""
    found = [e for evs in trace.modules.values() for e in _in_window(trace, evs)
             if module_name(e.name) == jit_name]
    if not found:
        raise KeyError(f"no XLA module named {jit_name!r} in the window")
    return found


def op_label(name: str) -> str:
    """``%fusion.12 = f32[4096,60]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.12 f32[4096,60]``: the HLO instruction and its result shape."""
    head, _, rest = name.partition(" = ")
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return head.lstrip("%") + (" " + shape.group(1) if shape else "")


def self_times(events) -> dict:
    """Device time of each op net of the ops nested inside it (a ``while``
    op spans its body's ops on the same line), summed by ``op_label``."""
    tot: dict = {}
    stack: list = []  # [end, label, child time]
    for e in sorted(events, key=lambda e: (e.start, -e.dur)):
        while stack and stack[-1][0] <= e.start:
            end, label, kids, dur = stack.pop()
            tot[label] = tot.get(label, 0) + dur - kids
        if stack:
            stack[-1][2] += e.dur
        stack.append([e.end, op_label(e.name), 0, e.dur])
    for end, label, kids, dur in stack:
        tot[label] = tot.get(label, 0) + dur - kids
    return tot


def top_ops(trace: Trace, top: int = 10) -> list:
    """[[op, device seconds of its own, summed over its runs]], most first."""
    tot: dict = {}
    for evs in trace.ops.values():
        for k, v in self_times(_in_window(trace, evs)).items():
            tot[k] = tot.get(k, 0) + v
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v / 1e9] for k, v in ranked]


def summary(trace: Trace) -> str:
    lines = []
    for dev in trace.devices:
        evs = trace.ops[dev]
        lines.append(f"{dev}: {len(evs)} ops, {len(trace.modules.get(dev, []))} modules")
        for e in evs[:5]:
            lines.append(f"  op {e.name} start={e.start} dur={e.dur} stats={e.stats}")
        for e in trace.modules.get(dev, [])[:5]:
            lines.append(f"  module {e.name} start={e.start} dur={e.dur}")
    names = {}
    for e in trace.host:
        names[e.name] = names.get(e.name, 0) + 1
    lines.append(f"host: {len(trace.host)} events; most frequent names:")
    for k, v in sorted(names.items(), key=lambda kv: -kv[1])[:15]:
        lines.append(f"  {v:7d}  {k}")
    try:
        busy, win = busy_share(trace)
        lines.append(f"window {win:.6f} s, busy {busy:.6f} s")
        lines.append(f"top ops {top_ops(trace)}")
        lines.append(f"idle gaps {idle_gaps(trace)}")
    except KeyError as e:
        lines.append(f"no window reading: {e}")
    return "\n".join(lines)


if __name__ == "__main__":
    print(summary(load(sys.argv[1])))
