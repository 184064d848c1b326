"""Reduction of one profiler trace (``.xplane.pb``) to device times.

Reads the trace with ``jax.profiler.ProfileData`` and nothing of the
program. The traced window is the host span named ``WINDOW`` that the
harness opens right after the profiler starts and closes when the
measured window ends; every device interval is clipped to it.

* busy: the union of the intervals in which an operation ran on a device
  (the ``XLA Ops`` line of each ``/device:TPU:n`` plane), averaged over
  the devices that ran any;
* programs: device time and executions per XLA module (``XLA Modules``),
  keyed by module name without its ``(id)`` suffix;
* ops: the op events of device 0, named as the trace names them (the
  HLO instruction text, ``%name.N = type[shape] op(operands)``), for
  readers that need shapes; ``top_ops`` sums them by name and result
  shape, leaving out control-flow ops (``while``, ``conditional``,
  ``call``) whose bodies' ops are listed themselves;
* idle gaps: the longest stretches with no op on device 0, each labelled
  by host events (the Python tracer's, on the thread that opened the
  window) that cover the gap's middle: the outermost call made inside
  the window (the engine loop's callee) and the innermost one.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW = "bench_traced_window"
_ID_SUFFIX = re.compile(r"\(\d+\)$")
_OP = re.compile(r"^%([A-Za-z_][\w-]*?)(?:\.\d+)? = (\S+?)(?:\{|\s)")
_CONTROL = ("while", "conditional", "call")


def op_label(name: str) -> str:
    """``%fused_plane_linear.83 = bf16[64,12800]{...} custom-call(...)``
    -> ``fused_plane_linear bf16[64,12800]``."""
    m = _OP.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


class Event:
    __slots__ = ("name", "start", "end")

    def __init__(self, name, start, end):
        self.name, self.start, self.end = name, start, end

    @property
    def dur(self):
        return self.end - self.start


def union_length(intervals) -> float:
    """Total length covered by a set of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo, hi):
    """(start, end) stretches of [lo, hi] that no interval covers."""
    out, pos = [], lo
    for s, e in sorted(intervals):
        if s > pos:
            out.append((pos, min(s, hi)))
        pos = max(pos, e)
        if pos >= hi:
            break
    if pos < hi:
        out.append((pos, hi))
    return [(a, b) for a, b in out if b > a]


def _clip(events, lo, hi):
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append(Event(e.name, s, t))
    return out


class Reduced:
    """What the readers of per-layer metrics get from one trace."""

    def __init__(self, window, device_ops, device_modules, host_events):
        self.lo, self.hi = window
        self.window_s = (self.hi - self.lo) * 1e-9
        self.device_ops = {d: _clip(ev, self.lo, self.hi) for d, ev in device_ops.items()}
        self.device_modules = {d: _clip(ev, self.lo, self.hi) for d, ev in device_modules.items()}
        self.host_events = host_events
        busy = [union_length((e.start, e.end) for e in ev) for ev in self.device_ops.values()]
        busy = [b for b in busy if b > 0]
        self.busy_s = (sum(busy) / len(busy)) * 1e-9 if busy else 0.0

    def _first_device(self):
        return sorted(self.device_ops)[0] if self.device_ops else None

    def programs(self) -> dict:
        """module name -> (device seconds, executions), device 0."""
        out: dict = {}
        d = self._first_device()
        for e in self.device_modules.get(d, []):
            name = _ID_SUFFIX.sub("", e.name)
            s, n = out.get(name, (0.0, 0))
            out[name] = (s + e.dur * 1e-9, n + 1)
        return out

    def ops(self, pattern=None) -> list:
        """Device-0 op events, optionally those whose name matches."""
        d = self._first_device()
        rx = re.compile(pattern) if pattern else None
        return [e for e in self.device_ops.get(d, []) if rx is None or rx.search(e.name)]

    def top_ops(self, n=10) -> list:
        tot: dict = {}
        for e in self.ops():
            label = op_label(e.name)
            if label.split(" ")[0] not in _CONTROL:
                tot[label] = tot.get(label, 0.0) + e.dur * 1e-9
        return sorted(tot.items(), key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n=10) -> list:
        """The ``n`` longest device-0 idle stretches, named by the host."""
        d = self._first_device()
        if d is None:
            return []
        ivs = [(e.start, e.end) for e in self.device_ops[d]]
        out = []
        for a, b in sorted(gaps(ivs, self.lo, self.hi), key=lambda g: g[0] - g[1])[:n]:
            mid = (a + b) / 2
            cover = [h for h in self.host_events if h.start <= mid <= h.end]
            if not cover:
                out.append(["(no host event)", (b - a) * 1e-9])
                continue
            inner = min(cover, key=lambda h: h.dur)
            inside = [h for h in cover if h.start >= self.lo]
            outer = max(inside, key=lambda h: h.dur) if inside else inner
            name = inner.name if outer is inner else f"{outer.name} > {inner.name}"
            out.append([name, (b - a) * 1e-9])
        return out


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise RuntimeError(f"the profiler wrote no .xplane.pb under {log_dir}")
    return paths[-1]


def _events(line):
    return [Event(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]


def reduce(profile) -> Reduced:
    """``profile``: a ``jax.profiler.ProfileData`` (or anything with the
    same ``planes`` / ``lines`` / ``events`` shape)."""
    device_ops, device_modules, window, host = {}, {}, None, []
    for plane in profile.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device_ops[plane.name] = _events(line)
                elif line.name == "XLA Modules":
                    device_modules[plane.name] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = _events(line)
                for e in evs:
                    if e.name == WINDOW:
                        window = (e.start, e.end)
                        host = evs
    if window is None:
        raise RuntimeError(f"the trace has no host span {WINDOW!r}")
    if not device_ops:
        raise RuntimeError("the trace has no device op events")
    return Reduced(window, device_ops, device_modules, [h for h in host if h.name != WINDOW])
