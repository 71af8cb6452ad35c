"""From a profiler trace (``*.xplane.pb``) to numbers.  Read with
``jax.profiler.ProfileData`` and nothing else.

What a TPU trace holds (looked at by hand on the v5e, PERF.md section 3):

- one plane per chip, ``/device:TPU:<n>``.  Its line ``XLA Ops`` has one
  event per executed HLO op, named by the instruction's whole text
  (``%fusion.3 = bf16[...] fusion(...)``); a ``while`` and the ops of its
  body are both there, nested in time.  A Mosaic (Pallas) kernel is a
  ``custom-call`` whose text holds ``custom_call_target="tpu_custom_call"``
  — the kernel's own name is not in the trace.  ``XLA Modules`` has one
  event per program execution (``jit_step(<hash>)``); ``Async XLA Ops``
  holds copies and collectives in flight (start to done); ``Steps``
  groups modules.
- one host plane, ``/host:CPU``, one line per thread.  A
  ``jax.profiler.TraceAnnotation`` is an event on the line of the thread
  that opened it, under the annotation's name.
- all ``start_ns`` are on one clock.
- the profiler's start and stop fall where they fall: the program that
  was running at either edge is there as an event of what is LEFT of it,
  with the ops that ran inside the trace and no others.  Only a chip's
  first and last module event can be such a piece (``whole_runs``).

Busy time is the UNION of the op intervals of a chip (nested and
overlapping events count once); an op's own time is its duration minus
what its nested children cover.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import os
import re
import statistics
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'
HOST_PLANE = "/host:CPU"
WHOLE_RUN_OPS = 0.98   # of a whole run's op events: ``Trace.whole_runs``

Interval = Tuple[float, float]          # (start_s, end_s)
Event = Tuple[float, float, str]        # (start_s, end_s, name)

# An event's name is the HLO instruction's text:
#   %name = <shape> opcode(operands...), attributes
_INSTRUCTION = re.compile(r"^(%[^ ]+) = (.*?) ([a-z][a-z\-]*)\(")
COLLECTIVE_OPCODES = ("all-gather", "all-reduce", "reduce-scatter",
                      "all-to-all", "collective-permute",
                      "collective-broadcast")


def opcode(name: str) -> str:
    m = _INSTRUCTION.match(name)
    return m.group(3) if m else ""


def is_collective(name: str) -> bool:
    return opcode(name).startswith(COLLECTIVE_OPCODES)


def short_name(name: str, width: int = 120) -> str:
    """``%fusion.3 fusion bf16[8,2048,960]`` from the instruction's text:
    its name, opcode (a Mosaic kernel marked as such) and result shape
    without layouts."""
    m = _INSTRUCTION.match(name)
    if not m:
        return name[:width]
    op = m.group(3)
    if op == "custom-call" and MOSAIC_CALL in name:
        op = "custom-call[mosaic]"
    shape = re.sub(r"\{[^{}]*\}", "", m.group(2))
    return f"{m.group(1)} {op} {shape}"[:width]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


# ------------------------------------------------------ interval algebra
def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], lo: float,
         hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of union ``a`` not covered by union ``b``."""
    out = []
    b = list(b)
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Seconds per op name, each event's duration less its nested
    children's (so a ``while`` does not count its body twice)."""
    out: Dict[str, float] = {}
    stack: List[List] = []   # [end, name, own]

    def close(until: float):
        while stack and stack[-1][0] <= until:
            end, name, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0)

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    close(float("inf"))
    return out


# -------------------------------------------------------------- reading
@dataclasses.dataclass
class DeviceTrace:
    index: int
    ops: List[Event]
    modules: List[Event]
    async_ops: List[Event] = dataclasses.field(default_factory=list)

    @functools.cached_property
    def busy_union(self) -> List[Interval]:
        return union((s, e) for s, e, _ in self.ops)

    @functools.cached_property
    def op_seconds(self) -> Dict[str, float]:
        return self_times(self.ops)

    @functools.cached_property
    def op_starts(self) -> List[float]:
        return sorted(s for s, _, _ in self.ops)

    def busy(self, lo: float, hi: float) -> List[Interval]:
        return clip(self.busy_union, lo, hi)

    def ops_inside(self, run: Event) -> int:
        """How many op events start inside a module's run."""
        return bisect.bisect_left(self.op_starts, run[1]) \
            - bisect.bisect_left(self.op_starts, run[0])


@dataclasses.dataclass
class Trace:
    devices: List[DeviceTrace]
    host: List[Event]          # TraceAnnotations and other host events
    lo: float                  # first device op
    hi: float                  # last device op's end

    # -- busy / idle -------------------------------------------------------
    @property
    def window_s(self) -> float:
        return self.hi - self.lo

    @property
    def busy_s(self) -> float:
        """Mean over the chips of the time an op ran."""
        if not self.devices:
            return 0.0
        return sum(total(d.busy(self.lo, self.hi))
                   for d in self.devices) / len(self.devices)

    def idle_share(self) -> Optional[float]:
        return (1.0 - self.busy_s / self.window_s) if self.window_s > 0 \
            else None

    # -- ops ---------------------------------------------------------------
    def op_seconds(self, device: int = 0) -> Dict[str, float]:
        return self.devices[device].op_seconds if self.devices else {}

    def seconds_matching(self, pattern: str, device: int = 0) -> float:
        rx = re.compile(pattern)
        return sum(s for name, s in self.op_seconds(device).items()
                   if rx.search(name))

    def module_runs(self, pattern: str, device: int = 0) -> List[Event]:
        rx = re.compile(pattern)
        return [m for m in self.devices[device].modules
                if rx.search(m[2])] if self.devices else []

    def whole_runs(self, pattern: str, device: int = 0) -> List[Event]:
        """The module's runs that the trace holds WHOLE.  A chip runs one
        program at a time, so only its first and its last module event
        can be a piece that the trace's edge cut; such a piece holds
        fewer ops than a whole run of the same program (the same
        ``jit_name(<fingerprint>)``) does.  An edge run is kept where it
        holds as many ops (to 2%: what a piece that short of whole is
        short by) as the median of that program's runs between the
        edges, and dropped where it holds fewer or there is none to
        compare it with."""
        runs = self.module_runs(pattern, device)
        if not runs:
            return []
        dev = self.devices[device]
        edges = (min(dev.modules), max(dev.modules))
        inner: Dict[str, List[int]] = {}
        for run in runs:
            if run not in edges:
                inner.setdefault(run[2], []).append(dev.ops_inside(run))
        return [run for run in runs if run not in edges or (
            run[2] in inner and dev.ops_inside(run)
            >= WHOLE_RUN_OPS * statistics.median(inner[run[2]]))]

    def busy_inside(self, spans: Sequence[Event],
                    device: int = 0) -> List[float]:
        """Per span: seconds of it in which an op ran on the chip."""
        if not self.devices:
            return []
        busy = self.devices[device].busy(self.lo, self.hi)
        return [total(clip(busy, s, e)) for s, e, _ in spans]

    # -- collectives -------------------------------------------------------
    def collective_seconds(self, device: int = 0) -> Tuple[float, float]:
        """(time in collective ops, the part of it during which no other
        op ran on that chip) — both as interval unions."""
        if not self.devices:
            return 0.0, 0.0
        dev = self.devices[device]
        coll = union((s, e) for s, e, n in dev.ops + dev.async_ops
                     if is_collective(n))
        # A while/conditional spans its body; it is not compute itself.
        other = union((s, e) for s, e, n in _leaves(dev.ops)
                      if not is_collective(n))
        return total(coll), total(subtract(coll, other))

    # -- idle gaps ---------------------------------------------------------
    def idle_gaps(self, device: int = 0, top: int = 10,
                  names: Optional[Sequence[str]] = None
                  ) -> List[Tuple[str, float]]:
        """Idle seconds of a chip by what the host was doing: each gap
        between ops goes to the host annotation covering most of it
        (``names`` restricts which host events count; a gap nothing
        covers is 'no annotation open')."""
        if not self.devices:
            return []
        busy = self.devices[device].busy(self.lo, self.hi)
        gaps = subtract([(self.lo, self.hi)], busy)
        host = [(s, e, _plain(n)) for s, e, n in self.host
                if names is None or _plain(n) in names]
        host.sort()
        by: Dict[str, float] = {}
        for gs, ge in gaps:
            best, cover = "no annotation open", 0.0
            for s, e, n in host:
                if s >= ge:
                    break
                c = min(e, ge) - max(s, gs)
                if c > cover:
                    best, cover = n, c
            by[best] = by.get(best, 0.0) + (ge - gs)
        return sorted(by.items(), key=lambda kv: -kv[1])[:top]

    def top_ops(self, device: int = 0,
                top: int = 10) -> List[Tuple[str, float]]:
        ranked = sorted(self.op_seconds(device).items(),
                        key=lambda kv: -kv[1])[:top]
        return [(short_name(n), s) for n, s in ranked]


def _plain(name: str) -> str:
    """``serve.prefill#trace=abc`` -> ``serve.prefill``."""
    return name.split("#", 1)[0]


def _leaves(events: Sequence[Event]) -> List[Event]:
    """Events with no event nested inside them."""
    ev = sorted(events, key=lambda e: (e[0], -e[1]))
    out = []
    for i, (s, e, n) in enumerate(ev):
        if i + 1 < len(ev) and ev[i + 1][0] < e and ev[i + 1][1] <= e:
            continue
        out.append((s, e, n))
    return out


def _events(line) -> List[Event]:
    return [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
             ev.name) for ev in line.events]


def read(path: str) -> Trace:
    """``path`` is an ``.xplane.pb`` or the directory given to
    ``jax.profiler.start_trace``."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: _events(line) for line in plane.lines
                     if line.name in (OPS_LINE, MODULES_LINE, ASYNC_LINE)}
            if lines.get(OPS_LINE):
                devices.append(DeviceTrace(
                    int(m.group(1)), lines[OPS_LINE],
                    lines.get(MODULES_LINE, []), lines.get(ASYNC_LINE, [])))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(ev for ev in _events(line)
                            if ev[1] > ev[0] and not ev[2].startswith("$"))
    devices.sort(key=lambda d: d.index)
    starts = [s for d in devices for s, _, _ in d.ops]
    ends = [e for d in devices for _, e, _ in d.ops]
    lo, hi = (min(starts), max(ends)) if starts else (0.0, 0.0)
    return Trace(devices, host, lo, hi)


def describe(path: str, top: int = 25) -> str:
    """What is in a trace, for a person: planes, lines, event counts and
    the commonest names.  Used once per kind of run to write down how
    things are named."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    out = [f"{path}: {os.path.getsize(path)} bytes"]
    for plane in data.planes:
        lines = list(plane.lines)
        out.append(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            if not evs:
                continue
            dur: Dict[str, float] = {}
            for ev in evs:
                dur[ev.name] = dur.get(ev.name, 0.0) + ev.duration_ns * 1e-9
            t0 = min(ev.start_ns for ev in evs) * 1e-9
            t1 = max(ev.start_ns + ev.duration_ns for ev in evs) * 1e-9
            out.append(f"  LINE {line.name!r}: {len(evs)} events, "
                       f"{t0:.6f}..{t1:.6f} s")
            for name, s in sorted(dur.items(),
                                  key=lambda kv: -kv[1])[:top]:
                out.append(f"      {s:10.6f} s  {short_name(name, 150)}")
            if plane.name.startswith("/device") and line.name == OPS_LINE:
                ev = max(evs, key=lambda e: e.duration_ns)
                out.append(f"      stats of the longest op: "
                           f"{[(k, str(v)[:120]) for k, v in ev.stats]}")
    return "\n".join(out)
