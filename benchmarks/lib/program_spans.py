"""The program's own account of a serve run: the spans it wrote to its
timeline (``ray_tpu/observability/timeline.py``, one ring per process)
while the window was open, read after the run.  The serve kind runs the
engine, the handle and the load generator in one process, and the ring
outlives ``serve.shutdown()``, so everything is there.

What the program writes (``ray_tpu/serve/llm.py``, ``serve/handle.py``):

    serve:<deployment>.generate   handle: routing + submission
    serve.request                 engine: submit -> done, with
      serve.wait_boundary           submit -> seen by the scheduler thread
      serve.wait_slot               seen -> bound to a slot
      serve.wait_prefill            bound -> first token
      serve.decode                  first token -> done
    serve.response                handle: submission -> settled
    serve.chunk                   a decode chunk, launch -> harvested
    serve.prefill_group           a padded prefill group, launch -> harvested

Spans carry wall-clock microseconds stamped through the program's ONE
clock (``timeline.wall_from_perf``), so they convert back to the
``time.perf_counter()`` readings the kinds window by (``obs["t_open"]``,
``obs["t_close"]``).  Kept: requests SUBMITTED inside the window, chunks
and groups LAUNCHED inside it.

A program that writes none of this (an older commit, tracing off) gives
None, and so does a ring that dropped part of the window: every reader
then returns None and the metric is left out of the line.
"""

from __future__ import annotations

import dataclasses
import re
import statistics
from typing import Any, Callable, Dict, List, Optional

from .runtime import percentile

PHASES = ("serve.wait_boundary", "serve.wait_slot", "serve.wait_prefill",
          "serve.decode")
# ``device.annotation`` names: ``serve.decode_chunk#trace=<id>,t=<s>``.
_ANNOTATION_CLOCK = re.compile(r"#(?:.*,)?t=([0-9.]+)$")


@dataclasses.dataclass
class Request:
    t_submit: float                 # perf_counter seconds
    t_done: float
    args: Dict[str, Any]            # serve.request's: rid, slot, outcome...
    phase_ms: Dict[str, float]      # PHASES it went through -> duration
    prefill: Dict[str, Any]         # serve.wait_prefill's launch_ms, bucket...
    inbound_ms: Optional[float]     # handle span start -> submit
    outbound_ms: Optional[float]    # done -> handle settled

    @property
    def harvests(self) -> List[List[float]]:
        """[ms after submit, tokens so far] per burst that reached the
        host."""
        return self.args.get("harvests", [])


@dataclasses.dataclass
class ProgramSpans:
    requests: List[Request]
    chunks: List[Dict[str, Any]]    # serve.chunk args + t_launch, dur_ms
    groups: List[Dict[str, Any]]    # serve.prefill_group args + same


def window(events: List[Dict[str, Any]], dropped: int, t_open: float,
           t_close: float,
           to_perf: Callable[[float], float]) -> Optional[ProgramSpans]:
    """``events`` as ``timeline.export_timeline()`` gives them (append
    order: a span is appended when it ENDS), cut to the window."""
    spans = [e for e in events if e.get("ph") == "X"]
    if dropped and events:
        oldest = events[0]
        # What was dropped ended before the oldest event left did.
        if to_perf((oldest["ts"] + oldest.get("dur", 0.0)) * 1e-6) > t_open:
            return None

    def start(e) -> float:
        return to_perf(e["ts"] * 1e-6)

    by_parent: Dict[str, List[Dict]] = {}
    by_trace: Dict[str, Dict[str, Dict]] = {}
    for e in spans:
        args = e.get("args") or {}
        if e["name"] in PHASES:
            by_parent.setdefault(args.get("parent_span_id"), []).append(e)
        elif e["name"] == "serve.response":
            by_trace.setdefault(args.get("trace_id"), {})["response"] = e
        elif e["name"].startswith("serve:"):     # the handle's span
            by_trace.setdefault(args.get("trace_id"), {}).setdefault(
                "handle", e)
    requests, chunks, groups = [], [], []
    for e in spans:
        t = start(e)
        if not t_open <= t < t_close:
            continue
        args = e.get("args") or {}
        if e["name"] == "serve.request":
            phases = by_parent.get(args.get("span_id"), [])
            prefill = next((p["args"] for p in phases
                            if p["name"] == "serve.wait_prefill"), {})
            t_done = t + e["dur"] * 1e-6
            ends = by_trace.get(args.get("trace_id"), {})
            handle, response = ends.get("handle"), ends.get("response")
            requests.append(Request(
                t_submit=t, t_done=t_done, args=args,
                phase_ms={p["name"]: p["dur"] * 1e-3 for p in phases},
                prefill=prefill,
                inbound_ms=None if handle is None
                else (t - start(handle)) * 1e3,
                outbound_ms=None if response is None else
                (start(response) + response["dur"] * 1e-6 - t_done) * 1e3))
        elif e["name"] == "serve.chunk":
            chunks.append(dict(args, t_launch=t, dur_ms=e["dur"] * 1e-3))
        elif e["name"] == "serve.prefill_group":
            groups.append(dict(args, t_launch=t, dur_ms=e["dur"] * 1e-3))
    requests.sort(key=lambda r: r.t_submit)
    return ProgramSpans(requests, chunks, groups)


def collect(obs) -> Optional[ProgramSpans]:
    """The run's program spans (read once, kept on ``obs``)."""
    if "program_spans" not in obs:
        obs["program_spans"] = _read(obs.get("t_open"), obs.get("t_close"))
    return obs["program_spans"]


def _read(t_open, t_close) -> Optional[ProgramSpans]:
    from ray_tpu.observability import timeline, tracing

    to_perf = getattr(timeline, "perf_from_wall", None)
    if to_perf is None or t_open is None or not tracing.enabled():
        return None
    return window(timeline.export_timeline(), timeline.dropped_events(),
                  t_open, t_close, to_perf)


# --------------------------------------------------------------- readers
def phase_percentile(phase: str, q: float):
    """Over the window's requests that went through ``phase``."""
    def read(obs) -> Optional[float]:
        got = collect(obs)
        if got is None:
            return None
        return percentile([r.phase_ms[phase] for r in got.requests
                           if phase in r.phase_ms], q)
    return read


def burst_gaps_ms(requests: List[Request]) -> List[float]:
    """Gaps between the bursts in which a request's tokens reached the
    host: what a streaming client would see between deliveries."""
    gaps = []
    for r in requests:
        times = [t for t, _n in r.harvests]
        gaps.extend(b - a for a, b in zip(times, times[1:]))
    return gaps


def token_burst_gap_percentile(q: float):
    def read(obs) -> Optional[float]:
        got = collect(obs)
        return None if got is None else percentile(
            burst_gaps_ms(got.requests), q)
    return read


def decode_slot_utilization(obs) -> Optional[float]:
    got = collect(obs)
    steps = sum(c["token_steps"] for c in got.chunks) if got else 0
    if not steps:
        return None
    return 100.0 * sum(c["tokens_kept"] for c in got.chunks) / steps


def prefill_padding_share(obs) -> Optional[float]:
    got = collect(obs)
    computed = sum(g["token_positions"] for g in got.groups) if got else 0
    if not computed:
        return None
    return 100.0 * (1.0 - sum(g["prompt_tokens"] for g in got.groups)
                    / computed)


def request_path_overhead_p50_ms(obs) -> Optional[float]:
    """Handle span start -> engine submit, plus engine done -> handle
    settled: routing, the actor call, the replica's wrapper, the reply."""
    got = collect(obs)
    if got is None:
        return None
    both = [r.inbound_ms + r.outbound_ms for r in got.requests
            if r.inbound_ms is not None and r.outbound_ms is not None]
    return statistics.median(both) if both else None


# ------------------------------------------------- the profiler's clock
def profiler_minus_perf(trace, to_perf) -> Optional[float]:
    """Seconds to add to a ``time.perf_counter()`` reading to place it on
    the clock of a traced run's events: the program's host annotations
    carry the host clock reading of their opening in their name, and the
    profiler stamped the same opening on its own (session-relative)
    clock.  None where no annotation carries a reading."""
    offsets = []
    for start, _end, name in trace.host:
        m = _ANNOTATION_CLOCK.search(name)
        if m:
            offsets.append(start - to_perf(float(m.group(1))))
    return statistics.median(offsets) if offsets else None
