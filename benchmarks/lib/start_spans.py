"""The program's own account of a start: the spans it wrote to its
timeline BEFORE the window opened (``ray_tpu/observability/timeline.py``,
one ring per process), read after the run.  One reader for the seven
start metrics of ``BENCHMARK.json`` (``setup_trace_s``, ``setup_lower_s``,
``setup_cache_fetch_s``, ``setup_before_engine_s``, ``setup_warmup_s``,
``setup_warm_unnamed_s``, ``train_worker_start_s``); the rest of the
account -- the programs counted, the engine's build, the warm-up's one
wait -- is in ``benchmarks/out/<cell>/start.json``, which this module
writes.

What the program writes (``serve/llm.py`` ``__init__`` / ``_warmup``,
``train/trainer.py``, the compile listener of ``observability/device.py``):

    serve.engine_start            LLMServer.__init__, entry -> ready
      serve.engine_build            programs built, pools and carries allocated
      serve.warmup                  _warmup() whole
        serve.warm_program            one warmed call: the HOST's seconds in it
          xla_trace / xla_lower / xla_compile   what jax times of that call
        serve.warm_wait               the one block_until_ready
    train.worker_start            fit() entry -> the user's loop entered
    xla_trace, xla_lower          a jitted function's first call, by phase,
    xla_compile                   wherever it was made (``fun_name``,
                                  ``cache_hit``, ``cache_fetch_s``)

A span's SELF time is its duration less what its children cover of it.
``setup_s`` splits, by construction, into: process start -> the engine's
constructor entered (``before_engine_s``), ``serve.engine_start``, and
the constructor's return -> the window's opening (``after_engine_s``:
the rest of ``serve.run``, the traffic's lead-in).

A program that writes none of this (an older commit, tracing off) gives
None, and so does a ring that dropped anything: what a drop-oldest ring
loses first is the start.  Every reader then returns None and the metric
is left out of the line.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Callable, Dict, List, Optional, Tuple

PHASES = ("xla_trace", "xla_lower", "xla_compile")
TREE = ("serve.engine_start", "serve.engine_build", "serve.warmup",
        "serve.warm_program", "serve.warm_wait", "train.worker_start")
_ATTRS = ("program", "rows", "bucket", "k", "s_active", "fun_name",
          "cache_hit", "cache_fetch_s")


@dataclasses.dataclass
class Span:
    name: str
    t0: float                       # seconds after process start
    t1: float
    args: Dict[str, Any]
    children: List["Span"] = dataclasses.field(default_factory=list)

    @property
    def dur(self) -> float:
        return self.t1 - self.t0

    @property
    def self_s(self) -> float:
        return self.dur - _covered(
            [(max(c.t0, self.t0), min(c.t1, self.t1))
             for c in self.children])

    def under(self, *names: str) -> List["Span"]:
        """Every span of these names below this one, at any depth."""
        out = []
        for c in self.children:
            if c.name in names:
                out.append(c)
            out.extend(c.under(*names))
        return out


def _covered(intervals: List[Tuple[float, float]]) -> float:
    """Seconds the union of the intervals covers."""
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


@dataclasses.dataclass
class Start:
    setup_s: float
    spans: List[Span]               # all kept, in the order they ended
    roots: List[Span]               # those whose parent is not among them
    events: int
    first_heard_s: Optional[float]  # the first xla_* span's opening

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def first(self, name: str) -> Optional[Span]:
        return min(self.named(name), key=lambda s: s.t0, default=None)

    # ------------------------------------------------ what the metrics read
    def phase_s(self, phase: str) -> Optional[float]:
        """The sum of one jax phase over every program heard before the
        window opened; None where the listener keeps no phases."""
        if not self.named("xla_trace"):
            return None
        return sum(s.dur for s in self.named(phase))

    def cache_fetch_s(self) -> Optional[float]:
        if not self.named("xla_trace"):
            return None
        return sum(float(s.args.get("cache_fetch_s", 0.0))
                   for s in self.named("xla_compile"))

    def programs(self) -> Optional[float]:
        if not self.named("xla_trace"):
            return None
        return float(len(self.named("xla_compile")))

    def span_s(self, name: str) -> Optional[float]:
        span = self.first(name)
        return None if span is None else span.dur

    def before_engine_s(self) -> Optional[float]:
        engine = self.first("serve.engine_start")
        return None if engine is None else engine.t0

    def warm_unnamed_s(self) -> Optional[float]:
        if self.first("serve.engine_start") is None:
            return None
        return sum(s.self_s for s in self.named("serve.warm_program"))

    # ------------------------------------------------------- start.json
    def report(self, lead_in_s: Optional[float]) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "setup_s": self.setup_s, "events": self.events,
            "first_event_heard_s": self.first_heard_s,
            "compile_phases_s": {
                p: self.phase_s(p) for p in PHASES},
            "cache_fetch_s": self.cache_fetch_s(),
            "programs": self.programs(),
            "programs_not_cached": len(
                [s for s in self.named("xla_compile")
                 if s.args.get("cache_hit") is False]),
            "by_function": _by_function(
                [s for s in self.spans if s.name in PHASES]),
            "tree": [_node(s) for s in self.roots if s.name in TREE],
        }
        engine = self.first("serve.engine_start")
        if engine is not None:
            after = self.setup_s - engine.t1
            out["parts_s"] = {
                "before_engine": engine.t0, "engine_start": engine.dur,
                "after_engine": after, "lead_in": lead_in_s,
                "after_engine_less_lead_in":
                    None if lead_in_s is None else after - lead_in_s}
        warmup = self.first("serve.warmup")
        if warmup is not None:
            warmed = warmup.under("serve.warm_program")
            named = {
                **{p: sum(s.dur for s in warmup.under(p)) for p in PHASES},
                "warm_unnamed": sum(s.self_s for s in warmed),
                "warm_wait": sum(
                    s.dur for s in warmup.under("serve.warm_wait"))}
            out["warmup_s"] = {
                "whole": warmup.dur, **named,
                "loop": warmup.dur - sum(named.values())}
            out["warmed_programs"] = [_row(s) for s in warmed]
        return out


def _node(span: Span) -> Dict[str, Any]:
    """A span of :data:`TREE` with its self time; the jax phases right
    under it as one sum (``warmed_programs`` has a warmed call's split)."""
    node = {"name": span.name, "start_s": round(span.t0, 4),
            "dur_s": round(span.dur, 4), "self_s": round(span.self_s, 4),
            "xla_s": round(sum(c.dur for c in span.children
                               if c.name in PHASES), 4),
            **{k: span.args[k] for k in _ATTRS if k in span.args}}
    below = [_node(c) for c in span.children if c.name in TREE]
    if below:
        node["children"] = below
    return node


def _row(warmed: Span) -> Dict[str, Any]:
    """One warmed program: its host seconds, and their split."""
    row = {k: warmed.args[k] for k in _ATTRS if k in warmed.args}
    row["host_s"] = round(warmed.dur, 4)
    for phase in PHASES:
        row[phase[len("xla_"):] + "_s"] = round(
            sum(s.dur for s in warmed.under(phase)), 4)
    compiles = warmed.under("xla_compile")
    row["cache_fetch_s"] = round(sum(
        float(s.args.get("cache_fetch_s", 0.0)) for s in compiles), 4)
    row["cache_hit"] = all(s.args.get("cache_hit") for s in compiles)
    row["unnamed_s"] = round(warmed.self_s, 4)
    return row


def _by_function(phases: List[Span], top: int = 12) -> List[Dict[str, Any]]:
    """The jax phases by the function they were of (``step``: the trace
    says ``step``, the lowering and the backend ``jit(step)``), largest
    first: what the weights' initialiser, the train step or an eager
    ``jnp.zeros`` took of the start."""
    table: Dict[str, Dict[str, Any]] = {}
    for s in phases:
        fun = str(s.args.get("fun_name") or "?")
        if fun.startswith("jit(") and fun.endswith(")"):
            fun = fun[4:-1]
        row = table.setdefault(fun, {
            "fun_name": fun, "calls": 0, "total_s": 0.0,
            **{p[len("xla_"):] + "_s": 0.0 for p in PHASES}})
        row[s.name[len("xla_"):] + "_s"] += s.dur
        row["total_s"] += s.dur
        row["calls"] += s.name == "xla_compile"
    rows = sorted(table.values(), key=lambda r: -r["total_s"])[:top]
    return [{k: round(v, 4) if isinstance(v, float) else v
             for k, v in row.items()} for row in rows]


def account(events: List[Dict[str, Any]], dropped: int, t_open: float,
            setup_s: float,
            to_perf: Callable[[float], float]) -> Optional[Start]:
    """``events`` as ``timeline.export_timeline()`` gives them; kept are
    the spans of :data:`TREE` and :data:`PHASES` that ENDED before
    ``t_open`` (a ``time.perf_counter()`` reading) and began after the
    process's start (``t_open - setup_s``), laid on seconds after that
    start and linked by ``parent_span_id``."""
    if dropped:
        return None
    t_process = t_open - setup_s
    spans: List[Span] = []
    by_id: Dict[str, Span] = {}
    for e in events:
        if e.get("ph") != "X" or e["name"] not in TREE + PHASES:
            continue
        t0 = to_perf(e["ts"] * 1e-6)
        t1 = t0 + e.get("dur", 0.0) * 1e-6
        if t1 > t_open or t0 < t_process:
            continue    # the window's, or an earlier run's in this process
        span = Span(e["name"], t0 - t_process, t1 - t_process,
                    e.get("args") or {})
        spans.append(span)
        if span.args.get("span_id"):
            by_id[span.args["span_id"]] = span
    if not spans:
        return None
    roots = []
    for span in spans:
        parent = by_id.get(span.args.get("parent_span_id"))
        (roots if parent is None else parent.children).append(span)
    for span in spans:
        span.children.sort(key=lambda s: s.t0)
    heard = [s.t0 for s in spans if s.name in PHASES]
    return Start(setup_s=setup_s, spans=spans,
                 roots=sorted(roots, key=lambda s: s.t0),
                 events=len(events),
                 first_heard_s=min(heard) if heard else None)


def collect(obs) -> Optional[Start]:
    """The run's start (read once, kept on ``obs``; ``start.json``
    written beside the run's other files)."""
    if "start_spans" not in obs:
        obs["start_spans"] = got = _read(obs.get("t_open"),
                                         obs.get("setup_s"))
        cell = obs.get("cell")
        if got is not None and cell is not None:
            out_dir = os.path.join(cell.bench_dir, "out", cell.name)
            os.makedirs(out_dir, exist_ok=True)
            lead_in = cell.traffic.get("arrivals", {}).get("lead_in_s")
            with open(os.path.join(out_dir, "start.json"), "w") as f:
                json.dump(got.report(lead_in), f, indent=1)
    return obs["start_spans"]


def _read(t_open, setup_s) -> Optional[Start]:
    from ray_tpu.observability import timeline, tracing

    to_perf = getattr(timeline, "perf_from_wall", None)
    if to_perf is None or t_open is None or setup_s is None \
            or not tracing.enabled():
        return None
    return account(timeline.export_timeline(), timeline.dropped_events(),
                   t_open, setup_s, to_perf)


def reader(method: str, *args):
    """``read(obs)`` of one metric: ``Start.<method>(*args)``."""
    def read(obs) -> Optional[float]:
        got = collect(obs)
        return None if got is None else getattr(got, method)(*args)
    return read
