"""The reader of the program's own account of a start
(``lib/start_spans.py``): on the tiny cells through the same
``run.measure`` the command uses, and its arithmetic on hand-made rings."""

import json
import math
import os

import pytest

from benchmarks.lib import start_spans
from benchmarks.tests.test_rehearsal import (  # noqa: F401 - fixtures
    _measure, cpu_peaks, tree)

SERVE_METRICS = ("setup_trace_s", "setup_lower_s", "setup_cache_fetch_s",
                 "setup_before_engine_s", "setup_warmup_s",
                 "setup_warm_unnamed_s")
TRAIN_METRICS = ("setup_trace_s", "setup_lower_s", "setup_cache_fetch_s",
                 "train_worker_start_s")


@pytest.fixture(scope="module")
def traced(tree):
    """One traced run a tiny cell, kept for the module's cases."""
    runs = {}

    def run(cell):
        if cell not in runs:
            result, obs = _measure(tree, cell, trace=1, seconds=2.0)
            with open(os.path.join(tree[0], "out", cell,
                                   "start.json")) as f:
                runs[cell] = result, obs, json.load(f)
        return runs[cell]

    return run


@pytest.mark.parametrize("cell,names", [
    ("tiny.tiny-closed", SERVE_METRICS), ("tiny.tiny-train", TRAIN_METRICS)])
def test_metrics_on_a_traced_tiny_cell(traced, cpu_peaks, cell, names):
    result, _obs, _report = traced(cell)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(names) <= set(metrics), sorted(metrics)
    assert all(math.isfinite(metrics[n]) and metrics[n] >= 0.0
               for n in names)
    assert all(result["metrics"][n]["unit"] == "s" for n in names)
    assert metrics["setup_trace_s"] > 0.0 < metrics["setup_lower_s"]
    if "train" in cell:     # no engine: the engine's metrics are left out
        assert not (set(SERVE_METRICS) - set(names)) & set(metrics)
    else:                   # and no trainer: nor is its start
        assert "train_worker_start_s" not in metrics


def test_the_three_parts_of_a_serve_start_are_setup_s(traced, cpu_peaks):
    result, obs, report = traced("tiny.tiny-closed")
    parts = report["parts_s"]
    assert parts["before_engine"] > 0.0 < parts["after_engine"]
    assert parts["before_engine"] + parts["engine_start"] \
        + parts["after_engine"] == pytest.approx(obs["setup_s"], abs=1e-6)
    assert parts["lead_in"] == 0.5
    assert parts["after_engine_less_lead_in"] == pytest.approx(
        parts["after_engine"] - 0.5)
    assert result["metrics"]["setup_before_engine_s"]["value"] \
        == parts["before_engine"]
    # the account is whole from before the engine: the weights'
    # initialiser is heard (the kind installs the listener after it on an
    # older program; this one's own first event lies before the engine)
    assert report["first_event_heard_s"] < parts["before_engine"] \
        + parts["engine_start"]


def test_warm_up_is_named_to_the_loop_itself(traced, cpu_peaks):
    _result, obs, report = traced("tiny.tiny-closed")
    warm = report["warmup_s"]
    named = sum(warm[k] for k in ("xla_trace", "xla_lower", "xla_compile",
                                  "warm_unnamed", "warm_wait"))
    assert warm["loop"] == pytest.approx(warm["whole"] - named, abs=1e-9)
    assert 0.0 <= warm["loop"] < max(1.0, 0.05 * warm["whole"])
    rows = report["warmed_programs"]
    # prefill_shapes (2 rungs x 2 buckets), 2 attended lengths, 2 seats
    assert [r["program"] for r in rows] == \
        ["serve.prefill"] * 4 + ["serve.decode_k"] * 2 + ["serve.seat"] * 2
    assert [(r["rows"], r["bucket"]) for r in rows[:4]] == \
        [(2, 32), (2, 64), (4, 32), (4, 64)]
    for r in rows:
        assert r["host_s"] == pytest.approx(
            r["trace_s"] + r["lower_s"] + r["compile_s"] + r["unnamed_s"],
            abs=2e-4)
        assert 0.0 <= r["unnamed_s"] and r["cache_fetch_s"] <= r["compile_s"]
    got = start_spans.collect(obs)
    assert got.warm_unnamed_s() == pytest.approx(warm["warm_unnamed"])
    engine, = [n for n in report["tree"] if n["name"] == "serve.engine_start"]
    assert [c["name"] for c in engine["children"]] == \
        ["serve.engine_build", "serve.warmup"]
    assert engine["self_s"] == pytest.approx(
        engine["dur_s"] - sum(c["dur_s"] for c in engine["children"]),
        abs=2e-4)


def test_a_train_start_names_the_worker_and_the_step(traced, cpu_peaks):
    result, obs, report = traced("tiny.tiny-train")
    worker, = [n for n in report["tree"]
               if n["name"] == "train.worker_start"]
    assert 0.0 < worker["dur_s"] < obs["setup_s"]
    assert result["metrics"]["train_worker_start_s"]["value"] \
        == pytest.approx(worker["dur_s"], abs=1e-4)
    # the seconds read back from the persistent cache are part of what
    # the listener timed as compiles (none is kept here: 0, not absent)
    assert 0.0 <= result["metrics"]["setup_cache_fetch_s"]["value"] \
        == report["cache_fetch_s"] <= report["compile_phases_s"][
            "xla_compile"]
    assert "parts_s" not in report and "warmup_s" not in report
    step, = [r for r in report["by_function"] if r["fun_name"] == "step"]
    assert step["calls"] == 1
    assert step["trace_s"] > 0.0 < step["lower_s"]
    assert step["total_s"] == pytest.approx(
        step["trace_s"] + step["lower_s"] + step["compile_s"], abs=2e-4)


# ----------------------------------------------------- hand-made rings
T_PROCESS, SETUP_S = 1000.0, 60.0
T_OPEN = T_PROCESS + SETUP_S


def _span(name, start, dur, **args):
    """``start`` in seconds after the process's start."""
    return {"name": name, "ph": "X", "pid": "p", "tid": "t",
            "ts": (T_PROCESS + start) * 1e6, "dur": dur * 1e6, "args": args}


def _ring():
    """A start of 60 s: 20 s before the engine, an engine of 25 s (build
    2 s with an eager compile, warm-up 22 s: two programs and the wait),
    then 15 s to the window; a window's spans after it."""
    return [
        _span("xla_trace", 5.0, 0.5, fun_name="init"),
        _span("xla_lower", 5.5, 0.25, fun_name="jit(init)"),
        _span("xla_compile", 5.75, 1.0, fun_name="jit(init)",
              cache_hit=True, cache_fetch_s=0.75),
        _span("xla_trace", 20.5, 0.25, fun_name="zeros",
              parent_span_id="build"),
        _span("xla_compile", 20.75, 0.25, fun_name="jit(zeros)",
              parent_span_id="build", cache_hit=False, cache_fetch_s=0.0),
        _span("serve.engine_build", 20.0, 2.0, span_id="build",
              parent_span_id="engine"),
        _span("xla_trace", 23.0, 2.0, fun_name="prefill",
              parent_span_id="w1"),
        _span("xla_lower", 25.0, 1.0, fun_name="jit(prefill)",
              parent_span_id="w1"),
        _span("xla_compile", 26.0, 0.5, fun_name="jit(prefill)",
              parent_span_id="w1", cache_hit=True, cache_fetch_s=0.25),
        _span("serve.warm_program", 23.0, 6.0, span_id="w1",
              parent_span_id="warm", program="serve.prefill", rows=8,
              bucket=256),
        _span("xla_trace", 30.0, 4.0, fun_name="decode_k",
              parent_span_id="w2"),
        _span("xla_lower", 34.0, 2.0, fun_name="jit(decode_k)",
              parent_span_id="w2"),
        _span("xla_compile", 36.0, 1.0, fun_name="jit(decode_k)",
              parent_span_id="w2", cache_hit=True, cache_fetch_s=0.5),
        _span("serve.warm_program", 30.0, 8.0, span_id="w2",
              parent_span_id="warm", program="serve.decode_k", k=16,
              s_active=512),
        _span("serve.warm_wait", 38.5, 5.5, span_id="wait",
              parent_span_id="warm"),
        _span("serve.warmup", 22.5, 22.0, span_id="warm",
              parent_span_id="engine"),
        _span("serve.engine_start", 20.0, 25.0, span_id="engine"),
        _span("serve.chunk", 61.0, 0.5),
        _span("xla_compile", 59.5, 1.0, fun_name="jit(late)",
              cache_hit=False, cache_fetch_s=0.0),   # ends in the window
    ]


def _account(events, dropped=0):
    return start_spans.account(events, dropped, T_OPEN, SETUP_S,
                               lambda wall: wall)


@pytest.mark.parametrize("method,args,value", [
    ("phase_s", ("xla_trace",), 6.75),
    ("phase_s", ("xla_lower",), 3.25),
    ("phase_s", ("xla_compile",), 2.75),
    ("cache_fetch_s", (), 1.5),
    ("programs", (), 4.0),
    ("before_engine_s", (), 20.0),
    ("span_s", ("serve.engine_build",), 2.0),
    ("span_s", ("serve.warmup",), 22.0),
    ("span_s", ("serve.warm_wait",), 5.5),
    ("span_s", ("train.worker_start",), None),
    # 6 - (2 + 1 + 0.5) and 8 - (4 + 2 + 1): load, transfer, dispatch
    ("warm_unnamed_s", (), 3.5),
])
def test_account_of_a_hand_made_ring(method, args, value):
    got = getattr(_account(_ring()), method)(*args)
    assert got == (value if value is None else pytest.approx(value))


def test_report_of_a_hand_made_ring():
    report = _account(_ring()).report(lead_in_s=15.0)
    parts = report["parts_s"]
    assert (parts["before_engine"], parts["engine_start"],
            parts["after_engine"]) == pytest.approx((20.0, 25.0, 15.0))
    assert sum(parts[k] for k in ("before_engine", "engine_start",
                                  "after_engine")) == pytest.approx(SETUP_S)
    assert parts["after_engine_less_lead_in"] == pytest.approx(0.0)
    assert report["first_event_heard_s"] == pytest.approx(5.0)
    assert report["programs_not_cached"] == 1
    # self time: a span less what its children cover of it
    warm = report["warmup_s"]
    assert warm == pytest.approx({
        "whole": 22.0, "xla_trace": 6.0, "xla_lower": 3.0,
        "xla_compile": 1.5, "warm_unnamed": 3.5, "warm_wait": 5.5,
        "loop": 2.5})
    engine, = report["tree"]
    build, warmup = engine["children"]
    assert (engine["self_s"], build["self_s"], build["xla_s"],
            warmup["self_s"]) == pytest.approx((1.0, 1.5, 0.5, 2.5))
    assert [r["unnamed_s"] for r in report["warmed_programs"]] == \
        pytest.approx([2.5, 1.0])
    assert report["by_function"][0] == {
        "fun_name": "decode_k", "calls": 1, "total_s": 7.0,
        "trace_s": 4.0, "lower_s": 2.0, "compile_s": 1.0}


@pytest.mark.parametrize("case", ["dropped", "tracing_off", "older_program",
                                  "children_overlap"])
def test_what_gives_nothing(case, monkeypatch):
    if case == "dropped":       # a drop-oldest ring loses the start first
        assert _account(_ring(), dropped=1) is None
    elif case == "tracing_off":
        from ray_tpu.observability import tracing

        monkeypatch.setattr(tracing, "_enabled", False)
        obs = {"t_open": T_OPEN, "setup_s": SETUP_S}
        assert start_spans.collect(obs) is None
        assert start_spans.reader("phase_s", "xla_trace")(obs) is None
    elif case == "older_program":
        # what the parent commit writes: one reconstructed xla_compile a
        # compilation, no phases, no engine spans -- every metric is None
        old = [_span("xla_compile", 5.0, 1.0, duration_s=1.0)]
        got = _account(old)
        assert [got.phase_s("xla_trace"), got.cache_fetch_s(),
                got.programs(), got.before_engine_s(),
                got.span_s("serve.warmup"), got.warm_unnamed_s()] \
            == [None] * 6
        assert _account([_span("serve.chunk", 61.0, 0.5)]) is None
    else:
        # a compile inside a trace (an eager op at trace time) is covered
        # once: self time takes the union of the children
        events = [
            _span("xla_compile", 1.5, 1.0, parent_span_id="w",
                  cache_hit=True, cache_fetch_s=0.5),
            _span("xla_trace", 1.0, 2.0, parent_span_id="w"),
            _span("serve.warm_program", 1.0, 4.0, span_id="w",
                  parent_span_id="e"),
            _span("serve.engine_start", 0.5, 5.0, span_id="e")]
        assert _account(events).warm_unnamed_s() == pytest.approx(2.0)
