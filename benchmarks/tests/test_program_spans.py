"""The readers of the program's own spans (``lib/program_spans.py``) on
the tiny serve cells through the same ``run.measure`` the command uses,
their windowing on hand-made events, and the flash kernels' names
(``lib/flash_names.py``) on a trace recorded on the chip."""

import os

import pytest

from benchmarks.lib import flash_names, program_spans, trace_reduce
from benchmarks.tests.test_rehearsal import (  # noqa: F401 - fixtures
    _measure, cpu_peaks, tree)
from benchmarks.tools import lifecycle_report

HERE = os.path.dirname(os.path.abspath(__file__))
NAMED_FIXTURE = os.path.join(HERE, "fixtures", "named_flash_tpu.xplane.pb")
SERVE_READERS = (
    "boundary_wait_p50_ms", "slot_wait_p50_ms", "prefill_wait_p50_ms",
    "token_burst_gap_p50_ms", "token_burst_gap_p90_ms",
    "decode_slot_utilization", "prefill_padding_share",
    "request_path_overhead_p50_ms")


@pytest.mark.parametrize("cell,group,only_here", [
    ("tiny.tiny-open", "chat", ["slot_wait_p90_ms"]),
    ("tiny.tiny-closed", "batch", []),
])
def test_serve_readers_on_the_tiny_cells(tree, cpu_peaks, cell, group,
                                         only_here):
    result, obs = _measure(tree, cell, trace=1, seconds=3.0)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    names = [f"{group}.{r}" for r in SERVE_READERS] + only_here
    assert set(names) <= set(metrics), sorted(metrics)
    assert not any("roofline" in m or "device" in m for m in metrics)
    assert all(metrics[n] >= 0.0 for n in names)
    assert 0.0 < metrics[f"{group}.decode_slot_utilization"] <= 100.0
    assert 0.0 <= metrics[f"{group}.prefill_padding_share"] < 100.0
    assert metrics[f"{group}.request_path_overhead_p50_ms"] > 0.0

    got = program_spans.collect(obs)
    measured = obs["measured"]
    assert got.requests and got.chunks and got.groups
    assert all(obs["t_open"] <= r.t_submit < obs["t_close"]
               for r in got.requests)
    assert all(obs["t_open"] <= c["t_launch"] < obs["t_close"]
               for c in got.chunks + got.groups)
    # the program's account against the generator's: every request the
    # program saw submitted in the window is one the generator sent, and
    # its three waits are the ttft_ms of its reply
    pairs = lifecycle_report.join_to_log(got.requests, obs["log"].records)
    assert len(pairs) == len(got.requests)
    assert len(pairs) >= 0.8 * len(
        [r for r in measured if r.sent >= obs["t_open"]])
    for req, rec in pairs:
        assert set(req.phase_ms) == set(program_spans.PHASES)
        assert sum(req.phase_ms[p] for p in program_spans.PHASES[:3]) \
            == pytest.approx(rec.ttft_ms, abs=0.006)
        assert req.harvests[-1][1] == rec.got_tokens
        assert req.inbound_ms >= 0.0 and req.outbound_ms >= 0.0
    # what a chunk keeps is what requests were handed, less the first
    # tokens (the prefills') — over requests wholly inside the window
    inside = [r for r in got.requests if r.t_done <= obs["t_close"]]
    assert sum(c["tokens_kept"] for c in got.chunks) >= sum(
        r.args["output_tokens"] - 1 for r in inside) * 0.5


def _span(name, start, dur, **args):
    return {"name": name, "ph": "X", "pid": "p", "tid": "t",
            "ts": start * 1e6, "dur": dur * 1e6, "args": args}


def _request(trace, start, wait=(0.1, 0.2, 0.3), decode=1.0):
    sid = f"s{trace}"
    out = [_span("serve:llm.generate", start - 0.002, 0.001,
                 trace_id=trace, span_id=f"h{trace}")]
    t = start
    for name, dur in zip(program_spans.PHASES, (*wait, decode)):
        extra = {"launch_ms": 1.0, "bucket": 32, "rows": 4} \
            if name == "serve.wait_prefill" else {}
        out.append(_span(name, t, dur, trace_id=trace, parent_span_id=sid,
                         **extra))
        t += dur
    out.append(_span("serve.request", start, t - start, trace_id=trace,
                     span_id=sid, parent_span_id="task", rid=trace,
                     prompt_tokens=8, output_tokens=17, outcome="ok",
                     harvests=[[600.0, 1], [900.0, 17]]))
    out.append(_span("serve.response", start - 0.001, t - start + 0.004,
                     trace_id=trace, parent_span_id=f"h{trace}"))
    return out


def test_windowing_keeps_what_was_submitted_or_launched_inside():
    events = (_request("a", 9.0) + _request("b", 10.5)
              + _request("c", 19.9) + _request("d", 20.0) + [
        _span("serve.chunk", 9.9, 0.5, tokens_kept=10, token_steps=64),
        _span("serve.chunk", 10.0, 0.5, tokens_kept=48, token_steps=64),
        _span("serve.prefill_group", 12.0, 0.2, prompt_tokens=40,
              token_positions=128),
        {"name": "an instant", "ph": "i", "ts": 11e6, "pid": "p"}])
    events.sort(key=lambda e: e["ts"] + e.get("dur", 0.0))   # append order
    got = program_spans.window(events, 0, 10.0, 20.0, lambda t: t)
    assert [r.args["rid"] for r in got.requests] == ["b", "c"]
    b = got.requests[0]
    assert b.phase_ms == pytest.approx({
        "serve.wait_boundary": 100.0, "serve.wait_slot": 200.0,
        "serve.wait_prefill": 300.0, "serve.decode": 1000.0})
    assert b.prefill["bucket"] == 32
    assert b.inbound_ms == pytest.approx(2.0)
    assert b.outbound_ms == pytest.approx(3.0)
    assert [c["tokens_kept"] for c in got.chunks] == [48]
    assert len(got.groups) == 1
    obs = {"program_spans": got}
    assert program_spans.decode_slot_utilization(obs) == 75.0
    assert program_spans.prefill_padding_share(obs) == 68.75
    assert program_spans.phase_percentile("serve.wait_slot", 50)(obs) \
        == pytest.approx(200.0)
    assert program_spans.token_burst_gap_percentile(90)(obs) \
        == pytest.approx(300.0)
    assert program_spans.request_path_overhead_p50_ms(obs) \
        == pytest.approx(5.0)
    # a ring that dropped events: sound while the oldest event left
    # ended before the window opened, refused once it did not
    assert program_spans.window(events, 5, 10.0, 20.0, lambda t: t)
    assert program_spans.window(events[4:], 5, 9.5, 20.0,
                                lambda t: t) is None
    # and every reader gives None where there are no spans to read
    for read in (program_spans.decode_slot_utilization,
                 program_spans.prefill_padding_share,
                 program_spans.request_path_overhead_p50_ms,
                 program_spans.phase_percentile("serve.decode", 50),
                 program_spans.token_burst_gap_percentile(50)):
        assert read({"program_spans": None}) is None


def test_no_spans_with_the_plane_off():
    from ray_tpu.observability import tracing

    tracing.disable()
    try:
        assert program_spans.collect({"t_open": 0.0, "t_close": 1.0}) is None
    finally:
        tracing.enable()
    assert program_spans.collect({"kind": "train_lm"}) is None


def test_flash_kernel_names_are_told_apart():
    text = ('%flash_attention_{}.{} = f32[1,2,256,128]{{3,2,1,0}} '
            'custom-call(bf16[1,2,256,128] %x), '
            'custom_call_target="tpu_custom_call"')
    dev = trace_reduce.DeviceTrace(0, ops=[
        (0.0, 1.0, text.format("fwd", 3)), (1.0, 3.0, text.format("dq", 1)),
        (3.0, 6.0, text.format("dkdv", 1)),
        (6.0, 7.0, text.format("fwd", "2.remat")),
        (7.0, 9.0, '%checkpoint.25 = f32[8] custom-call(f32[8] %y), '
                   'custom_call_target="tpu_custom_call"')],
        modules=[(0.0, 10.0, "jit_step(123)")])
    obs = {"trace": trace_reduce.Trace([dev], [], 0.0, 10.0)}
    assert flash_names.time_share("fwd")(obs) == pytest.approx(20.0)
    assert flash_names.time_share("dq")(obs) == pytest.approx(20.0)
    assert flash_names.time_share("dkdv")(obs) == pytest.approx(30.0)
    # a program whose kernels carry no name: nothing to read
    dev = trace_reduce.DeviceTrace(0, ops=dev.ops[-1:], modules=dev.modules)
    obs = {"trace": trace_reduce.Trace([dev], [], 0.0, 10.0)}
    assert flash_names.time_share("dq")(obs) is None
    assert flash_names.time_share("dq")({}) is None


def test_names_and_clock_in_a_recorded_tpu_trace():
    """Recorded on a v5e (PR 23, ``tools/record_fixture.py``): three runs
    of one jitted step holding the program's flash forward and backward,
    launched under the program's ``device.annotation``."""
    trace = trace_reduce.read(NAMED_FIXTURE)
    assert len(trace.module_runs(r"^jit_step\b")) == 3
    seconds = {k: flash_names.kernel_seconds(trace, k)
               for k in flash_names.KERNEL_OPS}
    assert all(s > 0 for s in seconds.values())
    # the forward runs twice a step (once more under remat), so it is
    # the longest here; the three names account for every Mosaic call
    assert seconds["fwd"] > seconds["dkdv"] > seconds["dq"]
    assert sum(seconds.values()) == pytest.approx(
        trace.seconds_matching(trace_reduce.MOSAIC_CALL.replace(
            '"', r'\"')), rel=1e-9)
    obs = {"trace": trace}
    shares = [flash_names.time_share(k)(obs) for k in seconds]
    assert all(0 < s < 100 for s in shares)
    # a fused op's event carries its instruction's text and no scope
    # name: nothing of "optimizer" or "head_loss" is in the trace
    assert not any("optimizer" in n or "head_loss" in n
                   for n in trace.op_seconds())
    # the host annotations carry the host clock of their opening: every
    # one gives the same offset between the profiler's clock and it
    names = [n for _s, _e, n in trace.host if "#t=" in n]
    assert {n.split("#")[0] for n in names} == {"train.step",
                                                "serve.harvest_chunk"}
    offset = program_spans.profiler_minus_perf(trace, lambda wall: wall)
    for start, _end, name in trace.host:
        if "#t=" in name:
            assert start - float(name.split("t=")[1]) == pytest.approx(
                offset, abs=1e-3)
    assert program_spans.profiler_minus_perf(
        trace_reduce.Trace([], [(0.0, 1.0, "train.step")], 0.0, 1.0),
        lambda wall: wall) is None
    # and the reduction's own reading of annotations still sees them
    gaps = dict(trace.idle_gaps(names=("train.step",
                                       "serve.harvest_chunk")))
    assert gaps["serve.harvest_chunk"] > 0.5 * sum(gaps.values())
