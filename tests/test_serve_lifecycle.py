"""The serve engine measured from inside (``serve/llm.py``): a request's
life and a chunk's work as spans on the process's one timeline and
counters in its one registry — at toy size, on the CPU."""

import asyncio
import time

import pytest

from ray_tpu import serve
from ray_tpu.exceptions import BackPressureError, DeadlineExceededError
from ray_tpu.observability import metrics, timeline, tracing

PHASES = ("serve.wait_boundary", "serve.wait_slot", "serve.wait_prefill",
          "serve.decode")
COUNTERS = ("ray_tpu_serve_decode_tokens_kept_total",
            "ray_tpu_serve_decode_slot_steps_total",
            "ray_tpu_serve_prefill_prompt_tokens_total",
            "ray_tpu_serve_prefill_padded_tokens_total",
            "ray_tpu_serve_decode_kv_positions_attended_total",
            "ray_tpu_serve_decode_kv_positions_bucket_total",
            "ray_tpu_serve_slots_released_early_total")
ENGINE = dict(model_preset="debug", max_slots=4, max_len=128,
              prefill_buckets=(32, 64), decode_chunk=4,
              prefill_groups=(2, 4))


def _spans(name=None):
    return [e for e in timeline.export_timeline() if e.get("ph") == "X"
            and (name is None or e["name"] == name)]


def _engine_spans():
    return [e for e in _spans() if e["name"].startswith("serve.")]


def _counters(deployment):
    summary = metrics.metrics_summary()
    return {name: summary.get(name, {}).get(deployment, 0.0)
            for name in COUNTERS}


def _generate(server, requests):
    async def run():
        return await asyncio.gather(
            *[server.generate(r) for r in requests],
            return_exceptions=True)

    return asyncio.run(run())


def _life(request_span):
    """A serve.request's phase spans, in order of start."""
    sid = request_span["args"]["span_id"]
    return sorted((e for e in _spans() if e["name"] in PHASES
                   and e["args"].get("parent_span_id") == sid),
                  key=lambda e: e["ts"])


@pytest.fixture
def fresh_timeline():
    assert tracing.enabled()
    timeline.clear()
    yield
    tracing.enable()


def test_request_life_under_the_handles_trace(ray_start_regular,
                                              fresh_timeline):
    from ray_tpu.serve.llm import LLMServer

    before = _counters("LLMServer")
    waits_before = metrics.serve_engine_counters()["queue_wait"].buckets(
        {"deployment": "LLMServer"})
    handle = serve.run(serve.deployment(LLMServer).options(
        max_ongoing_requests=64).bind(**ENGINE))
    try:
        prompts = [list(range(1, 5 + 3 * i)) for i in range(7)]
        replies = [handle.generate.remote(
            {"prompt": p, "max_new_tokens": 6 + i}).result(timeout=120)
            for i, p in enumerate(prompts[:2])]
        responses = [handle.generate.remote(
            {"prompt": p, "max_new_tokens": 8 + i})
            for i, p in enumerate(prompts[2:])]
        replies += [r.result(timeout=120) for r in responses]
    finally:
        serve.shutdown()
    assert all(set(r) == {"tokens", "ttft_ms"} for r in replies)
    deadline = time.time() + 5.0      # the last settle callbacks
    while len(_spans("serve.response")) < 7 and time.time() < deadline:
        time.sleep(0.01)
    handles = {e["args"]["trace_id"]: e for e in _spans()
               if e["name"] == "serve:LLMServer.generate"}
    requests = _spans("serve.request")
    assert len(requests) == len(handles) == 7
    returned = sorted(len(r["tokens"]) for r in replies)
    assert sorted(e["args"]["output_tokens"] for e in requests) == returned
    ttfts = sorted(r["ttft_ms"] for r in replies)
    sums = []
    for e in requests:
        args = e["args"]
        handle_span = handles[args["trace_id"]]       # one trace id
        assert args["outcome"] == "ok" and args["preemptions"] == 0
        phases = _life(e)
        assert [p["name"] for p in phases] == list(PHASES)
        # each phase starts where the one before it ended; the four
        # cover the request exactly
        assert phases[0]["ts"] == pytest.approx(e["ts"], abs=1.0)
        for a, b in zip(phases, phases[1:]):
            assert a["ts"] + a["dur"] == pytest.approx(b["ts"], abs=1.0)
        assert sum(p["dur"] for p in phases) == pytest.approx(
            e["dur"], abs=1.0)
        sums.append(sum(p["dur"] for p in phases[:3]) * 1e-3)
        assert phases[2]["args"]["bucket"] in ENGINE["prefill_buckets"]
        assert phases[2]["args"]["rows"] in ENGINE["prefill_groups"]
        assert phases[2]["args"]["launch_ms"] >= 0.0
        # bursts: the first token alone, then a chunk's worth at a time
        counts = [n for _t, n in args["harvests"]]
        assert counts[0] == 1 and counts[-1] == args["output_tokens"]
        assert all(0 < b - a <= ENGINE["decode_chunk"]
                   for a, b in zip(counts, counts[1:]))
        assert args["harvests"][0][0] == pytest.approx(
            sums[-1], abs=2e-3)
        # the return leg hangs under the handle's span, and both legs
        # of the request path have a length
        response = [r for r in _spans("serve.response")
                    if r["args"]["trace_id"] == args["trace_id"]]
        assert len(response) == 1
        assert response[0]["args"]["parent_span_id"] == \
            handle_span["args"]["span_id"]
        assert handle_span["ts"] <= e["ts"]
        assert response[0]["ts"] + response[0]["dur"] >= e["ts"] + e["dur"]
    # the three waits are ttft_ms, to its rounding (two decimals) and the
    # quarter microsecond a wall-clock stamp resolves
    for ours, replied in zip(sorted(sums), ttfts):
        assert ours == pytest.approx(replied, abs=0.006)

    chunks, groups = _spans("serve.chunk"), _spans("serve.prefill_group")
    kept = sum(c["args"]["tokens_kept"] for c in chunks)
    steps = sum(c["args"]["token_steps"] for c in chunks)
    assert kept + len(requests) == sum(returned)   # firsts are prefill's
    assert steps == len(chunks) * ENGINE["decode_chunk"] \
        * ENGINE["max_slots"]
    assert all(0 < c["args"]["active"] <= 4 for c in chunks)
    assert sum(g["args"]["rows"] for g in groups) == 7
    assert sum(g["args"]["prompt_tokens"] for g in groups) == \
        sum(len(p) for p in prompts)
    assert all(g["args"]["token_positions"]
               == g["args"]["rows_padded"] * g["args"]["bucket"]
               and g["args"]["rows"] <= g["args"]["rows_padded"]
               for g in groups)
    after = _counters("LLMServer")
    grown = [after[c] - before[c] for c in COUNTERS]
    assert grown == [kept, steps,
                     sum(g["args"]["prompt_tokens"] for g in groups),
                     sum(g["args"]["token_positions"] for g in groups),
                     sum(c["args"]["kv_positions_attended"] for c in chunks),
                     sum(c["args"]["kv_positions_bucket"] for c in chunks),
                     sum(c["args"]["released_early"] for c in chunks)]
    # a live row holds at least its prompt and less than the bucket
    assert all(c["args"]["kv_positions_bucket"]
               == ENGINE["max_slots"] * c["args"]["s_active"]
               and 4 * c["args"]["active"]
               <= c["args"]["kv_positions_attended"]
               < c["args"]["active"] * c["args"]["s_active"]
               for c in chunks)
    waits = metrics.serve_engine_counters()["queue_wait"].buckets(
        {"deployment": "LLMServer"})
    assert sum(waits) - sum(waits_before) == 7


@pytest.mark.parametrize("flavour", [
    dict(paged=False),
    dict(paged=True, block_size=8),
    dict(paged=True, block_size=8, spec_k=4, draft_layers=1),
])
def test_every_plane_stamps_the_same_boundaries(fresh_timeline, flavour):
    """Dense, paged and speculative engines called directly (no handle:
    the request mints its own trace)."""
    from ray_tpu.serve.llm import LLMServer

    server = LLMServer(model_preset="debug", max_slots=4, max_len=64,
                       prefill_buckets=(16,), decode_chunk=8,
                       prefill_groups=(4,), **flavour)
    try:
        outs = _generate(server, [
            {"prompt": [i + 1] * (3 + i), "max_new_tokens": 10}
            for i in range(5)])
    finally:
        server.shutdown()
    assert all(len(o["tokens"]) == 10 for o in outs)
    requests = _spans("serve.request")
    assert len(requests) == 5
    assert len({e["args"]["trace_id"] for e in requests}) == 5
    for e in requests:
        assert [p["name"] for p in _life(e)] == list(PHASES)
        assert e["args"]["harvests"][-1][1] == 10
    chunks = _spans("serve.chunk")
    assert sum(c["args"]["tokens_kept"] for c in chunks) == 5 * 10 - 5
    k = flavour.get("spec_k", 8)
    assert all(c["args"]["token_steps"] == k * 4 for c in chunks)


@pytest.mark.parametrize("flavour", [dict(paged=False),
                                     dict(paged=True, block_size=8)])
def test_a_chunk_counts_the_positions_its_rows_hold(fresh_timeline,
                                                    flavour):
    """One request alone: the chunks launched while it lives find its
    row 5, 9, 13... positions long, one chunk's steps more each time
    (and none is launched past its end: its slot is vacated while the
    chunk that holds its last token is in flight), against
    ``max_slots x s_active`` positions of bucket."""
    from ray_tpu.serve.llm import LLMServer

    server = LLMServer(model_preset="debug", max_slots=4, max_len=64,
                       prefill_buckets=(16,), decode_chunk=4,
                       prefill_groups=(4,), **flavour)
    try:
        out, = _generate(server, [{"prompt": [7, 8, 9, 10, 11],
                                   "max_new_tokens": 10}])
    finally:
        server.shutdown()
    assert len(out["tokens"]) == 10
    chunks = sorted(_spans("serve.chunk"), key=lambda e: e["ts"])
    assert len(chunks) == 3         # 1 token of prefill + 4 + 4 + 1
    for i, c in enumerate(chunks):
        args = c["args"]
        assert args["active"] == 1
        assert args["kv_positions_attended"] == 5 + 4 * i
        assert args["kv_positions_bucket"] == 4 * args["s_active"]
        assert args["kv_positions_attended"] < args["s_active"]
    attended = sum(c["args"]["kv_positions_attended"] for c in chunks)
    bucket = sum(c["args"]["kv_positions_bucket"] for c in chunks)
    assert 0 < attended / bucket < 0.25     # one row of four, part full


@pytest.mark.parametrize("flavour", [dict(paged=False),
                                     dict(paged=True, block_size=8)])
def test_a_released_request_keeps_its_life_and_hands_over_its_slot(
        fresh_timeline, flavour):
    """Two requests on ONE slot: the first ends inside a chunk in flight
    and its slot goes to the second at that boundary (``released_early``).
    The first's four phases still cover its life exactly, to the harvest
    of the chunk that holds its last token; the second's wait for a slot
    ends at the hand-over, before that harvest, and its prefill is
    launched behind that chunk."""
    from ray_tpu.serve.llm import LLMServer

    server = LLMServer(model_preset="debug", max_slots=1, max_len=64,
                       prefill_buckets=(16,), decode_chunk=4,
                       prefill_groups=(1,), warmup=False, **flavour)
    before = _counters("llm")
    try:
        first, second = _generate(server, [
            {"prompt": [3, 4, 5, 6, 7], "max_new_tokens": 11},
            {"prompt": [8, 9, 10], "max_new_tokens": 6}])
        # a request's waiter wakes before its last chunk's span is
        # written: one more, of one token, goes through the loop first
        _generate(server, [{"prompt": [1], "max_new_tokens": 1}])
    finally:
        server.shutdown()
    assert len(first["tokens"]) == 11 and len(second["tokens"]) == 6
    old, new, _settle = sorted(_spans("serve.request"),
                               key=lambda e: e["ts"] + e["dur"])
    assert old["args"]["prompt_tokens"] == 5
    assert old["args"]["slot"] == new["args"]["slot"] == 0
    for e in (old, new):
        assert e["args"]["outcome"] == "ok"
        phases = _life(e)
        assert [p["name"] for p in phases] == list(PHASES)
        assert phases[0]["ts"] == pytest.approx(e["ts"], abs=1.0)
        for a, b in zip(phases, phases[1:]):
            assert a["ts"] + a["dur"] == pytest.approx(b["ts"], abs=1.0)
        assert sum(p["dur"] for p in phases) == pytest.approx(
            e["dur"], abs=1.0)
    chunks = sorted(_spans("serve.chunk"), key=lambda e: e["ts"])
    # 10 tokens behind the first in chunks of 4, then 5 behind the
    # second's: no chunk over a finished tenant
    assert [c["args"]["released_early"] for c in chunks] == [0, 0, 0, 1, 0]
    assert [c["args"]["tokens_kept"] for c in chunks] == [4, 4, 2, 4, 1]
    last_of_old = chunks[2]
    old_end = old["ts"] + old["dur"]
    assert old_end == pytest.approx(
        last_of_old["ts"] + last_of_old["dur"], abs=500.0)
    # the hand-over: the second is bound to the slot while the first's
    # last chunk is in flight, so before the first is done
    wait_slot, wait_prefill = _life(new)[1:3]
    handed_over = wait_slot["ts"] + wait_slot["dur"]
    assert last_of_old["ts"] < handed_over < old_end
    launched = wait_prefill["ts"] + wait_prefill["args"]["launch_ms"] * 1e3
    assert handed_over <= launched < old_end
    assert chunks[3]["ts"] > launched and chunks[3]["args"]["seated"] == 1
    grown = _counters("llm")
    assert grown["ray_tpu_serve_slots_released_early_total"] - before[
        "ray_tpu_serve_slots_released_early_total"] == 1


def test_shed_and_preempted_requests_leave_outcome_and_count(
        fresh_timeline):
    """A pool of 6 usable blocks under four 40-position requests
    preempts (recompute on readmit); a request the pool can never hold
    is shed while it decodes, one whose deadline has passed before it
    gets a slot."""
    from ray_tpu.serve.llm import LLMServer

    server = LLMServer(model_preset="debug", max_slots=4, max_len=64,
                       prefill_buckets=(16,), decode_chunk=8,
                       paged=True, block_size=8, prefill_groups=(4,),
                       num_blocks=7)
    try:
        outs = _generate(server, [
            {"prompt": [i + 1] * 10, "max_new_tokens": 30}
            for i in range(4)])
        assert all(len(o["tokens"]) == 30 for o in outs)
        shed = _generate(server, [
            {"prompt": [1] * 12, "max_new_tokens": 60},
            {"prompt": [2] * 4, "max_new_tokens": 4, "deadline_s": -1.0}])
    finally:
        server.shutdown()
    assert isinstance(shed[0], BackPressureError)
    assert isinstance(shed[1], DeadlineExceededError)
    requests = _spans("serve.request")
    done = [e for e in requests if e["args"]["outcome"] == "ok"]
    assert len(done) == 4
    assert sum(e["args"]["preemptions"] for e in done) >= 1
    for e in done:      # a preempted request's bursts start over
        assert [p["name"] for p in _life(e)] == list(PHASES)
        counts = [n for _t, n in e["args"]["harvests"]]
        assert counts[0] == 1 and counts[-1] == 30 \
            and counts == sorted(counts)
    big, late = sorted(
        (e for e in requests if e["args"]["outcome"] == "shed"),
        key=lambda e: -e["args"]["prompt_tokens"])
    assert [p["name"] for p in _life(big)] == list(PHASES)
    assert 0 < big["args"]["output_tokens"] < 60
    # ended waiting for a slot: two phases, the second cut at the end
    phases = _life(late)
    assert [p["name"] for p in phases] == list(PHASES[:2])
    assert phases[1]["ts"] + phases[1]["dur"] == pytest.approx(
        late["ts"] + late["dur"], abs=1.0)
    assert late["args"]["slot"] is None
    assert late["args"]["output_tokens"] == 0


def test_tracing_off_writes_nothing_and_changes_no_reply(fresh_timeline):
    from ray_tpu.serve.llm import LLMServer

    server = LLMServer(model_preset="debug", max_slots=2, max_len=64,
                       prefill_buckets=(16,), decode_chunk=4,
                       prefill_groups=(2,))
    request = {"prompt": [5, 6, 7, 8], "max_new_tokens": 9}
    try:
        traced, = _generate(server, [request])
        assert _engine_spans()
        tracing.disable()
        timeline.clear()
        before = _counters("llm")
        plain, = _generate(server, [request])
        assert not _engine_spans()
        assert _counters("llm") == before
    finally:
        tracing.enable()
        server.shutdown()
    assert set(plain) == set(traced) == {"tokens", "ttft_ms"}
    assert plain["tokens"] == traced["tokens"] and plain["ttft_ms"] > 0


def test_one_clock_round_trips():
    t = time.perf_counter()
    wall = timeline.wall_from_perf(t)
    assert timeline.perf_from_wall(wall) == pytest.approx(t, abs=1e-6)
    assert abs(wall - time.time()) < 0.5       # wall-clock, to a slew
    assert abs(timeline.now() - wall) < 0.5
    # differences of perf_counter stamps survive the conversion
    assert timeline.wall_from_perf(t + 0.25) - wall == pytest.approx(
        0.25, abs=1e-6)
