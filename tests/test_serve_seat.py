"""A prefilled row joins the chunk that follows its prefill
(``serve/llm.py`` ``_seat_group``, ``llama_serve.build_seat``): its first
token and length are scattered into the decode programs' carries on the
device, behind the prefill that makes them, so the chunk launched in the
same iteration decodes it and the host has waited for nothing.  Toy
widths, dense and paged, on the CPU: the tokens are the ones a lone
request gets, the ``serve.chunk`` spans say which chunk a row joined, a
request of one token never decodes, a row preempted with its prefill in
flight leaves nothing behind, and warm-up holds a seat program a rung."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama, llama_serve
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.observability import device as device_plane
from ray_tpu.observability import metrics, timeline, tracing
from ray_tpu.serve import llm

VOCAB = 256
# float32, so that a prompt's numbers do not move with the rows beside it
TOY = dict(vocab_size=VOCAB, hidden_size=64, n_layers=2, n_heads=4,
           n_kv_heads=2, head_dim=16, intermediate_size=128,
           max_seq_len=128, rope_theta=10000.0, remat=False,
           tie_embeddings=True, dtype=jnp.float32)
CHUNK = 4
ENGINE = dict(max_slots=2, max_len=128, prefill_buckets=(16, 32),
              decode_chunk=CHUNK, warmup=False)
PLANES = {"dense": {}, "paged": dict(paged=True, block_size=8)}


@pytest.fixture
def build(monkeypatch):
    assert tracing.enabled()
    servers = []

    def make(plane, **over):
        monkeypatch.setattr(
            LlamaConfig, "seat_toy",
            classmethod(lambda cls, **kw: cls(**{**TOY, **kw})),
            raising=False)
        params = llama.init_params(jax.random.key(5), LlamaConfig(**TOY))
        servers.append(llm.LLMServer(
            model_preset="seat_toy", params=params,
            **{**ENGINE, **PLANES[plane], **over}))
        return servers[-1]

    timeline.clear()
    yield make
    for server in servers:
        server.shutdown()


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(1, VOCAB, n).tolist()


def _staggered(server, first, later, tokens_before=1):
    """``first`` alone until it holds ``tokens_before`` tokens, then every
    request of ``later`` at once -> their tokens, in that order."""
    async def run():
        head = asyncio.ensure_future(server.generate(first))
        while not any(r is not None and len(r.tokens) >= tokens_before
                      for r in server.slot_req):
            assert not head.done()
            await asyncio.sleep(0.001)
        rest = await asyncio.gather(*[server.generate(r) for r in later])
        return [(await head)["tokens"]] + [r["tokens"] for r in rest]

    return asyncio.run(run())


def _alone(server, request):
    return asyncio.run(server.generate(request))["tokens"]


def _settle(server):
    """-> the timeline's clock now, in its unit, once every span of what
    ran before is written.  A request's waiter wakes before its last
    chunk's span is recorded, so one more request (of one token: it
    launches no chunk) goes through the loop first."""
    mark = timeline.now() * 1e6
    _alone(server, {"prompt": [1], "max_new_tokens": 1})
    return mark


def _spans(name, before):
    """The spans of one name that started before ``before``, by start."""
    return sorted((e for e in timeline.export_timeline()
                   if e.get("ph") == "X" and e["name"] == name
                   and e["ts"] < before), key=lambda e: e["ts"])


def _prefill_launch_us(request_span):
    """When the request's prefill was launched, on the timeline's clock."""
    wait = next(e for e in _spans("serve.wait_prefill", float("inf"))
                if e["args"]["parent_span_id"]
                == request_span["args"]["span_id"])
    return wait["ts"] + wait["args"]["launch_ms"] * 1e3


@pytest.mark.parametrize("plane", sorted(PLANES))
def test_a_row_admitted_beside_a_decoding_one_joins_the_next_chunk(
        plane, build):
    server = build(plane)
    a = {"prompt": _prompt(1, 20), "max_new_tokens": 60}
    b = {"prompt": _prompt(2, 11), "max_new_tokens": 13}
    got_a, got_b = _staggered(server, a, [b])
    mark = _settle(server)
    requests = {e["args"]["prompt_tokens"]: e
                for e in _spans("serve.request", mark)}
    chunks = _spans("serve.chunk", mark)
    # the same tokens as with the batch to itself
    assert got_b == _alone(server, b) and len(got_b) == 13
    assert got_a == _alone(server, a) and len(got_a) == 60

    assert all(c["args"]["waiting"] == 0 for c in chunks)
    # each request was seated once, in its first chunk, and in no other
    assert sum(c["args"]["seated"] for c in chunks) == 2
    span_b = requests[11]
    launched = _prefill_launch_us(span_b)
    after = [c for c in chunks if c["ts"] >= launched]
    # the chunk launched behind b's prefill, in the same iteration, holds
    # both rows, b straight from its prefill at its prompt's length ...
    joined = after[0]["args"]
    assert joined["active"] == 2 and joined["seated"] == 1
    assert all(c["args"]["seated"] == 0 for c in after[1:])
    before = [c for c in chunks if c["ts"] < launched]
    assert before and before[-1]["args"]["active"] == 1
    assert joined["kv_positions_attended"] == 11 + (
        before[-1]["args"]["kv_positions_attended"] + CHUNK)
    # ... and b's first burst behind its first token is that chunk's
    bursts = span_b["args"]["harvests"]
    assert [n for _t, n in bursts[:3]] == [1, 1 + CHUNK, 1 + 2 * CHUNK]
    assert bursts[0][0] < bursts[1][0]
    assert span_b["ts"] + bursts[1][0] * 1e3 == pytest.approx(
        after[0]["ts"] + after[0]["dur"], abs=100.0)
    assert span_b["ts"] + bursts[2][0] * 1e3 == pytest.approx(
        after[1]["ts"] + after[1]["dur"], abs=100.0)


@pytest.mark.parametrize("plane", sorted(PLANES))
def test_a_request_of_one_token_never_decodes(plane, build):
    """It ends at its prefill: its slot sits out the chunk launched
    meanwhile (``waiting``), and the slot's next tenant, seated over
    whatever the carries held, gets its own tokens."""
    server = build(plane)
    a = {"prompt": _prompt(3, 9), "max_new_tokens": 40}
    one = {"prompt": _prompt(4, 14), "max_new_tokens": 1}
    nxt = {"prompt": _prompt(5, 23), "max_new_tokens": 9}
    got_a, got_one, got_next = _staggered(server, a, [one, nxt])
    mark = _settle(server)
    requests = {e["args"]["prompt_tokens"]: e
                for e in _spans("serve.request", mark)}
    chunks = _spans("serve.chunk", mark)
    assert got_one == _alone(server, dict(one, max_new_tokens=5))[:1]
    assert got_next == _alone(server, nxt) and len(got_next) == 9
    assert got_a == _alone(server, a)
    # one slot, one tenant after the other
    assert requests[14]["args"]["slot"] == requests[23]["args"]["slot"]
    assert requests[14]["args"]["harvests"] == [
        requests[14]["args"]["harvests"][0]]
    # two requests were seated; the third held its slot through one chunk
    # without decoding in it
    assert sum(c["args"]["seated"] for c in chunks) == 2
    sat_out = [c["args"] for c in chunks if c["args"]["waiting"]]
    assert len(sat_out) == 1
    assert sat_out[0]["waiting"] == 1 and sat_out[0]["active"] == 1 \
        and sat_out[0]["seated"] == 0
    kept = sum(c["args"]["tokens_kept"] for c in chunks)
    assert kept == (40 - 1) + (9 - 1)


def test_a_row_preempted_with_its_prefill_in_flight_comes_back_whole(
        build):
    """6 usable blocks under two 40-position requests: the later one is
    admitted and seated, then evicted by the other's growth in the same
    iteration, before the host has read its first token.  The length its
    seat left in the carries is no one's; readmitted, it reproduces its
    tokens."""
    server = build("paged", num_blocks=7, decode_chunk=8,
                   prefill_buckets=(16,))
    in_flight = []
    preempt = server._preempt

    def watched(slot):
        req = server.slot_req[slot]
        in_flight.append(req.t_prefill_launched is not None
                         and not req.tokens
                         and not server.slot_waiting[slot])
        preempt(slot)

    server._preempt = watched
    a = {"prompt": _prompt(6, 10), "max_new_tokens": 30}
    b = {"prompt": _prompt(7, 10), "max_new_tokens": 30}

    async def both():
        return await asyncio.gather(server.generate(a), server.generate(b))

    got_a, got_b = (r["tokens"] for r in asyncio.run(both()))
    assert any(in_flight), in_flight
    mark = _settle(server)
    roomy = build("paged")
    assert got_a == _alone(roomy, a) and len(got_a) == 30
    assert got_b == _alone(roomy, b) and len(got_b) == 30
    done = _spans("serve.request", mark)
    assert len(done) == 2
    assert sum(e["args"]["preemptions"] for e in done) == len(in_flight)
    for e in done:      # a preempted request's bursts start over
        counts = [n for _t, n in e["args"]["harvests"]]
        assert counts[0] == 1 and counts[-1] == 30 \
            and counts == sorted(counts)
    assert all(c["args"]["waiting"] == 0
               for c in _spans("serve.chunk", mark))


@pytest.mark.parametrize("plane", sorted(PLANES))
def test_every_rung_is_seated_by_a_program_warm_up_compiled(plane, build):
    device_plane.clear_programs()
    server = build(plane, warmup=True, max_slots=8, max_len=64)
    rungs = {g for g, _ in llm.prefill_shapes(
        server.prefill_groups, server.buckets, server.max_slots)}
    assert rungs == set(llm.PREFILL_GROUPS)
    assert device_plane.registered_programs().count("serve.seat") \
        == len(rungs)
    device_plane.sample_once()       # installs the compile listener

    def compiles():
        return metrics.metrics_summary().get(
            "ray_tpu_xla_compiles_total", {}).get("backend_compile", 0.0)

    before = compiles()
    timeline.clear()
    for seed, count in enumerate((1, 4, 8, 3)):
        requests = [{"prompt": _prompt(10 * seed + i, 5 + 3 * i),
                     "max_new_tokens": 2 + i} for i in range(count)]

        async def wave():
            return await asyncio.gather(*[server.generate(r)
                                          for r in requests])

        assert [len(r["tokens"]) for r in asyncio.run(wave())] == [
            r["max_new_tokens"] for r in requests]
    assert compiles() == before
    mark = _settle(server)
    assert compiles() == before
    assert {g["args"]["rows_padded"]
            for g in _spans("serve.prefill_group", mark)} == rungs
    chunks = _spans("serve.chunk", mark)
    assert sum(c["args"]["seated"] for c in chunks) == 16
    assert all(c["args"]["waiting"] == 0 for c in chunks)


def test_padding_and_unseated_rows_touch_no_slot():
    """A negative slot is dropped: it does not wrap to the last one."""
    seat = llama_serve.build_seat()
    tok, lens = seat(jnp.arange(10, 16, dtype=jnp.int32),
                     jnp.arange(20, 26, dtype=jnp.int32),
                     jnp.asarray([7, 8, 9, 5], jnp.int32),
                     jnp.asarray([3, 4, 6, 2], jnp.int32),
                     jnp.asarray([4, -1, 0, -1], jnp.int32))
    assert tok.tolist() == [9, 11, 12, 13, 7, 15]
    assert lens.tolist() == [6, 21, 22, 23, 3, 25]
