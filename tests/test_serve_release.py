"""A slot whose tenant must end inside the chunk in flight goes to the
next request at that boundary (``serve/llm.py`` ``_release_ending``): the
engine has no stop token, so the host knows a request's end from the
lengths it holds (``_ends_by``) before it has the chunk's tokens, and the
chunk it launches now decodes the slot's NEXT tenant where it decoded a
finished one for all its steps.  Toy widths on the CPU, dense and paged,
a plain decoder and the toy hybrid and windowed models: every request's
tokens are a lone run's, the chunks launched are exactly those with a live
tenant, ``serve.chunk`` ``released_early`` counts the hand-overs, and what
must keep the old path keeps it (a tenant whose K/V is read at its end, a
request of one token, the speculative rounds)."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.observability import timeline, tracing
from ray_tpu.serve import llm

VOCAB, CHUNK = 256, 16
# float32, so that a prompt's numbers do not move with what ran before it
PLAIN = dict(vocab_size=VOCAB, max_seq_len=128, dtype=jnp.float32)
MODELS = {
    "plain": lambda **kw: LlamaConfig.debug(**{**PLAIN, **kw}),
    # tests/test_granite_serve.py's and tests/test_smallthinker_serve.py's
    "hybrid": lambda **kw: LlamaConfig.hybrid_debug(**{**PLAIN, **kw}),
    "windowed": lambda **kw: LlamaConfig.debug(**{**dict(
        PLAIN, n_layers=8, n_heads=8, n_kv_heads=4, intermediate_size=32,
        moe_experts=8, moe_top_k=3, moe_norm_topk=True,
        moe_router_input="layer", moe_activation="relu", window_size=8,
        layer_pattern=("attention", "window", "window", "window"),
        nope_kinds=("attention",), tie_embeddings=False), **kw}),
}
PLANES = {"dense": {}, "paged": dict(paged=True, block_size=8)}
# ONE slot: whoever decodes next decodes in the rows the last one left
ENGINE = dict(max_slots=1, max_len=128, prefill_buckets=(16, 32),
              decode_chunk=CHUNK, prefill_groups=(1,), warmup=False)


def _params(model):
    cfg = MODELS[model]()
    params = llama.init_params(jax.random.key(5), cfg)
    if model == "hybrid":
        # a recurrence that MATTERS (tests/test_granite_serve.py): under
        # the initial values a toy's tokens do not read its state at all,
        # and a state left behind by the slot's last tenant would not show
        layers = params["layers"]
        layers["ssm_dt_bias"] = jnp.full_like(
            layers["ssm_dt_bias"], float(np.log(np.expm1(0.5))))
        layers["ssm_A_log"] = jnp.log(jax.random.uniform(
            jax.random.key(6), layers["ssm_A_log"].shape, minval=0.02,
            maxval=0.5))
        layers["ssm_D"] = jnp.full_like(layers["ssm_D"], 0.2)
    return params


@pytest.fixture
def build(monkeypatch):
    assert tracing.enabled()
    servers = []

    def make(model="plain", plane="dense", **over):
        monkeypatch.setattr(
            LlamaConfig, "release_toy",
            classmethod(lambda cls, **kw: MODELS[model](**kw)),
            raising=False)
        server = llm.LLMServer(
            model_preset="release_toy", params=_params(model),
            **{**ENGINE, **PLANES[plane], **over})
        servers.append(server)
        # every request that ends, in order, for what the spans do not say
        server.concluded = []
        conclude = server._conclude

        def watched(req):
            server.concluded.append(req)
            conclude(req)

        server._conclude = watched
        return server

    timeline.clear()
    yield make
    for server in servers:
        server.shutdown()


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(1, VOCAB, n).tolist()


def _together(server, requests):
    """Every request at once, admitted in this order -> their tokens."""
    async def run():
        return await asyncio.gather(*[server.generate(r)
                                      for r in requests])

    return [r["tokens"] for r in asyncio.run(run())]


def _alone(server, request):
    return asyncio.run(server.generate(request))["tokens"]


def _settle(server):
    """-> the timeline's clock now, once every span of what ran before is
    written: a request's waiter wakes before its last chunk's span is
    recorded, so one more request of one token goes through the loop."""
    mark = timeline.now() * 1e6
    _alone(server, {"prompt": [1], "max_new_tokens": 1})
    return mark


def _spans(name, before):
    return sorted((e for e in timeline.export_timeline()
                   if e.get("ph") == "X" and e["name"] == name
                   and e["ts"] < before), key=lambda e: e["ts"])


def _chunks_of(tokens):
    """Chunks in which a request of ``tokens`` tokens is alive: the first
    token is its prefill's."""
    return -(-(tokens - 1) // CHUNK)


def _old_loop_chunks(tokens):
    """... and what the loop launched for it before: one more, decoded
    while the chunk holding its last token was still in flight."""
    return _chunks_of(tokens) + 1


@pytest.mark.parametrize("model,plane", [
    ("plain", "dense"), ("plain", "paged"), ("hybrid", "dense"),
    ("windowed", "dense")])
def test_three_tenants_of_one_slot_each_ending_mid_chunk(model, plane,
                                                         build):
    server = build(model, plane)
    requests = [{"prompt": _prompt(1, 20), "max_new_tokens": 21},
                {"prompt": _prompt(2, 11), "max_new_tokens": 38},
                {"prompt": _prompt(3, 27), "max_new_tokens": 9}]
    got = _together(server, requests)
    mark = _settle(server)
    for request, tokens in zip(requests, got):
        assert tokens == _alone(server, request)
        assert len(tokens) == request["max_new_tokens"]
    assert [r.released for r in server.concluded[:3]] == [True] * 3
    chunks = _spans("serve.chunk", mark)
    # the second and the third took a slot whose tenant's last chunk was
    # still in flight, and no chunk was launched over a finished tenant
    assert sum(c["args"]["released_early"] for c in chunks) == 2
    assert all(c["args"]["active"] == 1 for c in chunks)
    assert len(chunks) == sum(_chunks_of(r["max_new_tokens"])
                              for r in requests) == 2 + 3 + 1
    kept = sum(c["args"]["tokens_kept"] for c in chunks)
    steps = sum(c["args"]["token_steps"] for c in chunks)
    assert kept == 20 + 37 + 8
    old_steps = CHUNK * sum(_old_loop_chunks(r["max_new_tokens"])
                            for r in requests)
    assert kept / steps > kept / old_steps
    # the second's prefill was launched while the chunk that holds the
    # first's last token was in flight: behind its launch, before its
    # harvest returned
    spans = {e["args"]["prompt_tokens"]: e
             for e in _spans("serve.request", mark)}
    wait = next(e for e in _spans("serve.wait_prefill", mark)
                if e["args"]["parent_span_id"]
                == spans[11]["args"]["span_id"])
    launched = wait["ts"] + wait["args"]["launch_ms"] * 1e3
    last_of_first = chunks[_chunks_of(21) - 1]
    assert last_of_first["ts"] < launched \
        < last_of_first["ts"] + last_of_first["dur"]
    # and the chunk launched right behind that prefill holds it, seated
    joined = chunks[_chunks_of(21)]["args"]
    assert joined["seated"] == 1 and joined["released_early"] == 1
    assert joined["kv_positions_attended"] == 11


@pytest.mark.parametrize("plane", sorted(PLANES))
@pytest.mark.parametrize("tokens", [2, CHUNK, CHUNK + 1, 2 * CHUNK + 1])
def test_an_end_on_and_beside_a_chunks_last_step(tokens, plane, build):
    """2: the first step of the chunk it was seated into; 16: inside that
    chunk; 17: exactly its last step; 33: the last step of the next."""
    server = build("plain", plane)
    first = {"prompt": _prompt(4, 13), "max_new_tokens": tokens}
    second = {"prompt": _prompt(5, 22), "max_new_tokens": 7}
    got_first, got_second = _together(server, [first, second])
    mark = _settle(server)
    assert got_first == _alone(server, first) and len(got_first) == tokens
    assert got_second == _alone(server, second) and len(got_second) == 7
    chunks = [c["args"] for c in _spans("serve.chunk", mark)]
    assert len(chunks) == _chunks_of(tokens) + 1
    assert [c["released_early"] for c in chunks] == \
        [0] * _chunks_of(tokens) + [1]
    assert sum(c["tokens_kept"] for c in chunks) == tokens - 1 + 6


@pytest.mark.parametrize("plane", sorted(PLANES))
def test_the_horizon_releases_as_the_budget_does(plane, build):
    server = build("plain", plane, max_len=64)
    long = {"prompt": _prompt(6, 20), "max_new_tokens": 500}
    nxt = {"prompt": _prompt(7, 9), "max_new_tokens": 5}
    got_long, got_next = _together(server, [long, nxt])
    mark = _settle(server)
    # positions 20 .. 62 hold its tokens' keys: 63 = max_len - 1 is the end
    assert len(got_long) == 64 - 1 - 20 + 1
    assert got_long == _alone(server, long)
    assert got_next == _alone(server, nxt) and len(got_next) == 5
    assert server.concluded[0].released
    chunks = [c["args"] for c in _spans("serve.chunk", mark)]
    assert len(chunks) == _chunks_of(len(got_long)) + 1
    assert chunks[-1]["released_early"] == 1
    assert all(c["active"] == 1 for c in chunks)


@pytest.mark.parametrize("plane", sorted(PLANES))
def test_a_request_of_one_token_is_not_released(plane, build):
    """It ends at its prefill and is in no chunk: its slot is free when
    the harvest has read its token, as before."""
    server = build("plain", plane)
    one = {"prompt": _prompt(8, 14), "max_new_tokens": 1}
    nxt = {"prompt": _prompt(9, 23), "max_new_tokens": 9}
    got_one, got_next = _together(server, [one, nxt])
    mark = _settle(server)
    assert got_one == _alone(server, dict(one, max_new_tokens=5))[:1]
    assert got_next == _alone(server, nxt) and len(got_next) == 9
    assert [r.released for r in server.concluded[:2]] == [False, True]
    chunks = [c["args"] for c in _spans("serve.chunk", mark)]
    assert [c["released_early"] for c in chunks] == [0]


def _submit(server, req):
    asyncio.run(server._submit_and_wait(req))
    return req


def test_a_tenant_whose_kv_is_read_at_its_end_keeps_its_slot(build):
    """The prefill role's extraction reads the slot's blocks when the
    request concludes (``_finish``): such a tenant is not released, decodes
    as before and hands its blocks over whole."""
    server = build("plain", "paged")
    wanted = {"prompt": _prompt(10, 19), "max_new_tokens": 6}
    nxt = {"prompt": _prompt(11, 12), "max_new_tokens": 8}

    async def run():
        req = llm._Request(wanted["prompt"], wanted["max_new_tokens"])
        req.want_kv = True
        first = asyncio.ensure_future(server._submit_and_wait(req))
        await asyncio.sleep(0)
        reply = await server.generate(nxt)
        await first
        return req, reply["tokens"]

    req, got_next = asyncio.run(run())
    mark = _settle(server)
    assert not req.released and req.done and req.error is None
    assert req.tokens == _alone(server, wanted) and len(req.tokens) == 6
    assert got_next == _alone(server, nxt) and len(got_next) == 8
    k, v = req.kv
    assert k.shape[0] == v.shape[0] == -(-19 // 8)
    # every position of the prompt's whole blocks holds a written row
    assert np.isfinite(k).all() and np.isfinite(v).all()
    assert (np.abs(k[:2]).max(axis=(1, 3, 4)) > 0).all()
    chunks = [c["args"] for c in _spans("serve.chunk", mark)]
    assert sum(c["released_early"] for c in chunks) == 0
    # the old path: one chunk over the finished tenant
    assert len(chunks) == _old_loop_chunks(6) + _chunks_of(8)


def test_a_handed_over_row_takes_a_released_slot(build):
    """A decode-side ingest (K/V blocks and first token from a prefill
    replica) admitted into a slot whose tenant's last chunk is in flight:
    its blocks are injected and its overrides applied by the launch behind
    that chunk, and it emits a lone run's tokens."""
    server = build("plain", "paged")
    prompt, total = _prompt(12, 21), 11
    source = llm._Request(prompt, 1)
    source.want_kv = True
    _submit(server, source)
    first = {"prompt": _prompt(13, 10), "max_new_tokens": 12}

    async def run():
        head = asyncio.ensure_future(server.generate(first))
        await asyncio.sleep(0)      # queued: the ingest comes second
        ingest = llm._Request(prompt, total - 1)
        ingest.preseed = {"first": source.tokens[0], "k": source.kv[0],
                          "v": source.kv[1]}
        await server._submit_and_wait(ingest)
        return (await head)["tokens"], ingest

    got_first, ingest = asyncio.run(run())
    mark = _settle(server)
    assert got_first == _alone(server, first)
    whole = _alone(server, {"prompt": prompt, "max_new_tokens": total})
    assert source.tokens + ingest.tokens == whole and len(whole) == total
    assert ingest.released      # the last tenant: released to no one
    chunks = [c["args"] for c in _spans("serve.chunk", mark)]
    assert [c["released_early"] for c in chunks] == [0, 1]
    assert [c["seated"] for c in chunks] == [1, 0]


def test_preempting_a_released_slot_requeues_its_new_tenant_alone(build):
    """The slot's new tenant is evicted in the iteration that admitted it
    (as ``_grow_tables`` does under pool pressure), with the old tenant's
    last chunk still in flight: the new one starts over, the old one
    concludes once, with every token."""
    server = build("plain", "paged")
    launch, evicted = server._launch_chunk, []

    def evicting(early=frozenset()):
        for slot in early:
            if server.slot_req[slot] is not None and not evicted:
                evicted.append(server.slot_req[slot])
                server._preempt(slot)
        return launch(early)

    server._launch_chunk = evicting
    old = {"prompt": _prompt(14, 17), "max_new_tokens": 20}
    new = {"prompt": _prompt(15, 12), "max_new_tokens": 10}
    got_old, got_new = _together(server, [old, new])
    _settle(server)
    assert got_old == _alone(server, old) and len(got_old) == 20
    assert got_new == _alone(server, new) and len(got_new) == 10
    assert len(evicted) == 1
    ended = [r for r in server.concluded if len(r.prompt) in (17, 12)][:2]
    assert [len(r.prompt) for r in ended] == [17, 12]
    assert [r.preemptions for r in ended] == [0, 1]
    assert ended[1] is evicted[0]
    assert all(r.error is None and r.done for r in ended)


def test_the_speculative_rounds_are_untouched(build):
    """Synchronous rounds, no chunk in flight: nothing to release early,
    and the tokens are plain greedy decode's."""
    requests = [{"prompt": _prompt(16, 15), "max_new_tokens": 13},
                {"prompt": _prompt(17, 9), "max_new_tokens": 19},
                {"prompt": _prompt(18, 24), "max_new_tokens": 6}]
    server = build("plain", "paged", spec_k=2, draft_layers=1)
    got = _together(server, requests)
    mark = _settle(server)
    assert not any(r.released for r in server.concluded)
    assert server._in_flight_rows == [] and server._ending == []
    rounds = [c["args"] for c in _spans("serve.chunk", mark)]
    assert rounds and all(c["released_early"] == 0 for c in rounds)
    plain = build("plain", "paged")
    assert got == [_alone(plain, r) for r in requests]
