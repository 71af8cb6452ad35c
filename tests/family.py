"""What the test file of a served family shares with the others (a helper
module: pytest collects nothing here; ``tests/README.md`` says how a
family's file is written).

A family file keeps its toy config, its reference, its tolerances and the
tests only it has.  From here it takes the scaffolding -- the preset
installer, the two serve programs built once a config a process, a padded
prefill group and a decode chunk around them, ``generate``, and ``engines``,
which keeps an ``LLMServer`` by its arguments for the whole module -- and
the cases every family owes, as plain functions it calls with its own nouns.

ONE GEOMETRY A FILE.  Every shape a test does not examine is the file's:
4 slots, prefill groups of 2 and 4 rows, buckets of 16 and 32, decode chunks
of 4 (``ENGINE``; a file names its ``max_len`` and whatever else it must
differ in when it makes its ``engines``).  A program is compiled for a
shape, so a test that picks its own pays a compile for nothing.
"""

import asyncio
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama, llama_serve
from ray_tpu.models.llama import LlamaConfig

MARGIN = 0.25       # kinds/serve_llm.py's LOGIT_MARGIN
SLOTS = 4
ENGINE = dict(max_slots=SLOTS, prefill_buckets=(16, 32), decode_chunk=4,
              prefill_groups=(2, 4), warmup=False)

# The planes that rest on a cache of K and V rows by position: blocks,
# shared prefixes, a rejected draft's rewind, a K/V hand-off, K/V
# quantization.  (word the refusal names, the arguments that ask for it)
PLANES = [
    ("paged", dict(paged=True)),
    ("prefix sharing", dict(paged=True, block_size=8, num_blocks=64)),
    ("speculative", dict(paged=True, spec_k=2)),
    ("disaggregat", dict(paged=True, role="prefill")),
    ("kv_quant", dict(paged=True, kv_quant="int8")),
]


# ---------------------------------------------------------------- presets
def presets(factories):
    """A module-scoped autouse fixture that installs ``{name: factory}`` on
    ``LlamaConfig`` as presets (``LLMServer`` takes a preset's NAME, as the
    benchmark gives it) and takes them off when the module ends.  Assign
    it to a name of the family file."""

    @pytest.fixture(scope="module", autouse=True)
    def installed():
        with pytest.MonkeyPatch.context() as patch:
            for name, factory in factories.items():
                patch.setattr(
                    LlamaConfig, name,
                    classmethod(lambda cls, _make=factory, **kw: _make(**kw)),
                    raising=False)
            yield

    return installed


# ---------------------------------------------------------------- weights
@functools.lru_cache(maxsize=None)
def _init(cfg, dtype):
    return jax.jit(lambda key: llama.init_params(key, cfg, dtype))


def init_params(key, cfg, dtype=jnp.float32):
    """``llama.init_params`` as ONE program a config: op by op the
    initialiser is a hundred small compiles, most of a ``model`` fixture's
    seconds."""
    return _init(cfg, dtype)(key)


# --------------------------------------------------------------- programs
@functools.lru_cache(maxsize=None)
def _programs(cfg, flash_from):
    return llama_serve.build_prefill(cfg), llama_serve.build_decode_k(cfg)


def programs(cfg):
    """(prefill, decode_k) of a config: built once a process, so compiled
    once a shape for the whole file (``build_*`` return a new ``jax.jit``
    at every call).  What the prefill was traced under
    (``llama.FLASH_PREFILL_FROM``, which tests move) is part of the key."""
    return _programs(cfg, llama.FLASH_PREFILL_FROM)


forget_programs = _programs.cache_clear     # for a test that patches a trace


def prefill(cfg, params, cache, prompts, slots, bucket=32):
    """One padded group: the prompts, right-padded to the bucket, and one
    padding row (length 0, slot -1) behind them.  (cache, each prompt's
    first token, the program's load report)"""
    rows = len(prompts) + 1
    toks = np.zeros((rows, bucket), np.int32)
    for g, prompt in enumerate(prompts):
        toks[g, :len(prompt)] = prompt
    lengths = [len(p) for p in prompts] + [0]
    cache, first, load = programs(cfg)[0](
        params, cache, jnp.asarray(toks), jnp.asarray(lengths, jnp.int32),
        jnp.asarray(list(slots) + [-1], jnp.int32))
    return cache, np.asarray(first)[:len(prompts)], load


def decode(cfg, params, cache, tok, lens, who, k=4, s_active=None):
    """One chunk of ``k`` steps in which the slots ``who`` are active; the
    attended prefix is the cache's whole length unless ``s_active`` says."""
    n = tok.shape[0]
    active = jnp.zeros(n, bool).at[jnp.asarray(who, jnp.int32)].set(True)
    zeros, no = jnp.zeros(n, jnp.int32), jnp.zeros(n, bool)
    cache, out, tok, lens, load = programs(cfg)[1](
        params, cache, tok, lens, zeros, zeros, no, active, k=k,
        s_active=s_active or cfg.max_seq_len)
    return cache, np.asarray(out), tok, lens, load


def seat(first, lengths, slots, n=SLOTS):
    """The decode step's (tok, lens) with each prompt's first token and
    length in its slot."""
    at = jnp.asarray(slots)
    return (jnp.zeros(n, jnp.int32).at[at].set(jnp.asarray(first)),
            jnp.zeros(n, jnp.int32).at[at].set(jnp.asarray(lengths)))


def serve_one(cfg, params, prompt, new_tokens, cache=None, slot=2,
              bucket=None, slots=SLOTS):
    """One request alone through the two programs (a group of one row,
    then chunks of 4): its tokens and the cache it leaves.  The bucket is
    the cache's whole length whatever the prompt's: a sweep over prompt
    lengths is a sweep over DATA, one compiled program.  A case that is
    ABOUT the bucket (a prompt that fills it exactly) names its own."""
    prefill_program = programs(cfg)[0]
    if cache is None:
        cache = llama_serve.init_cache(cfg, slots, cfg.max_seq_len)
    n = len(prompt)
    bucket = bucket or cfg.max_seq_len
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :n] = prompt
    cache, first, _ = prefill_program(
        params, cache, jnp.asarray(toks), jnp.asarray([n], jnp.int32),
        jnp.asarray([slot], jnp.int32))
    emitted = [int(first[0])]
    tok, lens = seat(first[:1], (n,), (slot,), slots)
    while len(emitted) < new_tokens:
        cache, out, tok, lens, _ = decode(cfg, params, cache, tok, lens,
                                          (slot,))
        emitted += [int(t) for t in out[:, slot]]
    return emitted[:new_tokens], cache


# ---------------------------------------------------------------- engines
def generate(server, requests):
    """The replies to ``requests``, all in flight at once."""
    async def run():
        return await asyncio.gather(*[server.generate(r)
                                      for r in requests])

    return asyncio.run(run())


def settle(server):
    """One more request through ``server``.  A chunk's span and counters
    are written just AFTER its replies are given; when a later request has
    been answered, those of every chunk before it are there, without
    shutting the server down."""
    generate(server, [{"prompt": [1], "max_new_tokens": 1}])


def engines(preset=None, **defaults):
    """A module-scoped fixture ``engines(**args) -> LLMServer``: a real
    server on the file's geometry (``ENGINE`` under ``defaults`` under the
    call's own arguments), KEPT BY ITS ARGUMENTS -- two tests of a file
    that ask for the same arguments get the same server, compiled once --
    and shut down when the module ends.  ``fresh=True`` builds one that no
    other test sees (it is shut down with the others): for a test that
    fills every slot, shuts down or breaks its server; say in the test
    why.  Weights are told apart by identity, so a file hands every test
    the same ``params`` object.  Assign it to a name of the family file."""
    if preset is not None:
        defaults["model_preset"] = preset

    @pytest.fixture(scope="module")
    def kept():
        from ray_tpu.serve import llm

        servers, by_arguments = [], {}

        def build(fresh=False, **kw):
            args = {**ENGINE, **defaults, **kw}
            key = tuple(sorted(
                (name, id(value) if name.endswith("params") else value)
                for name, value in args.items()))
            if fresh or key not in by_arguments:
                servers.append(llm.LLMServer(**args))
                if fresh:
                    return servers[-1]
                by_arguments[key] = servers[-1]
            return by_arguments[key]

        yield build
        for server in servers:
            server.shutdown()

    return kept


def span_args(events, name):
    """The attributes of every complete span called ``name``."""
    return [e["args"] for e in events
            if e.get("ph") == "X" and e["name"] == name]


# ------------------------------------------------ the cases a family owes
def reads_as(gap, variant, tol, margin=MARGIN):
    """A broken variant's largest gap is over the benchmark's margin; the
    intact program's, run the same way, is within the tolerance."""
    if variant == "intact":
        assert gap <= tol, gap
    else:
        assert gap > margin, gap


def refuses_plane(preset, plane, args, match, words=(), absent=()):
    """An engine of ``preset`` on a plane that cannot hold its cache
    refuses at construction: the refusal matches ``match``, names the
    plane that was asked for, says ``words`` and none of ``absent``."""
    from ray_tpu.serve import llm

    with pytest.raises(ValueError, match=match) as refusal:
        llm.LLMServer(model_preset=preset, warmup=False, **args)
    said = str(refusal.value)
    assert plane in said
    for word in words:
        assert word in said
    for word in absent:
        assert word not in said


def reused_slot_inherits_nothing(serve, gap, tol, vocab=256):
    """A long request, then a short one in the same slot: the short one's
    tokens are those it gets in a fresh cache, though the slot's pools
    still hold the first one's rows past its length.  ``serve(prompt,
    new_tokens, cache=None) -> (tokens, cache)``; ``gap(prompt, tokens)``."""
    rng = np.random.default_rng(5)
    long, short = (rng.integers(0, vocab, n).astype(np.int32)
                   for n in (30, 4))
    _, cache = serve(long, 20)
    reused, _ = serve(short, 14, cache=cache)
    fresh, _ = serve(short, 14)
    assert reused == fresh
    assert gap(short, reused) <= tol


def serves_through_generate(server, sizes, gap, tol, vocab=256):
    """``LLMServer.generate`` on the dense plane, no option: admission,
    prefill waves of several rows, chunks, slots reused by later requests
    (``sizes``: (prompt tokens, new tokens) of more requests than slots)
    -- every reply of the length asked for and within ``tol`` of the
    reference (``gap(prompt, tokens)``: the largest over its positions)."""
    rng = np.random.default_rng(2)
    requests = [{"prompt": rng.integers(0, vocab, n).tolist(),
                 "max_new_tokens": m} for n, m in sizes]
    for request, reply in zip(requests, generate(server, requests)):
        assert len(reply["tokens"]) == request["max_new_tokens"]
        worst = gap(request["prompt"], reply["tokens"])
        assert worst <= tol, (request, worst)
