"""The engine's answers do not depend on how a wave is cut
(``serve/llm.py`` ``cut_prefill_wave``): the same seeded requests through
one 4-row rung (every wave cut per 4 rows, as before the cut had anything
to choose from) and through the module's ladder give the same tokens on
every plane that prefills — toy sizes, on the CPU.  Also: what the
``serve.prefill_group`` spans say of the groups, and that warm-up holds
every program a wave can ask for."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family
from ray_tpu.models import llama
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.observability import device as device_plane
from ray_tpu.observability import metrics, timeline, tracing
from ray_tpu.serve import llm

BUCKETS = (16, 32, 64)
ENGINE = dict(max_slots=8, max_len=96, prefill_buckets=BUCKETS,
              decode_chunk=4, warmup=False)
VOCAB, LAYERS, EXPERTS, TOP_K = 256, 2, 8, 2
# float32, so that a prompt's numbers do not move with the rows beside it
# (in bfloat16 on the CPU a matmul's blocking follows its row count)
DENSE = dict(vocab_size=VOCAB, hidden_size=64, n_layers=LAYERS, n_heads=4,
             n_kv_heads=2, head_dim=16, intermediate_size=128,
             max_seq_len=128, rope_theta=10000.0, remat=False,
             tie_embeddings=True, dtype=jnp.float32)
# tests/test_olmoe_serve.py's configuration
EXPERT = dict(DENSE, n_kv_heads=4, intermediate_size=32, norm_eps=1e-5,
              tie_embeddings=False, moe_experts=EXPERTS, moe_top_k=TOP_K,
              moe_norm_topk=False, qk_norm=True)
PLANES = {
    "dense": (DENSE, {}),
    "paged": (DENSE, dict(paged=True, block_size=8)),
    "experts": (EXPERT, {}),
    "speculative": (DENSE, dict(paged=True, block_size=8, spec_k=2,
                                draft_layers=1)),
}


def _requests(seed, count=14):
    """Prompts over all three buckets, a third of them sharing a 24-token
    head (the paged plane's second wave finds it in the prefix cache)."""
    rng = np.random.default_rng(seed)
    head = rng.integers(1, VOCAB, 24).tolist()
    out = []
    for i in range(count):
        n = int(rng.integers(2, BUCKETS[-1] + 1))
        prompt = rng.integers(1, VOCAB, n).tolist()
        if i % 3 == 0:
            prompt = (head + prompt)[:max(n, 30)]
        out.append({"prompt": prompt,
                    "max_new_tokens": int(rng.integers(2, 9))})
    return out


def _generate(server, requests):
    return [r["tokens"] for r in family.generate(server, requests)]


def _groups():
    return [e["args"] for e in timeline.export_timeline()
            if e.get("ph") == "X" and e["name"] == "serve.prefill_group"]


@pytest.fixture
def build(monkeypatch):
    servers = []

    def make(plane, **over):
        fields, args = PLANES[plane]
        name = f"cut_toy_{plane}"
        monkeypatch.setattr(
            LlamaConfig, name,
            classmethod(lambda cls, **kw: cls(**{**fields, **kw})),
            raising=False)
        params = llama.init_params(jax.random.key(3),
                                   LlamaConfig(**fields))
        servers.append(llm.LLMServer(
            model_preset=name, params=params,
            **{**ENGINE, **args, **over}))
        return servers[-1]

    yield make
    for server in servers:
        server.shutdown()


@pytest.mark.parametrize("plane", sorted(PLANES))
def test_tokens_do_not_depend_on_the_cut(plane, build):
    assert tracing.enabled()
    first, second = _requests(11), _requests(12)
    one_rung = build(plane, prefill_groups=(4,))
    expected = [_generate(one_rung, first), _generate(one_rung, second),
                _generate(one_rung, first)]
    ladder = build(plane)
    assert ladder.prefill_groups == llm.PREFILL_GROUPS
    timeline.clear()
    # the third wave repeats the first: on the paged plane every prompt of
    # it is a prefix-cache hit, so its groups are warm ones
    got = [_generate(ladder, first), _generate(ladder, second),
           _generate(ladder, first)]
    assert got == expected
    groups = _groups()
    assert groups
    for g in groups:
        assert 0 < g["rows"] <= g["rows_padded"]
        assert g["rows_padded"] in llm.PREFILL_GROUPS
        assert g["bucket"] in BUCKETS
        assert g["token_positions"] == g["rows_padded"] * g["bucket"]
        assert 0 < g["prompt_tokens"] <= g["rows"] * g["bucket"]
        if plane == "experts":
            # padding rows and padded positions reach no expert
            assert g["expert_rows"] == g["prompt_tokens"] * TOP_K * LAYERS
    prompts = sum(len(r["prompt"]) for r in first + second + first)
    if plane in ("paged", "speculative"):
        assert ladder.kv_stats()["ray_tpu_prefix_cache_hits"].get(
            "llm", 0) > 0
        assert sum(g["prompt_tokens"] for g in groups) < prompts
    else:
        assert sum(g["prompt_tokens"] for g in groups) == prompts
    assert sum(g["rows"] for g in groups) == 3 * len(first)
    # the ladder was used: some group is not a 4-row group
    assert {g["rows_padded"] for g in groups} - {4}


@pytest.mark.parametrize("plane", ["dense", "speculative"])
def test_random_waves_after_warm_up_compile_nothing(plane, build):
    """Every program a wave asks for was compiled at warm-up: the dense
    prefill, and on the speculative engine the cold and warm paged ones
    and the draft's, which is cut by whole prompts."""
    server = build(plane, warmup=True, max_slots=6)
    device_plane.sample_once()       # installs the compile listener

    def compiles():
        return metrics.metrics_summary().get(
            "ray_tpu_xla_compiles_total", {}).get("backend_compile", 0.0)

    before = compiles()
    timeline.clear()
    for seed in (0, 1, 2, 3, 1):     # the repeat: prefix-cache hits
        requests = _requests(20 + seed, count=3 + 4 * seed)
        tokens = _generate(server, requests)
        assert [len(t) for t in tokens] == [r["max_new_tokens"]
                                            for r in requests]
    assert compiles() == before
    shapes = {(g["rows_padded"], g["bucket"]) for g in _groups()}
    assert shapes <= set(llm.prefill_shapes(llm.PREFILL_GROUPS, BUCKETS, 6))
    assert len(shapes) > 3
