"""The latent-attention configuration's part of the benchmark:
``lib/mla_flops.py`` and the configuration file's parameter counts against
hand-worked numbers; the programs the cell's engine warms compiled at the
REAL widths for a v5e that is described, not attached (they fit, the
latent pool occupies its own bytes, one Mosaic attention call a layer); a
CPU rehearsal of a toy DeepSeek-V2 through ``run.measure`` with
``deepseek_v2_decoder`` as its reference, and of ``tools/mla_check.py``;
and the eight ``mla_*`` readers on a synthetic trace made of instruction
texts of the shapes a v5e compile of the cell holds.
"""

import importlib
import json
import os
import shutil
import time
import types

import pytest

from benchmarks import run as bench_run
from benchmarks.kinds import serve_llm_even
from benchmarks.lib import (loadgen, mla_flops, mla_names, moe_flops,
                            program, program_spans, scope_names, spec,
                            swa_names, trace_reduce)
from benchmarks.tools import replay_spread
from benchmarks.tests import test_rehearsal
from benchmarks.tests.test_aot_real_widths import (  # noqa: F401
    MOSAIC, _json, _on, compiled_kernels, one_chip, topo)

os.environ.setdefault("TPU_LOG_DIR", "disabled")
CONFIG = "deepseek-v2"
CELL = "deepseek-v2.serve-long-prompt"
_READERS = ("mla_decode_attention_roofline",
            "mla_decode_attention_time_share",
            "mla_prefill_attention_roofline",
            "mla_prefill_attention_time_share", "mla_absorb_time_share",
            "mla_held_rows_share")
# what the cell joins for its step and its experts: one reader each for
# every configuration (``lib/readers.py``, ``lib/moe_names.py``)
_JOINED = ("decode_step_roofline", "moe_expert_ffn_time_share",
           "moe_expert_matmul_roofline", "moe_expert_load_imbalance",
           "moe_shared_expert_time_share")


# ------------------------------------------------- parameters and bytes
def test_parameters_operations_and_bytes_by_hand():
    c = _json("configs", CONFIG)
    # attention of a layer: q_a + its norm + q_b + kv_a + its norm + kv_b + o
    attention = (5120 * 1536 + 1536 + 1536 * 128 * 192 + 5120 * 576 + 512
                 + 512 * 128 * 256 + 16384 * 5120)
    assert mla_flops.attention_params(c) == attention == 149_227_520
    outside = attention + 10_240 + 5120 * 160 + 3 * 5120 * 3072
    assert mla_flops.expert_layer_params_outside_experts(c) == outside \
        == 197_242_880
    assert mla_flops.expert_params(c) == 3 * 5120 * 1536 == 23_592_960
    dense = attention + 10_240 + 3 * 5120 * 12_288
    assert mla_flops.dense_layer_params(c) == dense == 337_981_440
    expert_layer = outside + 40 * 23_592_960
    assert expert_layer == 1_140_961_280
    head = 2 * 25_600 * 5120 + 5120
    assert head == 262_149_120
    assert dense + 4 * expert_layer + head == mla_flops.parameters(c) \
        == c["parameters"] == 5_163_975_680
    whole_layer = outside + 160 * 23_592_960
    assert whole_layer == 3_972_116_480
    assert 59 * whole_layer + dense + 2 * 102_400 * 5120 + 5120 \
        == mla_flops.parameters(c, 60, 160, 102_400) \
        == c["parameters_published_depth"] == 235_741_434_880
    # the latent row and the absorbed attention over it: on the ridge
    assert mla_flops.latent_bytes_per_position(c) == 1152
    assert mla_flops.decode_attention_flops_per_position(c) \
        == 2 * 128 * (576 + 512) == 278_528
    assert 278_528 / 1152 == pytest.approx(241.8, abs=0.1)
    assert 128 * 320 * 2 / 1152 == pytest.approx(71.1, abs=0.1)   # plain MHA
    lengths = [1_000, 6_000]
    assert mla_flops.decode_attention_bytes(c, lengths) == 5 * 7_000 * 1152
    assert mla_flops.decode_attention_flops(c, lengths) \
        == 5 * 7_000 * 278_528
    # a bucket of 8,192, whole: 128 heads x 8192 x 8193 / 2 pairs x 2 x 320
    assert mla_flops.prefill_attention_flops(c, 8192) \
        == 2 * (8192 * 8193 / 2) * 128 * 320
    assert mla_flops.prefill_attention_flops(c, 8192, 32) * 4 \
        == mla_flops.prefill_attention_flops(c, 8192)
    # a step of two rows that touched 100 (layer, held expert) pairs
    every = dense + 4 * outside + 5120 * 25_600
    assert mla_flops.step_matmul_params(c) == every
    assert mla_flops.decode_step_bytes(c, 100, lengths) \
        == 2 * (every + 100 * 23_592_960) + 5 * 7_000 * 1152
    assert mla_flops.decode_step_flops(c, lengths, 12) \
        == 2 * every * 2 + 5 * 7_000 * 278_528 + 2 * 12 * 23_592_960


def test_the_file_is_the_catalogs_entry_cut_as_it_says():
    c = _json("configs", CONFIG)
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    entry = next(e for e in benchmark["configs"] if e["name"] == CONFIG)
    assert entry["reduced"] == [r["key"] for r in c["reduced"]] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert [(r["published"], r["here"]) for r in c["reduced"]] == [
        (60, 5), (160, 40), (102_400, 25_600)]
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (5, 40, 25_600)
    # every width as published
    assert (c["hidden_size"], c["intermediate_size"],
            c["moe_intermediate_size"], c["num_attention_heads"],
            c["q_lora_rank"], c["kv_lora_rank"], c["qk_nope_head_dim"],
            c["qk_rope_head_dim"], c["v_head_dim"], c["n_shared_experts"],
            c["num_experts_per_tok"], c["n_group"], c["topk_group"],
            c["max_position_embeddings"]) == (
        5120, 12_288, 1536, 128, 1536, 512, 128, 64, 128, 2, 6, 8, 3,
        163_840)
    assert c["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert c["share"]["chips_that_share_a_layer"] == 4
    assert (c["share"]["experts_first"], c["share"]["experts_held"]) == (0, 40)
    assert c["assumed"] and c["deployment"]
    cfg = program.llama_config(c)
    assert (cfg.n_layers, cfg.first_dense_layers, cfg.moe_experts,
            cfg.moe_held, cfg.vocab_size) == (5, 1, 160, (0, 40), 25_600)
    assert (cfg.head_dim, cfg.latent_row, cfg.o_dim, cfg.expert_width) \
        == (192, 640, 16_384, 1536)
    assert cfg.attn_scale == pytest.approx(0.11472, abs=1e-5)
    assert (cfg.moe_groups, cfg.moe_top_groups, cfg.moe_routed_scale,
            cfg.moe_norm_topk, cfg.moe_shared_size) == (8, 3, 16.0, False,
                                                        3072)


def the_cells_entries(root=spec.ROOT):
    """What THIS cell reports, on the tree at ``root`` (the rehearsal's has
    a later PR's entries appended: nothing here counts the table or says
    what another family's names are)."""
    from benchmarks.tests.test_yardstick import (benchmark_at, cell_at,
                                                 reader_at)

    benchmark = benchmark_at(root)
    mine = {m["name"]: m for m in benchmark["per_layer"]
            if m["name"] in _READERS}
    assert sorted(mine) == sorted(_READERS)
    for m in mine.values():
        assert CELL in m["workloads"]
        assert m["moves"] == "serve_output_tokens_per_s"
        assert callable(reader_at(root, m["name"]).read)
    entry = next(w for w in benchmark["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["config"] == CONFIG
    cell = cell_at(root, CELL)
    reported = {e["name"] for e, _ in cell.readers("per_layer")}
    assert set(_READERS) <= reported
    assert {"batch.decode_kv_read_share", "batch.slot_wait_p50_ms",
            "batch.prefill_expert_dispatch_time_share",
            "batch.decode_step_device_ms"} <= reported
    # the step's floor is the file's (lib/mla_flops.py: a latent row, the
    # experts HELD), and the experts' entries are every expert cell's: an
    # expert's width is ``moe_intermediate_size``, its layers all but the
    # leading dense one, its experts the 40 of the program's ``moe_held``
    assert cell.config["roofline"] == "mla_flops"
    assert (moe_flops.expert_width(cell.config),
            moe_flops.expert_layers(cell.config),
            moe_flops.experts_held(cell.config)) == (1536, 4, 40)
    assert {"batch.decode_step_roofline", "moe_expert_matmul_roofline",
            "moe_expert_ffn_time_share", "moe_routing_time_share",
            "moe_expert_load_imbalance",
            "moe_shared_expert_time_share"} <= reported
    assert not {m for m in reported if m.startswith("swa_")}
    assert {e["name"] for e, _ in cell.readers("end_to_end")} == {
        "serve_output_tokens_per_s", "setup_s"}


def test_the_cells_readers_lead_to_files():
    the_cells_entries()


def test_the_traffic_is_the_issues():
    t = _json("traffic", "serve-long-prompt")
    assert t["arrivals"] == {"process": "closed", "callers": 64,
                             "lead_in_s": 20.0, "drain_s": 30.0}
    assert t["prompt_tokens"] == {
        "dist": "lognormal", "median": 6144, "sigma": 0.45, "min": 1024,
        "max": 12288, "stratified": 16}
    assert t["output_tokens"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.5, "min": 32,
        "max": 768, "stratified": 16}
    workload = _json("workloads", CELL)
    engine = workload["engine"]
    assert workload["traffic"] == "serve-long-prompt"
    # the file's draws, its strata held over all callers (below)
    assert workload["kind"] == "serve_llm_even"
    assert engine["max_len"] == 16384 and not engine["paged"]
    assert engine["prefill_buckets"] == [4096, 8192, 12288]
    assert engine["max_slots"] % 8 == 0 and engine["max_slots"] >= 16


# -------------------------------------- every seed the same amount of work
def _created(seed, n):
    """The first ``n`` requests the cell's closed loop creates."""
    traffic = _json("traffic", "serve-long-prompt")
    generator = serve_llm_even.EvenLoadGenerator(traffic, seed, 25_600, None)
    first = list(generator._first)
    return traffic, first + [generator.source.next()
                             for _ in range(n - len(first))]


def test_every_sixteen_requests_created_hold_the_files_strata():
    traffic, requests = _created(2147486801, 96)
    strata = {key: sorted(loadgen.quantile(traffic[key], (i + 0.5) / 16)
                          for i in range(16))
              for key in ("prompt_tokens", "output_tokens")}
    for at in range(0, 96, 16):
        block = requests[at:at + 16]
        assert sorted(len(r["prompt"]) for r in block) \
            == strata["prompt_tokens"]
        if at >= 64:    # the 64 callers' first requests start part-way
            assert sorted(r["max_new_tokens"] for r in block) \
                == strata["output_tokens"]
    assert all(1 <= t < 25_600 for r in requests for t in r["prompt"])
    # the part-way starts: their range's 64 evenly spaced values, once each
    scales = serve_llm_even.start_scales(2147486801, 64)
    assert sorted(scales) == pytest.approx(
        [0.05 + 0.95 * (i + 0.5) / 64 for i in range(64)])
    assert scales != sorted(scales)


def test_two_seeds_offer_the_same_work_and_one_seed_the_same_requests():
    _, a = _created(2147486801, 80)
    _, b = _created(2147486802, 80)
    _, again = _created(2147486801, 80)
    assert a == again and a != b
    for lengths in (lambda r: len(r["prompt"]),
                    lambda r: r["max_new_tokens"]):
        assert sorted(map(lengths, a[64:])) == sorted(map(lengths, b[64:]))
    assert sorted(len(r["prompt"]) for r in a) \
        == sorted(len(r["prompt"]) for r in b)
    # the per-caller generator's first 80 do not: that is the refusal
    traffic = _json("traffic", "serve-long-prompt")

    def per_caller(seed):
        return sorted(
            len(loadgen.RequestSource(traffic, seed, c, 25_600).next()
                ["prompt"]) for c in range(64))
    assert per_caller(2147486801) != per_caller(2147486802)


def test_the_even_loop_runs_sends_in_order_and_refuses_an_open_loop():
    traffic = dict(test_rehearsal.TRAFFIC["tiny-closed"])
    sent = []

    class Reply:
        def __init__(self, request):
            self.request = request

        def result(self, timeout):
            time.sleep(0.01)
            return {"tokens": [1] * self.request["max_new_tokens"],
                    "ttft_ms": 1.0}

    def send(request):
        sent.append(request)
        return Reply(request)

    generator = serve_llm_even.EvenLoadGenerator(traffic, 7, 50, send)
    log = generator.run(0.3)
    assert generator.join(5.0) == 0
    measured = log.measured()
    assert measured and all(r.ok and r.got_tokens == r.asked_tokens
                            for r in measured)
    assert {r.caller for r in log.records} == set(range(8))
    # nothing created is skipped: what was sent is the stream's first draws
    again = serve_llm_even.EvenLoadGenerator(traffic, 7, 50, send)
    stream = again._first + [again.source.next()
                             for _ in range(len(sent) - 8)]
    assert sorted(map(str, sent)) == sorted(map(str, stream))
    assert log.tokens_in_window() > 0
    with pytest.raises(ValueError, match="closed loop"):
        serve_llm_even.EvenLoadGenerator(
            test_rehearsal.TRAFFIC["tiny-open"], 7, 50, send)
    assert loadgen.LoadGenerator is not serve_llm_even.EvenLoadGenerator


def test_the_replay_says_the_shared_strata_halve_the_spread_and_more():
    traffic = _json("traffic", "serve-long-prompt")
    seconds = {4096: 0.15, 8192: 0.37, 12288: 0.66}
    read = {kind: replay_spread.study(traffic, kind, 32, seconds, 0.272,
                                      40.0, seeds=36, admit=0.05)
            for kind in ("serve_llm", "serve_llm_even")}
    assert read["serve_llm_even"]["deviation"] < 0.03 \
        < 0.045 < read["serve_llm"]["deviation"]
    assert read["serve_llm_even"]["sets_admitted_share"] == 1.0
    for r in read.values():
        assert 380 < r["median_tokens_per_s"] < 460
    assert replay_spread.set_spread([100, 101, 102, 103, 104, 150]) \
        < replay_spread.spread([100, 101, 102, 103, 104, 150])


# ------------------------------------------------ the programs, real widths
def _programs(one_chip):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama, llama_serve

    engine = _json("workloads", CELL)["engine"]
    slots, max_len = engine["max_slots"], engine["max_len"]
    cfg = program.llama_config(_json("configs", CONFIG), max_seq_len=max_len)
    params = _on(one_chip, jax.eval_shape(
        lambda k: llama.init_params(k, cfg, cfg.dtype), jax.random.key(0)))
    cache = _on(one_chip, jax.eval_shape(
        lambda: llama_serve.init_cache(cfg, slots, max_len)))

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    ints, bools = arr(jnp.int32, slots), arr(jnp.bool_, slots)
    decode = llama_serve.build_decode_k(cfg).lower(
        params, cache, ints, ints, ints, ints, bools, bools, k=16,
        s_active=max_len)
    bucket = engine["prefill_buckets"][-1]
    prefill = llama_serve.build_prefill(cfg).lower(
        params, cache, arr(jnp.int32, 1, bucket), arr(jnp.int32, 1),
        arr(jnp.int32, 1))
    return cfg, slots, max_len, decode, prefill


def test_the_engines_programs_fit_and_the_pool_occupies_its_own_bytes(
        one_chip):
    """At the cell's slots x 16,384: weights 10.33 GB, a slot's latent rows
    16,384 x 5 x 640 x 2 = 104.9 MB (the compiler's own account of the
    arguments: nothing padded beyond the leaf's own 640), the widest
    prefill's scratch inside the chip, the cache updated in place; the
    decode step holds one Mosaic attention call for the dense layer and
    one for the scanned ones, the prefill one flash call each."""
    from ray_tpu.models import llama_serve

    cfg, slots, max_len, decode, prefill = _programs(one_chip)
    pools = llama_serve.cache_pools(cfg, slots, max_len)
    cache_bytes = slots * 16_384 * 5 * 640 * 2
    assert pools == {"latent": (cache_bytes, "bfloat16")}
    weights = 2 * _json("configs", CONFIG)["parameters"]
    for lowered, scratch, attention in ((decode, 0.6e9,
                                         "mla_decode_attention"),
                                        (prefill, 2.5e9,
                                         "flash_prefill_attention")):
        compiled = lowered.compile()   # RESOURCE_EXHAUSTED if it does not fit
        memory = compiled.memory_analysis()
        held = memory.argument_size_in_bytes
        assert weights + cache_bytes <= held < weights + cache_bytes + 1e6
        assert memory.alias_size_in_bytes >= cache_bytes   # updated in place
        assert memory.temp_size_in_bytes < scratch
        calls = [line for line in compiled.as_text().splitlines()
                 if MOSAIC in line and f"%{attention}" in line.split("=")[0]]
        assert len(calls) == 2         # the prologue's and the scan's


# ------------------------------------------------- a rehearsal on the CPU
_YARN = {"type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
         "mscale": 0.707, "mscale_all_dim": 0.707,
         "original_max_position_embeddings": 16}
TINY_LATENT = {
    "name": "tiny-latent", "source": "none (test, latent attention)",
    "reference": "deepseek_v2_decoder", "roofline": "mla_flops",
    "vocab_size": 256,
    "hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 24, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_routed_experts": 8,
    "n_shared_experts": 2, "num_experts_per_tok": 3, "n_group": 4,
    "topk_group": 2, "routed_scaling_factor": 16, "norm_topk_prob": False,
    "scoring_func": "softmax", "topk_method": "group_limited_greedy",
    "first_k_dense_replace": 1, "moe_layer_freq": 1, "q_lora_rank": 48,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "rope_scaling": _YARN,
    "max_position_embeddings": 256, "rope_theta": 10000,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "share": {"n_routed_experts_published": 16, "experts_first": 0,
              "experts_held": 8},
    "reduced": [], "assumed": ["test"],
    # float32 throughout: a request's gap against the reference is then
    # the order of float32 sums whichever requests a window completes
    "dtype": {"serve": "float32"},
    "program_fields": {
        "kv_lora_rank": 32, "q_lora_rank": 48, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_scaling": _YARN,
        "first_dense_layers": 1, "moe_experts": 16, "moe_held": [0, 8],
        "moe_top_k": 3, "moe_norm_topk": False, "moe_intermediate_size": 32,
        "moe_shared_size": 64, "moe_groups": 4, "moe_top_groups": 2,
        "moe_routed_scale": 16.0, "moe_dispatch_chunk": 16,
        "dtype": "float32"},
}
TINY_CELL = "tiny-latent.tiny-closed"


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark with a toy latent configuration dropped in
    and its cell appended wherever the real one is."""
    root = tmp_path_factory.mktemp("bench_deepseek_v2")
    bench = str(root / "benchmarks")
    shutil.copytree(spec.BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "out", "__pycache__", "tests"))

    def drop(rel, payload):
        path = os.path.join(bench, rel)
        assert not os.path.exists(path), f"{rel} would be an edit"
        with open(path, "w") as f:
            json.dump(payload, f)

    drop("configs/tiny-latent.json", TINY_LATENT)
    drop("traffic/tiny-closed.json", test_rehearsal.TRAFFIC["tiny-closed"])
    drop(f"workloads/{TINY_CELL}.json",
         dict(test_rehearsal.SERVE, kind="serve_llm_even", name=TINY_CELL,
              config="tiny-latent", traffic="tiny-closed", why="test"))
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    benchmark["configs"].append(
        {"name": "tiny-latent", "source": TINY_LATENT["source"],
         "reduced": [], "file": "benchmarks/configs/tiny-latent.json",
         "why": "test"})
    benchmark["workloads"].append(
        {"name": TINY_CELL, "config": "tiny-latent",
         "traffic": "tiny-closed", "chips": 1, "why": "test"})
    for group in ("end_to_end", "per_layer"):
        for metric in benchmark[group]:
            if CELL in metric.get("workloads", []):
                metric["workloads"].append(TINY_CELL)
    path = str(root / "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(benchmark, f)
    return bench, path


cpu_peaks = test_rehearsal.cpu_peaks


def test_a_toy_latent_model_runs_end_to_end_on_the_cpu(tree, cpu_peaks,
                                                       monkeypatch):
    """One traced run of the toy cell through ``run.measure``, its
    prefills through the flash forward at a v head of its own width
    (interpreted) and a dispatch chunk of 16: ``correct`` against
    ``deepseek_v2_decoder``, nothing failed, the metrics the cell joins
    and the program's own count of held rows are there; what only a
    device trace knows is left out on a CPU, not invented."""
    from benchmarks.tests.test_yardstick import names_lead_to_files
    from ray_tpu.models import llama

    monkeypatch.setattr(llama, "FLASH_PREFILL_FROM", 16)
    monkeypatch.setattr(llama, "LATENT_HEAD_GROUP", 2)
    # this file's compiles are for a described chip (``compiled_kernels``);
    # this run is on the CPU, its kernels interpreted
    monkeypatch.setattr(
        importlib.import_module("ray_tpu.ops.flash_attention"),
        "_use_interpret", lambda: True)
    bench, benchmark_json = tree
    names_lead_to_files(os.path.dirname(benchmark_json))
    result, obs = bench_run.measure(
        ["--workload", TINY_CELL, "--seed", "2147486037", "--seconds", "3",
         "--trace", "1"],
        allow_platforms=("cpu",), bench_dir=bench,
        benchmark_json=benchmark_json, t_process=time.perf_counter())
    assert result["correct"] is True, obs["checks"]
    assert result["failed"] == 0 < result["attempted"]
    assert obs["cell"].reference.__name__.endswith("deepseek_v2_decoder")
    assert len(obs["logit_gaps"]) == 4 and obs["logit_gap_max"] < 1e-2
    metrics = result["metrics"]
    assert {"batch.slot_wait_p50_ms", "batch.token_burst_gap_p50_ms",
            "mla_held_rows_share", "moe_expert_load_imbalance",
            "batch.decode_slot_utilization", "batch.decode_kv_read_share",
            "batch.prefill_padding_share", "window_compiles"} <= set(metrics)
    assert not {"batch.decode_step_roofline", "mla_decode_attention_roofline",
                "mla_prefill_attention_roofline",
                "mla_decode_attention_time_share"} & set(metrics)
    assert 10 < metrics["mla_held_rows_share"]["value"] < 90
    spans = program_spans.collect(obs)
    chunk = next(c for c in spans.chunks if c.get("latent_bytes"))
    assert chunk["latent_bytes"] == chunk["kv_positions_attended"] \
        * 3 * 128 * 4
    assert chunk["expert_rows"] + chunk["expert_rows_elsewhere"] \
        == chunk["active"] * chunk["k"] * 2 * 3


def test_the_published_width_check_rehearsed_at_toy_size(tree, capsys):
    """``tools/mla_check.py`` (what is run on the chip at the published
    widths) end to end on the toy: the intact reply within rounding of the
    reference, every broken program off it by more than the benchmark's
    margin."""
    from benchmarks.tools import mla_check

    bench, _ = tree
    assert mla_check.main([
        "--config", "tiny-latent", "--seed", "2147486033", "--bench-dir",
        bench, "--prompt", "40", "--new-tokens", "24", "--bucket", "64",
        "--max-len", "128"]) == 0
    done = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(done) == {"event", *mla_check.VARIANTS}
    assert done["intact"]["gap_max"] < 1e-3 and done["intact"]["passes"]
    assert done["intact"]["counts"]["over_0.05"] == 0
    loud = 0
    for variant in mla_check.VARIANTS[1:]:
        assert done[variant]["gap_max"] > 0.25, variant
        # 24 positions decide little by count (12 may be swaps): what moves
        # every position is judged off the margin here too
        loud += not done[variant]["passes"]
    assert loud >= 4
    for variant in ("no_k_rope", "other_head_w_uk", "no_shared_expert"):
        assert done[variant]["judged_max"] > 0.25, variant


# --------------------------------------- the readers on a synthetic trace
# One expert layer of one decode step and one layer of one prefill of
# 8,192 positions, in instruction texts of the shapes the cell's programs
# compile to for a v5e (AOT, PR 37; cut to what the readers look at),
# durations in microseconds.
_ABSORB = ("%fusion.41 = bf16[32,128,512]{2,1,0} fusion(bf16[32,128,128] "
           "%q_nope, bf16[4,128,128,512] %wk_b)")
_SHARED = ("%fusion.52 = bf16[32,3072]{1,0} fusion(bf16[32,5120] %h, "
           "bf16[4,5120,3072] %ws_gate)")
_DECODE_LAYER = [
    ("%fusion.900 = bf16[32,1536]{1,0} fusion(bf16[32,1,5120] %x, "
     "bf16[4,5120,1536] %wq_a)", 200.0),
    (_ABSORB, 100.0),
    ("%mla_decode_attention.3 = bf16[32,128,512]{2,1,0} custom-call(s32[1] "
     "%layer, s32[32] %n, bf16[32,128,640] %q, bf16[5,32,16384,640] "
     "%latent), custom_call_target=\"tpu_custom_call\"", 1200.0),
    (_SHARED, 150.0),
    ("%ragged-dot-none.2 = f32[192,1536]{1,0} custom-call(bf16[192,5120] "
     "%rows, bf16[160,5120,1536] %w_gate), "
     "custom_call_target=\"tpu_custom_call\"", 1350.0),
]
_PREFILL_LAYER = [
    ("%fusion.77 = bf16[1,8192,1536]{2,1,0} fusion(bf16[1,8192,5120] %x, "
     "bf16[4,5120,1536] %wq_a)", 2000.0),
    *[("%flash_prefill_attention.3 = bf16[1,32,8192,128]{3,2,1,0} "
       "custom-call(bf16[1,32,8192,192] %q, bf16[1,32,8192,192] %k, "
       "bf16[1,32,8192,128] %v), custom_call_target=\"tpu_custom_call\"",
       5000.0)] * 4,
    ("%ragged-dot-none.5 = bf16[24576,5120]{1,0} custom-call("
     "bf16[24576,1536] %act, bf16[160,1536,5120] %w_down), "
     "custom_call_target=\"tpu_custom_call\"", 8000.0),
]


def _synthetic_obs(steps=16, runs=2):
    ops, modules, t = [], [], 0.0
    for run in range(runs):
        start, body = t, []
        for _ in range(steps * 5):
            for name, us in _DECODE_LAYER:
                body.append((t, t + us * 1e-6, name))
                t += us * 1e-6
        ops.append((start, t, "%while.7 = (s32[]) while((s32[]) %t), "
                    "body=%step"))
        ops.extend(body)
        modules.append((start, t, f"jit_decode_k({run})"))
        t += 1e-4
    start = t
    for _ in range(5):
        for name, us in _PREFILL_LAYER:
            ops.append((t, t + us * 1e-6, name))
            t += us * 1e-6
    modules.append((start, t, "jit_prefill(9)"))
    trace = trace_reduce.Trace(
        [trace_reduce.DeviceTrace(0, ops, modules)], [], 0.0, t)
    cell = types.SimpleNamespace(config=_json("configs", CONFIG),
                                 workload=_json("workloads", CELL),
                                 bench_dir=spec.BENCH_DIR, name=CELL)
    # 30 sequences in flight, each 6,000 positions at the span's middle
    records = [types.SimpleNamespace(
        ok=True, got_tokens=201, sent=0.0, ttft_ms=0.0, done=2.0,
        prompt_tokens=5899) for _ in range(30)]
    chunk = {"k": 16, "active": 30, "expert_rows": 16 * 4 * 45,
             "expert_rows_elsewhere": 16 * 4 * 135,
             "experts_touched": 16 * 4 * 27, "expert_rows_max": 16 * 4,
             "kv_positions_attended": 30 * 6000,
             "latent_bytes": 30 * 6000 * 5 * 1280}
    group = {"bucket": 8192, "rows": 1, "prompt_tokens": 7000}
    from ray_tpu.observability.device import instruction_key

    # the program's own map: which instruction is under which scope
    scopes = {"jit_decode_k": {
        instruction_key(_ABSORB): ("mla_absorb", "forward"),
        instruction_key(_SHARED): ("shared_expert", "forward"),
        instruction_key(_DECODE_LAYER[0][0]): ("qkv_proj", "forward"),
        instruction_key(_DECODE_LAYER[-1][0]): ("expert_ffn", "forward")}}
    return {
        "trace": trace, "cell": cell, "decode_chunk": 16,
        "trace_span": [0.9, 1.1], "scope_map": scopes,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        "log": types.SimpleNamespace(records=records),
        "program_spans": program_spans.ProgramSpans(
            [], [chunk, chunk], [group]),
    }


def test_the_eight_readers_on_a_synthetic_trace(monkeypatch):
    monkeypatch.setattr(scope_names, "_write_report", lambda obs: None)
    obs = _synthetic_obs()
    reads = {name: spec.load_module("metrics", name).read(obs)
             for name in _READERS + _JOINED}
    layer_us = sum(us for _n, us in _DECODE_LAYER)               # 3,000
    assert reads["mla_decode_attention_time_share"] == pytest.approx(
        100 * 1200 / layer_us)
    assert reads["mla_absorb_time_share"] == pytest.approx(
        100 * 100 / layer_us)
    assert reads["moe_shared_expert_time_share"] == pytest.approx(
        100 * 150 / layer_us)
    prefill_us = sum(us for _n, us in _PREFILL_LAYER)            # 30,000
    assert reads["mla_prefill_attention_time_share"] == pytest.approx(
        100 * 20_000 / prefill_us)
    assert reads["mla_held_rows_share"] == pytest.approx(25.0)
    c = obs["cell"].config
    lengths = [6000.0] * 30
    assert swa_names.lengths_in_flight(obs, 1.0) == pytest.approx(lengths)
    step_s = 5 * layer_us * 1e-6
    # the step: weight-bound; its attention: the FLOP floor is the larger
    assert mla_names.chunk_medians(obs) == (4 * 45, 4 * 27)
    floor = mla_flops.decode_step_bytes(c, 4 * 27, lengths) / 819e9
    assert floor > mla_flops.decode_step_flops(c, lengths, 4 * 45) / 197e12
    assert reads["decode_step_roofline"] == pytest.approx(
        100 * floor / step_s, rel=1e-3)
    # the held experts as every expert cell's entries read them: the
    # grouped matmuls of 1,536-wide experts against 4 x 27 touched pairs
    # and 4 x 45 rows; the busiest of 4 x 40 held (layer, expert) pairs
    assert reads["moe_expert_ffn_time_share"] == pytest.approx(
        100 * 1350 / layer_us)
    experts = (4 * 27 * 3 * 5120 * 1536
               + 4 * 45 * (3 * 5120 + 3 * 1536)) * 2 / 819e9
    assert reads["moe_expert_matmul_roofline"] == pytest.approx(
        100 * experts / (5 * 1350e-6), rel=1e-3)
    assert reads["moe_expert_load_imbalance"] == pytest.approx(
        16 * 4 / (16 * 4 * 45 / (4 * 40)))
    attention = mla_flops.decode_attention_flops(c, lengths) / 197e12
    assert attention > mla_flops.decode_attention_bytes(c, lengths) / 819e9
    assert obs["mla_decode_attention_bound"] == "flops"
    assert reads["mla_decode_attention_roofline"] == pytest.approx(
        100 * attention / (5 * 1200e-6), rel=1e-3)
    # 20 traced calls, each 32 heads' share of a layer over 7,000 tokens
    whole = 5 * mla_flops.prefill_attention_flops(c, 7000) / 197e12
    assert reads["mla_prefill_attention_roofline"] == pytest.approx(
        100 * whole / (20 * 5000e-6), rel=1e-3)
    for name in _READERS + _JOINED[:3]:
        assert 0 < reads[name] < 100, name


def test_a_program_without_a_latent_leaf_reads_nothing(monkeypatch):
    """Another cell's observations, the parent commit's (whose spans carry
    no rows elsewhere, whose map knows no such scope and whose trace holds
    no such kernel) and an untraced run: every reader returns None, none
    raises."""
    monkeypatch.setattr(scope_names, "_write_report", lambda obs: None)
    obs = _synthetic_obs()
    other = dict(obs, cell=types.SimpleNamespace(
        config=_json("configs", "smallthinker-21b-a3b"),
        workload=obs["cell"].workload))
    parent = _synthetic_obs()
    parent["trace"] = trace_reduce.Trace(
        [trace_reduce.DeviceTrace(
            0, [(s, e, n.replace("mla_decode_attention", "fusion")
                 .replace("flash_prefill_attention", "fusion"))
                for s, e, n in obs["trace"].devices[0].ops],
            obs["trace"].devices[0].modules)], [], 0.0, 1.0)
    parent["scope_map"] = {"jit_decode_k": {
        key: ("qkv_proj", "forward")
        for key in obs["scope_map"]["jit_decode_k"]}}
    parent["program_spans"] = program_spans.ProgramSpans(
        [], [{"k": 16, "tokens_kept": 1, "token_steps": 2,
              "expert_rows": 5, "experts_touched": 3}], [])
    no_trace = dict(obs, trace=None)
    for name in _READERS:
        read = spec.load_module("metrics", name).read
        assert read(dict(other)) is None, name
        if name != "mla_held_rows_share":           # reads spans alone
            assert read(dict(no_trace)) is None, name
        assert read(dict(parent)) is None, name
    step = spec.load_module("metrics", "decode_step_roofline").read
    assert step(dict(parent)) is None and step(dict(no_trace)) is None
