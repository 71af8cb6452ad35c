"""The learned-sparse-attention configuration's part of the benchmark:
``lib/dsa_flops.py`` and the configuration file's parameter counts against
hand-worked numbers and the program's own tree, to the unit, at the 6
layers here and at the published 48; the file against the catalog's entry;
the programs the cell's engine warms compiled at the REAL widths (16 slots
x 16,384) for a v5e that is described, not attached, the arguments
reckoned to the byte; a CPU rehearsal of a toy model with an indexer
through ``run.measure`` with ``keye_sparse_decoder`` as its reference, and
of ``tools/dsa_check.py``; and the ``dsa_*`` readers on a synthetic trace.
"""

import importlib
import json
import os
import shutil
import time
import types

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import (dsa_flops, moe_flops, program, program_spans,
                            scope_names, spec, swa_names, trace_reduce)
from benchmarks.tests import test_rehearsal
from benchmarks.tests.test_aot_real_widths import (  # noqa: F401
    _json, _on, compiled_kernels, kernels_by_name_and_scope, one_chip, topo)

os.environ.setdefault("TPU_LOG_DIR", "disabled")
CONFIG = "keye-vl-2.0-30b-a3b"
CELL = "keye-vl-2.0-30b-a3b.serve-long-prompt"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
_READERS = ("dsa_indexer_time_share", "dsa_select_time_share",
            "dsa_sparse_attention_time_share",
            "dsa_prefill_selection_time_share", "dsa_selected_share",
            "dsa_sparse_prefill_attention_roofline")
# what the cell joins for its step and its experts: one reader each for
# every configuration (``lib/readers.py``, ``lib/moe_names.py``)
_JOINED = ("decode_step_roofline", "moe_expert_ffn_time_share",
           "moe_routing_time_share", "moe_expert_matmul_roofline",
           "moe_expert_load_imbalance")
LAYER = 625_381_632
ENDS = 2 * 151_936 * 2048 + 2048


# ------------------------------------------------- parameters and bytes
@pytest.mark.parametrize("layers", [6, 48])
def test_parameters_by_hand_and_by_the_programs_tree(layers):
    import jax

    from ray_tpu.models import llama

    c = _json("configs", CONFIG)
    assert dsa_flops.attention_matmul_params(c) \
        == 2 * 2048 * 4096 + 2 * 2048 * 512 == 18_874_368
    assert dsa_flops.indexer_params(c) \
        == 2048 * (16 * 64) + 2048 * 64 + 2048 * 16 == 2_260_992
    assert dsa_flops.router_params(c) == 262_144
    assert dsa_flops.expert_params(c) == 3 * 2048 * 768 == 4_718_592
    assert dsa_flops.layer_params(c) == 18_874_368 + 256 + 2_260_992 \
        + 4_096 + 262_144 + 128 * 4_718_592 == LAYER
    want = layers * LAYER + ENDS
    assert dsa_flops.parameters(c, layers) == want
    assert c["parameters"] == 6 * LAYER + ENDS == 4_374_621_696
    assert c["parameters_published_depth"] == 48 * LAYER + ENDS \
        == 30_640_650_240
    cfg = program.llama_config(c, n_layers=layers)
    tree = jax.eval_shape(lambda k: llama.init_params(k, cfg, cfg.dtype),
                          jax.random.key(0))
    assert llama.param_count(tree) == want
    assert {str(x.dtype) for x in jax.tree.leaves(tree)} == {"bfloat16"}


def test_operations_and_bytes_by_hand():
    c = _json("configs", CONFIG)
    assert dsa_flops.kv_bytes_per_position(c) == 6 * 2 * 4 * 128 * 2 \
        == 12_288
    assert dsa_flops.index_key_bytes_per_position(c) == 6 * 64 * 2 == 768
    assert dsa_flops.slot_bytes(c, 16_384) == 16_384 * 13_056 \
        == 213_909_504
    step = 2048 * 151_936 + 6 * (18_874_368 + 2_260_992 + 262_144)
    assert dsa_flops.step_matmul_params(c) == step == 439_549_952
    # 16 rows: 8 of 1,000 keys (all attended), 8 of 9,000 (2,048 of them);
    # 600 (layer, expert) pairs touched, 16 x 8 x 6 expert rows
    lengths = [1000.0] * 8 + [9000.0] * 8
    attended = 8 * 1000 + 8 * 2048
    assert dsa_flops.decode_step_bytes(c, 600, lengths) == pytest.approx(
        2 * (step + 600 * 4_718_592) + 80_000 * 768 + attended * 12_288)
    assert dsa_flops.decode_step_flops(c, lengths, 768) == pytest.approx(
        2 * step * 16 + 6 * 80_000 * 16 * (2 * 64 + 3)
        + 6 * attended * 4 * 32 * 128 + 2 * 768 * 4_718_592)
    # a prompt of 5,000: the first 2,048 queries see all before them
    pairs = 2048 * 2049 / 2 + (5000 - 2048) * 2048
    assert dsa_flops.selected_pairs(c, 5000) == pairs
    assert dsa_flops.selected_pairs(c, 100) == 100 * 101 / 2
    assert dsa_flops.prefill_attention_flops(c, 5000) \
        == 6 * pairs * 4 * 32 * 128
    # the step the issue planned with: weights outside experts + head
    # 0.88 GB, ~630 pairs touched 5.9 GB, a median row's index keys and
    # selected rows 0.5 GB over 16 rows
    median = [6400.0] * 16
    read = dsa_flops.decode_step_bytes(c, 630, median)
    assert 6.5e9 < read < 7.5e9
    every_key = read + 16 * (6400 - 2048) * 12_288
    assert every_key - read == pytest.approx(0.856e9, rel=0.01)


def test_the_file_is_the_catalogs_entry_cut_as_it_says():
    c = _json("configs", CONFIG)
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    entry = next(e for e in benchmark["configs"] if e["name"] == CONFIG)
    assert entry["reduced"] == [r["key"] for r in c["reduced"]] == [
        "num_hidden_layers"]
    assert [(r["published"], r["here"]) for r in c["reduced"]] == [(48, 6)]
    assert "ONE OF 8 PIPELINE STAGES" in c["reduced"][0]["why"]
    assert c["deployment"].startswith("One of 8 pipeline stages")
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Keye-VL-2.0-30B-A3B")
        assert c["source"] == entry["source"] == row["source_url"]
        # every key of the catalog's config, verbatim, but the depth
        assert {k: v for k, v in row["config"].items()
                if k != "num_hidden_layers"} == {
            k: c[k] for k in row["config"] if k != "num_hidden_layers"}
        assert row["config"]["num_hidden_layers"] == 48
    assert c["num_hidden_layers"] == 6
    assert c["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    assert (c["num_experts"], c["num_experts_per_tok"], c["vocab_size"],
            c["hidden_size"], c["max_position_embeddings"]) == (
        128, 8, 151_936, 2048, 262_144)
    assert len(c["assumed"]) >= 6
    said = " ".join(c["assumed"])
    for words in ("RoPE on the index queries", "LayerNorm on the index key",
                  "64^-0.5", "q_chunk_size", "mrope_section",
                  "lower position", "float32"):
        assert words in said, words
    cfg = program.llama_config(c)
    assert (cfg.n_layers, cfg.moe_experts, cfg.moe_top_k, cfg.expert_width,
            cfg.index_heads, cfg.index_head_dim, cfg.index_topk) == (
        6, 128, 8, 768, 16, 64, 2048)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.qk_head_norm,
            cfg.moe_norm_topk, cfg.tie_embeddings, cfg.rope_theta) == (
        32, 4, 128, True, True, False, 1e7)
    assert not cfg.plain_decoder and cfg.parts() == [(cfg, "layers", 0)]


def the_cells_entries(root=spec.ROOT):
    """What THIS cell reports, on the tree at ``root`` (the rehearsal's has
    a later PR's cells and entries appended: nothing here counts the table
    or the cells, or says what another family's names are)."""
    from benchmarks.tests.test_yardstick import (benchmark_at, cell_at,
                                                 reader_at)

    benchmark = benchmark_at(root)
    mine = [m for m in benchmark["per_layer"] if m["name"] in _READERS]
    assert [m["name"] for m in mine] == list(_READERS)
    layers = {m["layer"] for m in benchmark["per_layer"]
              if m["name"] not in _READERS}
    for m in mine:
        assert CELL in m["workloads"]
        assert m["moves"] == "serve_output_tokens_per_s"
        assert m["unit"] == "%" and m["layer"] in layers
        assert callable(reader_at(root, m["name"]).read)
    cell = cell_at(root, CELL)
    assert cell.chips == 1 and cell.reference.__name__.endswith(
        "keye_sparse_decoder")
    reported = {e["name"] for e, _ in cell.readers("per_layer")}
    assert set(_READERS) <= reported
    assert {"batch.decode_kv_read_share", "batch.slot_wait_p50_ms",
            "batch.prefill_expert_dispatch_time_share",
            "batch.decode_step_device_ms", "setup_cache_fetch_s",
            "window_compiles"} <= reported
    # the step's floor is the file's (lib/dsa_flops.py: index keys, the
    # selected rows), and the experts' entries are every expert cell's: an
    # expert is ``moe_intermediate_size`` wide (768, not the 6,144 of a
    # dense FFN no layer has) and all 6 layers have all 128
    assert cell.config["roofline"] == "dsa_flops"
    assert (moe_flops.expert_width(cell.config),
            moe_flops.expert_layers(cell.config),
            moe_flops.experts_held(cell.config)) == (768, 6, 128)
    assert {m for m in reported if m.startswith(
        ("moe_", "swa_", "ssm_", "mla_", "lfm2_"))} == {
        "moe_expert_load_imbalance", "moe_expert_ffn_time_share",
        "moe_routing_time_share", "moe_expert_matmul_roofline"}
    assert "batch.decode_step_roofline" in reported
    assert {e["name"] for e, _ in cell.readers("end_to_end")} == {
        "serve_output_tokens_per_s", "setup_s"}


def test_the_readers_names_lead_to_files():
    the_cells_entries()


def test_the_cell_and_its_traffic_are_the_issues():
    t = _json("traffic", "serve-long-prompt")
    assert t["arrivals"] == {"process": "closed", "callers": 64,
                             "lead_in_s": 20.0, "drain_s": 30.0}
    assert t["prompt_tokens"] == {
        "dist": "lognormal", "median": 6144, "sigma": 0.45, "min": 1024,
        "max": 12288, "stratified": 16}
    assert t["output_tokens"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.5, "min": 32,
        "max": 768, "stratified": 16}
    w = _json("workloads", CELL)
    assert (w["kind"], w["chips"], w["traffic"], w["config"]) == (
        "serve_llm_even", 1, "serve-long-prompt", CONFIG)
    assert w["engine"] == {"max_slots": 16, "max_len": 16_384,
                           "prefill_buckets": [4096, 8192, 12_288],
                           "paged": False}
    assert w["deployment"] == {"max_ongoing_requests": 512}


# ------------------------------------------------ the programs, real widths
def test_the_engines_programs_fit_a_v5e_at_16_slots(one_chip):
    """The widest of each kind of program the engine warms (decode at the
    whole 16,384 context, one row of 12,288 prefilled), compiled for a v5e:
    the arguments are the weights and the three pools to the byte -- the
    index keys 64 wide, positions along lanes, no padded multiple -- the
    cache is updated in place, one Mosaic attention call a layer.  Eight
    slots more still compile by this account but leave the run's own
    ``correct`` (the reference in blocks beside the loaded engine, ~2.7 GB)
    no room, and sixteen more are over the chip: 16 by PERF.md section 4's
    rule."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama, llama_serve

    c = _json("configs", CONFIG)
    engine = _json("workloads", CELL)["engine"]
    slots, max_len = engine["max_slots"], engine["max_len"]
    cfg = program.llama_config(c, max_seq_len=max_len)
    params = _on(one_chip, jax.eval_shape(
        lambda k: llama.init_params(k, cfg, cfg.dtype), jax.random.key(0)))
    cache = _on(one_chip, jax.eval_shape(
        lambda: llama_serve.init_cache(cfg, slots, max_len)))
    pools = llama_serve.cache_pools(cfg, slots, max_len)
    assert {k: v[0] for k, v in pools.items()} == {
        "kv": slots * 6 * 2 * 16_384 * 4 * 128 * 2,
        "index_keys": slots * 6 * 64 * 16_384 * 2}
    cache_bytes = sum(v[0] for v in pools.values())
    assert cache_bytes == slots * dsa_flops.slot_bytes(c, max_len) \
        == 16 * 213_909_504
    weights = 2 * c["parameters"]

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
    chip = 15.75 * 2 ** 30
    for lowered, scratch in ((decode, 0.3e9), (prefill, 2.2e9)):
        compiled = lowered.compile()   # RESOURCE_EXHAUSTED if it does not fit
        memory = compiled.memory_analysis()
        held = memory.argument_size_in_bytes
        assert weights + cache_bytes <= held < weights + cache_bytes + 1e6
        assert memory.alias_size_in_bytes >= cache_bytes   # updated in place
        assert memory.temp_size_in_bytes < scratch
        # the kernels the cell's readers name, under the scope they sum
        kernels = kernels_by_name_and_scope(compiled.as_text())
        assert kernels["ragged-dot-none", "expert_ffn"] >= 3
        if lowered is decode:
            assert kernels["decode_attention", "decode_attention"] >= 1
        else:
            assert kernels["sparse_prefill_attention",
                           "flash_attention.fwd"] >= 1
        more = held + memory.temp_size_in_bytes \
            + 8 * dsa_flops.slot_bytes(c, max_len)
        if lowered is prefill:
            assert chip - 2.7e9 < more < chip          # 24: no room to check
            assert more + 8 * dsa_flops.slot_bytes(c, max_len) > chip   # 32


# ------------------------------------------------- a rehearsal on the CPU
TINY = {
    "name": "tiny-indexed", "source": "none (test, an indexer)",
    "reference": "keye_sparse_decoder", "roofline": "dsa_flops",
    "vocab_size": 256,
    "hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_experts": 8, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "attention_bias": False, "hidden_act": "silu",
    "mlp_only_layers": [], "decoder_sparse_step": 1,
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default",
                     "type": "default"},
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 16},
    "max_position_embeddings": 256, "rope_theta": 10000000,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False, "reduced": [],
    "assumed": ["test"],
    # float32 throughout: a request's gap against the reference is then
    # the order of float32 sums whichever requests a window completes
    "dtype": {"serve": "float32"},
    "program_fields": {
        "moe_experts": 8, "moe_top_k": 2, "moe_norm_topk": True,
        "moe_intermediate_size": 32, "qk_head_norm": True, "index_heads": 4,
        "index_head_dim": 8, "index_topk": 16, "dtype": "float32"},
}
TINY_CELL = "tiny-indexed.tiny-closed"


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark with a toy configuration with an indexer
    dropped in and its cell appended wherever the real one is."""
    root = tmp_path_factory.mktemp("bench_keye")
    bench = str(root / "benchmarks")
    shutil.copytree(spec.BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "out", "__pycache__", "tests"))

    def drop(rel, payload):
        path = os.path.join(bench, rel)
        assert not os.path.exists(path), f"{rel} would be an edit"
        with open(path, "w") as f:
            json.dump(payload, f)

    drop("configs/tiny-indexed.json", TINY)
    drop("traffic/tiny-closed.json", test_rehearsal.TRAFFIC["tiny-closed"])
    drop(f"workloads/{TINY_CELL}.json",
         dict(test_rehearsal.SERVE, kind="serve_llm_even", name=TINY_CELL,
              config="tiny-indexed", traffic="tiny-closed", why="test",
              # one bucket, one rung: fewer programs to warm
              engine=dict(test_rehearsal.ENGINE, prefill_buckets=[64],
                          prefill_groups=[4])))
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    benchmark["configs"].append(
        {"name": "tiny-indexed", "source": TINY["source"], "reduced": [],
         "file": "benchmarks/configs/tiny-indexed.json", "why": "test"})
    benchmark["workloads"].append(
        {"name": TINY_CELL, "config": "tiny-indexed",
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


def test_a_toy_model_with_an_indexer_runs_end_to_end_on_the_cpu(
        tree, cpu_peaks, monkeypatch):
    """One traced run of the toy cell through ``run.measure``: ``correct``
    against ``keye_sparse_decoder`` on requests most of whose queries
    select (``topk`` 16), nothing failed, the metrics the cell joins and
    the program's own count of keys present and attended are there; what
    only a device trace knows is left out on a CPU, not invented."""
    from benchmarks.tests.test_yardstick import names_lead_to_files

    # this file's compiles are for a described chip (``compiled_kernels``);
    # this run is on the CPU, its kernels interpreted
    monkeypatch.setattr(
        importlib.import_module("ray_tpu.ops.flash_attention"),
        "_use_interpret", lambda: True)
    bench, benchmark_json = tree
    names_lead_to_files(os.path.dirname(benchmark_json))
    result, obs = bench_run.measure(
        ["--workload", TINY_CELL, "--seed", "2147486045", "--seconds", "3",
         "--trace", "1"],
        allow_platforms=("cpu",), bench_dir=bench,
        benchmark_json=benchmark_json, t_process=time.perf_counter())
    assert result["correct"] is True, obs["checks"]
    assert result["failed"] == 0 < result["attempted"]
    assert obs["cell"].reference.__name__.endswith("keye_sparse_decoder")
    assert len(obs["logit_gaps"]) == 4 and obs["logit_gap_max"] < 1e-2
    metrics = result["metrics"]
    assert {"batch.slot_wait_p50_ms", "batch.token_burst_gap_p50_ms",
            "dsa_selected_share", "moe_expert_load_imbalance",
            "batch.decode_slot_utilization", "batch.decode_kv_read_share",
            "batch.prefill_padding_share", "window_compiles"} <= set(metrics)
    assert not {"batch.decode_step_roofline", "dsa_indexer_time_share",
                "dsa_sparse_prefill_attention_roofline"} & set(metrics)
    spans = program_spans.collect(obs)
    chunk = next(c for c in spans.chunks if c.get("kv_positions_present"))
    assert chunk["kv_positions_attended"] <= 16 * chunk["active"]
    assert 0 < metrics["dsa_selected_share"]["value"] < 100


def test_the_published_width_check_rehearsed_at_toy_size(tree, capsys):
    """``tools/dsa_check.py`` (what is run on the chip at the published
    widths) end to end on the toy in float32: the intact reply within
    rounding of the reference and every set it selected the reference's;
    each broken program off the reference by more than the benchmark's
    margin."""
    from benchmarks.tools import dsa_check

    bench, _ = tree
    # (every variant at toy size: tests/test_keye_serve.py)
    variants = ("intact", "no_selection", "float8_weights")
    assert dsa_check.VARIANTS[0] == variants[0] \
        and set(variants) <= set(dsa_check.VARIANTS)
    assert dsa_check.main([
        "--config", "tiny-indexed", "--seed", "2147486047", "--bench-dir",
        bench, "--before", "30", "--prompt", "50", "--new-tokens", "24",
        "--buckets", "32,64", "--max-len", "128", "--variants",
        ",".join(variants)]) == 0
    done = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(done) == {"event", *(f"{v}.0" for v in variants)}
    intact = done["intact.0"]
    assert intact["raw_max"] < 1e-3 and intact["passes"]
    assert intact["undecided_allowed"] == 14     # float32: one pass
    assert intact["selection"]["sets"] == 23 * 3
    assert intact["selection"]["set_size_max"] == 16
    assert intact["selection"]["keys_not_in_reference_max"] == 0
    # (24 positions decide little by count: the raw gaps say it)
    for variant in variants[1:]:
        assert done[f"{variant}.0"]["raw_max"] > 0.25, variant


def test_undecided_positions_are_taken_out_by_the_requests_own_count():
    """Positions over the margin are near-ties broken the other way while
    they are at most 2.75 times those that the model's own second pass
    (stored tensors rounded to the weights' type) reads over it, and 14; what
    moves more positions than that is judged as read."""
    import numpy as np

    reference = spec.load_module("references", "keye_sparse_decoder")
    quiet = np.full(650, 0.01)
    own = quiet.copy()
    own[:49] = 0.4                      # the second pass: 49 undecided
    assert reference.undecided_allowed(own) == 14 + 11 * 49 // 4 == 148
    assert reference.undecided_allowed(quiet) == 14
    sound = quiet.copy()                # the most a sound request read
    sound[:62] = 0.9
    sound[62:200] = 0.2                 # under the margin: not counted
    assert reference.take_out_undecided(sound, own).max() == 0.2
    wild = quiet.copy()                 # float8's mantissa: 4 times own
    wild[:196] = 0.3
    assert reference.take_out_undecided(wild, own).max() == 0.3
    stale = np.full(650, 4.0)           # a wrong cache row: every position
    assert reference.take_out_undecided(stale, own).max() == 4.0
    cycle = quiet.copy()                # tokens in a cycle: all decided
    cycle[:15] = 0.3
    assert reference.take_out_undecided(cycle, quiet).max() == 0.3
    cycle[14] = 0.01
    assert reference.take_out_undecided(cycle, quiet).max() == 0.01
    counts = reference.gap_counts(sound, own)
    assert (counts["over_margin"], counts["own_over_margin"],
            counts["undecided_allowed"]) == (62, 49, 148)


def test_the_second_pass_rounds_what_the_model_stores():
    """``own_gap``: zeros where the weights are float32 (one pass), and
    under bfloat16 weights the gap of the token that the rounded pass
    leads with -- never negative, zero wherever the two passes agree."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama

    reference = spec.load_module("references", "keye_sparse_decoder")
    x = jnp.asarray([1.0 + 2.0 ** -10, -3.3])
    assert (reference._stored(x, jnp.bfloat16)
            == x.astype(jnp.bfloat16).astype(jnp.float32)).all()
    assert reference._stored(x, None) is x
    config = TINY
    cfg = program.llama_config(config, max_seq_len=256)
    rng = np.random.default_rng(1)
    prompt, emitted = (rng.integers(0, 256, n) for n in (200, 24))
    for dtype, one_pass in ((jnp.float32, True), (jnp.bfloat16, False)):
        params = llama.init_params(jax.random.key(3), cfg, dtype)
        got = reference.teacher_forced_report(params, prompt, emitted, config)
        assert got["gap"].shape == got["own_gap"].shape == (24,)
        assert (got["own_gap"] >= 0).all() and (got["gap"] > 0).any()
        assert (got["own_gap"] == 0).all() == one_pass


# --------------------------------------- the readers on a synthetic trace
# One layer of one decode step and one layer of a prefill of 8,192
# positions, in instruction texts of the shapes the cell's programs compile
# to for a v5e (cut to what the readers look at), durations in
# microseconds, with the scope the program's map gives each.
_STEP = [
    ("%fusion.900 = bf16[16,4096]{1,0} fusion(bf16[16,1,2048] %x, "
     "bf16[6,2048,4096] %wq)", 40.0, "qkv_proj"),
    ("%fusion.901 = f32[16,1,16,16384]{3,2,1,0} fusion(bf16[16,1,16,64] "
     "%qi, bf16[1,16,64,16384] %keys)", 100.0, "indexer"),
    ("%fusion.903 = u32[16]{0} fusion(u32[16,16384] %u, u32[16] %v)", 200.0,
     "index_select"),
    ("%fusion.905 = f32[16,64,1024]{2,1,0} fusion(pred[16,16384] %keep)",
     150.0, "sparse_attention"),
    ("%decode_attention.7 = bf16[16,32,128]{2,1,0} custom-call(s32[1] "
     "%layer, s32[16] %n, bf16[16,32,128] %q, f32[32,1024] %bias, "
     "bf16[6,16,65536,128] %k, bf16[6,16,65536,128] %v), "
     "custom_call_target=\"tpu_custom_call\"", 110.0, "decode_attention"),
    ("%ragged-dot-none.2 = f32[128,768]{1,0} custom-call(bf16[128,2048] "
     "%rows, bf16[768,2048,768] %w_gate), "
     "custom_call_target=\"tpu_custom_call\"", 1000.0, "expert_ffn"),
    ("%fusion.910 = f32[16,128]{1,0} fusion(bf16[16,2048] %h, "
     "f32[2048,128] %router)", 40.0, "router"),
    ("%fusion.911 = bf16[128,2048]{1,0} fusion(bf16[16,2048] %h, "
     "s32[128] %order)", 60.0, "expert_dispatch"),
]
_PREFILL_LAYER = [
    ("%fusion.77 = f32[1,512,16,8192]{3,2,1,0} fusion(bf16[1,512,16,64] "
     "%qi, bf16[1,64,8192] %keys)", 2000.0, "indexer"),
    ("%fusion.78 = s32[1,512]{1,0} fusion(u32[1,512,8192] %u, u32[1,512] "
     "%v)", 3000.0, "index_select"),
    ("%sparse_prefill_attention.3 = bf16[1,32,8192,128]{3,2,1,0} "
     "custom-call(bf16[1,32,8192,128] %q, bf16[1,4,8192,128] %k, "
     "bf16[1,4,8192,128] %v, s8[1,8192,8192] %keep), "
     "custom_call_target=\"tpu_custom_call\"", 5000.0, "flash_attention.fwd"),
]


def _synthetic_obs(steps=16, runs=2):
    from ray_tpu.observability.device import instruction_key

    ops, modules, t = [], [], 0.0
    for run in range(runs):
        start, body = t, []
        for _ in range(steps * 6):
            for name, us, _scope in _STEP:
                body.append((t, t + us * 1e-6, name))
                t += us * 1e-6
        ops.append((start, t, "%while.7 = (s32[]) while((s32[]) %t), "
                    "body=%step"))
        ops.extend(body)
        modules.append((start, t, f"jit_decode_k({run})"))
        t += 1e-4
    start = t
    for _ in range(6):
        for name, us, _scope in _PREFILL_LAYER:
            ops.append((t, t + us * 1e-6, name))
            t += us * 1e-6
    modules.append((start, t, "jit_prefill(9)"))
    trace = trace_reduce.Trace(
        [trace_reduce.DeviceTrace(0, ops, modules)], [], 0.0, t)
    cell = types.SimpleNamespace(config=_json("configs", CONFIG),
                                 workload=_json("workloads", CELL),
                                 bench_dir=spec.BENCH_DIR, name=CELL)
    # 16 sequences in flight, each 6,000 positions at the span's middle
    records = [types.SimpleNamespace(
        ok=True, got_tokens=201, sent=0.0, ttft_ms=0.0, done=2.0,
        prompt_tokens=5899) for _ in range(16)]
    chunk = {"k": 16, "active": 16, "expert_rows": 16 * 8 * 16 * 6,
             "experts_touched": 16 * 480, "expert_rows_max": 16 * 4,
             "kv_positions_present": 16 * 6000,
             "kv_positions_attended": 16 * 2048}
    group = {"bucket": 8192, "rows": 1, "prompt_tokens": 7000}
    scopes = {module: {instruction_key(name): (scope, "forward")
                       for name, _us, scope in rows}
              for module, rows in (("jit_decode_k", _STEP),
                                   ("jit_prefill", _PREFILL_LAYER))}
    return {
        "trace": trace, "cell": cell, "decode_chunk": 16,
        "trace_span": [0.9, 1.1], "scope_map": scopes,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        "log": types.SimpleNamespace(records=records),
        "program_spans": program_spans.ProgramSpans(
            [], [chunk, chunk], [group]),
    }


def test_the_readers_on_a_synthetic_trace(monkeypatch):
    monkeypatch.setattr(scope_names, "_write_report", lambda obs: None)
    obs = _synthetic_obs()
    reads = {name: spec.load_module("metrics", name).read(obs)
             for name in _READERS + _JOINED}
    layer_us = sum(us for _n, us, _s in _STEP)                   # 1,700
    assert layer_us == 1700
    assert reads["dsa_indexer_time_share"] == pytest.approx(
        100 * 100 / layer_us)
    assert reads["dsa_select_time_share"] == pytest.approx(
        100 * 200 / layer_us)
    assert reads["dsa_sparse_attention_time_share"] == pytest.approx(
        100 * (150 + 110) / layer_us)
    assert reads["moe_expert_ffn_time_share"] == pytest.approx(
        100 * 1000 / layer_us)
    assert reads["moe_routing_time_share"] == pytest.approx(
        100 * (40 + 60) / layer_us)
    assert reads["dsa_prefill_selection_time_share"] == pytest.approx(
        100 * 5000 / 10_000)
    assert reads["dsa_selected_share"] == pytest.approx(100 * 2048 / 6000)
    c = obs["cell"].config
    lengths = [6000.0] * 16
    assert swa_names.lengths_in_flight(obs, 1.0) == pytest.approx(lengths)
    step_s = 6 * layer_us * 1e-6
    floor = dsa_flops.decode_step_bytes(c, 480, lengths) / 819e9
    assert floor > dsa_flops.decode_step_flops(c, lengths, 768) / 197e12
    assert reads["decode_step_roofline"] == pytest.approx(
        100 * floor / step_s, rel=1e-3)
    # 480 (layer, expert) pairs of 3 x 2,048 x 768 and 768 rows' activations
    # against the six grouped matmuls' 6 ms a step
    experts = moe_flops.expert_matmul_bytes(c, 480, 768)
    assert experts == (480 * 4_718_592 + 768 * (3 * 2048 + 3 * 768)) * 2
    assert experts / 819e9 > dsa_flops.expert_matmul_flops(c, 768) / 197e12
    assert reads["moe_expert_matmul_roofline"] == pytest.approx(
        100 * experts / 819e9 / (6 * 1000e-6), rel=1e-3)
    # 6 traced calls, each a layer's share of a 7,000-token prompt
    inside = dsa_flops.prefill_attention_flops(c, 7000) / 197e12
    assert reads["dsa_sparse_prefill_attention_roofline"] == pytest.approx(
        100 * inside / (6 * 5000e-6), rel=1e-3)
    # the busiest expert's 4 rows a step against 768 over 6 x 128 pairs
    assert reads["moe_expert_load_imbalance"] == pytest.approx(4.0)
    for name in _READERS + _JOINED[:4]:
        assert 0 < reads[name] < 100, name


def test_a_program_without_an_indexer_reads_nothing(monkeypatch):
    """Another cell's observations, the parent commit's (whose spans carry
    no keys present, whose map knows no such scope and whose trace holds
    no such kernel) and an untraced run: every reader returns None, none
    raises."""
    monkeypatch.setattr(scope_names, "_write_report", lambda obs: None)
    obs = _synthetic_obs()
    other = dict(obs, cell=types.SimpleNamespace(
        config=_json("configs", "olmoe-1b-7b"),
        workload=obs["cell"].workload))
    parent = _synthetic_obs()
    parent["scope_map"] = {module: {key: ("ffn", "forward") for key in rows}
                           for module, rows in obs["scope_map"].items()}
    parent["trace"] = trace_reduce.Trace(
        [trace_reduce.DeviceTrace(
            0, [(s, e, n.replace("sparse_prefill_attention", "fusion"))
                for s, e, n in obs["trace"].devices[0].ops],
            obs["trace"].devices[0].modules)], [], 0.0, 1.0)
    parent["program_spans"] = program_spans.ProgramSpans(
        [], [{"k": 16, "tokens_kept": 1, "token_steps": 2}], [])
    no_trace = dict(obs, trace=None)
    for name in _READERS + _JOINED:
        read = spec.load_module("metrics", name).read
        if name in _READERS:        # the experts' are every expert cell's
            assert read(dict(other)) is None, name
        if name not in ("dsa_selected_share",       # read spans alone
                        "moe_expert_load_imbalance"):
            assert read(dict(no_trace)) is None, name
        assert read(dict(parent)) is None, name
    # what says that a configuration selects is what its program is given:
    # an ``index_topk`` under ``program_fields`` or as a key of its own,
    # whatever the family spells beside it (``sa_config`` here)
    plain = {k: v for k, v in obs["cell"].config.items()
             if k != "sa_config"}
    unspelled = dict(plain, program_fields={
        k: v for k, v in plain["program_fields"].items()
        if k != "index_topk"})
    for config, selects in ((plain, True), (unspelled, False),
                            (dict(unspelled, index_topk=2048), True)):
        given = dict(obs, cell=types.SimpleNamespace(
            **{**vars(obs["cell"]), "config": config}))
        for name in ("dsa_indexer_time_share", "dsa_select_time_share",
                     "dsa_prefill_selection_time_share",
                     "dsa_selected_share"):
            read = spec.load_module("metrics", name).read
            assert read(dict(given)) == (read(dict(obs)) if selects
                                         else None), name
