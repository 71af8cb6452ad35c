"""The short-convolution + expert configuration's part of the benchmark:
``lib/lfm2_flops.py`` and the configuration file's parameter counts against
hand-worked numbers and the program's own tree, to the unit; the
``reduced`` entries against the file and the catalog's widths; the
programs the cell's engine warms compiled at the REAL widths (240 slots x
512) for a v5e that is described, not attached; a CPU rehearsal of a toy
LFM2 through ``run.measure`` with ``lfm2_moe_decoder`` as its reference,
and of ``tools/lfm2_check.py``; and the six ``lfm2_*`` readers on a
synthetic trace.
"""

import json
import os
import shutil
import time
import types

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import (lfm2_flops, lfm2_names, moe_flops, program,
                            program_spans, scope_names, spec, swa_names,
                            trace_reduce)
from benchmarks.tests import test_rehearsal
from benchmarks.tests.test_aot_real_widths import (  # noqa: F401
    _json, _on, compiled_kernels, kernels_by_name_and_scope, one_chip, topo)

os.environ.setdefault("TPU_LOG_DIR", "disabled")
CONFIG = "lfm2-8b-a1b"
CELL = "lfm2-8b-a1b.serve-batch-decode-wide"
_READERS = ("lfm2_conv_mixer_time_share", "lfm2_attention_time_share")
# what the cell joins for its step and its experts: one reader each for
# every configuration (``lib/readers.py``, ``lib/moe_names.py``)
_JOINED = ("decode_step_roofline", "moe_expert_ffn_time_share",
           "moe_routing_time_share", "moe_expert_matmul_roofline",
           "moe_expert_load_imbalance")
PUBLISHED = ["conv", "conv", "full_attention", "conv", "conv", "conv",
             "full_attention", "conv", "conv", "conv", "full_attention",
             "conv", "conv", "conv", "full_attention", "conv", "conv",
             "conv", "full_attention", "conv", "conv", "full_attention",
             "conv", "conv"]


# ------------------------------------------------- parameters and bytes
def test_parameters_by_hand_and_by_the_programs_tree():
    import jax

    from ray_tpu.models import llama

    c = _json("configs", CONFIG)
    conv = 2048 * 6144 + 2048 * 2048 + 3 * 2048
    assert lfm2_flops.conv_mixer_params(c) == conv == 16_783_360
    attention = 2 * (2048 * 2048) + 2 * (2048 * 512) + 128
    assert lfm2_flops.attention_params(c) == attention == 10_485_888
    assert lfm2_flops.expert_params(c) == 3 * 2048 * 1792 == 11_010_048
    assert lfm2_flops.router_params(c) == 65_536 + 32
    assert lfm2_flops.dense_ffn_params(c) == 3 * 2048 * 7168 == 44_040_192
    dense_conv = conv + 44_040_192 + 4_096
    assert lfm2_flops.layer_params(c, "conv", True) == dense_conv \
        == 60_827_648
    expert_conv = conv + 32 * 11_010_048 + 65_568 + 4_096
    assert lfm2_flops.layer_params(c, "conv", False) == expert_conv \
        == 369_174_560
    expert_attention = attention + 32 * 11_010_048 + 65_568 + 4_096
    assert lfm2_flops.layer_params(c, "full_attention", False) \
        == expert_attention == 362_877_088
    period = expert_attention + 3 * expert_conv
    assert period == 1_470_400_768
    embedding = 65_536 * 2048
    here = 2 * dense_conv + 3 * period + embedding + 2048
    assert here == lfm2_flops.parameters(c) == c["parameters"] \
        == 4_667_077_376
    whole = (2 * dense_conv + 6 * expert_attention + 16 * expert_conv
             + embedding + 2048)
    assert whole == lfm2_flops.parameters(c, PUBLISHED) \
        == c["parameters_published_depth"] == 8_339_930_560
    # the program's own tree, to the unit, at both depths
    for kinds, want in ((c["layer_types"], here), (PUBLISHED, whole)):
        cfg = program.llama_config(
            {**c, "num_hidden_layers": len(kinds),
             "program_fields": {**c["program_fields"],
                                "layer_types": kinds}})
        tree = jax.eval_shape(
            lambda k: llama.init_params(k, cfg, cfg.dtype), jax.random.key(0))
        assert llama.param_count(tree) == want
    full = program.llama_config(
        {**c, "num_hidden_layers": 24,
         "program_fields": {**c["program_fields"], "layer_types": PUBLISHED}})
    assert [(key, l0, part.n_layers) for part, key, l0 in full.parts()] == [
        ("dense_layers", 0, 2), ("layers", 2, 16), ("layers_1", 18, 6)]


def test_operations_and_bytes_by_hand():
    c = _json("configs", CONFIG)
    assert lfm2_flops.layers_of(c, "conv") == 11
    assert lfm2_flops.layers_of(c, "full_attention") == 3
    assert lfm2_flops.expert_layers(c) == 12
    # a slot: K and V of 3 layers x 8 heads x 64 a position, 11 states of
    # 2 rows x 2,048
    assert lfm2_flops.kv_bytes_per_position(c) == 3 * 2 * 8 * 64 * 2 == 6144
    assert lfm2_flops.conv_state_bytes_per_slot(c) == 11 * 8192 == 90_112
    assert lfm2_flops.slot_bytes(c, 512) == 3_145_728 + 90_112 == 3_235_840
    # what every token multiplies by: 11 conv mixers, 3 attentions' four
    # matrices, 2 dense FFNs, 12 routers, the tied head
    every = (11 * 16_783_360 + 3 * 10_485_760 + 2 * 44_040_192
             + 12 * 65_536 + 2048 * 65_536)
    assert lfm2_flops.step_matmul_params(c) == every == 439_158_784
    lengths = [100, 300]
    # all 12 x 32 experts touched: 8.46 of the step's 9.3 GB
    assert lfm2_flops.decode_step_bytes(c, 384, lengths, 2) \
        == (every + 384 * 11_010_048) * 2 + 400 * 6144 + 2 * 2 * 90_112
    assert 384 * 11_010_048 * 2 == 8_455_716_864
    assert lfm2_flops.decode_attention_flops(c, lengths) \
        == 3 * 400 * 2 * 2 * 32 * 64
    assert lfm2_flops.decode_step_flops(c, lengths, 96) \
        == 2 * every * 2 + 3 * 400 * 8192 + 2 * 96 * 11_010_048
    assert moe_flops.expert_matmul_bytes(c, 384, 960) \
        == (384 * 11_010_048 + 960 * (3 * 2048 + 3 * 1792)) * 2
    assert lfm2_flops.expert_matmul_flops(c, 960) == 2 * 960 * 11_010_048
    # the cell's step, every expert touched at 240 rows of ~256 positions:
    # bound by bytes, 11.5 ms at 819 GB/s
    full = [256.0] * 240
    floor = lfm2_flops.decode_step_bytes(c, 384, full, 240) / 819e9
    assert floor > lfm2_flops.decode_step_flops(c, full, 960) / 197e12
    assert floor == pytest.approx(11.9e-3, rel=0.02)


def test_the_file_is_the_catalogs_entry_cut_as_it_says():
    c = _json("configs", CONFIG)
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    entry = next(e for e in benchmark["configs"] if e["name"] == CONFIG)
    assert entry["reduced"] == [r["key"] for r in c["reduced"]] == [
        "num_hidden_layers", "layer_types"]
    assert [(r["published"], r["here"]) for r in c["reduced"]] == [
        (24, 14), (PUBLISHED, PUBLISHED[:14])]
    assert c["num_hidden_layers"] == 14
    assert c["layer_types"] == PUBLISHED[:14]
    # every width, and everything else, as the catalog's row has it
    assert {k: c[k] for k in (
        "conv_L_cache", "conv_bias", "hidden_size", "intermediate_size",
        "max_position_embeddings", "model_type", "moe_intermediate_size",
        "norm_eps", "norm_topk_prob", "num_attention_heads",
        "num_dense_layers", "num_experts", "num_experts_per_tok",
        "num_key_value_heads", "rope_theta", "routed_scaling_factor",
        "use_expert_bias", "vocab_size")} == {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "max_position_embeddings": 128_000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
        "num_experts_per_tok": 4, "num_key_value_heads": 8,
        "rope_theta": 1_000_000, "routed_scaling_factor": 1,
        "use_expert_bias": True, "vocab_size": 65_536}
    assert c["assumed"] and c["deployment"]
    assert c["tie_word_embeddings"] is True and c["head_dim"] == 64
    cfg = program.llama_config(c)
    assert (cfg.n_layers, cfg.first_dense_layers, cfg.moe_experts,
            cfg.moe_top_k, cfg.expert_width, cfg.conv_taps) == (
        14, 2, 32, 4, 1792, 3)
    assert cfg.layer_types == ("conv", "conv") + (
        "attention", "conv", "conv", "conv") * 3
    assert (cfg.moe_router_score, cfg.moe_router_bias, cfg.qk_head_norm,
            cfg.moe_norm_topk, cfg.tie_embeddings) == (
        "sigmoid", True, True, True, True)
    assert cfg.attn_scale == 0.125 and cfg.rope_theta == 1e6
    assert [(key, part.n_layers, part.layer_pattern)
            for part, key, _ in cfg.parts()] == [
        ("dense_layers", 2, ("conv",)),
        ("layers", 12, ("attention", "conv", "conv", "conv"))]


def the_cells_entries(root=spec.ROOT):
    """What THIS cell reports, on the tree at ``root`` (the rehearsal's has
    a later PR's cells and entries appended: nothing here counts the table
    or the cells, or says what another family's names are)."""
    from benchmarks.tests.test_yardstick import (benchmark_at, cell_at,
                                                 reader_at)

    benchmark = benchmark_at(root)
    mine = [m for m in benchmark["per_layer"] if m["name"] in _READERS]
    assert [m["name"] for m in mine] == list(_READERS)
    for m in mine:
        assert CELL in m["workloads"]
        assert m["moves"] == "serve_output_tokens_per_s"
        assert m["unit"] == "%"
        assert m["layer"] == "serve device programs"
        assert callable(reader_at(root, m["name"]).read)
    entry = next(w for w in benchmark["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["config"] == CONFIG
    cell = cell_at(root, CELL)
    reported = {e["name"] for e, _ in cell.readers("per_layer")}
    assert set(_READERS) <= reported
    assert {"batch.decode_kv_read_share", "batch.slot_wait_p50_ms",
            "batch.prefill_expert_dispatch_time_share",
            "batch.decode_step_device_ms", "setup_cache_fetch_s",
            "window_compiles"} <= reported
    # the step's floor is the file's (lib/lfm2_flops.py: conv states, 3
    # attention layers), and the experts' entries are every expert cell's:
    # an expert is ``moe_intermediate_size`` wide and 12 of the 14 layers
    # have 32 of them, so the busiest expert is held against 12 x 32 pairs
    assert cell.config["roofline"] == "lfm2_flops"
    assert (moe_flops.expert_width(cell.config),
            moe_flops.expert_layers(cell.config),
            moe_flops.experts_held(cell.config)) == (1792, 12, 32)
    assert {"batch.decode_step_roofline", "moe_expert_matmul_roofline",
            "moe_expert_ffn_time_share", "moe_routing_time_share",
            "moe_expert_load_imbalance"} <= reported
    assert not {m for m in reported if m.startswith(
        ("swa_", "ssm_", "mla_"))}
    assert {e["name"] for e, _ in cell.readers("end_to_end")} == {
        "serve_output_tokens_per_s", "setup_s"}


def test_the_readers_names_lead_to_files():
    the_cells_entries()


def test_the_cell_and_its_traffic_are_the_issues():
    t = _json("traffic", "serve-batch-decode-wide")
    narrow = _json("traffic", "serve-batch-decode")
    assert t["arrivals"] == {**narrow["arrivals"], "callers": 480}
    assert {k: v for k, v in t.items() if k not in ("arrivals", "why")} \
        == {k: v for k, v in narrow.items() if k not in ("arrivals", "why")}
    w = _json("workloads", CELL)
    assert w["kind"] == "serve_llm" and w["chips"] == 1
    assert w["engine"] == {"max_slots": 240, "max_len": 512,
                           "prefill_buckets": [64, 128, 256],
                           "paged": False}
    assert w["deployment"] == {"max_ongoing_requests": 1024}


# ------------------------------------------------ the programs, real widths
def test_the_engines_programs_fit_a_v5e_at_240_slots(one_chip):
    """At the cell's 240 slots x 512: weights 9.33 GB, a slot 3.24 MB (the
    compiler's own account of the arguments: nothing padded), the cache
    updated in place, the decode step's scratch and the widest prefill's
    (8 rows x 256) inside the chip beside them.  The attention layers at
    head 64 go through XLA (no Mosaic attention call); the grouped matmuls
    are the only kernels."""
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

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    ints, bools = arr(jnp.int32, slots), arr(jnp.bool_, slots)
    decode = llama_serve.build_decode_k(cfg).lower(
        params, cache, ints, ints, ints, ints, bools, bools, k=16,
        s_active=max_len)
    bucket = engine["prefill_buckets"][-1]
    prefill = llama_serve.build_prefill(cfg).lower(
        params, cache, arr(jnp.int32, 8, bucket), arr(jnp.int32, 8),
        arr(jnp.int32, 8))
    pools = llama_serve.cache_pools(cfg, slots, max_len)
    assert pools == {"kv": (slots * 512 * 6144, "bfloat16"),
                     "conv": (slots * 90_112, "bfloat16")}
    cache_bytes = slots * lfm2_flops.slot_bytes(c, max_len)
    assert cache_bytes == sum(nbytes for nbytes, _ in pools.values()) \
        == 776_601_600
    # the bias is a float32 leaf: 12 x 32 x 2 bytes more than 2 a parameter
    weights = 2 * c["parameters"] + 12 * 32 * 2
    for lowered, scratch in ((decode, 2.0e9), (prefill, 0.5e9)):
        compiled = lowered.compile()   # RESOURCE_EXHAUSTED if it does not fit
        memory = compiled.memory_analysis()
        held = memory.argument_size_in_bytes
        assert weights + cache_bytes <= held < weights + cache_bytes + 1e6
        assert memory.alias_size_in_bytes >= cache_bytes   # updated in place
        assert memory.temp_size_in_bytes < scratch
        assert held + memory.temp_size_in_bytes < 15.75e9
        # the kernels the cell's readers name, under the scope they sum:
        # the grouped matmuls (attention at head 64 is XLA's)
        kernels = kernels_by_name_and_scope(compiled.as_text())
        assert kernels["ragged-dot-none", "expert_ffn"] >= 3


# ------------------------------------------------- a rehearsal on the CPU
TINY = {
    "name": "tiny-lfm2", "source": "none (test, short convolution)",
    "reference": "lfm2_moe_decoder", "roofline": "lfm2_flops",
    "vocab_size": 256, "hidden_size": 64,
    "num_hidden_layers": 10, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_experts": 8, "num_experts_per_tok": 2,
    "num_dense_layers": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "use_expert_bias": True, "conv_L_cache": 3,
    "conv_bias": False, "layer_types": PUBLISHED[:10],
    "max_position_embeddings": 256, "rope_theta": 1000000,
    "norm_eps": 1e-5, "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
    "reduced": [], "assumed": ["test"],
    # float32 throughout: a request's gap against the reference is then
    # the order of float32 sums whichever requests a window completes
    "dtype": {"serve": "float32"},
    "program_fields": {
        "layer_types": PUBLISHED[:10], "first_dense_layers": 2,
        "conv_taps": 3, "qk_head_norm": True, "moe_experts": 8,
        "moe_top_k": 2, "moe_norm_topk": True, "moe_intermediate_size": 32,
        "moe_router_score": "sigmoid", "moe_router_bias": True,
        "dtype": "float32"},
}
TINY_CELL = "tiny-lfm2.tiny-closed"


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark with a toy LFM2 (two dense conv layers, two
    periods of four) dropped in and its cell appended wherever the real
    one is."""
    root = tmp_path_factory.mktemp("bench_lfm2")
    bench = str(root / "benchmarks")
    shutil.copytree(spec.BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "out", "__pycache__", "tests"))

    def drop(rel, payload):
        path = os.path.join(bench, rel)
        assert not os.path.exists(path), f"{rel} would be an edit"
        with open(path, "w") as f:
            json.dump(payload, f)

    drop("configs/tiny-lfm2.json", TINY)
    drop("traffic/tiny-closed.json", test_rehearsal.TRAFFIC["tiny-closed"])
    drop(f"workloads/{TINY_CELL}.json",
         dict(test_rehearsal.SERVE, name=TINY_CELL, config="tiny-lfm2",
              traffic="tiny-closed", why="test"))
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    benchmark["configs"].append(
        {"name": "tiny-lfm2", "source": TINY["source"], "reduced": [],
         "file": "benchmarks/configs/tiny-lfm2.json", "why": "test"})
    benchmark["workloads"].append(
        {"name": TINY_CELL, "config": "tiny-lfm2", "traffic": "tiny-closed",
         "chips": 1, "why": "test"})
    for group in ("end_to_end", "per_layer"):
        for metric in benchmark[group]:
            if CELL in metric.get("workloads", []):
                metric["workloads"].append(TINY_CELL)
    path = str(root / "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(benchmark, f)
    return bench, path


cpu_peaks = test_rehearsal.cpu_peaks


def test_a_toy_lfm2_runs_end_to_end_on_the_cpu(tree, cpu_peaks):
    """One traced run of the toy cell through ``run.measure``: ``correct``
    against ``lfm2_moe_decoder``, nothing failed, the metrics the cell
    joins are there and the program's spans carry the conv states' and the
    experts' counts; what only a device trace knows is left out on a CPU,
    not invented."""
    from benchmarks.tests.test_yardstick import names_lead_to_files

    bench, benchmark_json = tree
    names_lead_to_files(os.path.dirname(benchmark_json))
    result, obs = bench_run.measure(
        ["--workload", TINY_CELL, "--seed", "2147486437", "--seconds", "3",
         "--trace", "1"],
        allow_platforms=("cpu",), bench_dir=bench,
        benchmark_json=benchmark_json, t_process=time.perf_counter())
    assert result["correct"] is True, obs["checks"]
    assert result["failed"] == 0 < result["attempted"]
    assert obs["cell"].reference.__name__.endswith("lfm2_moe_decoder")
    assert len(obs["logit_gaps"]) == 4 and obs["logit_gap_max"] < 1e-2
    metrics = result["metrics"]
    assert {"batch.slot_wait_p50_ms", "batch.token_burst_gap_p50_ms",
            "batch.decode_slot_utilization", "batch.decode_kv_read_share",
            "moe_expert_load_imbalance",
            "batch.prefill_padding_share", "window_compiles"} <= set(metrics)
    assert not {*_READERS, "batch.decode_step_roofline",
                "moe_expert_matmul_roofline"} & set(metrics)
    spans = program_spans.collect(obs)
    chunk = next(c for c in spans.chunks if c.get("state_rows_updated"))
    assert chunk["state_rows_updated"] == chunk["active"] * chunk["k"]
    # 8 conv layers of 2 rows x 64 float32, read and written
    assert chunk["state_bytes"] == 2 * chunk["state_rows_updated"] \
        * 8 * 2 * 64 * 4
    assert chunk["expert_rows"] == chunk["active"] * chunk["k"] * 2 * 8
    assert lfm2_names.chunk_medians(obs)[2] == pytest.approx(
        chunk["active"], abs=4)


def test_the_published_width_check_rehearsed_at_toy_size(tree, capsys):
    """``tools/lfm2_check.py`` (what is run on the chip at the published
    widths) end to end on the toy: the intact reply within rounding of the
    reference, every broken program off it by more than the benchmark's
    margin but the two that move a 0.02-wide bias, which a toy of 8
    experts does not show.  (24 positions decide little by count: 21 may
    be swaps.  What the count and the ceiling make of each variant is read
    at the published widths on the chip: PERF.md section 6.)"""
    from benchmarks.tools import lfm2_check

    bench, _ = tree
    assert lfm2_check.main([
        "--config", "tiny-lfm2", "--seed", "2147486433", "--bench-dir",
        bench, "--before", "20", "--prompt", "40", "--new-tokens", "24",
        "--bucket", "64", "--max-len", "128"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    done = json.loads(lines[-1])
    assert set(done) == {"event", *(f"{v}.0" for v in lfm2_check.VARIANTS)}
    intact = done["intact.0"]
    assert intact["counts"]["max"] < 1e-3 and intact["passes"]
    for variant in ("conv_tap_dropped", "stale_conv_state",
                    "idle_slot_advanced", "qk_norm_whole",
                    "float8_weights"):
        assert done[f"{variant}.0"]["counts"]["max"] > 0.25, variant
    assert not done["conv_tap_dropped.0"]["passes"]


def test_swaps_are_taken_out_by_count_and_under_the_ceiling():
    import numpy as np

    reference = spec.load_module("references", "lfm2_moe_decoder")
    n = 200
    allowed = reference.swaps_allowed(n)
    assert allowed == 8 + 110
    quiet = np.full(n, 0.01)
    loud = quiet.copy()
    loud[:allowed] = 1.0
    assert reference.take_out_swaps(loud).max() == pytest.approx(0.01)
    loud[allowed] = 1.0                       # one more than allowed
    assert reference.take_out_swaps(loud).max() == 1.0
    wild = quiet.copy()
    wild[3] = reference.SWAP_CEILING + 0.1    # one may be a swap still
    assert reference.take_out_swaps(wild).max() == pytest.approx(0.01)
    wild[7] = reference.SWAP_CEILING + 2.0    # two are a state gone wrong
    assert reference.take_out_swaps(wild).max() > reference.SWAP_CEILING
    assert reference.take_out_swaps(quiet).max() == pytest.approx(0.01)


# --------------------------------------- the readers on a synthetic trace
# One conv expert layer and one attention expert layer of one decode step,
# in instruction texts of the shapes the cell's programs compile to for a
# v5e (cut to what the readers look at), durations in microseconds.
_CONV_IN = ("%fusion.10 = bf16[240,6144]{1,0} fusion(bf16[240,2048] %h, "
            "bf16[11,2048,6144] %conv_in)")
_SHORT_CONV = ("%fusion.11 = f32[240,2048]{1,0} fusion(bf16[11,2,240,2048] "
               "%conv, bf16[240,6144] %bcx)")
_CONV_OUT = ("%fusion.12 = bf16[240,2048]{1,0} fusion(f32[240,2048] %v, "
             "bf16[11,2048,2048] %conv_out)")
_ATTEND = ("%fusion.20 = bf16[240,32,64]{2,1,0} fusion(bf16[240,512,8,64] "
           "%k, bf16[240,512,8,64] %v, bf16[240,32,64] %q)")
_ROUTER = ("%fusion.30 = f32[240,32]{1,0} fusion(bf16[240,2048] %h, "
           "bf16[12,2048,32] %router)")
_SORT = "%sort.3 = s32[960]{0} sort(s32[960] %flat)"
_ACT = ("%fusion.31 = bf16[960,1792]{1,0} fusion(f32[960,1792] %g, "
        "f32[960,1792] %u)")
_GROUPED = ("%ragged-dot-none.2 = f32[960,1792]{1,0} custom-call("
            "bf16[960,2048] %rows, bf16[384,2048,1792] %w_gate), "
            "custom_call_target=\"tpu_custom_call\"")
_EXPERTS = [(_ROUTER, 20.0), (_SORT, 30.0), (_GROUPED, 700.0), (_ACT, 50.0)]
_STEP = ([(_CONV_IN, 60.0), (_SHORT_CONV, 10.0), (_CONV_OUT, 30.0)]
         + _EXPERTS + [(_ATTEND, 300.0)] + _EXPERTS)
_SCOPE_OF = {_CONV_IN: "conv_proj", _SHORT_CONV: "short_conv",
             _CONV_OUT: "conv_out", _ATTEND: "attention", _ROUTER: "router",
             _SORT: "expert_dispatch", _ACT: "expert_ffn"}


def _synthetic_obs(steps=16, runs=3):
    from ray_tpu.observability.device import instruction_key

    ops, modules, t = [], [], 0.0
    for _run in range(runs):
        start, body = t, []
        for _ in range(steps):
            for name, us in _STEP:
                body.append((t, t + us * 1e-6, name))
                t += us * 1e-6
        ops.append((start, t, "%while.7 = (s32[]) while((s32[]) %t), "
                    "body=%step"))
        ops.extend(body)
        modules.append((start, t, "jit_decode_k(7)"))
        t += 1e-4
    trace = trace_reduce.Trace(
        [trace_reduce.DeviceTrace(0, ops, modules)], [], 0.0, t)
    cell = types.SimpleNamespace(config=_json("configs", CONFIG),
                                 workload=_json("workloads", CELL),
                                 bench_dir=spec.BENCH_DIR, name=CELL)
    # 240 sequences in flight, each 250 positions at the span's middle
    records = [types.SimpleNamespace(
        ok=True, got_tokens=201, sent=0.0, ttft_ms=0.0, done=2.0,
        prompt_tokens=149) for _ in range(240)]
    chunk = {"k": 16, "active": 240, "expert_rows": 16 * 12 * 960,
             "experts_touched": 16 * 384, "expert_rows_max": 16 * 40,
             "state_rows_updated": 16 * 240,
             "state_bytes": 2 * 16 * 240 * 90_112}
    # the program's own map: which instruction is under which scope (the
    # compiler names the grouped matmul itself: ``device._COMPILER_NAMED``)
    scopes = {"jit_decode_k": {
        **{instruction_key(name): (scope, "forward")
           for name, scope in _SCOPE_OF.items()},
        instruction_key(_GROUPED): ("expert_ffn", "forward")}}
    return {
        "trace": trace, "cell": cell, "decode_chunk": 16,
        "trace_span": [0.9, 1.1], "scope_map": scopes,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        "log": types.SimpleNamespace(records=records),
        "program_spans": program_spans.ProgramSpans([], [chunk, chunk], []),
    }


def test_the_six_readers_on_a_synthetic_trace(monkeypatch):
    monkeypatch.setattr(scope_names, "_write_report", lambda obs: None)
    obs = _synthetic_obs()
    reads = {name: spec.load_module("metrics", name).read(obs)
             for name in _READERS + _JOINED}
    step_us = sum(us for _n, us in _STEP)                        # 2,000
    assert step_us == 2000
    assert reads["lfm2_conv_mixer_time_share"] == pytest.approx(
        100 * 100 / step_us)
    assert reads["lfm2_attention_time_share"] == pytest.approx(
        100 * 300 / step_us)
    assert reads["moe_routing_time_share"] == pytest.approx(
        100 * 2 * 50 / step_us)
    assert reads["moe_expert_ffn_time_share"] == pytest.approx(
        100 * 2 * 750 / step_us)
    c = obs["cell"].config
    lengths = [250.0] * 240
    assert swa_names.lengths_in_flight(obs, 1.0) == pytest.approx(lengths)
    assert lfm2_names.chunk_medians(obs) == (12 * 960, 384, 240)
    floor = lfm2_flops.decode_step_bytes(c, 384, lengths, 240) / 819e9
    assert floor > lfm2_flops.decode_step_flops(c, lengths, 12 * 960) / 197e12
    assert reads["decode_step_roofline"] == pytest.approx(
        100 * floor / (step_us * 1e-6), rel=1e-3)
    grouped = moe_flops.expert_matmul_bytes(c, 384, 12 * 960) / 819e9
    assert reads["moe_expert_matmul_roofline"] == pytest.approx(
        100 * grouped / (2 * 700e-6), rel=1e-3)
    # the busiest expert's 40 rows a step against 12 x 960 over the 12 x 32
    # pairs that HAVE experts (14 x 32 would read 1.56)
    assert reads["moe_expert_load_imbalance"] == pytest.approx(40 / 30)


def test_a_program_without_a_conv_layer_reads_nothing(monkeypatch):
    """Another cell's observations, the parent commit's (whose spans carry
    no state rows for this model, whose map knows no such scope) and an
    untraced run: every reader returns None, none raises."""
    monkeypatch.setattr(scope_names, "_write_report", lambda obs: None)
    obs = _synthetic_obs()
    other = dict(obs, cell=types.SimpleNamespace(
        config=_json("configs", "olmoe-1b-7b"),
        workload=obs["cell"].workload))
    parent = _synthetic_obs()
    parent["scope_map"] = {"jit_decode_k": {
        key: ("ffn", "forward") for key in obs["scope_map"]["jit_decode_k"]}}
    parent["program_spans"] = program_spans.ProgramSpans(
        [], [{"k": 16, "tokens_kept": 1, "token_steps": 2}], [])
    no_trace = dict(obs, trace=None)
    for name in _READERS + _JOINED:
        read = spec.load_module("metrics", name).read
        if name in _READERS:        # the experts' are every expert cell's
            assert read(dict(other)) is None, name
        if name != "moe_expert_load_imbalance":     # reads spans alone
            assert read(dict(no_trace)) is None, name
        assert read(dict(parent)) is None, name
