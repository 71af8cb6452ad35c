"""The window/full-attention configuration's part of the benchmark:
``lib/swa_flops.py`` against hand-worked numbers; the programs the cell's
engine warms compiled at the REAL widths for a v5e that is described, not
attached (they fit, the two K/V pools occupy their own bytes, and the
decode program slices no ``[..., 4, 128]`` prefix out of a cache); a CPU
rehearsal of a toy windowed model through ``run.measure`` with
``smallthinker_decoder`` as its reference; and the six ``swa_*`` readers
on a synthetic trace made of instruction texts of the shapes a v5e
compile of the cell holds.
"""

import importlib
import json
import os
import shutil
import time
import types

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import (moe_flops, program, program_spans, spec,
                            swa_flops, swa_names, trace_reduce)
from benchmarks.tests import test_rehearsal
from benchmarks.tests.test_aot_real_widths import (  # noqa: F401
    _json, _on, compiled_kernels, kernels_by_name_and_scope, one_chip, topo)

os.environ.setdefault("TPU_LOG_DIR", "disabled")
CONFIG = "smallthinker-21b-a3b"
CELL = "smallthinker-21b-a3b.serve-long-prompt"
_READERS = ("swa_decode_attention_time_share",
            "swa_decode_attention_roofline",
            "swa_prefill_attention_time_share",
            "swa_prefill_attention_roofline", "swa_window_kv_read_share")


# ------------------------------------------------------------------ flops
def test_operations_and_bytes_by_hand():
    c = _json("configs", CONFIG)
    assert swa_flops.layer_counts(c) == (2, 6)
    assert c["sliding_window_layout"] == c["rope_layout"] == [0, 1, 1, 1] * 13
    assert c["program_fields"]["layer_pattern"] == [
        "attention", "window", "window", "window"]
    # a layer outside its experts: q, k, v, o, the router, two norms
    dense = 2 * 2560 * 3584 + 2 * 2560 * 512 + 2560 * 64
    assert moe_flops.dense_matmul_params_per_layer(c) == dense == 21_135_360
    assert moe_flops.expert_params(c) == 3 * 2560 * 768 == 5_898_240
    layer = dense + 2 * 2560 + 64 * 5_898_240
    assert layer == 398_627_840
    assert 8 * layer + 2 * 151_936 * 2560 + 2560 == c["parameters"] \
        == 3_966_937_600
    assert 52 * layer + 2 * 151_936 * 2560 + 2560 \
        == c["parameters_published_depth"]
    # K and V of a position of a layer: 2 x 4 x 128 bf16
    assert swa_flops.kv_bytes_per_key(c) == 2048
    # a slot at 16,384 positions: 2 full layers + 6 rings of 4,096
    assert (2 * 16_384 + 6 * 4_096) * 2048 == 117_440_512
    # rows of 1,000, 4,096 and 10,000 positions: a ring holds min(n, 4096)
    full, ring = swa_flops.keys_read(c, [1_000, 4_096, 10_000])
    assert (full, ring) == (2 * 15_096, 6 * (1_000 + 4_096 + 4_096))
    assert swa_flops.decode_attention_bytes(c, [10_000]) \
        == (2 * 10_000 + 6 * 4_096) * 2048
    assert swa_flops.decode_attention_flops(c, [10_000]) \
        == 4 * (2 * 10_000 + 6 * 4_096) * 28 * 128
    # a step of one row that touched 48 (layer, expert) pairs
    weights = 8 * dense + 2560 * 151_936 + 48 * 5_898_240
    assert swa_flops.decode_step_bytes(c, 48, [10_000]) \
        == 2 * weights + (2 * 10_000 + 6 * 4_096) * 2048
    # the band: every key up to the window, then the window's width
    assert swa_flops.band_pairs(3) == 6 == swa_flops.band_pairs(3, 4)
    assert swa_flops.band_pairs(6, 4) == 10 + 2 * 4
    n, w = 8_192, 4_096
    pairs = 2 * n * (n + 1) / 2 + 6 * (w * (w + 1) / 2 + (n - w) * w)
    assert swa_flops.prefill_attention_flops(c, n) == 4 * pairs * 28 * 128


def the_cells_entries(root=spec.ROOT):
    """What THIS cell reports, on the tree at ``root`` (the rehearsal's has
    a later PR's entries appended: nothing here counts the table or says
    what another family's names are)."""
    from benchmarks.tests.test_yardstick import (benchmark_at, cell_at,
                                                 reader_at)

    mine = {m["name"]: m for m in benchmark_at(root)["per_layer"]
            if m["name"] in _READERS}
    assert sorted(mine) == sorted(_READERS)
    for m in mine.values():
        assert CELL in m["workloads"]
        assert m["moves"] == "serve_output_tokens_per_s"
        assert callable(reader_at(root, m["name"]).read)
    cell = cell_at(root, CELL)
    reported = {e["name"] for e, _ in cell.readers("per_layer")}
    assert set(_READERS) <= reported
    # the step's floor is the file's: lib/swa_flops.py counts a ring's keys
    assert cell.config["roofline"] == "swa_flops"
    assert {"batch.decode_step_roofline", "moe_expert_matmul_roofline",
            "moe_expert_ffn_time_share", "moe_routing_time_share",
            "moe_expert_load_imbalance"} <= reported
    assert {e["name"] for e, _ in cell.readers("end_to_end")} == {
        "serve_output_tokens_per_s", "setup_s"}


def test_the_readers_names_lead_to_files():
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
    engine = _json("workloads", CELL)["engine"]
    assert engine["max_len"] == 16384 and not engine["paged"]
    assert engine["prefill_buckets"] == [4096, 8192, 12288]


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


def test_the_engines_programs_fit_and_the_pools_occupy_their_own_bytes(
        one_chip):
    """At the cell's slots x 16,384: weights 7.93 GB, a slot's K/V 117.4 MB in
    both pools together (no padded multiple: the compiler's own account of
    the arguments), the widest prefill's scratch inside the chip, four
    Mosaic attention calls a period in the decode step and no slice of a
    ``[..., s_active, 4, 128]`` prefix."""
    from ray_tpu.models import llama_serve

    cfg, slots, max_len, decode, prefill = _programs(one_chip)
    pools = llama_serve.cache_pools(cfg, slots, max_len)
    assert {k: v[0] for k, v in pools.items()} == {
        "kv_full": slots * 2 * 16_384 * 2048,
        "kv_window": slots * 6 * 4_096 * 2048}
    cache_bytes = sum(v[0] for v in pools.values())
    assert cache_bytes == slots * 117_440_512
    weights = 2 * _json("configs", CONFIG)["parameters"]
    for lowered, scratch in ((decode, 0.5e9), (prefill, 3.0e9)):
        compiled = lowered.compile()   # RESOURCE_EXHAUSTED if it does not fit
        memory = compiled.memory_analysis()
        held = memory.argument_size_in_bytes
        assert weights + cache_bytes <= held < weights + cache_bytes + 1e6
        assert memory.alias_size_in_bytes >= cache_bytes   # updated in place
        assert memory.temp_size_in_bytes < scratch
        text = compiled.as_text()
        # the kernels the cell's readers name, under the scope they sum
        # (AOT, PR 32: 4 attention + 16 grouped matmul and metadata)
        kernels = kernels_by_name_and_scope(text)
        assert kernels["ragged-dot-none", "expert_ffn"] >= 3
        attention = ("decode_attention", "decode_attention") \
            if lowered is decode \
            else ("flash_prefill_attention", "flash_attention.fwd")
        assert kernels[attention] >= 1
        assert not [line for line in text.splitlines()
                    if "dynamic-slice(" in line
                    and ",4,128]" in line.split(" dynamic-slice(")[0]]


# ------------------------------------------------- a rehearsal on the CPU
TINY_WINDOWED = {
    "name": "tiny-windowed", "source": "none (test, window layers)",
    "reference": "smallthinker_decoder", "roofline": "swa_flops",
    "vocab_size": 256,
    "hidden_size": 64, "num_hidden_layers": 8, "num_attention_heads": 8,
    "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 32,
    "moe_ffn_hidden_size": 32, "moe_num_primary_experts": 8,
    "moe_num_active_primary_experts": 3, "num_experts": 8,
    "num_experts_per_tok": 3, "moe_primary_router_apply_softmax": True,
    "norm_topk_prob": True, "rope_layout": [0, 1, 1, 1] * 2,
    "sliding_window_layout": [0, 1, 1, 1] * 2, "sliding_window_size": 16,
    "rope_scaling": None, "max_position_embeddings": 256,
    "rope_theta": 10000, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "reduced": [], "assumed": ["test"],
    # float32 throughout: a request's gap against the reference is then
    # the order of float32 sums whichever requests a window completes
    "dtype": {"serve": "float32"},
    "program_fields": {
        "moe_experts": 8, "moe_top_k": 3, "moe_norm_topk": True,
        "moe_router_input": "layer", "moe_activation": "relu",
        "layer_pattern": ["attention", "window", "window", "window"],
        "window_size": 16, "nope_kinds": ["attention"], "dtype": "float32"},
}
TINY_CELL = "tiny-windowed.tiny-closed"


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark with a toy windowed configuration dropped
    in and its cell appended wherever the real one is."""
    root = tmp_path_factory.mktemp("bench_smallthinker")
    bench = str(root / "benchmarks")
    shutil.copytree(spec.BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "out", "__pycache__", "tests"))

    def drop(rel, payload):
        path = os.path.join(bench, rel)
        assert not os.path.exists(path), f"{rel} would be an edit"
        with open(path, "w") as f:
            json.dump(payload, f)

    drop("configs/tiny-windowed.json", TINY_WINDOWED)
    drop("traffic/tiny-closed.json", test_rehearsal.TRAFFIC["tiny-closed"])
    drop(f"workloads/{TINY_CELL}.json",
         dict(test_rehearsal.SERVE, name=TINY_CELL, config="tiny-windowed",
              traffic="tiny-closed", why="test"))
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    benchmark["configs"].append(
        {"name": "tiny-windowed", "source": TINY_WINDOWED["source"],
         "reduced": [], "file": "benchmarks/configs/tiny-windowed.json",
         "why": "test"})
    benchmark["workloads"].append(
        {"name": TINY_CELL, "config": "tiny-windowed",
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


def test_a_toy_windowed_model_runs_end_to_end_on_the_cpu(tree, cpu_peaks,
                                                         monkeypatch):
    """One traced run of the toy cell through ``run.measure``, its
    prefills through the banded flash forward (interpreted): ``correct``
    against ``smallthinker_decoder`` on requests that wrap a ring of 16
    several times, nothing failed, the metrics the cell joins and the
    program's own count of keys by pool are there; what only a device
    trace knows is left out on a CPU, not invented."""
    from benchmarks.tests.test_yardstick import names_lead_to_files
    from ray_tpu.models import llama

    monkeypatch.setattr(llama, "FLASH_PREFILL_FROM", 16)
    # this file's compiles are for a described chip (``compiled_kernels``);
    # this run is on the CPU, its kernels interpreted
    monkeypatch.setattr(
        importlib.import_module("ray_tpu.ops.flash_attention"),
        "_use_interpret", lambda: True)
    bench, benchmark_json = tree
    names_lead_to_files(os.path.dirname(benchmark_json))
    result, obs = bench_run.measure(
        ["--workload", TINY_CELL, "--seed", "2147486032", "--seconds", "3",
         "--trace", "1"],
        allow_platforms=("cpu",), bench_dir=bench,
        benchmark_json=benchmark_json, t_process=time.perf_counter())
    assert result["correct"] is True, obs["checks"]
    assert result["failed"] == 0 < result["attempted"]
    assert obs["cell"].reference.__name__.endswith("smallthinker_decoder")
    assert len(obs["logit_gaps"]) == 4 and obs["logit_gap_max"] < 1e-2
    metrics = result["metrics"]
    assert {"batch.slot_wait_p50_ms", "batch.token_burst_gap_p50_ms",
            "swa_window_kv_read_share",
            "batch.decode_slot_utilization", "moe_expert_load_imbalance",
            "batch.prefill_padding_share", "window_compiles"} <= set(metrics)
    assert not {"batch.decode_step_roofline", "swa_decode_attention_roofline",
                "swa_prefill_attention_roofline",
                "moe_expert_matmul_roofline"} & set(metrics)
    spans = program_spans.collect(obs)
    chunk = next(c for c in spans.chunks
                 if c.get("kv_full_positions_attended"))
    assert chunk["kv_window_bucket"] == 16 == TINY_WINDOWED[
        "sliding_window_size"]
    assert (chunk["kv_full_layers"], chunk["kv_window_layers"]) == (2, 6)
    assert chunk["kv_window_positions_attended"] <= 16 * chunk["active"]
    assert 0 < metrics["swa_window_kv_read_share"]["value"] < 100
    group = next(g for g in spans.groups if g.get("window_band_share"))
    b = group["bucket"]
    assert group["window_band_share"] == pytest.approx(
        16 * (2 * b - 15) / (b * b), abs=1e-4)


def test_the_published_width_check_rehearsed_at_toy_size(tree, capsys):
    """``tools/window_check.py`` (what is run on the chip at the published
    widths: one prompt that laps the ring through both pools against the
    reference, then four broken programs) end to end on the toy: the
    intact reply within rounding of the reference and no expert choice
    flipped; an un-roped window layer, a misplaced router and weights in
    float8's mantissa flip most positions' choices, the first two off the
    reference by more than the benchmark's margin; and one key too many in a window of 16 by far less (a seventeenth of
    a layer's attention: what holds the window's edge is
    ``tests/test_smallthinker_serve.py`` in float32, not this margin)."""
    from benchmarks.tools import window_check

    bench, _ = tree
    assert window_check.main([
        "--config", "tiny-windowed", "--seed", "2147486033", "--bench-dir",
        bench, "--prompt", "40", "--new-tokens", "24", "--bucket", "64",
        "--max-len", "128", "--variants",
        ",".join(window_check.VARIANTS)]) == 0
    done = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(done) == {"event", *window_check.VARIANTS}
    assert done["intact"]["gap_max"] < 1e-3
    assert done["intact"]["flipped_share"] == 0.0
    assert done["intact"]["swaps_allowed"] == 8 + 9 * 24 // 50
    assert 0.01 < done["window_off_by_one"]["gap_max"] < 0.25
    for variant in ("unroped_window", "router_after", "float8_weights"):
        assert done[variant]["flipped_share"] > 0.5, variant
    assert done["unroped_window"]["gap_max"] > 0.25
    # 24 positions decide little by count (12 may be swaps): only what
    # moves every position is judged off the margin here
    assert done["router_after"]["over_swap_gap"] > 12
    assert done["router_after"]["judged_max"] > 0.25


# --------------------------------------- the readers on a synthetic trace
# One period of one decode step (a full layer and a window layer stand
# for the four) and one prefill of 8,192 positions, in instruction texts
# of the shapes the cell's programs compile to for a v5e (AOT, PR 32; cut
# to what the readers look at), durations in microseconds.
_DECODE_LAYERS = [
    ("%fusion.900 = bf16[32,3584]{1,0} fusion(bf16[32,1,2560] %x, "
     "bf16[8,2560,3584] %wq)", 30.0),
    ("%decode_attention.7 = bf16[32,32,128]{2,1,0} custom-call(s32[1] "
     "%layer, s32[32] %n, bf16[32,32,128] %q, f32[32,1024] %bias, "
     "bf16[2,32,65536,128] %k, bf16[2,32,65536,128] %v), "
     "custom_call_target=\"tpu_custom_call\"", 600.0),        # full pool
    ("%decode_attention.9 = bf16[32,32,128]{2,1,0} custom-call(s32[1] "
     "%layer, s32[32] %n, bf16[32,32,128] %q, f32[32,1024] %bias, "
     "bf16[6,32,16384,128] %wk, bf16[6,32,16384,128] %wv), "
     "custom_call_target=\"tpu_custom_call\"", 300.0),        # a ring
    ("%ragged-dot-none.2 = f32[192,768]{1,0} custom-call(bf16[192,2560] "
     "%rows, bf16[512,2560,768] %w_gate), "
     "custom_call_target=\"tpu_custom_call\"", 2070.0),
]
_PREFILL_LAYER = [
    ("%fusion.77 = bf16[1,8192,3584]{2,1,0} fusion(bf16[1,8192,2560] %x, "
     "bf16[8,2560,3584] %wq)", 900.0),
    ("%flash_prefill_attention.3 = (bf16[1,28,8192,128]{3,2,1,0}, "
     "f32[1,28,8192,1]{3,2,1,0}) custom-call(bf16[1,28,8192,128] %q, "
     "bf16[1,4,8192,128] %k, bf16[1,4,8192,128] %v), "
     "custom_call_target=\"tpu_custom_call\"", 3000.0),
    ("%ragged-dot-none.5 = f32[49152,768]{1,0} custom-call("
     "bf16[49152,2560] %rows, bf16[512,2560,768] %w_gate), "
     "custom_call_target=\"tpu_custom_call\"", 6100.0),
]


def _synthetic_obs(steps=16, runs=2):
    ops, modules, t = [], [], 0.0
    for run in range(runs):
        start, body = t, []
        for _ in range(steps * 4):
            for name, us in _DECODE_LAYERS:
                body.append((t, t + us * 1e-6, name))
                t += us * 1e-6
        ops.append((start, t, "%while.7 = (s32[]) while((s32[]) %t), "
                    "body=%step"))
        ops.extend(body)
        modules.append((start, t, f"jit_decode_k({run})"))
        t += 1e-4
    start = t
    for _ in range(8):
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
    chunk = {"k": 16, "active": 30, "expert_rows": 16 * 8 * 30 * 6,
             "experts_touched": 16 * 8 * 60, "expert_rows_max": 16 * 9,
             "kv_full_positions_attended": 30 * 6000,
             "kv_window_positions_attended": 30 * 4096}
    group = {"bucket": 8192, "rows": 1, "prompt_tokens": 7000}
    return {
        "trace": trace, "cell": cell, "decode_chunk": 16,
        "trace_span": [0.9, 1.1],
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        "log": types.SimpleNamespace(records=records),
        "program_spans": program_spans.ProgramSpans(
            [], [chunk, chunk], [group]),
    }


def test_the_six_readers_on_a_synthetic_trace():
    obs = _synthetic_obs()
    reads = {name: spec.load_module("metrics", name).read(obs)
             for name in (*_READERS, "decode_step_roofline")}
    period_us = sum(us for _n, us in _DECODE_LAYERS)             # 3,000
    assert reads["swa_decode_attention_time_share"] == pytest.approx(
        100 * 900 / period_us)
    prefill_us = sum(us for _n, us in _PREFILL_LAYER)            # 10,000
    assert reads["swa_prefill_attention_time_share"] == pytest.approx(
        100 * 3000 / prefill_us)
    assert reads["swa_window_kv_read_share"] == pytest.approx(
        100 * 4096 / 6000)
    c = obs["cell"].config
    lengths = [6000.0] * 30
    assert swa_names.lengths_in_flight(obs, 1.0) == pytest.approx(lengths)
    step_s = 4 * period_us * 1e-6
    floor = swa_flops.decode_step_bytes(c, 8 * 60, lengths) / 819e9
    assert reads["decode_step_roofline"] == pytest.approx(
        100 * floor / step_s, rel=1e-3)
    attention = swa_flops.decode_attention_bytes(c, lengths) / 819e9
    assert reads["swa_decode_attention_roofline"] == pytest.approx(
        100 * attention / (4 * 900e-6), rel=1e-3)
    # 8 traced calls, each a layer's share of a 7,000-token prompt's band
    band = swa_flops.prefill_attention_flops(c, 7000) / 197e12
    assert reads["swa_prefill_attention_roofline"] == pytest.approx(
        100 * band / (8 * 3000e-6), rel=1e-3)
    for name in reads:
        assert 0 < reads[name] < 100, name


def test_a_program_without_window_layers_reads_nothing():
    """A dense cell's observations, the parent commit's (whose spans carry
    no keys by pool and whose trace holds no such kernel) and an untraced
    run: every reader returns None, none raises."""
    obs = _synthetic_obs()
    dense = dict(obs, cell=types.SimpleNamespace(
        config=_json("configs", "internlm2-1.8b"),
        workload=obs["cell"].workload))
    parent = _synthetic_obs()
    parent["trace"] = trace_reduce.Trace(
        [trace_reduce.DeviceTrace(
            0, [(s, e, n.replace("decode_attention", "fusion")
                 .replace("flash_prefill_attention", "fusion"))
                for s, e, n in obs["trace"].devices[0].ops],
            obs["trace"].devices[0].modules)], [], 0.0, 1.0)
    parent["program_spans"] = program_spans.ProgramSpans(
        [], [{"k": 16, "tokens_kept": 1, "token_steps": 2}], [])
    no_trace = dict(obs, trace=None)
    for name in _READERS:
        read = spec.load_module("metrics", name).read
        if name != "swa_window_kv_read_share":      # reads spans alone
            assert read(dict(dense)) is None, name
            assert read(dict(no_trace)) is None, name
        assert read(dict(parent)) is None, name
    step = spec.load_module("metrics", "decode_step_roofline").read
    assert step(dict(parent)) is None and step(dict(no_trace)) is None
