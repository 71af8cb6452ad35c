"""The hybrid configuration's part of the benchmark: ``lib/ssm_flops.py``
against hand-worked numbers; every program the cell's engine warms
compiled at the REAL widths for a v5e that is described, not attached
(they fit, eight more slots compile too, and the decode program updates
the recurrent state in place); a CPU rehearsal of a toy hybrid through
``run.measure`` with ``granite_hybrid_decoder`` as its reference; and the
four ``ssm_*`` readers on a synthetic trace made of instruction texts of
the shapes a v5e compile of the cell holds.
"""

import json
import os
import re
import shutil
import time
import types

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import (program, program_spans, spec, ssm_flops,
                            ssm_names, trace_reduce)
from benchmarks.tests import test_rehearsal
# ``topo`` is described inside that file's fixture (never at import);
# ``compiled_kernels`` keeps these compiles out of the persistent cache.
from benchmarks.tests.test_aot_real_widths import (  # noqa: F401
    _json, _on, compiled_kernels, one_chip, topo)

os.environ.setdefault("TPU_LOG_DIR", "disabled")
CONFIG = "granite-4.0-h-micro"
CELL = "granite-4.0-h-micro.serve-batch-decode"


# ------------------------------------------------------------------ flops
def test_operations_and_bytes_by_hand():
    c = _json("configs", CONFIG)
    assert ssm_flops.layer_counts(c) == (4, 36)
    assert c["layer_types"] == (["mamba"] * 5 + ["attention"]
                                + ["mamba"] * 4) * 4
    assert c["program_fields"]["layer_pattern"] == c["layer_types"][:10]
    # a Mamba mixer: in 2048 x (4096 + 4352 + 64), out 4096 x 2048; conv
    # 4352 x 4 + 4352, three vectors of 64, the gated norm's 4096
    assert ssm_flops.mamba_dims(c) == (4096, 4352, 8512)
    mixer = 2048 * 8512 + 4096 * 2048
    assert ssm_flops.mixer_matmul_params(c) == mixer == 25_821_184
    small = 4352 * 4 + 4352 + 3 * 64 + 4096
    assert mixer + small == 25_847_232
    mlp = 3 * 2048 * 8192
    mamba_layer = mixer + small + 2 * 2048 + mlp
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    attention_layer = attention + 2 * 2048 + mlp
    assert (mamba_layer, attention_layer) == (76_182_976, 60_821_504)
    assert ssm_flops.attention_matmul_params(c) == attention
    embedding = 100_352 * 2048
    assert ssm_flops.param_count(c) == 36 * mamba_layer \
        + 4 * attention_layer + embedding + 2048 \
        == 3_191_396_096 == c["parameters"]
    # a slot: 36 x 64 x 64 x 128 state elements in float32 (the file's
    # dtype.ssm_state); 36 x 3 x 4352 conv inputs in bfloat16; K and V of
    # 4 layers x 8 heads x 64
    assert c["dtype"] == {"serve": "bfloat16", "residual": "float32",
                          "ssm_state": "float32"}
    assert ssm_flops.state_bytes_per_slot(c) == {"ssm": 75_497_472,
                                                 "conv": 940_032}
    assert ssm_flops.kv_bytes_per_token(c) == 2 * 4 * 8 * 64 * 2 == 8_192
    assert ssm_flops.state_bytes_per_slot(
        dict(c, dtype={"serve": "bfloat16", "ssm_state": "bfloat16"})
    )["ssm"] == 37_748_736
    # a decode step advancing 100 slots that hold 20,000 positions
    weights = 2 * ssm_flops.matmul_params(c)
    assert weights == 2 * (36 * (mixer + mlp) + 4 * (attention + mlp)
                           + embedding) == 6_380_584_960
    assert ssm_flops.state_update_bytes(c, 100) == 2 * 100 * 75_497_472
    assert ssm_flops.decode_step_bytes(c, 100, 20_000) == weights \
        + 2 * 100 * (75_497_472 + 940_032) + 20_000 * 8_192
    assert ssm_flops.state_update_flops(c, 100) \
        == 5 * 100 * 36 * 64 * 64 * 128
    assert ssm_flops.decode_step_flops(c, 100, 20_000) \
        == 2 * (weights // 2) * 100 + 4 * 20_000 * 32 * 64 * 4 \
        + ssm_flops.state_update_flops(c, 100)
    # bandwidth bounds the step and the update alike, by far
    assert ssm_flops.decode_step_bytes(c, 100, 20_000) / 819e9 \
        > 5 * ssm_flops.decode_step_flops(c, 100, 20_000) / 197e12
    assert ssm_flops.state_update_bytes(c, 100) / 819e9 \
        > 5 * ssm_flops.state_update_flops(c, 100) / 197e12


def test_the_programs_state_is_what_the_yardstick_counts():
    """``lib/ssm_flops.py`` counts from the published keys alone; the
    program's own cache of the configuration holds exactly those bytes,
    and its parameter tree that many weights."""
    import jax

    from ray_tpu.models import llama, llama_serve

    c = _json("configs", CONFIG)
    cfg = program.llama_config(c)
    assert llama_serve.state_bytes_per_slot(cfg) \
        == ssm_flops.state_bytes_per_slot(c)
    engine = _json("workloads", CELL)["engine"]
    pools = llama_serve.cache_pools(cfg, engine["max_slots"],
                                    engine["max_len"])
    assert pools["kv"][0] == engine["max_slots"] * engine["max_len"] \
        * ssm_flops.kv_bytes_per_token(c)
    assert pools["ssm"] == (engine["max_slots"] * 75_497_472,
                            c["dtype"]["ssm_state"])
    shapes = jax.eval_shape(lambda k: llama.init_params(k, cfg, cfg.dtype),
                            jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == c["parameters"]


# ------------------------------------------- the real widths, for the chip
def _engine_programs(one_chip, max_slots=None):
    """Yield (label, thunk that compiles) for EVERY program the cell's
    engine warms: each prefill shape ``llm.prefill_shapes`` gives, each
    decode bucket."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama, llama_serve
    from ray_tpu.serve import llm

    engine = _json("workloads", CELL)["engine"]
    slots = max_slots or engine["max_slots"]
    max_len = engine["max_len"]
    buckets = tuple(engine["prefill_buckets"])
    cfg = program.llama_config(_json("configs", CONFIG),
                               max_seq_len=max_len)
    params = _on(one_chip, jax.eval_shape(
        lambda k: llama.init_params(k, cfg, cfg.dtype), jax.random.key(0)))
    cache = _on(one_chip, jax.eval_shape(
        lambda: llama_serve.init_cache(cfg, slots, max_len)))
    prefill = llama_serve.build_prefill(cfg)
    decode_k = llama_serve.build_decode_k(cfg)

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    decode_buckets, b = [], max(64, buckets[0])
    while b < max_len:
        decode_buckets.append(b)
        b *= 2
    decode_buckets.append(max_len)
    for s_active in decode_buckets:
        yield f"decode_k s_active={s_active}", (
            lambda s=s_active: decode_k.lower(
                params, cache, arr(jnp.int32, slots), arr(jnp.int32, slots),
                arr(jnp.int32, slots), arr(jnp.int32, slots),
                arr(jnp.bool_, slots), arr(jnp.bool_, slots),
                k=16, s_active=s).compile())
    for rows, bucket in llm.prefill_shapes(
            tuple(sorted(engine.get("prefill_groups", llm.PREFILL_GROUPS))),
            buckets, slots):
        yield f"prefill {rows}x{bucket}", (
            lambda g=rows, p=bucket: prefill.lower(
                params, cache, arr(jnp.int32, g, p), arr(jnp.int32, g),
                arr(jnp.int32, g)).compile())


_VIEWS = ("parameter", "get-tuple-element", "tuple", "bitcast", "while")


def test_engine_programs_fit_one_chip_and_the_state_is_updated_in_place(
        one_chip):
    """Every program the cell's engine warms compiles for one 16 GB chip
    (the compiler raises RESOURCE_EXHAUSTED if not).  The decode program
    holds no second recurrent state: its scratch is far under the state's
    6.0 GB, and the ONLY thing any of its loops makes that has the stacked
    state's shape, a layer's or a slot's is the result of the
    ``ssm_state_update`` kernel, one Mosaic call a Mamba layer of the
    period, which is the loops' carry itself (operand aliased to result);
    nothing is a copy, a select, a slice or a fusion of it; nor is a
    period's or a stack's worth of layer weights made in a loop."""
    engine = _json("workloads", CELL)["engine"]
    c = _json("configs", CONFIG)
    slots, max_len = engine["max_slots"], engine["max_len"]
    compiled = {}
    for label, compile_it in _engine_programs(one_chip):
        compiled[label] = compile_it()
    assert len(compiled) == 4 + 9        # decode buckets; 3 rungs x 3
    decode = compiled[f"decode_k s_active={max_len}"]
    memory = decode.memory_analysis()
    weights = 2 * ssm_flops.param_count(c)
    per_slot = ssm_flops.state_bytes_per_slot(c)
    state = slots * per_slot["ssm"]
    cache = state + slots * per_slot["conv"] \
        + slots * max_len * ssm_flops.kv_bytes_per_token(c)
    assert (slots, weights, state, cache) == (
        80, 6_382_792_192, 6_039_797_760, 6_450_544_640)
    assert memory.argument_size_in_bytes < weights + cache + (1 << 20)
    assert memory.temp_size_in_bytes < state / 4       # AOT, PR 30: 0.8 GB

    stacked = (36, slots, 128, 4096)
    hlo = decode.as_text()
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) \(.*\{\s*$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    kernels = 0
    for body, lines in comps.items():
        if body.startswith("fused_"):
            continue                      # a fusion's inside makes nothing
        for line in lines:
            made = re.match(r"\s*(?:ROOT )?%(\S+) = (.*?) ([\w-]+)\(", line)
            if not made or made.group(3) in _VIEWS:
                continue
            for dims in re.findall(r"\w+\[([\d,]*)\]", made.group(2)):
                dims = tuple(int(d) for d in dims.split(",") if d)
                if dims == stacked:
                    assert made.group(3) == "custom-call" \
                        and made.group(1).startswith("ssm_state_update") \
                        and "tpu_custom_call" in line, line[:160]
                    kernels += 1
                elif dims[-2:] == stacked[-2:] or (
                        dims[:1] in ((9,), (10,), (36,), (40,))
                        and dims[-2:] in ((2048, 8448), (2048, 8192),
                                          (8192, 2048), (4096, 2048))):
                    raise AssertionError(line[:200])
    assert kernels == 9                   # the Mamba layers of ONE period
    assert hlo.count("output_to_operand_aliasing={{0}: (3, {})}") == 9


def test_eight_more_slots_compile_but_leave_the_check_no_room(one_chip):
    """88 x 512 compiles too -- 13.48 GB of arguments and at most 1.64 GB
    of scratch, 0.6 GB under the chip's 15.75 --; the cell keeps 80
    because ``correct`` runs the float32 reference beside the loaded
    engine and needs more than that (PERF.md section 4's one sizing rule,
    its second half); 96 would be refused by the compiler itself."""
    assert _json("workloads", CELL)["engine"]["max_slots"] + 8 == 88
    largest = 0
    for label, compile_it in _engine_programs(one_chip, max_slots=88):
        # the programs with the most scratch: the largest attended
        # bucket, and each row count at the largest prefill bucket
        if label.endswith(("=512", "x256", "8x64")):
            memory = compile_it().memory_analysis()
            largest = max(largest, memory.argument_size_in_bytes
                          + memory.temp_size_in_bytes)
    assert 14.5e9 < largest < 15.75 * 2 ** 30


# ------------------------------------------------- a rehearsal on the CPU
TINY_HYBRID = {
    "name": "tiny-hybrid", "source": "none (test, state-space layers)",
    "reference": "granite_hybrid_decoder", "roofline": "ssm_flops",
    "vocab_size": 256,
    "hidden_size": 64, "num_hidden_layers": 6, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "layer_types": ["mamba", "mamba", "attention"] * 2,
    "attention_multiplier": 0.0625, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 8,
    "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_chunk_size": 8,
    "mamba_proj_bias": False, "position_embedding_type": "nope",
    "max_position_embeddings": 256, "rope_theta": 10000,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
    "hidden_act": "silu", "bias": False, "reduced": [],
    "assumed": ["test"],
    "dtype": {"serve": "float32", "ssm_state": "float32"},
    # float32 throughout: any request's gap against the reference is then
    # the order of float32 sums (~1e-5 deviations), so ``correct`` does
    # not depend on WHICH requests a short window happened to complete
    "program_fields": {
        "layer_pattern": ["mamba", "mamba", "attention"], "rope": False,
        "attention_multiplier": 0.0625, "embedding_multiplier": 12,
        "residual_multiplier": 0.22, "logits_scaling": 8, "ssm_heads": 4,
        "ssm_head_dim": 16, "ssm_state": 16, "ssm_conv": 4, "ssm_chunk": 8,
        "ssm_state_dtype": "float32", "dtype": "float32"},
}
TINY_CELL = "tiny-hybrid.tiny-closed"


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark with a toy hybrid configuration dropped in
    and its cell appended wherever the real one is."""
    root = tmp_path_factory.mktemp("bench_granite")
    bench = str(root / "benchmarks")
    shutil.copytree(spec.BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "out", "__pycache__", "tests"))

    def drop(rel, payload):
        path = os.path.join(bench, rel)
        assert not os.path.exists(path), f"{rel} would be an edit"
        with open(path, "w") as f:
            json.dump(payload, f)

    drop("configs/tiny-hybrid.json", TINY_HYBRID)
    drop("traffic/tiny-closed.json", test_rehearsal.TRAFFIC["tiny-closed"])
    drop(f"workloads/{TINY_CELL}.json",
         dict(test_rehearsal.SERVE, name=TINY_CELL, config="tiny-hybrid",
              traffic="tiny-closed", why="test"))
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    benchmark["configs"].append(
        {"name": "tiny-hybrid", "source": TINY_HYBRID["source"],
         "reduced": [], "file": "benchmarks/configs/tiny-hybrid.json",
         "why": "test"})
    benchmark["workloads"].append(
        {"name": TINY_CELL, "config": "tiny-hybrid",
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


def test_a_toy_hybrid_runs_end_to_end_on_the_cpu(tree, cpu_peaks):
    """One traced run of the toy cell through ``run.measure``: ``correct``
    against ``granite_hybrid_decoder``, nothing failed, the metrics the
    cell joins and the program's own count of advanced states are there;
    what only a device trace knows is left out on a CPU, not invented."""
    from benchmarks.tests.test_yardstick import names_lead_to_files

    bench, benchmark_json = tree
    names_lead_to_files(os.path.dirname(benchmark_json))
    result, obs = bench_run.measure(
        ["--workload", TINY_CELL, "--seed", "2147486030", "--seconds", "3",
         "--trace", "1"],
        allow_platforms=("cpu",), bench_dir=bench,
        benchmark_json=benchmark_json, t_process=time.perf_counter())
    assert result["correct"] is True, obs["checks"]
    assert result["failed"] == 0 < result["attempted"]
    assert obs["cell"].reference.__name__.endswith("granite_hybrid_decoder")
    assert len(obs["logit_gaps"]) == 4 and obs["logit_gap_max"] < 1e-2
    metrics = result["metrics"]
    assert {"batch.slot_wait_p50_ms", "batch.token_burst_gap_p50_ms",
            "batch.decode_slot_utilization", "batch.decode_kv_read_share",
            "batch.prefill_padding_share", "window_compiles"} <= set(metrics)
    assert not {"batch.decode_step_roofline", "ssm_state_update_time_share",
                "ssm_state_update_roofline",
                "ssm_prefill_scan_time_share"} & set(metrics)
    assert 0 < ssm_names.rows_a_step(obs) <= 4             # 4 slots
    chunk = next(c for c in program_spans.collect(obs).chunks
                 if c.get("state_rows_updated"))
    assert chunk["state_bytes"] == 2 * chunk["state_rows_updated"] * sum(
        ssm_flops.state_bytes_per_slot(TINY_HYBRID).values())


def test_the_published_width_check_rehearsed_at_toy_size(tree, capsys):
    """``tools/hybrid_check.py`` (what is run on the chip at the published
    widths: engine against reference, then two slots' states swapped) end
    to end on the toy: intact replies within rounding of the reference,
    the swapped slots off it, the others untouched."""
    from benchmarks.tools import hybrid_check

    bench, _ = tree
    assert hybrid_check.main([
        "--config", "tiny-hybrid", "--seed", "2147486031", "--bench-dir",
        bench, "--state-dtypes", "float32", "--prompts", "1,7,8,9,20",
        "--new-tokens", "64", "--max-len", "128", "--buckets",
        "8,16,32"]) == 0
    done = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert 0.003 < done["logit_deviation"] < 0.03
    assert max(g for g, _ in done["float32"].values()) < 1e-2
    assert set(done["float32"]) == {"1", "7", "8", "9", "20",
                                    "reused_slot"}
    # Under the INITIAL dt and D the skip path outweighs the state (the
    # tool measures by how much at the published widths): the swapped
    # slots move, though not past the margin as in tests/test_granite_serve
    swapped = done["swapped_states"]
    assert max(swapped["7"][0], swapped["9"][0]) > 1e-2
    assert max(swapped[n][0] for n in ("1", "8", "20")) < 1e-2


# --------------------------------------- the readers on a synthetic trace
# One Mamba layer of one decode step, and one of a prefill group, in
# instruction texts of the shapes the cell's programs compile to for a
# v5e (AOT, PR 30; cut to what the readers look at), durations in
# microseconds.
_DECODE_LAYER = [
    ("%fusion.1380 = bf16[80,8448]{1,0} fusion(bf16[80,1,2048] %x, "
     "bf16[36,2048,8448] %ssm_in)", 60.0),                   # in-projection
    ("%fusion.1441 = bf16[36,3,80,4352]{3,2,1,0} fusion(bf16[36,3,80,4352]"
     " %conv, bf16[80,4352] %xbc)", 3.0),                   # conv window
    ("%ssm_state_update.100 = (f32[36,80,128,4096]{3,2,1,0}, "
     "f32[80,1,4096]{2,1,0}) custom-call(s32[1] %layer, s32[80] %block, "
     "s32[80] %mode, f32[36,80,128,4096] %ssm, f32[80,1,4096] %decay), "
     "custom_call_target=\"tpu_custom_call\"", 500.0),        # the kernel
    ("%fusion.1390 = bf16[80,1,2048]{2,1,0} fusion(bf16[80,4096] %y, "
     "bf16[36,4096,2048] %ssm_out)", 25.0),                  # out
    ("%fusion.1395 = bf16[80,8192]{1,0} fusion(bf16[80,1,2048] %x, "
     "bf16[40,2048,8192] %w_gate)", 112.0),                  # MLP
]
_PREFILL_LAYER = [
    ("%fusion.77 = bf16[8,256,8448]{2,1,0} fusion(bf16[8,256,2048] %x, "
     "bf16[36,2048,8448] %ssm_in)", 900.0),
    ("%fusion.81 = f32[8,1,64,256,256]{4,3,2,1,0} fusion(f32[8,1,64,256] "
     "%acum)", 200.0),                                       # decay
    ("%fusion.83 = f32[8,1,256,64,64]{4,3,2,1,0} fusion(bf16[8,1,64,256,256]"
     " %mix, bf16[8,1,256,64,64] %x)", 300.0),               # mix @ x
    ("%fusion.85 = f32[8,1,64,64,128]{4,3,2,1,0} fusion(bf16[8,1,256,64,64]"
     " %xw, bf16[8,1,256,128] %b)", 60.0),                   # chunk states
    ("%fusion.88 = f32[36,80,128,4096]{3,2,1,0} fusion("
     "f32[36,80,128,4096] %ssm, f32[8,128,4096] %final)", 40.0),  # insert
    ("%fusion.90 = bf16[8,256,8192]{2,1,0} fusion(bf16[8,256,2048] %x, "
     "bf16[40,2048,8192] %w_gate)", 1500.0),
]


def _synthetic_obs(layers=36, steps=16, runs=2):
    ops, modules, t = [], [], 0.0
    for run in range(runs):
        start, body = t, []
        for _ in range(steps * layers):
            for name, us in _DECODE_LAYER:
                body.append((t, t + us * 1e-6, name))
                t += us * 1e-6
        ops.append((start, t, "%while.7 = (s32[]) while((s32[]) %t), "
                    "body=%step"))
        ops.extend(body)
        modules.append((start, t, f"jit_decode_k({run})"))
        t += 1e-4
    start = t
    for _ in range(layers):
        for name, us in _PREFILL_LAYER:
            ops.append((t, t + us * 1e-6, name))
            t += us * 1e-6
    modules.append((start, t, "jit_prefill(9)"))
    trace = trace_reduce.Trace(
        [trace_reduce.DeviceTrace(0, ops, modules)], [], 0.0, t)
    cell = types.SimpleNamespace(config=_json("configs", CONFIG),
                                 workload=_json("workloads", CELL),
                                 bench_dir=spec.BENCH_DIR, name=CELL)
    # 60 sequences in flight, each 150 positions at the span's middle
    records = [types.SimpleNamespace(
        ok=True, got_tokens=101, sent=0.0, ttft_ms=0.0, done=2.0,
        prompt_tokens=99) for _ in range(60)]
    chunk = {"k": 16, "state_rows_updated": 60 * 16,
             "state_bytes": 2 * 60 * 16 * (75_497_472 + 940_032)}
    return {
        "trace": trace, "cell": cell, "decode_chunk": 16,
        "trace_span": [0.9, 1.1],
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        "log": types.SimpleNamespace(records=records),
        "program_spans": program_spans.ProgramSpans([], [chunk, chunk], []),
    }


_READERS = ("decode_step_roofline", "ssm_state_update_time_share",
            "ssm_state_update_roofline", "ssm_prefill_scan_time_share")


def test_the_four_readers_on_a_synthetic_trace():
    obs = _synthetic_obs()
    reads = {name: spec.load_module("metrics", name).read(obs)
             for name in _READERS}
    layer_us = sum(us for _n, us in _DECODE_LAYER)               # 700
    # the kernel; not the conv window's fusion
    assert reads["ssm_state_update_time_share"] == pytest.approx(
        100 * 500 / layer_us)
    prefill_us = sum(us for _n, us in _PREFILL_LAYER)            # 3,000
    assert reads["ssm_prefill_scan_time_share"] == pytest.approx(
        100 * 600 / prefill_us)
    c = obs["cell"].config
    step_s = 36 * layer_us * 1e-6
    floor = ssm_flops.decode_step_bytes(c, 60, 60 * 150) / 819e9
    assert reads["decode_step_roofline"] == pytest.approx(
        100 * floor / step_s, rel=1e-3)
    update_floor = ssm_flops.state_update_bytes(c, 60) / 819e9
    assert reads["ssm_state_update_roofline"] == pytest.approx(
        100 * update_floor / (36 * 500e-6), rel=1e-3)
    assert reads["ssm_state_update_roofline"] < 100 > \
        reads["decode_step_roofline"]


def test_a_program_without_state_space_layers_reads_nothing():
    """A dense cell's observations, the parent commit's (whose spans
    carry no state traffic) and an untraced run: every reader returns
    None, none raises."""
    obs = _synthetic_obs()
    dense = dict(obs, cell=types.SimpleNamespace(
        config=_json("configs", "internlm2-1.8b"),
        workload=obs["cell"].workload))
    no_spans = dict(obs, program_spans=program_spans.ProgramSpans(
        [], [{"k": 16, "tokens_kept": 1, "token_steps": 2}], []))
    no_trace = dict(obs, trace=None)
    for name in _READERS:
        read = spec.load_module("metrics", name).read
        if name != "decode_step_roofline":    # the dense file names its own
            assert read(dict(dense)) is None
        assert read(dict(no_trace)) is None
    for name in ("decode_step_roofline", "ssm_state_update_roofline"):
        assert spec.load_module("metrics", name).read(dict(no_spans)) is None
