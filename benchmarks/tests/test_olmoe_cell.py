"""The expert configuration's part of the benchmark: ``lib/moe_flops.py``
against hand-worked numbers; the cell's engine programs compiled at the
REAL widths for a v5e that is described, not attached (they fit, eight
more slots fit too, and the expert step reads its ``[L, E, D, H]`` stacks
in place); a CPU rehearsal of a toy expert configuration through
``run.measure`` with ``olmoe_decoder`` as its reference; and the five
``moe_*`` readers on a synthetic trace made of the instruction names a
v5e trace of the cell holds (my chip run, PR 26).
"""

import json
import os
import re
import shutil
import time
import types

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import (moe_flops, moe_names, program_spans, scope_names,
                            spec, trace_reduce)
from benchmarks.tests import test_rehearsal
# ``topo`` is described inside that file's fixture (never at import);
# ``compiled_kernels`` keeps these compiles out of the persistent cache.
from benchmarks.tests.test_aot_real_widths import (  # noqa: F401
    _engine_programs, _json, compiled_kernels, one_chip, topo)

os.environ.setdefault("TPU_LOG_DIR", "disabled")
CELL = "olmoe-1b-7b.serve-batch-decode"


# ------------------------------------------------------------------ flops
def test_operations_and_bytes_by_hand():
    c = _json("configs", "olmoe-1b-7b")
    # a layer: q, k, v, o 4 x 2048 x 2048 + router 2048 x 64 (the dense
    # matmuls) + q and k norms 2 x 2048 + block norms 2 x 2048 + 64
    # experts x 3 x 2048 x 1024
    dense = 4 * 2048 * 2048 + 2048 * 64
    expert = 3 * 2048 * 1024
    layer = dense + 4 * 2048 + 64 * expert
    assert (dense, expert, layer) == (16_908_288, 6_291_456, 419_569_664)
    assert moe_flops.dense_matmul_params_per_layer(c) == dense
    assert moe_flops.expert_params(c) == expert
    ends = 2 * 50304 * 2048 + 2048          # embedding, head, final norm
    assert moe_flops.param_count(c, layers=16) == 16 * layer + ends \
        == 6_919_161_856 == c["parameters_published_depth"]
    assert moe_flops.param_count(c) == 8 * layer + ends \
        == 3_562_604_544 == c["parameters"]
    # a token meets the dense part, 8 experts and the head: 1.18 B at 16
    assert moe_flops.active_params(c, layers=16) \
        == 16 * (dense + 8 * expert) + 2048 * 50304 == 1_178_861_568
    # K and V: 8 layers x 16 heads x 128 x 2 bytes, twice
    assert moe_flops.kv_bytes_per_token(c) == 65_536
    # one decode step at 8 layers, every one of the 512 (layer, expert)
    # pairs touched, 10,000 positions in flight
    weights = 8 * dense + 2048 * 50304 + 512 * expert
    assert weights == 3_459_514_368
    assert moe_flops.decode_step_bytes(c, 512, 10_000) \
        == 2 * weights + 10_000 * 65_536 == 7_574_388_736
    # ... and 100 sequences' tokens, 8 experts each in 8 layers
    rows = 100 * 8 * 8
    assert moe_flops.expert_matmul_flops(c, rows) == 2 * rows * expert
    assert moe_flops.decode_step_flops(c, 100, 10_000, rows) \
        == 2 * (8 * dense + 2048 * 50304) * 100 \
        + 4 * 10_000 * 16 * 128 * 8 + 2 * rows * expert
    assert moe_flops.expert_matmul_bytes(c, 512, rows) \
        == 2 * (512 * expert + rows * (3 * 2048 + 3 * 1024))
    # a step's floor is its bytes: 9.2 ms at 819 GB/s against 0.7 ms of
    # FLOPs at 197 TFLOP/s
    assert moe_flops.decode_step_bytes(c, 512, 10_000) / 819e9 \
        > 10 * moe_flops.decode_step_flops(c, 100, 10_000, rows) / 197e12


# ------------------------------------------- the real widths, for the chip
def test_engine_programs_fit_one_chip_and_experts_are_read_in_place(
        one_chip):
    """Every program the cell's engine warms compiles for one 16 GB chip
    (the compiler raises RESOURCE_EXHAUSTED if not), and the decode
    program holds the structural claims of ``tests/test_decode_inplace``
    for the expert step: the grouped matmuls are Mosaic kernels whose
    weight operand is the WHOLE ``[L x E, ...]`` stack, a bitcast of the
    loop's carry; nothing of a stack's or of one layer's expert matrices'
    size is produced inside a loop (no copy, no transpose, no slice, no
    park in VMEM)."""
    engine = _json("workloads", CELL)["engine"]
    c = _json("configs", "olmoe-1b-7b")
    layers, experts = c["num_hidden_layers"], c["num_experts"]
    hidden, width = c["hidden_size"], c["intermediate_size"]
    compiled = None
    for label, compile_it in _engine_programs(CELL, one_chip):
        got = compile_it()
        if label == f"decode_k s_active={engine['max_len']}":
            compiled = got
    memory = compiled.memory_analysis()
    weights = 2 * moe_flops.param_count(c)
    cache = engine["max_slots"] * engine["max_len"] \
        * moe_flops.kv_bytes_per_token(c)
    assert (weights, cache) == (7_125_209_088, 4_026_531_840)
    assert memory.argument_size_in_bytes < weights + cache + (1 << 20)
    assert memory.temp_size_in_bytes < 256 << 20      # AOT, PR 26: 80 MB

    hlo = compiled.as_text()
    rows = engine["max_slots"] * c["num_experts_per_tok"]
    stack = {f"bf16[{layers * experts},{hidden},{width}]",
             f"bf16[{layers * experts},{width},{hidden}]"}
    bitcasts = dict(re.findall(
        r"(%bitcast[\w.]*) = (bf16\[[\d,]+\])\{[^}]*\} bitcast\(", hlo))
    kernels = re.findall(
        r"%ragged-dot-none[\w.]* = f32\[(\d+),(\d+)\][^\n]*custom-call\("
        r"([^\n]*?)\), custom_call_target=\"tpu_custom_call\"", hlo)
    assert len(kernels) == 3                  # gate, up, down: one body
    for m, _n, operands in kernels:
        assert int(m) == rows
        weight = operands.rsplit(", ", 1)[-1].split()[-1]
        assert bitcasts[weight] in stack, (weight, bitcasts.get(weight))
    views = ("parameter", "get-tuple-element", "bitcast", "tuple", "while")
    for line in hlo.splitlines():
        made = re.match(r"\s*(?:ROOT )?%(\S+) = (.*?) ([\w-]+)\(", line)
        if not made or made.group(3) in views:
            continue
        for dims in re.findall(r"\w+\[([\d,]*)\]", made.group(2)):
            dims = tuple(int(d) for d in dims.split(",") if d)
            assert dims[-2:] not in ((hidden, width), (width, hidden)), \
                line[:200]


def test_eight_more_slots_compile_too(one_chip):
    """128 x 512 fits as well (as it does for cell 3): the cell keeps the
    120 slots of ``internlm2-1.8b.serve-batch-decode`` so that the two
    ledger lines differ by the model alone; nothing refuses."""
    assert _json("workloads", CELL)["engine"]["max_slots"] + 8 == 128
    for _label, compile_it in _engine_programs(CELL, one_chip,
                                               max_slots=128):
        compile_it()


def test_weights_are_made_in_their_serving_type(one_chip):
    """``init_params`` under ``jit`` at bfloat16 (what the serve kind
    calls) holds no float32 copy of an expert stack beside the tree:
    4.3 GB a leaf would not fit."""
    import jax

    from benchmarks.lib import program
    from ray_tpu.models import llama

    cfg = program.llama_config(_json("configs", "olmoe-1b-7b"))
    compiled = jax.jit(
        lambda key: llama.init_params(key, cfg, cfg.dtype)).lower(
        jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                             sharding=one_chip)).compile()
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes < 7_125_209_088 + (1 << 20)
    assert memory.temp_size_in_bytes < 64 << 20        # AOT, PR 26: 0.6 MB


# ------------------------------------------------- a rehearsal on the CPU
TINY_OLMOE = {
    "name": "tiny-olmoe", "source": "none (test, experts)",
    "reference": "olmoe_decoder", "roofline": "moe_flops",
    "vocab_size": 256, "hidden_size": 64,
    "num_hidden_layers": 1, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 32,
    "num_experts": 8, "num_experts_per_tok": 3, "norm_topk_prob": False,
    "max_position_embeddings": 256, "rope_theta": 10000,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    "hidden_act": "silu", "bias": False, "clip_qkv": None,
    "reduced": [{"key": "num_hidden_layers", "published": 2, "here": 1,
                 "why": "test: half the depth"}],
    "assumed": ["test"],
    "program_fields": {"moe_experts": 8, "moe_top_k": 3,
                       "moe_norm_topk": False, "qk_norm": True},
}
TINY_CELL = "tiny-olmoe.tiny-closed"


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark with a toy expert configuration dropped in
    and its cell appended wherever the real one is."""
    root = tmp_path_factory.mktemp("bench_olmoe")
    bench = str(root / "benchmarks")
    shutil.copytree(spec.BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "out", "__pycache__", "tests"))

    def drop(rel, payload):
        path = os.path.join(bench, rel)
        assert not os.path.exists(path), f"{rel} would be an edit"
        with open(path, "w") as f:
            json.dump(payload, f)

    drop("configs/tiny-olmoe.json", TINY_OLMOE)
    drop("traffic/tiny-closed.json", test_rehearsal.TRAFFIC["tiny-closed"])
    drop(f"workloads/{TINY_CELL}.json",
         dict(test_rehearsal.SERVE, name=TINY_CELL, config="tiny-olmoe",
              traffic="tiny-closed", why="test"))
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    benchmark["configs"].append(
        {"name": "tiny-olmoe", "source": TINY_OLMOE["source"],
         "reduced": ["num_hidden_layers"],
         "file": "benchmarks/configs/tiny-olmoe.json", "why": "test"})
    benchmark["workloads"].append(
        {"name": TINY_CELL, "config": "tiny-olmoe",
         "traffic": "tiny-closed", "chips": 1, "why": "test"})
    for group in ("end_to_end", "per_layer"):
        for metric in benchmark[group]:
            if CELL in metric.get("workloads", []):
                metric["workloads"].append(TINY_CELL)
    path = str(root / "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(benchmark, f)
    return bench, path


def _measure(tree, trace):
    bench, benchmark_json = tree
    result, obs = bench_run.measure(
        ["--workload", TINY_CELL, "--seed", "2147485999", "--seconds", "3",
         "--trace", str(trace)],
        allow_platforms=("cpu",), bench_dir=bench,
        benchmark_json=benchmark_json, t_process=time.perf_counter())
    assert result["correct"] is True, obs["checks"]
    assert result["failed"] == 0 < result["attempted"]
    return result, obs


def test_a_toy_expert_configuration_runs_end_to_end_on_the_cpu(
        tree, cpu_peaks):
    from benchmarks.tests.test_yardstick import names_lead_to_files

    names_lead_to_files(os.path.dirname(tree[1]))
    result, obs = _measure(tree, trace=0)
    assert set(result["metrics"]) == {"serve_output_tokens_per_s",
                                      "setup_s"}
    assert obs["cell"].reference.__name__.endswith("olmoe_decoder")
    assert len(obs["logit_gaps"]) == 4


def test_traced_run_reports_the_joined_metrics_and_the_programs_own(
        tree, cpu_peaks):
    """The metrics the cell joins and ``moe_expert_load_imbalance`` (from
    the program's spans) are there; what only a device trace knows is
    left out on a CPU, not invented."""
    result, obs = _measure(tree, trace=1)
    metrics = result["metrics"]
    assert {"batch.slot_wait_p50_ms", "batch.token_burst_gap_p50_ms",
            "batch.decode_slot_utilization",
            "batch.prefill_padding_share", "window_compiles",
            "moe_expert_load_imbalance"} <= set(metrics)
    assert 1.0 <= metrics["moe_expert_load_imbalance"]["value"] <= 8.0
    assert not {"batch.decode_step_roofline", "moe_expert_matmul_roofline",
                "moe_expert_ffn_time_share",
                "moe_routing_time_share"} & set(metrics)
    rows, touched, _ = moe_names.chunk_medians(obs)
    assert rows <= 4 * 3 and 0 < touched <= 8        # 4 slots x top-3


cpu_peaks = test_rehearsal.cpu_peaks


# --------------------------------------- the readers on a synthetic trace
# One layer of one decode step as a v5e trace of the cell names it (my chip
# run, PR 26; instruction texts cut to what the readers look at), with
# durations in microseconds and the scope the program's map gives each.
_LAYER = [
    ("%fusion.189 = f32[4,512,16]{2,1,0} fusion(f32[4,16,128] %q, "
     "bf16[8,120,512,16,128] %k)", 400.0, "attention"),
    ("%fusion.169 = (f32[120], bf16[120,1,2048]) fusion(bf16[120,1,2048] "
     "%x, bf16[8,2048,2048] %wo)", 16.0, "attn_out"),
    ("%fusion.171 = (f32[120]{0}, f32[120,64]{0,1}) fusion(bf16[120,2048] "
     "%h, bf16[8,2048,64] %router)", 1.5, "router"),
    ("%sort.31 = (f32[120,64]{0,1}, s32[120,64]{0,1}) sort(f32[120,64] "
     "%probs, s32[120,64] %iota)", 1.0, "router"),
    ("%sort.32 = (s32[960]{0}, s32[960]{0}) sort(s32[960] %experts, "
     "s32[960] %iota)", 4.5, "expert_dispatch"),
    ("%fusion.174 = bf16[960,2048]{1,0} fusion(bf16[120,2048] %h, "
     "s32[1024] %order)", 13.0, "expert_dispatch"),           # gather
    ("%ragged-dot-metadata = (s32[513]{0}, s32[526]{0}, s32[526]{0}, "
     "s32[1]{0}) custom-call(s32[512]{0} %sizes), "
     "custom_call_target=\"tpu_custom_call\"", 22.0, "expert_dispatch"),
    ("%ragged-dot-none.1 = f32[960,1024]{1,0} custom-call(s32[1] %n, "
     "bf16[960,2048] %rows, bf16[512,2048,1024] %bitcast.213), "
     "custom_call_target=\"tpu_custom_call\"", 540.0, "expert_ffn"),
    ("%ragged-dot-none = f32[960,1024]{1,0} custom-call(s32[1] %n, "
     "bf16[960,2048] %rows, bf16[512,2048,1024] %bitcast.212), "
     "custom_call_target=\"tpu_custom_call\"", 540.0, "expert_ffn"),
    ("%convert_multiply_fusion.10 = bf16[960,1024]{1,0} fusion("
     "f32[960,1024] %gate, f32[960,1024] %up)", 1.5, "expert_ffn"),
    ("%ragged-dot-none.2 = f32[960,2048]{1,0} custom-call(s32[1] %n, "
     "bf16[960,1024] %act, bf16[512,1024,2048] %bitcast.214), "
     "custom_call_target=\"tpu_custom_call\"", 520.0, "expert_ffn"),
    ("%fusion.176 = f32[960,2048]{1,0} fusion(f32[960,2048] "
     "%ragged-dot-none.2, s32[1024] %inverse)", 8.0,
     "expert_dispatch"),                                      # un-sort
    ("%fusion.177 = bf16[120,2048]{1,0} fusion(f32[960,2048] %fusion.176, "
     "f32[120,8] %gates)", 10.0, "expert_dispatch"),          # combine
    ("%fusion.178 = bf16[120,1,2048]{2,0,1} fusion(bf16[120,1,2048] %x, "
     "bf16[120,2048] %fusion.177)", 1.0, "ffn"),              # residual
]


def _synthetic_obs(layers=8, steps=16, runs=2):
    from ray_tpu.observability.device import instruction_key

    ops, modules, t = [], [], 0.0
    for run in range(runs):
        start = t
        body = []
        for _ in range(steps * layers):
            for name, us, _scope in _LAYER:
                body.append((t, t + us * 1e-6, name))
                t += us * 1e-6
        ops.append((start, t, "%while.7 = (s32[]) while((s32[]) %t), "
                    "body=%step"))
        ops.extend(body)
        modules.append((start, t, "jit_decode_k(7)"))
        t += 1e-4
    # a prefill program's own grouped matmuls do not count
    ops.append((t, t + 0.05, "%ragged-dot-none.2 = f32[65536,2048]{1,0} "
                "custom-call(bf16[65536,1024] %a), "
                "custom_call_target=\"tpu_custom_call\""))
    modules.append((t, t + 0.05, "jit_prefill(9)"))
    trace = trace_reduce.Trace(
        [trace_reduce.DeviceTrace(0, ops, modules)], [], 0.0, t + 0.05)
    cell = types.SimpleNamespace(
        config=_json("configs", "olmoe-1b-7b"),
        workload=_json("workloads", CELL), bench_dir=spec.BENCH_DIR,
        name=CELL)
    scopes = {"jit_decode_k": {instruction_key(name): (scope, "forward")
                               for name, _us, scope in _LAYER}}
    # 100 sequences in flight, each 150 positions at the span's middle
    records = [types.SimpleNamespace(
        ok=True, got_tokens=101, sent=0.0, ttft_ms=0.0, done=2.0,
        prompt_tokens=99) for _ in range(100)]
    chunk = {"k": 16, "expert_rows": 100 * 16 * 8 * 8,
             "experts_touched": 16 * 8 * 60, "expert_rows_max": 300}
    return {
        "trace": trace, "cell": cell, "decode_chunk": 16,
        "trace_span": [0.9, 1.1], "scope_map": scopes,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        "log": types.SimpleNamespace(records=records),
        "program_spans": program_spans.ProgramSpans([], [chunk, chunk], []),
    }


_READERS = ("decode_step_roofline", "moe_expert_matmul_roofline",
            "moe_expert_ffn_time_share", "moe_routing_time_share",
            "moe_expert_load_imbalance")


def test_the_five_readers_on_a_synthetic_trace(monkeypatch):
    monkeypatch.setattr(scope_names, "_write_report", lambda obs: None)
    obs = _synthetic_obs()
    reads = {name: spec.load_module("metrics", name).read(obs)
             for name in _READERS}
    layer_us = sum(us for _n, us, _s in _LAYER)                 # 2,079
    # what the program traced under ``expert_ffn``: the three grouped
    # matmuls and the activation between them
    assert reads["moe_expert_ffn_time_share"] == pytest.approx(
        100 * (1600 + 1.5) / layer_us)
    # ``router`` and ``expert_dispatch``: router, sorts, gather, the
    # kernels' metadata, un-sort, combine; not attention, wo, the residual
    assert reads["moe_routing_time_share"] == pytest.approx(
        100 * 60 / layer_us)
    # busiest (layer, expert) 300 rows a chunk; mean 102,400 / 512 = 200
    assert reads["moe_expert_load_imbalance"] == pytest.approx(1.5)
    c = obs["cell"].config
    rows, touched, context = 100 * 8 * 8, 8 * 60, 100 * 150
    floor = moe_flops.decode_step_bytes(c, touched, context) / 819e9
    assert floor > moe_flops.decode_step_flops(c, 100, context, rows) \
        / 197e12
    assert reads["decode_step_roofline"] == pytest.approx(
        100 * floor / (8 * layer_us * 1e-6), rel=1e-3)
    matmul_floor = moe_flops.expert_matmul_bytes(c, touched, rows) / 819e9
    assert reads["moe_expert_matmul_roofline"] == pytest.approx(
        100 * matmul_floor / (8 * 1600e-6), rel=1e-3)
    assert reads["moe_expert_matmul_roofline"] < 100 > \
        reads["decode_step_roofline"]


def test_a_program_without_experts_reads_nothing(monkeypatch):
    """A dense program's observations (its map knows no expert scope, its
    spans carry no expert load: the parent commit's too) and an untraced
    run: every reader returns None, none raises.  What says that a cell
    has experts is what its program traced, not a key of its file."""
    monkeypatch.setattr(scope_names, "_write_report", lambda obs: None)
    obs = _synthetic_obs()
    no_spans = dict(obs, program_spans=program_spans.ProgramSpans(
        [], [{"k": 16, "tokens_kept": 1, "token_steps": 2}], []))
    dense = dict(no_spans, scope_map={
        module: {key: ("ffn", "forward") for key in rows}
        for module, rows in obs["scope_map"].items()})
    no_trace = dict(obs, trace=None)
    for name in _READERS:
        read = spec.load_module("metrics", name).read
        assert read(dict(dense)) is None, name
        if name != "moe_expert_load_imbalance":         # reads spans alone
            assert read(dict(no_trace)) is None, name
    for name in ("decode_step_roofline", "moe_expert_matmul_roofline",
                 "moe_expert_load_imbalance"):
        assert spec.load_module("metrics", name).read(dict(no_spans)) is None
    # the same trace under another configuration's file: the shares are
    # the program's scopes' whatever the file says of its experts
    other = dict(obs, cell=types.SimpleNamespace(
        **{**vars(obs["cell"]),
           "config": _json("configs", "internlm2-1.8b")}))
    for name in ("moe_expert_ffn_time_share", "moe_routing_time_share"):
        read = spec.load_module("metrics", name).read
        assert read(dict(other)) == read(dict(obs)) is not None
