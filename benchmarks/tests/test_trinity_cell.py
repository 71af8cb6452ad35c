"""Trinity-Mini's part of the benchmark: ``lib/afmoe_flops.py`` (the train
step's counts, which the file names under ``train_counts``) and
``lib/moe_flops.py`` (the grouped matmuls') against hand-worked numbers and
the program's own tree; the cell's names lead to its files and it joins
what its program does (``train_mfu``, ``flash_attention_roofline``, the
``train_expert_*`` entries);
the train step of a toy of the same shape compiled for a v5e that is
described, not attached, with its kernels under the scopes the readers sum
(the REAL widths' step takes a minute or more to compile:
``benchmarks/tools/train_step_aot.py`` does that by hand, PERF.md section
4); a CPU rehearsal of the toy through ``run.measure`` with
``afmoe_decoder`` as its reference; and the readers it reports through, on
observations that are given.
"""

import json
import os
import shutil
import time
import types

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import (afmoe_flops, flash_names, moe_flops, moe_names,
                            program, readers, scope_names, spec)
from benchmarks.tests import test_rehearsal
# ``topo`` is described inside that file's fixture (never at import);
# ``compiled_kernels`` keeps these compiles out of the persistent cache.
from benchmarks.tests.test_aot_real_widths import (  # noqa: F401
    _json, _on, compiled_kernels, kernels_by_name_and_scope, one_chip, topo)

os.environ.setdefault("TPU_LOG_DIR", "disabled")
CONFIG = "trinity-mini"
CELL = "trinity-mini.train-8k-1chip"
OWN_ENTRIES = ("train_expert_matmul_roofline", "train_expert_ffn_time_share",
               "train_routing_time_share")


# ------------------------------------------------------------------ flops
def test_operations_by_hand():
    c = _json("configs", CONFIG)
    assert [r["key"] for r in c["reduced"]] == [
        "num_hidden_layers", "layer_types", "num_dense_layers",
        "num_experts", "vocab_size"]
    assert c["assumed"] and all(isinstance(a, str) for a in c["assumed"])
    # every published width
    assert (c["hidden_size"], c["num_attention_heads"], c["head_dim"],
            c["num_key_value_heads"], c["intermediate_size"],
            c["moe_intermediate_size"], c["num_experts_per_tok"],
            c["sliding_window"], c["share"]["num_experts_published"]) \
        == (2048, 32, 128, 4, 6144, 1024, 8, 2048, 128)
    assert c["share"]["chips_that_share_a_layer"] == 8
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) \
        == (5, 16, 25024) and 8 * 25024 == 200192
    assert c["layer_types"] == ["sliding_attention"] * 4 + ["full_attention"]
    assert afmoe_flops.layer_counts(c) == (1, 4)
    attention = 2048 * 4096 * 3 + 2 * 2048 * 512
    assert afmoe_flops.attention_params(c) == attention == 27_262_976
    assert afmoe_flops.expert_params(c) == moe_flops.expert_params(c) \
        == 6_291_456
    # the experts' facts as every expert cell's readers take them
    assert (moe_flops.expert_width(c), moe_flops.expert_layers(c),
            moe_flops.experts_held(c), moe_flops.expert_matrices(c),
            moe_flops.expert_row_width(c)) == (1024, 4, 16, 3, 2048)
    assert afmoe_flops.held_rows_per_token(c) == 1.0
    matmuls = 5 * attention + 3 * 2048 * 6144 \
        + 4 * (2048 * 128 + 2 * 6_291_456) + 2048 * 25024
    assert afmoe_flops.matmul_params_per_token(c) == matmuls == 276_692_992
    banded = 2048 * 2049 // 2 + 6144 * 2048
    full = 8192 * 8193 // 2
    assert (banded, full) == (14_681_088, 33_558_528)
    assert afmoe_flops.attention_pairs(c, 8192) == 4 * banded + full
    assert afmoe_flops.flops_per_pair(c) == 16_384
    forward = 2 * matmuls + (4 * banded + full) * 16_384 / 8192
    assert afmoe_flops.forward_flops_per_token(c, 8192) == forward
    assert 737e6 < forward < 739e6
    assert afmoe_flops.train_flops_per_token(c, 8192) == 3 * forward
    # lib/flops.py would count every layer a 6,144 SwiGLU and a whole square
    assert afmoe_flops.flash_train_flops(c, 1, 8192) \
        == 3.5 * (4 * banded + full) * 16_384
    assert 5 * full / (4 * banded + full) == pytest.approx(1.82, abs=0.005)
    # the grouped matmuls of a step over the rows the held experts got:
    # at the expected 8,192 a layer FLOPs bound them on a v5e, at the 1,400
    # a layer the first traced run's kernels point to (PERF.md) the bytes
    assert moe_flops.expert_matmul_train_flops(c, 4 * 8192) \
        == 3 * 2 * 4 * 8192 * 6_291_456
    assert moe_flops.expert_matmul_train_bytes(c, 4 * 8192) \
        == 4 * 16 * 6_291_456 * 8 + 4 * 4 * 8192 * 2048 * 2
    assert moe_flops.expert_matmul_train_flops(c, 4 * 8192) / 197e12 \
        > moe_flops.expert_matmul_train_bytes(c, 4 * 8192) / 819e9
    assert moe_flops.expert_matmul_train_flops(c, 4 * 1400) / 197e12 \
        < moe_flops.expert_matmul_train_bytes(c, 4 * 1400) / 819e9


def test_the_programs_tree_is_what_the_file_counts():
    import jax

    from ray_tpu.models import llama

    c = _json("configs", CONFIG)
    cfg = program.llama_config(c)
    assert cfg.plain_decoder and cfg.held_experts == (0, 16)
    assert [(key, part.period, part.n_layers)
            for part, key, _ in cfg.parts()] == [
        ("dense_layers", ("window",), 1), ("layers", ("window",), 3),
        ("layers_1", ("attention",), 1)]
    shapes = jax.eval_shape(lambda k: llama.init_params(k, cfg),
                            jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == c["parameters"] \
        == 705_474_304
    dense = sum(x.size for x in jax.tree.leaves(shapes["dense_layers"]))
    layer = sum(x.size for x in jax.tree.leaves(shapes["layers_1"]))
    assert (dense, layer) == (65_020_160, 134_488_448)
    assert {x.dtype.name for x in jax.tree.leaves(shapes)} == {"float32"}
    # nothing is sized by the published context of 131,072
    assert max(max(x.shape) for x in jax.tree.leaves(shapes)) <= 25024


def the_cells_entries(root=spec.ROOT):
    """What THIS cell reports, on the tree at ``root`` (the rehearsal's has
    a later PR's entries appended: nothing here counts the table or says
    what another family's names are)."""
    from benchmarks.tests.test_yardstick import (cell_at, names_lead_to_files,
                                                 reader_at)

    names_lead_to_files(root)
    cell = cell_at(root, CELL)
    assert cell.chips == 1 and cell.workload["kind"] == "train_lm"
    assert cell.workload["trainer"] == {
        "mesh": None, "fused_optimizer": True, "prefetch_batches": 2,
        "sync_every_steps": 10, "warmup_steps": 3}
    assert cell.traffic == {**cell.traffic, "generator": "token_batches",
                            "batch": 1, "seq_len": 8192,
                            "distinct_batches": 32}
    entry = next(c for c in cell.benchmark["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == [r["key"] for r in cell.config["reduced"]]
    assert len(entry["why"]) <= 200 and len(cell.entry["why"]) <= 200
    names = {m["name"] for m in cell.metric_entries("per_layer")}
    assert {"train_mfu", "flash_attention_roofline", "train_step_device_ms",
            "flash_dq_time_share", "flash_dkdv_time_share",
            "flash_fwd_time_share", "train_optimizer_time_share",
            "train_ffn_time_share", "train.device_idle_share",
            "train.hbm_peak_in_use_bytes", "train_worker_start_s",
            "setup_trace_s", *OWN_ENTRIES} <= names
    # the whole step's share of the peak and the flash kernels' of their
    # floor are every train cell's entries, over the counts the file names:
    # a layer inside its mask, experts held, not lib/flops.py's dense
    # full-causal decoder
    assert cell.config["train_counts"] == "afmoe_flops"
    assert readers.train_counts(cell).__name__.endswith("afmoe_flops")
    assert {m["name"] for m in cell.metric_entries("end_to_end")} \
        == {"train_tokens_per_s_per_chip", "setup_s"}
    entries = {m["name"]: m for m in cell.metric_entries("per_layer")}
    assert entries["train_mfu"]["source"] == "host_clock"
    for name in OWN_ENTRIES:
        assert entries[name] == {
            "name": name, "unit": "%", "source": "device_trace",
            "better": "higher" if name.endswith("roofline") else "lower",
            "layer": "expert layer", "moves": "train_tokens_per_s_per_chip",
            "workloads": entries[name]["workloads"]}
        assert callable(reader_at(root, name).read)


def test_the_cells_names_lead_to_files_and_join_the_train_metrics():
    the_cells_entries()


# ------------------------------------------------ a toy of the same shape
TINY = {
    "name": "tiny-afmoe", "source": "none (test)",
    "reference": "afmoe_decoder", "train_counts": "afmoe_flops",
    "model_type": "afmoe", "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 96, "max_position_embeddings": 256,
    "rope_theta": 10000, "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    "hidden_act": "silu", "bias": False, "sliding_window": 32,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "num_dense_layers": 1, "num_experts": 4, "num_experts_per_tok": 4,
    "num_shared_experts": 1, "moe_intermediate_size": 32,
    "route_norm": True, "route_scale": 2.826, "mup_enabled": True,
    "share": {"num_experts_published": 16, "experts_first": 0,
              "experts_held": 4},
    "reduced": [], "assumed": ["test"],
    "program_fields": {
        "layer_types": ["window"] * 3 + ["attention"], "window_size": 32,
        "first_dense_layers": 1, "nope_kinds": ["attention"],
        "qk_head_norm": True, "attn_gate": True, "embedding_multiplier": 8.0,
        "sandwich_norm": True, "moe_experts": 16, "moe_held": [0, 4],
        "moe_top_k": 4, "moe_norm_topk": True, "moe_routed_scale": 2.826,
        "moe_router_score": "sigmoid", "moe_router_bias": True,
        "moe_intermediate_size": 32, "moe_shared_size": 32,
        "moe_aux_weight": 0.0, "attention_impl": "dot",
        "remat_policy": "attn", "dtype": "float32"},
}
TINY_CELL = "tiny-afmoe.tiny-train"


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark with a toy of the same shape dropped in and
    its cell appended wherever the real one is."""
    root = tmp_path_factory.mktemp("bench_afmoe")
    bench = str(root / "benchmarks")
    shutil.copytree(spec.BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "out", "__pycache__", "tests"))

    def drop(rel, payload):
        path = os.path.join(bench, rel)
        assert not os.path.exists(path), f"{rel} would be an edit"
        with open(path, "w") as f:
            json.dump(payload, f)

    drop("configs/tiny-afmoe.json", TINY)
    drop("traffic/tiny-train.json",
         dict(test_rehearsal.TRAFFIC["tiny-train"], batch=2))
    drop(f"workloads/{TINY_CELL}.json", {
        "kind": "train_lm", "chips": 1, "name": TINY_CELL,
        "config": "tiny-afmoe", "traffic": "tiny-train", "why": "test",
        "trainer": {"mesh": None, **test_rehearsal.TRAINER}})
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    benchmark["configs"].append(
        {"name": "tiny-afmoe", "source": TINY["source"], "reduced": [],
         "file": "benchmarks/configs/tiny-afmoe.json", "why": "test"})
    benchmark["workloads"].append(
        {"name": TINY_CELL, "config": "tiny-afmoe",
         "traffic": "tiny-train", "chips": 1, "why": "test"})
    for group in ("end_to_end", "per_layer"):
        for metric in benchmark[group]:
            if CELL in metric.get("workloads", []):
                metric["workloads"].append(TINY_CELL)
    path = str(root / "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(benchmark, f)
    return bench, path


cpu_peaks = test_rehearsal.cpu_peaks


def test_a_toy_of_the_same_shape_trains_end_to_end_on_the_cpu(
        tree, cpu_peaks, monkeypatch):
    """One run of the toy cell through ``run.measure``: every check of
    ``train_lm`` against ``afmoe_decoder`` under the harness's own limits,
    the whole step's share of the peak from the file's own counts (the
    reader called on the run's observations: untraced, a run reports its
    end-to-end metrics alone, and a CPU's profiler is most of a traced
    run's seconds); the readers that need a device trace return nothing."""
    import importlib

    # (this file's compiles are for the chip; this run is the CPU's own)
    monkeypatch.setattr(
        importlib.import_module("ray_tpu.ops.flash_attention"),
        "_use_interpret", lambda: True)
    bench, benchmark_json = tree
    result, obs = bench_run.measure(
        ["--workload", TINY_CELL, "--seed", "2147486530", "--seconds", "3",
         "--trace", "0"],
        allow_platforms=("cpu",), bench_dir=bench,
        benchmark_json=benchmark_json, t_process=time.perf_counter())
    assert result["correct"] is True, obs["checks"]
    assert result["failed"] == 0 < result["attempted"]
    assert obs["cell"].reference.__name__.endswith("afmoe_decoder")
    gaps = obs["grad_leaf_gaps"]
    assert gaps["router_bias"] == 0.0 and max(gaps.values()) < 1e-3
    assert {"router", "w_gate", "ws_up", "dense.w_down", "post_attn_norm",
            "w_attn_gate", "q_norm"} <= set(gaps)
    assert set(result["metrics"]) == {"train_tokens_per_s_per_chip",
                                      "setup_s"}
    rate = result["metrics"]["train_tokens_per_s_per_chip"]["value"]
    reads = {entry["name"]: read
             for entry, read in obs["cell"].readers("per_layer")}
    assert reads["train_mfu"](obs) == pytest.approx(
        100 * rate * afmoe_flops.train_flops_per_token(TINY, 128) / 197e12)
    # the last step's own count of its experts' rows is handed over: 3
    # expert layers x the 16 experts the router scores, 4 picks a token
    rows = obs["expert_rows"]
    assert len(rows) == 3 and {len(layer) for layer in rows} == {16}
    assert {sum(layer) for layer in rows} == {2 * 128 * 4}
    assert moe_names.held_rows_a_step(obs) == sum(
        sum(layer[:4]) for layer in rows) > 0
    # what needs a device trace finds none to read
    for name in ("flash_attention_roofline", *OWN_ENTRIES):
        assert reads[name](obs) is None


# ----------------------------------------- the toy's step, for the chip
def test_the_toys_step_compiles_for_a_v5e_with_its_kernels_in_scope(
        one_chip):
    """The train step of the toy's first and last layer (a dense window
    layer, a full expert layer) at a sequence of whole lanes (4 x 256
    through a window of 128, heads of 128) compiled for one described v5e:
    the three flash kernels a layer kind under the names ``flash_names``
    reads, the band inside them; the grouped matmuls as
    ``%ragged-dot-none*`` under ``expert_ffn``; the balance update under
    ``router_balance``."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.observability import device

    fields = dict(TINY["program_fields"], dtype="bfloat16", window_size=128,
                  attention_impl="flash", layer_types=["window", "attention"])
    cfg = program.llama_config(dict(
        TINY, hidden_size=256, num_attention_heads=2, num_key_value_heads=1,
        head_dim=128, num_hidden_layers=2, program_fields=fields))
    step = llama.make_train_step(cfg, fused=True)
    state = _on(one_chip, jax.eval_shape(
        llama._train_state_builder(cfg, None, True, None, None),
        jax.random.key(0)))
    batch = {"tokens": jax.ShapeDtypeStruct((4, 256), jnp.int32,
                                            sharding=one_chip)}
    compiled = step.lower(state, batch).compile()
    text = compiled.as_text()
    found = kernels_by_name_and_scope(text)
    kernels = {kernel for kernel, _scope in found}
    assert {"flash_attention_fwd", "flash_attention_dq",
            "flash_attention_dkdv", "ragged-dot-none"} <= kernels
    assert {scope for (kernel, scope) in found
            if kernel == "ragged-dot-none"} == {"expert_ffn"}
    assert {scope for (kernel, scope) in found
            if kernel.startswith("flash_attention")} <= {
        "flash_attention.fwd", "flash_attention.dq", "flash_attention.dkdv",
        "attention"}
    scopes = {scope for scope, _phase in device.scopes_of_text(text).values()}
    assert {"router", "expert_dispatch", "expert_ffn", "shared_expert",
            "router_balance", "optimizer", "head_loss"} <= scopes
    # the state is 12 bytes a trained parameter + the bias, donated
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 0.99 * memory.argument_size_in_bytes


# ------------------------------------------------- the readers' arithmetic
def test_the_readers_arithmetic_on_given_observations(monkeypatch):
    """A traced step of 0.5 s whose flash kernels take 60 ms, grouped
    matmuls 20 ms, and whose scopes are given: each reader is its count of
    ``afmoe_flops`` / ``moe_flops`` at the chip's peaks over those seconds,
    and nothing where the trace or the scopes hold nothing."""
    c = _json("configs", CONFIG)
    obs = {"cell": types.SimpleNamespace(config=c, bench_dir=spec.BENCH_DIR,
                                         name=CELL),
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
           "chips": 1, "batch": 1, "seq_len": 8192, "tokens_per_step": 8192,
           "groups": [{"steps": 10, "t_start": 0.0, "t_end": 5.0}],
           "trace": types.SimpleNamespace(devices=[object()])}
    # 16,384 tokens/s x 3 x 737.95 MFLOP / 197 TFLOP/s
    assert readers.train_mfu(obs) == pytest.approx(
        100 * 16384 * 3 * 737_951_744 / 197e12)
    assert 18 < readers.train_mfu(obs) < 19
    assert readers.train_mfu({**obs, "groups": []}) is None
    # a file that names no module is counted as a dense full-causal decoder
    dense = {**obs, "cell": types.SimpleNamespace(
        config={k: v for k, v in c.items() if k != "train_counts"},
        bench_dir=spec.BENCH_DIR, name=CELL)}
    assert readers.train_counts(dense["cell"]).__name__.endswith(".flops")
    assert readers.train_mfu(dense) > 1.3 * readers.train_mfu(obs)
    with pytest.raises(spec.SpecError):
        readers.train_counts(types.SimpleNamespace(
            config=dict(c, train_counts="none"), bench_dir=spec.BENCH_DIR,
            name=CELL))

    seconds = {"fwd": 0.02, "dq": 0.03, "dkdv": 0.07}
    monkeypatch.setattr(flash_names, "kernel_seconds",
                        lambda trace, kernel: seconds[kernel])
    monkeypatch.setattr(readers, "train_step_device_ms", lambda obs: 500.0)
    # two steps in the trace: a kernel's seconds are half a step's each
    monkeypatch.setattr(readers, "_share_of_steps",
                        lambda obs, s: 100.0 * s / 1.0)
    least = 3.5 * (4 * 14_681_088 + 33_558_528) * 16_384 / 197e12
    assert readers.flash_attention_roofline(obs) == pytest.approx(
        100 * least / 0.06)
    assert 40 < readers.flash_attention_roofline(obs) < 50
    # half the square in each of five layers would read 1.82 x that
    assert readers.flash_attention_roofline(dense) == pytest.approx(
        100 * 3.5 * 5 * (8192 * 8192 // 2) * 16_384 / 197e12 / 0.06)
    monkeypatch.setattr(
        moe_names.ssm_names, "_leaves_inside", lambda trace, module: [
            (0.0, 0.03, "%ragged-dot-none.3 = f32[65536,1024] custom-call("),
            (0.1, 0.11, "%ragged-dot-none = bf16[65536,2048] custom-call("),
            (0.2, 0.9, "%fusion.7 = f32[8192,2048] fusion(")])
    # no step metric, no rows: nothing, not the expected rows' 100+%
    assert moe_names.train_expert_matmul_roofline(obs) is None
    rows = [[500] * 16 + [40] * 112] * 4        # (expert layers, experts)
    obs["expert_rows"] = rows
    assert moe_names.held_rows_a_step(obs) == 4 * 16 * 500
    least = 3 * 2 * 32_000 * 6_291_456 / 197e12
    assert moe_names.train_expert_matmul_roofline(obs) == pytest.approx(
        100 * least / 0.02)
    assert moe_names.train_expert_matmul_roofline(obs) < 105
    splits = {"train": scope_names.Split(
        {("expert_ffn", "forward"): 0.05, ("expert_ffn", "backward"): 0.10,
         ("router", "forward"): 0.01, ("expert_dispatch", "backward"): 0.03,
         ("router_balance", "forward"): 0.001, ("ffn", "forward"): 0.2},
        1.0, [])}
    monkeypatch.setattr(scope_names, "split",
                        lambda obs, which: splits.get(which))
    assert moe_names.train_expert_ffn_time_share(obs) == pytest.approx(15.0)
    assert moe_names.train_routing_time_share(obs) == pytest.approx(4.0)
    # a served step's entries read the decode programs, not the train step
    assert moe_names.expert_ffn_time_share(obs) is None
    # a program without the kernels or the scopes (the parent): nothing
    seconds.update(fwd=0.0, dq=0.0, dkdv=0.0)
    monkeypatch.setattr(moe_names.ssm_names, "_leaves_inside",
                        lambda trace, module: [])
    splits["train"] = scope_names.Split({("ffn", "forward"): 0.2}, 1.0, [])
    for read in (readers.flash_attention_roofline,
                 moe_names.train_expert_matmul_roofline,
                 moe_names.train_expert_ffn_time_share,
                 moe_names.train_routing_time_share):
        assert read(obs) is None
        assert read({**obs, "trace": None}) is None
