"""The KDA configuration's part of the benchmark: ``lib/kda_flops.py``
against hand-worked numbers and the program's own trees; the widest
programs the cell's engine warms compiled at the REAL widths for a v5e that
is described, not attached; a CPU rehearsal of a toy of the same shape
through ``run.measure`` with ``solar_open2_decoder`` as its reference and of
``tools/kda_check.py``; and the ``kda_*`` readers' arithmetic on a split
that is given.
"""

import json
import os
import shutil
import time
import types

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import (kda_flops, kda_names, program, program_spans,
                            scope_names, spec)
from benchmarks.tests import test_rehearsal
# ``topo`` is described inside that file's fixture (never at import);
# ``compiled_kernels`` keeps these compiles out of the persistent cache.
from benchmarks.tests.test_aot_real_widths import (  # noqa: F401
    _json, _on, compiled_kernels, kernels_by_name_and_scope, one_chip, topo)

os.environ.setdefault("TPU_LOG_DIR", "disabled")
CONFIG = "solar-open2-250b"
CELL = "solar-open2-250b.serve-long-prompt"


# ------------------------------------------------------------------ flops
def test_operations_and_bytes_by_hand():
    c = _json("configs", CONFIG)
    assert [r["key"] for r in c["reduced"]] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert c["assumed"] and all(isinstance(a, str) for a in c["assumed"])
    assert kda_flops.layer_counts(c) == {"attention": 1, "kda": 3}
    assert c["program_fields"]["layer_pattern"] \
        == ["attention", "kda", "kda", "kda"]
    # every published width
    assert (c["hidden_size"], c["num_attention_heads"], c["head_dim"],
            c["num_key_value_heads"], c["moe_intermediate_size"],
            c["num_experts_per_tok"],
            c["share"]["n_routed_experts_published"]) \
        == (4096, 64, 128, 8, 1280, 8, 320)
    assert c["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    kda = 3 * 4096 * 8192 + 8192 * 4096 + 2 * (4096 * 128 + 128 * 8192) \
        + 4096 * 64 + 3 * 8192 * 4 + 64 + 8192 + 128
    attention = 4096 * (8192 + 2 * 1024) + 8192 * 4096 + 4096 * 8192
    ffn = 4096 * 320 + 320 + 3 * 4096 * 1280 + 40 * 3 * 4096 * 1280
    assert (kda, attention, ffn) == (137_732_288, 109_051_904, 646_185_280)
    per, small = (kda_flops.mixer_matmul_params(c),
                  kda_flops.mixer_small_params(c))
    assert {k: per[k] + small[k] for k in per} \
        == {"kda": kda, "attention": attention}
    layers = attention + 3 * kda + 4 * (ffn + 2 * 4096)
    assert layers == 3_107_022_656
    assert kda_flops.parameters(c) == layers + 2 * 24576 * 4096 + 4096 \
        == 3_308_353_344 == c["parameters"]
    # a slot of 16,384 positions
    assert kda_flops.slot_bytes(c, 16384) == {
        "kv": 67_108_864, "ssm": 12_582_912, "conv": 442_368}
    assert kda_flops.state_bytes(c) == 4_194_304
    # a step that advances 64 slots: each state once in, once out, 3 layers
    assert kda_flops.state_update_bytes(c, 64) == 64 * 2 * 4_194_304 * 3
    # a decode step over 64 rows of 6,500 positions, 64 x 4 picks landing on
    # ~150 (layer, expert) pairs: bytes, not FLOPs, bound it (>= 10 ms)
    lengths = [6500] * 64
    dense = 2 * kda_flops.dense_matmul_params(c)
    assert kda_flops.decode_step_bytes(c, lengths, 150) == dense \
        + 150 * 2 * 3 * 4096 * 1280 + 4096 * 64 * 6500 \
        + 2 * 64 * (12_582_912 + 442_368)
    least = kda_flops.decode_step_bytes(c, lengths, 150) / 819e9
    assert 0.009 < least < 0.012
    assert least > 3 * kda_flops.decode_step_flops(c, lengths, 256) / 197e12


def test_the_programs_trees_are_what_the_yardstick_counts():
    import jax

    from ray_tpu.models import llama, llama_serve

    c = _json("configs", CONFIG)
    engine = _json("workloads", CELL)["engine"]
    cfg = program.llama_config(c, max_seq_len=engine["max_len"])
    assert not cfg.plain_decoder and cfg.held_experts == (0, 40)
    pools = llama_serve.cache_pools(cfg, engine["max_slots"],
                                    engine["max_len"])
    per_slot = kda_flops.slot_bytes(c, engine["max_len"])
    assert sum(per_slot.values()) == 80_134_144
    assert {k: v[0] for k, v in pools.items()} \
        == {k: engine["max_slots"] * v for k, v in per_slot.items()}
    assert (pools["ssm"][1], pools["kv"][1]) == ("float32", "bfloat16")
    shapes = jax.eval_shape(lambda k: llama.init_params(k, cfg, cfg.dtype),
                            jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == c["parameters"]
    # nothing is sized by the published context of 1,048,576
    assert max(max(x.shape) for x in jax.tree.leaves(shapes)) <= 24576


# ------------------------------------------- the real widths, for the chip
def test_the_widest_programs_fit_one_chip(one_chip):
    """The decode program at the whole 16,384 positions and the prefill of
    a 12,288 bucket compile for one 16 GB chip at the cell's 64 slots: the
    decode step through the ``kda_state_update`` kernel under its scope (the
    stack aliased: no copy of the 3.2 GB of states) and the decode attention
    kernel at a group of eight queries a KV head; the prefill through the
    flash forward and XLA's chunked rule."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama, llama_serve

    engine = _json("workloads", CELL)["engine"]
    slots, max_len = engine["max_slots"], engine["max_len"]
    c = _json("configs", CONFIG)
    cfg = program.llama_config(c, max_seq_len=max_len)
    params = _on(one_chip, jax.eval_shape(
        lambda k: llama.init_params(k, cfg, cfg.dtype), jax.random.key(0)))
    cache = _on(one_chip, jax.eval_shape(
        lambda: llama_serve.init_cache(cfg, slots, max_len)))

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    decode = llama_serve.build_decode_k(cfg).lower(
        params, cache, arr(jnp.int32, slots), arr(jnp.int32, slots),
        arr(jnp.int32, slots), arr(jnp.int32, slots), arr(jnp.bool_, slots),
        arr(jnp.bool_, slots), k=16, s_active=max_len).compile()
    held = 2 * c["parameters"] + slots * 80_134_144
    memory = decode.memory_analysis()
    assert memory.argument_size_in_bytes < held + (1 << 20)
    assert memory.temp_size_in_bytes < 1 << 30
    kernels = kernels_by_name_and_scope(decode.as_text())
    assert kernels["kda_state_update", "kda_state_update"] >= 1
    assert kernels["decode_attention", "decode_attention"] >= 1
    assert kernels["ragged-dot-none", "expert_ffn"] >= 1
    bucket = max(engine["prefill_buckets"])
    prefill = llama_serve.build_prefill(cfg).lower(
        params, cache, arr(jnp.int32, 1, bucket), arr(jnp.int32, 1),
        arr(jnp.int32, 1)).compile()
    # (the compiler raises RESOURCE_EXHAUSTED if the program does not fit;
    # the donated cache is argument and result at once)
    assert prefill.memory_analysis().temp_size_in_bytes < 4 << 30
    kernels = kernels_by_name_and_scope(prefill.as_text())
    assert kernels["flash_prefill_attention", "flash_attention.fwd"] >= 1


# ------------------------------------------------- a rehearsal on the CPU
TINY = {
    "name": "tiny-kda", "source": "none (test, gated delta rule)",
    "reference": "solar_open2_decoder", "roofline": "kda_flops",
    "model_type": "solar_open2", "vocab_size": 256, "hidden_size": 64,
    "num_hidden_layers": 4, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 8, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_routed_experts": 8,
    "n_shared_experts": 1, "num_experts_per_tok": 4, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "first_k_dense_replace": 0,
    "use_rope": False, "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "gqa_interval": 3, "gqa_layers": [0, 4],
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                           "num_heads": 4, "num_kv_heads": None},
    "kda_gate_rank": 8, "max_position_embeddings": 256, "rope_theta": 10000,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    "share": {"n_routed_experts_published": 16, "experts_first": 0,
              "experts_held": 8},
    "reduced": [], "assumed": ["test"],
    # float32 throughout: a request's gap against the reference is then the
    # order of float32 sums, whichever requests a short window completes
    "dtype": {"serve": "float32", "kda_state": "float32"},
    "program_fields": {
        "layer_pattern": ["attention", "kda", "kda", "kda"], "rope": False,
        "attn_gate": True, "kda_heads": 4, "kda_head_dim": 16,
        "kda_conv": 4, "kda_gate_rank": 8, "kda_chunk": 8,
        "ssm_state_dtype": "float32", "moe_experts": 16,
        "moe_held": [0, 8], "moe_top_k": 4, "moe_norm_topk": True,
        "moe_intermediate_size": 32, "moe_shared_size": 32,
        "moe_router_score": "sigmoid", "moe_router_bias": True,
        "moe_dispatch_chunk": 16, "dtype": "float32"},
}
TINY_CELL = "tiny-kda.tiny-closed"


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark with a toy of the same shape dropped in and
    its cell appended wherever the real one is."""
    root = tmp_path_factory.mktemp("bench_kda")
    bench = str(root / "benchmarks")
    shutil.copytree(spec.BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "out", "__pycache__", "tests"))

    def drop(rel, payload):
        path = os.path.join(bench, rel)
        assert not os.path.exists(path), f"{rel} would be an edit"
        with open(path, "w") as f:
            json.dump(payload, f)

    drop("configs/tiny-kda.json", TINY)
    drop("traffic/tiny-closed.json", test_rehearsal.TRAFFIC["tiny-closed"])
    drop(f"workloads/{TINY_CELL}.json",
         dict(test_rehearsal.SERVE, kind="serve_llm_even", name=TINY_CELL,
              config="tiny-kda", traffic="tiny-closed", why="test"))
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    benchmark["configs"].append(
        {"name": "tiny-kda", "source": TINY["source"], "reduced": [],
         "file": "benchmarks/configs/tiny-kda.json", "why": "test"})
    benchmark["workloads"].append(
        {"name": TINY_CELL, "config": "tiny-kda",
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


def the_cells_entries(root=spec.ROOT):
    """What THIS cell reports, on the tree at ``root`` (the rehearsal's has
    a later PR's entries appended: nothing here counts the table or says
    what another family's names are)."""
    from benchmarks.tests.test_yardstick import cell_at, names_lead_to_files

    names_lead_to_files(root)
    cell = cell_at(root, CELL)
    assert cell.chips == 1 and cell.workload["kind"] == "serve_llm_even"
    assert cell.workload["engine"] == {
        "max_slots": 64, "max_len": 16384,
        "prefill_buckets": [4096, 8192, 12288], "paged": False}
    assert len(cell.workload["why"]) <= 200
    entry = next(c for c in cell.benchmark["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == [r["key"] for r in cell.config["reduced"]]
    assert len(entry["why"]) <= 200 and len(cell.entry["why"]) <= 200
    names = {m["name"] for m in cell.metric_entries("per_layer")}
    assert {n for n in names if n.startswith("kda_")} == {
        "kda_prefill_chunk_time_share", "kda_state_update_time_share",
        "kda_state_update_roofline", "kda_conv_gate_time_share"}
    assert {"batch.slot_wait_p50_ms", "batch.decode_kv_read_share",
            "batch.prefill_unscoped_time_share", "batch.decode_step_roofline",
            "moe_expert_matmul_roofline", "moe_expert_ffn_time_share",
            "moe_routing_time_share", "moe_expert_load_imbalance",
            "moe_shared_expert_time_share",
            "batch.prefill_expert_dispatch_time_share",
            "setup_before_engine_s", "setup_warmup_s"} <= names
    assert not {n for n in names if n.startswith(
        ("swa_", "ssm_", "dsa_", "mla_", "lfm2_", "sambay_", "chat."))}
    assert cell.config["roofline"] == "kda_flops"
    assert {m["name"] for m in cell.metric_entries("end_to_end")} \
        == {"serve_output_tokens_per_s", "setup_s"}


def test_the_cells_names_lead_to_files_and_join_the_serve_metrics():
    the_cells_entries()


def test_a_toy_kda_model_runs_end_to_end_on_the_cpu(tree, cpu_peaks):
    """One traced run of the toy cell through ``run.measure``: ``correct``
    against ``solar_open2_decoder`` with the harness's own limit, nothing
    failed, the metrics the cell joins and the program's own count of the
    state it moved are there; what only a device trace knows is left out on
    a CPU, not invented."""
    bench, benchmark_json = tree
    result, obs = bench_run.measure(
        ["--workload", TINY_CELL, "--seed", "2147486530", "--seconds", "2",
         "--trace", "1"],
        allow_platforms=("cpu",), bench_dir=bench,
        benchmark_json=benchmark_json, t_process=time.perf_counter())
    assert result["correct"] is True, obs["checks"]
    assert result["failed"] == 0 < result["attempted"]
    assert obs["cell"].reference.__name__.endswith("solar_open2_decoder")
    assert len(obs["logit_gaps"]) == 4 and obs["logit_gap_max"] < 1e-2
    metrics = result["metrics"]
    assert {"batch.slot_wait_p50_ms", "batch.token_burst_gap_p50_ms",
            "batch.decode_slot_utilization", "batch.decode_kv_read_share",
            "batch.prefill_padding_share", "window_compiles",
            "moe_expert_load_imbalance"} <= set(metrics)
    assert not {name for name in metrics if name.startswith("kda_")}
    chunk = next(c for c in program_spans.collect(obs).chunks
                 if c.get("kda_slots_advanced"))
    assert chunk["kda_state_bytes"] == 2 * chunk["kda_slots_advanced"] \
        * 3 * 4 * 16 * 16 * 4
    assert kda_names.slots_a_step(obs) <= 4


def test_the_published_width_check_rehearsed_at_toy_size(tree, capsys):
    """``tools/kda_check.py`` end to end on the toy: the intact engine
    within rounding of the reference in float32 arithmetic, the decay on
    the wrong side of the correction far off it."""
    from benchmarks.tools import kda_check

    bench, _ = tree
    assert kda_check.main([
        "--config", "tiny-kda", "--seed", "2147486531", "--bench-dir",
        bench, "--variants", "intact,decay_after_correction",
        "--before", "11", "--prompt", "21", "--new-tokens", "24",
        "--bucket", "32", "--max-len", "64"]) == 0
    done = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert done["intact.0"]["passes"] is True
    assert done["intact.0"]["counts"]["max"] < 1e-3
    assert done["decay_after_correction.0"]["passes"] is False


def test_the_references_swap_limits_lie_between_their_readings():
    """``take_out_swaps``: the sound requests' readings on the chip (PERF.md
    section 6, PR 55) are judged on what is left under 0.05, the mildest
    broken program's and an arbitrary token on their raw gaps."""
    import numpy as np

    reference = spec.load_module("references", "solar_open2_decoder")

    def request(n, over, gap=0.1):
        g = np.full(n, 0.01)
        g[:over] = gap
        return g

    for n, over in ((650, 24), (311, 17), (256, 9), (88, 7), (32, 2)):
        assert reference.take_out_swaps(request(n, over)).max() < 0.05
    assert reference.take_out_swaps(request(256, 70)).max() == 0.1
    assert reference.take_out_swaps(request(256, 1, gap=3.8)).max() == 3.8
    assert reference.take_out_swaps(request(256, 3, gap=0.49)).max() < 0.05


# ----------------------------------------------------------- the readers
def test_the_readers_arithmetic_on_a_given_split(monkeypatch):
    """A decode and a prefill program's seconds by scope as
    ``scope_names.split`` would hand them, 60 slots advanced a step: the
    shares are the scopes' own seconds over their programs', the update's
    roofline its 1.51 GB at the HBM peak over its 3 ms a step; a
    configuration of another family reads nothing."""
    c = _json("configs", CONFIG)
    splits = {
        "decode": scope_names.Split(
            {("kda_state_update", "forward"): 0.15,
             ("kda_gates", "forward"): 0.05, ("ffn", "forward"): 0.30},
            1.0, []),
        "prefill": scope_names.Split(
            {("kda_chunk", "forward"): 0.5, ("kda_gates", "forward"): 0.25,
             ("ffn", "forward"): 0.75}, 3.0, [])}
    monkeypatch.setattr(scope_names, "split",
                        lambda obs, which: splits.get(which))
    monkeypatch.setattr(kda_names.readers, "decode_step_device_ms",
                        lambda obs: 20.0)
    monkeypatch.setattr(kda_names, "slots_a_step", lambda obs: 60.0)
    obs = {"cell": types.SimpleNamespace(config=c, bench_dir=spec.BENCH_DIR,
                                         name=CELL),
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    assert kda_names.prefill_chunk_time_share(obs) \
        == pytest.approx(100 * 0.5 / 3.0)
    assert kda_names.state_update_time_share(obs) == pytest.approx(15.0)
    assert kda_names.conv_gate_time_share(obs) \
        == pytest.approx(100 * 0.30 / 4.0)
    least = 60 * 2 * 4_194_304 * 3 / 819e9
    assert kda_names.state_update_roofline(obs) \
        == pytest.approx(100 * least / (0.15 * 20e-3))
    # bytes, not FLOPs, set the floor: 7 FLOPs an element against 8 bytes
    assert kda_flops.state_update_flops(c, 60) / 197e12 < least
    # a program without the scopes: nothing to read, nothing raised
    splits["decode"] = scope_names.Split({("ffn", "forward"): 0.3}, 1.0, [])
    splits["prefill"] = None
    assert kda_names.state_update_time_share(obs) is None
    assert kda_names.state_update_roofline(obs) is None
    assert kda_names.prefill_chunk_time_share(obs) is None
    assert kda_names.conv_gate_time_share(obs) is None
