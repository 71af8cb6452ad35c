"""The Nemotron-H configuration's part of the benchmark:
``lib/nemotron_flops.py`` (the stack's parameters, pools and whole step) and
what its file states for ``lib/moe_flops.py`` / ``lib/ssm_flops.py`` (the
grouped matmuls', the state update's) against hand-worked numbers and the
program's own trees; the widest programs the cell's engine warms compiled at the REAL
widths for a v5e that is described, not attached; a CPU rehearsal of a toy
of the same shape through ``run.measure`` with ``nemotron_h_decoder`` as its
reference and of ``tools/nemotron_check.py``; and the arithmetic of the
``moe_*`` / ``ssm_*`` readers the cell joins, on a split that is given.
"""

import json
import os
import shutil
import time
import types

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import (moe_flops, moe_names, nemotron_flops, program,
                            program_spans, readers, scope_names, spec,
                            ssm_flops, ssm_names)
from benchmarks.tests import test_rehearsal
# ``topo`` is described inside that file's fixture (never at import);
# ``compiled_kernels`` keeps these compiles out of the persistent cache.
from benchmarks.tests.test_aot_real_widths import (  # noqa: F401
    _json, _on, compiled_kernels, kernels_by_name_and_scope, one_chip, topo)

os.environ.setdefault("TPU_LOG_DIR", "disabled")
CONFIG = "nemotron-3-super-120b-a12b"
CELL = "nemotron-3-super-120b-a12b.serve-reasoning-decode"
# the counted entries the cell joins by what its file states
# (``expert_shape``, ``ssm_shape``): one reader a question for every
# configuration with experts or a state-space mixer
JOINED = (
    "moe_expert_matmul_roofline", "moe_expert_load_imbalance",
    "moe_expert_ffn_time_share", "moe_routing_time_share",
    "moe_shared_expert_time_share", "ssm_state_update_time_share",
    "ssm_state_update_roofline", "ssm_prefill_scan_time_share")
PUBLISHED_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                     "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


# ------------------------------------------------------------------ flops
def test_operations_and_bytes_by_hand():
    c = _json("configs", CONFIG)
    assert [r["key"] for r in c["reduced"]] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"]
    assert [(r["published"], r["here"]) for r in c["reduced"]] == [
        (88, 11), (PUBLISHED_PATTERN, "MEMEMEM*EME"), (512, 128),
        (131072, 32768)]
    assert len(PUBLISHED_PATTERN) == 88 and [
        PUBLISHED_PATTERN.count(k) for k in "ME*"] == [40, 40, 8]
    assert c["assumed"] and all(isinstance(a, str) for a in c["assumed"])
    assert any("NoPE" in a for a in c["assumed"])
    assert any("NOT built" in a for a in c["assumed"])
    assert nemotron_flops.block_counts(c) == {"M": 5, "*": 1, "E": 5}
    assert c["program_fields"]["block_pattern"] \
        == c["hybrid_override_pattern"] == PUBLISHED_PATTERN[:11]
    assert c["share"]["chips_that_share_a_layer"] == 4
    # every published width
    assert (c["hidden_size"], c["num_attention_heads"], c["head_dim"],
            c["num_key_value_heads"], c["mamba_num_heads"],
            c["mamba_head_dim"], c["ssm_state_size"], c["n_groups"],
            c["conv_kernel"], c["chunk_size"], c["moe_latent_size"],
            c["moe_intermediate_size"],
            c["moe_shared_expert_intermediate_size"],
            c["num_experts_per_tok"], c["routed_scaling_factor"],
            c["share"]["n_routed_experts_published"]) \
        == (4096, 32, 128, 2, 128, 64, 128, 8, 4, 128, 1024, 2688, 5376,
            22, 5, 512)
    mamba = 4096 * (8192 + 10240 + 128) + 5 * 10240 + 3 * 128 + 8192 \
        + 8192 * 4096 + 4096
    attention = 4096 * (4096 + 2 * 256) + 4096 * 4096 + 4096
    outside = 4096 * 512 + 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 + 4096
    expert = 2 * 1024 * 2688
    assert (mamba, attention, outside, expert) \
        == (109_640_064, 35_655_680, 54_530_560, 5_505_024)
    assert nemotron_flops.expert_params(c) == expert
    # what the file states for the readers every such configuration joins
    # is what its published keys say: two matrices an expert in the latent,
    # the pattern's E and M blocks, the mixer's own sizes
    assert c["expert_shape"] == {
        "why": c["expert_shape"]["why"], "matrices": 2,
        "row_width": c["moe_latent_size"], "layers": 5}
    assert (moe_flops.expert_params(c), moe_flops.expert_width(c),
            moe_flops.expert_layers(c), moe_flops.experts_held(c)) \
        == (expert, 2688, 5, 128)
    assert ssm_flops.shape(c) == (
        c["mamba_num_heads"], c["mamba_head_dim"], c["ssm_state_size"],
        c["n_groups"], c["conv_kernel"], 5) == (128, 64, 128, 8, 4, 5)
    assert ssm_flops.shape(c) == nemotron_flops.mamba_dims(c)[:4] + (4, 5)
    assert c["ssm_shape"]["ops"] == "scopes"
    per, small = (nemotron_flops.block_matmul_params(c),
                  nemotron_flops.block_small_params(c))
    assert {k: per[k] + small[k] for k in per} \
        == {"M": mamba, "*": attention, "E": outside}
    blocks = 5 * mamba + attention + 5 * (outside + 128 * expert)
    assert blocks == 4_379_724_160
    assert nemotron_flops.parameters(c) == blocks + 2 * 32768 * 4096 + 4096 \
        == 4_648_163_712 == c["parameters"]
    # a slot of 4,096 positions
    assert nemotron_flops.slot_bytes(c, 4096) == {
        "kv": 4_194_304, "ssm": 5 * 4_194_304, "conv": 307_200}
    assert nemotron_flops.state_bytes(c) == 128 * 64 * 128 * 4
    assert ssm_flops.state_bytes_per_slot(c) == {
        "ssm": 5 * 4_194_304, "conv": 307_200}
    # a step that advances 96 slots: each state once in, once out, 5 blocks
    assert ssm_flops.state_update_bytes(c, 96) == 96 * 2 * 4_194_304 * 5
    assert ssm_flops.state_update_bytes(c, 96) / 819e9 \
        == pytest.approx(4.9e-3, rel=0.01)
    assert ssm_flops.state_update_flops(c, 96) \
        == 5 * 96 * 5 * 128 * 64 * 128
    # 96 x 22 / 4 picks a block land on ~630 of its 640 (block, expert)
    # pairs: the grouped matmuls stream 7.0 GB, bytes and not FLOPs
    assert moe_flops.expert_matmul_bytes(c, 630, 2640) \
        == (630 * expert + 2640 * (2 * 1024 + 2 * 2688)) * 2
    assert moe_flops.expert_matmul_flops(c, 2640) == 2 * 2640 * expert
    assert moe_flops.expert_matmul_bytes(c, 630, 2640) / 819e9 \
        > 50 * moe_flops.expert_matmul_flops(c, 2640) / 197e12
    # the whole step at 96 rows of 1,000 positions: ~16 ms, bytes-bound
    lengths = [1000] * 96
    dense = 2 * nemotron_flops.dense_matmul_params(c)
    assert nemotron_flops.decode_step_bytes(c, lengths, 630) == dense \
        + 630 * 2 * expert + 1024 * 96 * 1000 \
        + 2 * 96 * (5 * 4_194_304 + 307_200)
    least = nemotron_flops.decode_step_bytes(c, lengths, 630) / 819e9
    assert 0.014 < least < 0.018
    assert nemotron_flops.decode_step_flops(c, lengths, 2640) \
        == 2.0 * nemotron_flops.dense_matmul_params(c) * 96 \
        + 2 * 2640 * expert + 2 * 2 * 32 * 128 * 96_000 \
        + 5 * 96 * 5 * 128 * 64 * 128
    assert least > 5 * nemotron_flops.decode_step_flops(c, lengths, 2640) \
        / 197e12


def test_the_programs_trees_are_what_the_yardstick_counts():
    import jax

    from ray_tpu.models import llama, llama_serve

    c = _json("configs", CONFIG)
    engine = _json("workloads", CELL)["engine"]
    cfg = program.llama_config(c, max_seq_len=engine["max_len"])
    assert not cfg.plain_decoder and cfg.held_experts == (0, 128)
    assert cfg.layer_types == ("mamba",) * 4 + ("attention", "mamba")
    assert [(key, part.n_layers, part.no_ffn)
            for part, key, _ in cfg.parts()] == [
        ("layers", 3, False), ("layers_1", 1, True), ("layers_2", 2, False)]
    pools = llama_serve.cache_pools(cfg, engine["max_slots"],
                                    engine["max_len"])
    per_slot = nemotron_flops.slot_bytes(c, engine["max_len"])
    assert sum(per_slot.values()) == 25_473_024
    assert {k: v[0] for k, v in pools.items()} \
        == {k: engine["max_slots"] * v for k, v in per_slot.items()}
    assert (pools["ssm"][1], pools["kv"][1]) == ("float32", "bfloat16")
    assert llama_serve.share_and_state(cfg) == {
        "state_bytes_per_slot": per_slot["ssm"] + per_slot["conv"],
        "ssm_groups": 8, "experts_held": 128, "experts_routed": 512}
    shapes = jax.eval_shape(lambda k: llama.init_params(k, cfg, cfg.dtype),
                            jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == c["parameters"]
    assert shapes["layers"]["w_up"].shape == (3, 128, 1024, 2688)
    assert "w_gate" not in shapes["layers"]
    # nothing is sized by the published context of 262,144
    assert max(max(x.shape) for x in jax.tree.leaves(shapes)) <= 32768


# ------------------------------------------- the real widths, for the chip
def test_the_widest_programs_fit_one_chip(one_chip):
    """The decode program at the whole 4,096 positions and the prefill of a
    2,048 bucket compile for one 16 GB chip at the cell's slots: the decode
    step through the GROUPED ``ssm_state_update`` kernel under its scope
    (the stack aliased: no copy of the 2 GB of states; a 4 MiB block, so the
    call asks Mosaic for more than its scoped 16 MiB), the decode attention
    KERNEL over 2 K/V heads stored as rows, and two grouped matmuls an
    expert block."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama, llama_serve

    engine = _json("workloads", CELL)["engine"]
    slots, max_len = engine["max_slots"], engine["max_len"]
    c = _json("configs", CONFIG)
    cfg = program.llama_config(c, max_seq_len=max_len)
    params = _on(one_chip, jax.eval_shape(
        lambda k: llama.init_params(k, cfg, cfg.dtype), jax.random.key(0)))
    cache = jax.eval_shape(lambda: llama_serve.init_cache(cfg, slots,
                                                          max_len))
    assert llama_serve.kv_rows(cfg, cache)["decode_attention"] == "kernel"
    cache = _on(one_chip, cache)

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    decode = llama_serve.build_decode_k(cfg).lower(
        params, cache, arr(jnp.int32, slots), arr(jnp.int32, slots),
        arr(jnp.int32, slots), arr(jnp.int32, slots), arr(jnp.bool_, slots),
        arr(jnp.bool_, slots), k=16, s_active=max_len).compile()
    held = 2 * c["parameters"] + slots * 25_473_024
    memory = decode.memory_analysis()
    assert memory.argument_size_in_bytes < held + (1 << 20)
    assert memory.temp_size_in_bytes < 1 << 29
    kernels = kernels_by_name_and_scope(decode.as_text())
    assert kernels["ssm_state_update", "ssm_state_update"] >= 1
    assert kernels["decode_attention", "decode_attention"] >= 1
    # two matrices an expert: two grouped matmuls a part of the stack
    assert kernels["ragged-dot-none", "expert_ffn"] == 2 * 3
    bucket = max(engine["prefill_buckets"])
    prefill = llama_serve.build_prefill(cfg).lower(
        params, cache, arr(jnp.int32, 1, bucket), arr(jnp.int32, 1),
        arr(jnp.int32, 1)).compile()
    # (the compiler raises RESOURCE_EXHAUSTED if the program does not fit;
    # the donated cache is argument and result at once)
    assert prefill.memory_analysis().temp_size_in_bytes < 2 << 30


def test_a_decode_step_of_two_slots_compiles(one_chip):
    """22 picks of 2 slots are 44 sorted rows, no whole sublane tiles: the
    v5e compiler failed on the grouped matmuls' 3 x 128 group sizes
    (INTERNAL, "Bitcast cannot have different shape sizes") until
    ``moe._sorted_ffn`` gathered whole tiles of rows (PR 61); an engine of
    few slots, and the published-width check's, is this program."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama, llama_serve

    slots, max_len = 2, 2048
    cfg = program.llama_config(_json("configs", CONFIG), max_seq_len=max_len)
    assert slots * cfg.moe_top_k % 8
    params = _on(one_chip, jax.eval_shape(
        lambda k: llama.init_params(k, cfg, cfg.dtype), jax.random.key(0)))
    cache = _on(one_chip, jax.eval_shape(
        lambda: llama_serve.init_cache(cfg, slots, max_len)))
    ints = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    flags = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip)
    decode = llama_serve.build_decode_k(cfg).lower(
        params, cache, ints, ints, ints, ints, flags, flags, k=16,
        s_active=max_len).compile()
    kernels = kernels_by_name_and_scope(decode.as_text())
    assert kernels["ragged-dot-none", "expert_ffn"] == 2 * 3


# ------------------------------------------------- a rehearsal on the CPU
TINY = {
    "name": "tiny-nemotron", "source": "none (test, single-sub-layer blocks)",
    "reference": "nemotron_h_decoder", "roofline": "nemotron_flops",
    "expert_shape": {"matrices": 2, "row_width": 32, "layers": 3},
    "ssm_shape": {"heads": 8, "head_dim": 8, "state": 16, "groups": 2,
                  "conv": 4, "layers": 3, "ops": "scopes"},
    "model_type": "nemotron_h", "vocab_size": 256, "hidden_size": 64,
    "num_hidden_layers": 7, "hybrid_override_pattern": "MEM*EME",
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 8,
    "intermediate_size": 32, "mamba_num_heads": 8, "mamba_head_dim": 8,
    "ssm_state_size": 16, "n_groups": 2, "conv_kernel": 4, "chunk_size": 8,
    "mlp_hidden_act": "relu2", "moe_latent_size": 32,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 48,
    "n_routed_experts": 8, "n_shared_experts": 1, "num_experts_per_tok": 3,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5, "n_group": 1,
    "topk_group": 1, "max_position_embeddings": 256, "rope_theta": 10000,
    "norm_eps": 1e-5, "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    "share": {"n_routed_experts_published": 16, "experts_first": 0,
              "experts_held": 8},
    "reduced": [], "assumed": ["test"],
    # float32 throughout: a request's gap against the reference is then the
    # order of float32 sums, whichever requests a short window completes
    "dtype": {"serve": "float32", "ssm_state": "float32"},
    "state_check": {"slots": test_rehearsal.ENGINE["max_slots"],
                    "prefill_buckets": test_rehearsal.ENGINE[
                        "prefill_buckets"]},
    "program_fields": {
        "n_layers": 4, "block_pattern": "MEM*EME", "rope": False,
        "ssm_heads": 8, "ssm_head_dim": 8, "ssm_state": 16, "ssm_groups": 2,
        "ssm_conv": 4, "ssm_chunk": 8, "ssm_state_dtype": "float32",
        "moe_experts": 16, "moe_held": [0, 8], "moe_top_k": 3,
        "moe_norm_topk": True, "moe_intermediate_size": 32,
        "moe_shared_size": 48, "moe_latent_size": 32,
        "moe_activation": "relu2", "moe_router_score": "sigmoid",
        "moe_router_bias": True, "moe_routed_scale": 2.5,
        "moe_dispatch_chunk": 16, "dtype": "float32"},
}
TINY_CELL = "tiny-nemotron.tiny-closed"


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark with a toy of the same shape dropped in and
    its cell appended wherever the real one is."""
    root = tmp_path_factory.mktemp("bench_nemotron")
    bench = str(root / "benchmarks")
    shutil.copytree(spec.BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "out", "__pycache__", "tests"))

    def drop(rel, payload):
        path = os.path.join(bench, rel)
        assert not os.path.exists(path), f"{rel} would be an edit"
        with open(path, "w") as f:
            json.dump(payload, f)

    drop("configs/tiny-nemotron.json", TINY)
    drop("traffic/tiny-closed.json", test_rehearsal.TRAFFIC["tiny-closed"])
    drop(f"workloads/{TINY_CELL}.json",
         dict(test_rehearsal.SERVE, kind="serve_llm_even", name=TINY_CELL,
              config="tiny-nemotron", traffic="tiny-closed", why="test"))
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    benchmark["configs"].append(
        {"name": "tiny-nemotron", "source": TINY["source"], "reduced": [],
         "file": "benchmarks/configs/tiny-nemotron.json", "why": "test"})
    benchmark["workloads"].append(
        {"name": TINY_CELL, "config": "tiny-nemotron",
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
    from benchmarks.tests.test_yardstick import (cell_at, names_lead_to_files,
                                                 reader_at)

    names_lead_to_files(root)
    cell = cell_at(root, CELL)
    assert cell.chips == 1 and cell.workload["kind"] == "serve_llm_even"
    engine = dict(cell.workload["engine"])
    slots = engine.pop("max_slots")
    assert 64 <= slots <= 96
    assert engine == {"max_len": 4096, "prefill_buckets": [512, 1024, 2048],
                      "paged": False}
    arrivals = cell.traffic["arrivals"]
    assert arrivals["process"] == "closed" \
        and arrivals["callers"] == 2 * slots
    assert cell.traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 512, "sigma": 0.6, "min": 128,
        "max": 2048, "stratified": 16}
    assert cell.traffic["output_tokens"] == {
        "dist": "lognormal", "median": 768, "sigma": 0.35, "min": 256,
        "max": 1024, "stratified": 16}
    assert len(cell.workload["why"]) <= 200
    entry = next(c for c in cell.benchmark["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == [r["key"] for r in cell.config["reduced"]]
    assert len(entry["why"]) <= 200 and len(cell.entry["why"]) <= 200
    names = {m["name"] for m in cell.metric_entries("per_layer")}
    # the cell brings no entry of its own: its experts, its shared expert
    # and its state-space mixer are asked the questions every such
    # configuration is, at the shapes its file states
    assert set(JOINED) <= names
    for reader in JOINED:
        assert callable(reader_at(root, reader).read)
    assert {"batch.slot_wait_p50_ms", "batch.decode_kv_read_share",
            "batch.prefill_unscoped_time_share", "batch.decode_step_roofline",
            "batch.prefill_expert_dispatch_time_share",
            "setup_before_engine_s", "setup_warmup_s"} <= names
    assert not {n for n in names if n.startswith(
        ("swa_", "dsa_", "mla_", "lfm2_", "sambay_", "kda_", "chat."))}
    assert cell.config["roofline"] == "nemotron_flops"
    assert {m["name"] for m in cell.metric_entries("end_to_end")} \
        == {"serve_output_tokens_per_s", "setup_s"}


def test_the_cells_names_lead_to_files_and_join_the_serve_metrics():
    the_cells_entries()


def test_a_toy_nemotron_model_runs_end_to_end_on_the_cpu(tree, cpu_peaks):
    """One traced run of the toy cell through ``run.measure``: ``correct``
    against ``nemotron_h_decoder`` with the harness's own limit, nothing
    failed, the metrics the cell joins and the program's own counts are
    there; what only a device trace knows is left out on a CPU, not
    invented."""
    bench, benchmark_json = tree
    result, obs = bench_run.measure(
        ["--workload", TINY_CELL, "--seed", "2147486630", "--seconds", "2",
         "--trace", "1"],
        allow_platforms=("cpu",), bench_dir=bench,
        benchmark_json=benchmark_json, t_process=time.perf_counter())
    assert result["correct"] is True, obs["checks"]
    assert result["failed"] == 0 < result["attempted"]
    assert obs["cell"].reference.__name__.endswith("nemotron_h_decoder")
    assert len(obs["logit_gaps"]) == 4 and obs["logit_gap_max"] < 1e-2
    metrics = result["metrics"]
    assert {"batch.slot_wait_p50_ms", "batch.token_burst_gap_p50_ms",
            "batch.decode_slot_utilization", "batch.decode_kv_read_share",
            "batch.prefill_padding_share", "window_compiles",
            "moe_expert_load_imbalance"} <= set(metrics)
    # the busiest of the 3 E blocks x 8 held experts against their mean
    assert metrics["moe_expert_load_imbalance"]["value"] >= 1.0
    # no device trace on a CPU: no share of a device's time is invented
    assert not {name for name in metrics if name.endswith(
        ("_roofline", "_time_share"))}
    load = moe_names.chunk_medians(obs)
    assert load[1] <= 3 * 8 and load[2] >= 1.0
    chunk = next(c for c in program_spans.collect(obs).chunks
                 if c.get("state_rows_updated"))
    assert chunk["expert_rows"] + chunk["expert_rows_elsewhere"] \
        == chunk["state_rows_updated"] * 3 * 3


def test_the_published_width_check_rehearsed_at_toy_size(tree, capsys):
    """``tools/nemotron_check.py`` end to end on the toy: the intact engine
    within rounding of the reference in float32 arithmetic, the gated norm
    over the whole width far off it."""
    from benchmarks.tools import nemotron_check

    bench, _ = tree
    assert nemotron_check.main([
        "--config", "tiny-nemotron", "--seed", "2147486631", "--bench-dir",
        bench, "--variants", "intact,bf16_state,whole_width_norm",
        "--before", "11", "--prompt", "21", "--new-tokens", "24",
        "--bucket", "32", "--max-len", "64"]) == 0
    done = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert done["intact.0"]["passes"] is True
    assert done["intact.0"]["counts"]["max"] < 1e-3
    assert done["whole_width_norm.0"]["passes"] is False
    # the states the programs hold of the reply, read back from the slot:
    # float32 arithmetic here, so the intact program's are the recurrence's
    # to the order of its sums, and a state KEPT in bfloat16 is a thousand
    # times further (which the reply's tokens do not show)
    intact, kept_in_bf16 = (done[f"{v}.0"]["state_deviation"]
                            for v in ("intact", "bf16_state"))
    assert len(intact["head"]) == len(intact["whole"]) == 3
    assert max(intact["head"]) < 1e-5 < 1e-3 < min(kept_in_bf16["head"])
    assert done["bf16_state.0"]["counts"]["max"] < 1e-3


# ----------------------------------------------------------- the readers
def test_the_readers_arithmetic_on_a_given_split(monkeypatch):
    """A decode and a prefill program's seconds by scope as
    ``scope_names.split`` would hand them, 90 slots advanced a step: the
    shares are the scopes' own seconds over their programs', the update's
    roofline its 3.77 GB at the HBM peak over its 6 ms a step, the grouped
    matmuls' their two matrices an expert in the latent over their kernels';
    a configuration whose file states no shape reads as it did."""
    c = _json("configs", CONFIG)
    splits = {
        "decode": scope_names.Split(
            {("ssm_state_update", "forward"): 0.20,
             ("latent_proj", "forward"): 0.04,
             ("shared_expert", "forward"): 0.06,
             ("decode_attention", "forward"): 0.03,
             ("expert_ffn", "forward"): 0.40}, 1.0, []),
        "prefill": scope_names.Split(
            {("ssm_scan", "forward"): 0.6, ("ffn", "forward"): 0.9},
            3.0, [])}
    monkeypatch.setattr(scope_names, "split",
                        lambda obs, which: splits.get(which))
    monkeypatch.setattr(readers, "decode_step_device_ms", lambda obs: 30.0)
    monkeypatch.setattr(ssm_names, "rows_a_step", lambda obs: 90.0)
    obs = {"cell": types.SimpleNamespace(config=c, bench_dir=spec.BENCH_DIR,
                                         name=CELL),
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    assert ssm_names.state_update_time_share(obs) == pytest.approx(20.0)
    assert moe_names.shared_expert_time_share(obs) == pytest.approx(6.0)
    assert moe_names.expert_ffn_time_share(obs) == pytest.approx(40.0)
    assert ssm_names.prefill_scan_time_share(obs) \
        == pytest.approx(100 * 0.6 / 3.0)
    least = 90 * 2 * 4_194_304 * 5 / 819e9
    assert ssm_names.state_update_roofline(obs) \
        == pytest.approx(100 * least / (0.20 * 30e-3))
    # bytes, not FLOPs, set the floor: 5 FLOPs an element against 8 bytes
    assert ssm_flops.state_update_flops(c, 90) / 197e12 < least
    # the grouped matmuls: 2,000 rows a step on 600 (block, expert) pairs,
    # their kernels a quarter of the decode programs' 2 s
    trace = types.SimpleNamespace(
        devices=[object()],
        module_runs=lambda module: [(0.0, 2.0, "jit_decode_k")])
    monkeypatch.setattr(
        ssm_names, "_leaves_inside", lambda trace, module: [
            (0.0, 0.3, "%ragged-dot-none.3 = f32[2112,2688] custom-call("),
            (0.4, 0.6, "%ragged-dot-none = bf16[2112,1024] custom-call("),
            (0.7, 0.9, "%fusion.7 = bf16[96,4096] fusion(")])
    monkeypatch.setattr(moe_names, "chunk_medians",
                        lambda obs: (2000.0, 600.0, 4.0))
    floor = (600 * 5_505_024 + 2000 * (2 * 1024 + 2 * 2688)) * 2 / 819e9
    assert moe_names.expert_matmul_roofline({**obs, "trace": trace}) \
        == pytest.approx(100 * floor / (0.25 * 30e-3))
    assert moe_names.load_imbalance(obs) == 4.0
    # Granite's file states no shape: its update and its scan are told by
    # the kernel's name and their arrays, not by a scope, and a trace that
    # holds neither reads nothing
    other = {**obs, "cell": types.SimpleNamespace(
        config=_json("configs", "granite-4.0-h-micro"),
        workload={"engine": {"max_slots": 80}}, bench_dir=spec.BENCH_DIR,
        name="x"), "trace": trace}
    assert ssm_flops.shape(other["cell"].config) == (64, 64, 128, 1, 4, 36)
    assert ssm_names.state_update_time_share(other) is None
    assert ssm_names.state_update_roofline(other) is None
    assert ssm_names.prefill_scan_time_share(other) is None
    # a configuration with neither layer: nothing is looked at
    dense = {**obs, "cell": types.SimpleNamespace(
        config=_json("configs", "internlm2-1.8b"),
        workload={"engine": {"max_slots": 120}}, bench_dir=spec.BENCH_DIR,
        name="y"), "trace": trace}
    assert ssm_flops.shape(dense["cell"].config) is None
    assert ssm_names.state_update_time_share(dense) is None
    # a program without the scopes
    splits["decode"] = scope_names.Split({("ffn", "forward"): 0.3}, 1.0, [])
    splits["prefill"] = None
    assert ssm_names.state_update_time_share(obs) is None
    assert ssm_names.state_update_roofline(obs) is None
    assert ssm_names.prefill_scan_time_share(obs) is None
    assert moe_names.shared_expert_time_share(obs) is None
    # on the yardstick's fixed observations, what 5e17fcb's
    # ``latent_moe_expert_load_imbalance`` read: the busiest expert against
    # the mean over the 5 E blocks x 128 held experts, not 11 layers' worth
    from benchmarks.tests.test_yardstick import fixed_observations

    monkeypatch.undo()
    assert moe_names.load_imbalance(fixed_observations(c, CELL)) \
        == pytest.approx(13.643410852713178, rel=1e-12)


def test_the_references_swap_limits_lie_between_their_readings():
    """``take_out_swaps``: a sound request's readings are judged on what is
    left under SWAP_GAP, a broken program's and an arbitrary token on their
    raw gaps."""
    import numpy as np

    reference = spec.load_module("references", "nemotron_h_decoder")

    def request(n, over, gap=0.1):
        g = np.full(n, 0.01)
        g[:over] = gap
        return g

    allowed = reference.swaps_allowed
    for n in (1024, 768, 256):
        assert reference.take_out_swaps(request(n, allowed(n))).max() \
            < reference.SWAP_GAP
        assert reference.take_out_swaps(request(n, allowed(n) + 1)).max() \
            == 0.1
    over = 1.5 * reference.SWAP_CEILING
    assert reference.take_out_swaps(request(256, 1, gap=over)).max() == over


def test_the_state_limit_is_judged_on_the_first_blocks_furthest_head():
    """``judged``: the gaps with the swaps taken out and, last, the first M
    block's furthest head over STATE_LIMIT (>= 1 where it is over, which the
    harness's LOGIT_MARGIN refuses; 0 else); ``lib/nemotron_state.deviation``
    by the whole state and by the head; the check's geometry is the cell's
    engine's."""
    import numpy as np

    from benchmarks.kinds import serve_llm
    from benchmarks.lib import nemotron_state

    reference = spec.load_module("references", "nemotron_h_decoder")
    limit, gaps = reference.STATE_LIMIT, np.full(256, 0.01)
    # between its two readings on the chip, with room on both sides: the
    # largest a sound engine read (0.0064 of 62 requests) and the smallest a
    # broken one did (0.0235, the recurrence run in bfloat16)
    assert 1.5 * 0.0064 < limit < 0.0235 / 1.5
    under = {"whole": [limit / 4, 1.0], "head": [limit / 2, 1.0]}
    over = {"whole": [limit / 4, 0.0], "head": [limit * 2, 0.0]}
    assert reference.judged(gaps, under).tolist() == [0.01] * 256 + [0.0]
    assert reference.judged(gaps, over)[-1] == 2.0 > serve_llm.LOGIT_MARGIN
    # one head of four a hundredth off: the whole state hardly moves
    rng = np.random.default_rng(0)
    state = rng.standard_normal((2, 4, 8, 16)).astype(np.float32)
    served = state.copy()
    served[1, 2] *= 1.01
    got = nemotron_state.deviation(served, state)
    assert got["whole"][0] == got["head"][0] == 0.0
    assert abs(got["head"][1] - 0.01) < 1e-6
    assert 0.004 < got["whole"][1] < 0.006
    engine = _json("workloads", CELL)["engine"]
    assert nemotron_state.geometry(_json("configs", CONFIG)) == {
        "slots": engine["max_slots"],
        "buckets": tuple(engine["prefill_buckets"])}
