"""The power-retention configuration's part of the benchmark:
``lib/power_flops.py`` against hand-worked numbers and the program's own
trees; the widest programs the cell's engine warms compiled at the REAL
widths for a v5e that is described, not attached; a CPU rehearsal of a toy
of the same shape through ``run.measure`` with ``brumby_decoder`` as its
reference and of ``tools/power_check.py``; and the ``power_*`` readers'
arithmetic on a split that is given.
"""

import json
import os
import shutil
import time
import types

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import (power_flops, power_names, program,
                            program_spans, scope_names, spec)
from benchmarks.tests import test_rehearsal
# ``topo`` is described inside that file's fixture (never at import);
# ``compiled_kernels`` keeps these compiles out of the persistent cache.
from benchmarks.tests.test_aot_real_widths import (  # noqa: F401
    _json, _on, compiled_kernels, kernels_by_name_and_scope, one_chip, topo)

os.environ.setdefault("TPU_LOG_DIR", "disabled")
CONFIG = "brumby-14b-base"
CELL = "brumby-14b-base.serve-long-doc-reason"
ENGINE = {"max_slots": 16, "max_len": 18432,
          "prefill_buckets": [9216, 10752, 12800, 16384], "paged": False}
# the catalog's row (model-configs guide, architectures.jsonl): every key
CATALOG = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 17408,
    "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


# ------------------------------------------------------------------ flops
def test_operations_and_bytes_by_hand():
    c = _json("configs", CONFIG)
    assert [r["key"] for r in c["reduced"]] == ["num_hidden_layers"]
    assert (c["reduced"][0]["published"], c["reduced"][0]["here"]) == (40, 8)
    assert c["assumed"] and all(isinstance(a, str) for a in c["assumed"])
    # every published key as published, but the one that is cut
    assert {k: c[k] for k in CATALOG} == {**CATALOG, "num_hidden_layers": 8}
    assert c["program_fields"]["layer_pattern"] == ["power"]
    assert c["gate_shift"] == c["program_fields"]["power_gate_shift"]
    assert c["power_eps"] == c["program_fields"]["power_eps"]
    mixer = 2 * 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 8
    assert mixer == power_flops.mixer_matmul_params(c) == 62_955_520
    layer = mixer + 256 + 10_240 + 3 * 5120 * 17408
    assert layer == power_flops.layer_params(c) == 330_352_896
    assert power_flops.parameters(c) == 8 * layer + 2 * 151936 * 5120 + 5120 \
        == 4_198_652_928 == c["parameters"]
    # a slot: 8 layers x 8 heads x (8,256 x 128 + 8,256) x 4 B, at any length
    assert power_flops.dims(c)[-1] == 8256
    assert power_flops.state_bytes(c) == 8 * (8256 * 128 + 8256) * 4 \
        == 34_080_768
    assert power_flops.slot_bytes(c) == 272_646_144
    # a step that advances 20 slots: each state once in, once out
    assert power_flops.state_update_bytes(c, 20) == 20 * 2 * 272_646_144
    weights = 2 * power_flops.dense_matmul_params(c)
    assert weights == 2 * (8 * (mixer + 3 * 5120 * 17408) + 5120 * 151936)
    assert power_flops.decode_step_bytes(c, 20) == weights + 10_905_845_760
    assert power_flops.state_bytes_share(c, 20) == pytest.approx(0.6145, 1e-3)
    assert power_flops.state_bytes_share(c, 16) == pytest.approx(0.5605, 1e-3)
    # bytes, not FLOPs, bound the step and the update
    least = power_flops.decode_step_bytes(c, 20) / 819e9
    assert 0.021 < least < 0.022
    assert least > 20 * power_flops.decode_step_flops(c, 20) / 197e12
    assert power_flops.state_update_flops(c, 20) / 197e12 \
        < power_flops.state_update_bytes(c, 20) / 819e9
    # the chunked form a position and layer: 85 M read, 17 M update, and
    # 20,480 c / 2 of the quadratic form
    assert power_flops.chunk_flops(c, 1, 128) == pytest.approx(
        2 * 40 * 8256 * 128 + 2 * 8 * 8256 * 128 + 20480 * 128 / 2)
    assert 84e6 < 2 * 40 * 8256 * 128 < 85e6 < 101e6 \
        < power_flops.chunk_flops(c, 1, 0) < 102e6
    assert power_flops.chunk_bytes(c, 128, 128) == 128 * 4 * 96 * 128
    assert power_flops.chunk_bytes(c, 1, 128) / 819e9 \
        < power_flops.chunk_flops(c, 1, 128) / 197e12


def test_the_programs_trees_are_what_the_yardstick_counts():
    import jax

    from ray_tpu.models import llama, llama_serve, power_retention

    c = _json("configs", CONFIG)
    engine = _json("workloads", CELL)["engine"]
    cfg = program.llama_config(c, max_seq_len=engine["max_len"])
    assert not cfg.plain_decoder and cfg.attending_layers() == 0
    assert power_retention.state_rows(cfg) == 8320
    pools = llama_serve.cache_pools(cfg, engine["max_slots"],
                                    engine["max_len"])
    # laid out: 8,320 rows and a whole tile for the normaliser (1.5% over
    # the algorithm's 272.6 MB a slot); nothing by position
    assert pools == {"ssm": (engine["max_slots"] * 8 * 8 * 66 * 128 * 128 * 4,
                             "float32")}
    assert 1.0 < pools["ssm"][0] / engine["max_slots"] \
        / power_flops.slot_bytes(c) < 1.016
    assert llama_serve.cache_pools(cfg, 1, 1) \
        == llama_serve.cache_pools(cfg, 1, 32768)
    shapes = jax.eval_shape(lambda k: llama.init_params(k, cfg, cfg.dtype),
                            jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == c["parameters"]
    # the state check reads the live engine's own cache: no geometry of a
    # second one in the file
    assert "state_check" not in c
    assert all(b % c["program_fields"]["power_chunk"] == 0
               for b in engine["prefill_buckets"])


# ------------------------------------------- the real widths, for the chip
def test_the_widest_programs_fit_one_chip(one_chip):
    """The decode program and the prefill of a 16,384 bucket compile for
    one 16 GB chip at the cell's slots: the decode step through the
    ``power_state_update`` kernel under its scope (the stack aliased: no
    copy of the 4.4 GB of states), neither program with an attention kernel
    or a K/V operand."""
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
    assert set(cache) == {"ssm"}

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    decode = llama_serve.build_decode_k(cfg).lower(
        params, cache, arr(jnp.int32, slots), arr(jnp.int32, slots),
        arr(jnp.int32, slots), arr(jnp.int32, slots), arr(jnp.bool_, slots),
        arr(jnp.bool_, slots), k=16, s_active=max_len).compile()
    held = 2 * c["parameters"] + slots * 8 * 8 * 66 * 128 * 128 * 4
    memory = decode.memory_analysis()
    assert memory.argument_size_in_bytes < held + (1 << 20)
    assert memory.temp_size_in_bytes < 1 << 30
    kernels = kernels_by_name_and_scope(decode.as_text())
    assert kernels["power_state_update", "power_state_update"] >= 1
    assert not [k for k in kernels if "attention" in k[0]]
    bucket = max(engine["prefill_buckets"])
    prefill = llama_serve.build_prefill(cfg).lower(
        params, cache, arr(jnp.int32, 1, bucket), arr(jnp.int32, 1),
        arr(jnp.int32, 1)).compile()
    # (the compiler raises RESOURCE_EXHAUSTED if the program does not fit;
    # the donated cache is argument and result at once)
    assert prefill.memory_analysis().temp_size_in_bytes < 3 << 30
    assert not [k for k in kernels_by_name_and_scope(prefill.as_text())
                if "attention" in k[0]]


# ------------------------------------------------- a rehearsal on the CPU
TINY = {
    "name": "tiny-power", "source": "none (test, power retention)",
    "reference": "brumby_decoder", "roofline": "power_flops",
    "model_type": "brumby", "vocab_size": 256, "hidden_size": 64,
    "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "max_position_embeddings": 256, "rope_theta": 1000000,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "attention_bias": False, "hidden_act": "silu", "rope_scaling": None,
    "power_degree": 2, "power_eps": 1e-6, "gate_shift": [1.0, 4.0],
    "reduced": [], "assumed": ["test"],
    # float32 throughout: a request's gap against the reference is then the
    # order of float32 sums, whichever requests a short window completes
    "dtype": {"serve": "float32", "power_state": "float32"},
    "program_fields": {
        "layer_pattern": ["power"], "power_chunk": 8,
        "power_gate_shift": [1.0, 4.0], "power_eps": 1e-6,
        "ssm_state_dtype": "float32", "dtype": "float32"},
}
TINY_CELL = "tiny-power.tiny-closed"


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark with a toy of the same shape dropped in and
    its cell appended wherever the real one is."""
    root = tmp_path_factory.mktemp("bench_power")
    bench = str(root / "benchmarks")
    shutil.copytree(spec.BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "out", "__pycache__", "tests"))

    def drop(rel, payload):
        path = os.path.join(bench, rel)
        assert not os.path.exists(path), f"{rel} would be an edit"
        with open(path, "w") as f:
            json.dump(payload, f)

    drop("configs/tiny-power.json", TINY)
    drop("traffic/tiny-closed.json", test_rehearsal.TRAFFIC["tiny-closed"])
    drop(f"workloads/{TINY_CELL}.json",
         dict(test_rehearsal.SERVE, kind="serve_llm_even", name=TINY_CELL,
              config="tiny-power", traffic="tiny-closed", why="test"))
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    benchmark["configs"].append(
        {"name": "tiny-power", "source": TINY["source"], "reduced": [],
         "file": "benchmarks/configs/tiny-power.json", "why": "test"})
    benchmark["workloads"].append(
        {"name": TINY_CELL, "config": "tiny-power",
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


def entries_of_the_cell(root=spec.ROOT):
    """What THIS cell reports, on the tree at ``root`` (a rehearsal's has a
    later PR's entries appended: nothing here counts the table or says what
    another family's names are).  ``the_cells_entries`` below is its name
    for callers; it is not DEFINED under that name because
    ``test_rehearsal.py`` holds the files that define one to its own list
    (``CELL_TESTS``), which a ``model_config`` PR may not edit: this file's
    own rehearsal tree runs it instead (PERF.md section 7)."""
    from benchmarks.tests.test_yardstick import cell_at, names_lead_to_files

    names_lead_to_files(root)
    cell = cell_at(root, CELL)
    assert cell.chips == 1 and cell.workload["kind"] == "serve_llm_even"
    assert cell.workload["engine"] == ENGINE
    assert len(cell.workload["why"]) <= 200
    arrivals = cell.traffic["arrivals"]
    assert (arrivals["process"], arrivals["callers"],
            arrivals["lead_in_s"]) == ("closed", 2 * ENGINE["max_slots"],
                                       20.0)
    assert cell.traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 10240, "sigma": 0.25, "min": 8192,
        "max": 16384, "stratified": 16}
    assert cell.traffic["output_tokens"] == {
        "dist": "lognormal", "median": 768, "sigma": 0.35, "min": 256,
        "max": 1024, "stratified": 16}
    assert cell.traffic["prompt_tokens"]["max"] \
        + cell.traffic["output_tokens"]["max"] <= ENGINE["max_len"]
    entry = next(c for c in cell.benchmark["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == [r["key"] for r in cell.config["reduced"]]
    assert entry["source"] == cell.config["source"]
    assert len(entry["why"]) <= 200 and len(cell.entry["why"]) <= 200
    names = {m["name"] for m in cell.metric_entries("per_layer")}
    assert {n for n in names if n.startswith("power_")} == {
        "power_state_update_time_share", "power_state_update_roofline",
        "power_prefill_chunk_time_share", "power_prefill_chunk_roofline",
        "power_state_bytes_share"}
    assert {"batch.slot_wait_p50_ms", "batch.prefill_unscoped_time_share",
            "batch.decode_step_roofline", "batch.decode_step_device_ms",
            "batch.decode_projection_time_share", "window_compiles",
            "setup_before_engine_s", "setup_warmup_s"} <= names
    # nothing to read: no K/V rows, no experts, no other family's layers
    assert not {n for n in names if n.startswith(
        ("swa_", "ssm_", "kda_", "moe_", "dsa_", "mla_", "lfm2_", "sambay_",
         "chat."))} and "batch.decode_kv_read_share" not in names
    assert cell.config["roofline"] == "power_flops"
    assert {m["name"] for m in cell.metric_entries("end_to_end")} \
        == {"serve_output_tokens_per_s", "setup_s"}


the_cells_entries = entries_of_the_cell


def test_the_cells_names_lead_to_files_and_join_the_serve_metrics():
    the_cells_entries()


def test_the_entry_assertions_hold_where_a_later_cell_was_appended(tree):
    """The same assertions on the rehearsal's tree, which has a later
    configuration, cell and ``workloads`` memberships appended."""
    the_cells_entries(os.path.dirname(tree[1]))


def test_a_toy_power_model_runs_end_to_end_on_the_cpu(tree, cpu_peaks):
    """One traced run of the toy cell through ``run.measure``: ``correct``
    against ``brumby_decoder`` (logits AND the first layer's states) with
    the harness's own limit, nothing failed, the metrics the cell joins and
    the program's own count of the state it moved are there; what only a
    device trace knows is left out on a CPU, not invented."""
    bench, benchmark_json = tree
    result, obs = bench_run.measure(
        ["--workload", TINY_CELL, "--seed", "2147486530", "--seconds", "2",
         "--trace", "1"],
        allow_platforms=("cpu",), bench_dir=bench,
        benchmark_json=benchmark_json, t_process=time.perf_counter())
    assert result["correct"] is True, obs["checks"]
    assert result["failed"] == 0 < result["attempted"]
    assert obs["cell"].reference.__name__.endswith("brumby_decoder")
    assert len(obs["logit_gaps"]) == 4 and obs["logit_gap_max"] < 1e-2
    metrics = result["metrics"]
    assert {"batch.slot_wait_p50_ms", "batch.token_burst_gap_p50_ms",
            "batch.decode_slot_utilization", "batch.prefill_padding_share",
            "window_compiles", "power_state_bytes_share"} <= set(metrics)
    assert "batch.decode_kv_read_share" not in metrics
    # a device trace's: left out on the CPU
    assert not {"power_state_update_roofline", "power_prefill_chunk_roofline",
                "power_state_update_time_share"} & set(metrics)
    chunk = next(c for c in program_spans.collect(obs).chunks
                 if c.get("power_slots_advanced"))
    assert chunk["power_state_bytes"] == 2 * chunk["power_slots_advanced"] \
        * 3 * 2 * 10 * 16 * 16 * 4
    assert power_names.slots_a_step(obs) <= 4
    group = program_spans.collect(obs).groups[0]
    # (a toy head of 16 keeps XLA's chunked form: the kernel's count is 0)
    assert group["power_chunk_positions"] == 0


def test_the_published_width_check_rehearsed_at_toy_size(tree, capsys):
    """``tools/power_check.py`` end to end on the toy: the intact engine
    within rounding of the reference in float32 arithmetic by its logits
    and by its states; without the gate far off by its logits; with the
    update rounded to bfloat16 a step, by its STATES alone."""
    from benchmarks.tools import power_check

    bench, _ = tree
    assert power_check.main([
        "--config", "tiny-power", "--seed", "2147486531", "--bench-dir",
        bench, "--variants", "intact,no_gate,bf16_update",
        "--prompt", "21", "--new-tokens", "24", "--bucket", "32",
        "--max-len", "64", "--slots", "2"]) == 0
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    done = lines[-1]
    assert done["intact"]["passes"] is True
    assert done["intact"]["logit_gap_max"] < 1e-3
    assert done["no_gate"]["passes"] is False
    # (a toy's fastest decay forgets a rounding in a few steps: the chip's
    # limit is not reached, the distance is a hundred times the intact one)
    read = [line for line in lines if line["event"] == "reference_gaps"]
    # the live engine's own two slots, a chunk of 16 a launch, all 3 layers
    assert all(line["state_of"]["slots"] == 2 and line["state_of"]["k"] == 16
               and len(line["state_deviation"]["head"]) == 3
               for line in read)
    intact, _no_gate, rounded = (
        max(max(layer) for layer in line["state_deviation"]["head"])
        for line in read)
    assert intact < 1e-5 and rounded > 100 * intact


# ----------------------------------------------------------- the readers
def test_the_readers_arithmetic_on_a_given_split(monkeypatch):
    """A decode and a prefill program's seconds by scope as
    ``scope_names.split`` would hand them, 16 slots advanced a step: the
    shares are the scopes' own seconds over their programs', the update's
    roofline its 8.7 GB at the HBM peak over its 15 ms a step, the chunked
    form's its 102.8 MFLOP a position and layer at the bf16 peak over the
    traced groups' seconds; another family's configuration reads nothing."""
    c = _json("configs", CONFIG)
    splits = {
        "decode": scope_names.Split(
            {("power_state_update", "forward"): 0.6,
             ("power_gate", "forward"): 0.05, ("ffn", "forward"): 0.30},
            1.0, []),
        "prefill": scope_names.Split(
            {("power_chunk", "forward"): 1.5, ("power_gate", "forward"): 0.25,
             ("ffn", "forward"): 0.75}, 3.0, [])}
    monkeypatch.setattr(scope_names, "split",
                        lambda obs, which: splits.get(which))
    monkeypatch.setattr(power_names.readers, "decode_step_device_ms",
                        lambda obs: 25.0)
    monkeypatch.setattr(power_names, "slots_a_step", lambda obs: 16.0)
    # one group wholly inside the traced span, one half inside it
    groups = [{"power_chunk_positions": 8 * 9216, "t_launch": 11.0,
               "dur_ms": 1000.0},
              {"power_chunk_positions": 8 * 16384, "t_launch": 13.5,
               "dur_ms": 1000.0},
              {"power_chunk_positions": 8 * 16384, "t_launch": 2.0,
               "dur_ms": 1000.0}]
    monkeypatch.setattr(
        power_names.program_spans, "collect",
        lambda obs: types.SimpleNamespace(groups=groups, chunks=[]))
    obs = {"cell": types.SimpleNamespace(config=c, bench_dir=spec.BENCH_DIR,
                                         name=CELL),
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
           "trace_span": [10.0, 14.0]}
    assert power_names.prefill_chunk_time_share(obs) == pytest.approx(50.0)
    assert power_names.state_update_time_share(obs) == pytest.approx(60.0)
    least = 16 * 2 * 272_646_144 / 819e9
    assert power_names.state_update_roofline(obs) \
        == pytest.approx(100 * least / (0.6 * 25e-3))
    positions = 8 * 9216 + 0.5 * 8 * 16384
    assert power_names.prefill_chunk_roofline(obs) == pytest.approx(
        100 * power_flops.chunk_flops(
            c, positions, c["program_fields"]["power_chunk"]) / 197e12 / 1.5)
    assert power_names.state_bytes_share(obs) == pytest.approx(56.05, 1e-3)
    # a program without the scope or the attribute reads nothing
    monkeypatch.setattr(scope_names, "split", lambda obs, which: None)
    monkeypatch.setattr(power_names, "slots_a_step", lambda obs: None)
    assert power_names.state_update_roofline(obs) is None
    assert power_names.prefill_chunk_roofline(obs) is None
    assert power_names.state_bytes_share(obs) is None
    assert power_flops.decode_step_least_s(obs) is None
