"""Each kind of run end to end on the CPU at a tiny configuration, through
the same ``run.measure`` the command uses (the platform check lifted
here and nowhere else) — and, by the way the tiny cells get there, the
proof that a configuration, a cell, a traffic mix and a per-layer metric
are added as NEW FILES and entries, with no edit to a file that exists:
the benchmark's tree is copied, files are dropped in, ``BENCHMARK.json``
gets entries appended, and ``run.py`` finds them by name.
"""

import json
import os
import shutil
import time
import types

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import spec

TINY = {
    "name": "tiny", "source": "none (test)", "reference": "dense_decoder",
    "roofline": "flops",
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 128, "max_position_embeddings": 256,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False, "hidden_act": "silu", "bias": False,
    "reduced": [], "assumed": ["test"],
    "program_fields": {"attention_impl": "flash", "remat_policy": "attn"},
}
# What the next ``model_config`` PR brings, at toy size: a configuration
# that is cut (half its source's depth, written out), its own reference
# module beside it, and fields of the program's config that the published
# keys do not spell, passed through ``program_fields``.
TINY_CUT = dict(
    TINY, name="tiny-cut", source="none (test, cut)",
    reference="tiny_cut_reference", num_hidden_layers=1,
    reduced=[{"key": "num_hidden_layers", "published": 2, "here": 1,
              "why": "test: half the depth, as a model that does not fit"}],
    program_fields={"attention_impl": "dot", "scan_unroll": 2})
TINY_CUT_REFERENCE = '''"""A reference brought by its configuration: here the dense decoder's
mathematics again (a real one differs: experts, norms on q and k), with
a count of the times the benchmark's ``correct`` came through it."""

from benchmarks.references import dense_decoder

CALLS = {"teacher_forced_gap": 0}
logits = dense_decoder.logits


def teacher_forced_gap(params, prompt, emitted, config, pad_to=0):
    CALLS["teacher_forced_gap"] += 1
    return dense_decoder.teacher_forced_gap(params, prompt, emitted,
                                            config, pad_to=pad_to)
'''
LENGTHS = {"prompt_tokens": {"dist": "lognormal", "median": 16,
                             "sigma": 0.5, "min": 4, "max": 32,
                             "stratified": 4},
           "output_tokens": {"dist": "lognormal", "median": 12,
                             "sigma": 0.5, "min": 2, "max": 40,
                             "stratified": 4}}
TRAFFIC = {
    "tiny-train": {"generator": "token_batches", "batch": 4, "seq_len": 128,
                   "distinct_batches": 4},
    "tiny-train4": {"generator": "token_batches", "batch": 8,
                    "seq_len": 128, "distinct_batches": 4},
    "tiny-closed": {"generator": "requests", **LENGTHS,
                    "arrivals": {"process": "closed", "callers": 8,
                                 "lead_in_s": 0.5, "drain_s": 20.0}},
    "tiny-open": {"generator": "requests", **LENGTHS,
                  "arrivals": {"process": "poisson", "rate_per_s": 15.0,
                               "lead_in_s": 0.5, "drain_s": 20.0}},
}
ENGINE = {"max_slots": 4, "max_len": 128, "prefill_buckets": [32, 64],
          "paged": False, "prefill_groups": [2, 4]}
TRAINER = {"fused_optimizer": True, "prefetch_batches": 2,
           "sync_every_steps": 3, "warmup_steps": 1}
SERVE = {"kind": "serve_llm", "chips": 1, "engine": ENGINE,
         "deployment": {"max_ongoing_requests": 64}}
CELLS = {   # name -> (cell file, the real cell whose metrics it reports)
    "tiny.tiny-train": (
        {"kind": "train_lm", "chips": 1,
         "trainer": {"mesh": None, **TRAINER}},
        "smollm2-360m.train-1chip"),
    "tiny.tiny-train4": (
        {"kind": "train_lm", "chips": 4,
         # the worker builds its mesh over ALL of jax.devices(), and the
         # test suite's CPU has eight: data=2 takes up the other four
         "trainer": {"mesh": {"data": 2, "fsdp": 4}, **TRAINER}},
        "internlm2-1.8b.train-fsdp4"),
    "tiny.tiny-closed": (SERVE, "internlm2-1.8b.serve-batch-decode"),
    "tiny.tiny-open": (SERVE, "internlm2-1.8b.serve-chat-busy"),
    "tiny-cut.tiny-closed": (SERVE, "internlm2-1.8b.serve-batch-decode"),
}
# What the ``model_config`` PR after PR 53 brings for its step and its
# layers, at toy size: a configuration of a family the benchmark has not
# (experts of which the chip HOLDS a range, behind a leading dense layer; a
# latent row; a learned selection), the floor of ITS decode step as a module
# its file names, and its cell's name in the lists of the entries that ask
# what its program does -- no reader, and no entry of its own for any of
# them.  The program cannot run such a model yet (ROADMAP.md Queue 2 item
# 2), so the readers are rehearsed on what a traced run of it would hand
# them: ``test_a_new_family_joins_by_files_and_list_entries``.
TINY_FAMILY = dict(
    TINY, name="tiny-latent-select", source="none (test, a new family)",
    roofline="tiny_latent_select_flops", num_hidden_layers=3,
    first_k_dense_replace=1, moe_intermediate_size=32, n_routed_experts=16,
    num_experts_per_tok=2, kv_lora_rank=16, qk_rope_head_dim=8,
    program_fields={
        "first_dense_layers": 1, "moe_experts": 16, "moe_held": [4, 8],
        "moe_top_k": 2, "moe_intermediate_size": 32, "kv_lora_rank": 16,
        "index_heads": 2, "index_head_dim": 8, "index_topk": 16})
TINY_FAMILY_CELL = "tiny-latent-select.tiny-closed"
TINY_FAMILY_FLOOR = '''"""What a decode step of the toy family must read: the touched experts'
matrices at an expert's own width, and of every live row the latent rows
its selection keeps, a layer."""

from benchmarks.lib import moe_flops, moe_names, swa_names


def step_bytes(c, touched, lengths):
    kept = sum(min(n, c["program_fields"]["index_topk"]) for n in lengths)
    row = 2 * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
    return 2 * touched * moe_flops.expert_params(c) \\
        + c["num_hidden_layers"] * kept * row


def decode_step_least_s(obs):
    lengths = swa_names._traced_lengths(obs)
    medians = moe_names.chunk_medians(obs)
    if lengths is None or medians is None:
        return None
    return step_bytes(obs["cell"].config, medians[1], lengths) \\
        / obs["peaks"]["hbm_bytes_per_s"]
'''
TINY_FAMILY_JOINS = (
    "batch.decode_step_roofline", "moe_expert_ffn_time_share",
    "moe_routing_time_share", "moe_expert_matmul_roofline",
    "moe_expert_load_imbalance", "dsa_indexer_time_share",
    "dsa_select_time_share", "dsa_prefill_selection_time_share",
    "dsa_selected_share", "mla_absorb_time_share",
    "moe_shared_expert_time_share", "mla_held_rows_share",
    "serve_output_tokens_per_s")
NEW_METRIC = '''"""Requests the generator measured (a count, from its log)."""


def read(obs):
    return float(len(obs["measured"])) if "measured" in obs else None
'''
# ... and for a layer of its own: a reader file built on
# ``scope_names.scopes_time_share`` with its entry APPENDED to ``per_layer``
# -- the step that the table's own tests once refused (three cell tests and
# ``test_scope_names`` counted its entries: PERF.md section 6, PR 64).
NEW_SCOPE_METRIC = '''"""Own device time of the ops under scope ``mla_absorb`` / device time of
the decode programs: a new family's own layer, by the program's scope."""

from benchmarks.lib import scope_names

read = scope_names.scopes_time_share("mla_absorb")
'''
# the cell tests whose entry assertions (``the_cells_entries``) the
# rehearsal runs on its tree
CELL_TESTS = ("test_smallthinker_cell", "test_deepseek_v2_cell",
              "test_lfm2_cell", "test_keye_cell", "test_phi4flash_cell",
              "test_solar_open2_cell", "test_trinity_cell",
              "test_nemotron_cell")


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark with the throw-away files dropped in."""
    root = tmp_path_factory.mktemp("bench")
    bench = str(root / "benchmarks")
    shutil.copytree(spec.BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "out", "__pycache__", "tests"))
    before = {os.path.relpath(os.path.join(d, f), bench): os.path.getmtime(
        os.path.join(d, f)) for d, _, fs in os.walk(bench) for f in fs}

    def drop(rel, text):
        path = os.path.join(bench, rel)
        assert not os.path.exists(path), f"{rel} would be an edit"
        with open(path, "w") as f:
            f.write(text)

    drop("configs/tiny.json", json.dumps(TINY))
    drop("configs/tiny-cut.json", json.dumps(TINY_CUT))
    drop("references/tiny_cut_reference.py", TINY_CUT_REFERENCE)
    for name, traffic in TRAFFIC.items():
        drop(f"traffic/{name}.json", json.dumps(traffic))
    drop("metrics/tiny_requests_measured.py", NEW_METRIC)
    drop("metrics/tiny_absorb_time_share.py", NEW_SCOPE_METRIC)
    drop("configs/tiny-latent-select.json", json.dumps(TINY_FAMILY))
    drop("lib/tiny_latent_select_flops.py", TINY_FAMILY_FLOOR)
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    for config in (TINY, TINY_CUT, TINY_FAMILY):
        benchmark["configs"].append(
            {"name": config["name"], "source": config["source"],
             "reduced": [cut["key"] for cut in config["reduced"]],
             "file": f"benchmarks/configs/{config['name']}.json",
             "why": "test"})
    for name, (cell, like) in CELLS.items():
        config, traffic = name.split(".", 1)
        cell = dict(cell, name=name, config=config, traffic=traffic,
                    why="test")
        drop(f"workloads/{name}.json", json.dumps(cell))
        benchmark["workloads"].append(
            {"name": name, "config": config, "traffic": traffic,
             "chips": cell["chips"], "why": "test"})
        for group in ("end_to_end", "per_layer"):
            for metric in benchmark[group]:
                if like in metric.get("workloads", []):
                    metric["workloads"].append(name)
    drop(f"workloads/{TINY_FAMILY_CELL}.json", json.dumps(dict(
        SERVE, name=TINY_FAMILY_CELL, config="tiny-latent-select",
        traffic="tiny-closed", why="test")))
    benchmark["workloads"].append(
        {"name": TINY_FAMILY_CELL, "config": "tiny-latent-select",
         "traffic": "tiny-closed", "chips": 1, "why": "test"})
    for group in ("end_to_end", "per_layer"):
        for metric in benchmark[group]:
            if metric["name"] in TINY_FAMILY_JOINS:
                metric["workloads"].append(TINY_FAMILY_CELL)
    benchmark["per_layer"].append(
        {"name": "tiny_requests_measured", "unit": "count",
         "better": "higher", "source": "program_counter",
         "layer": "request path", "moves": "serve_tpot_p50_ms",
         "workloads": ["tiny.tiny-open"]})
    benchmark["per_layer"].append(
        {"name": "tiny_absorb_time_share", "unit": "%", "better": "lower",
         "source": "device_trace", "layer": "serve device programs",
         "moves": "serve_output_tokens_per_s",
         "workloads": [TINY_FAMILY_CELL]})
    path = str(root / "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(benchmark, f)
    for rel, mtime in before.items():   # nothing that was there changed
        assert os.path.getmtime(os.path.join(bench, rel)) == mtime, rel
    return bench, path


@pytest.fixture
def cpu_peaks(monkeypatch):
    """Percent-of-peak metrics need a peaks row; on the CPU the test
    supplies one (no file of the benchmark lists a CPU)."""
    from benchmarks.lib import runtime

    real = runtime.load_peaks
    monkeypatch.setattr(
        runtime, "load_peaks",
        lambda kind, bench_dir=None: real("TPU v5 lite"))


def _measure(tree, cell, trace, seconds=2.0):
    bench, benchmark_json = tree
    result, obs = bench_run.measure(
        ["--workload", cell, "--seed", "3", "--seconds", str(seconds),
         "--trace", str(trace)],
        allow_platforms=("cpu",), bench_dir=bench,
        benchmark_json=benchmark_json, t_process=time.perf_counter())
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True, obs["checks"]
    assert result["failed"] == 0 < result["attempted"]
    assert result["device"]["platform"] == "cpu"
    json.dumps(result)      # the last line is JSON
    # what ``correct`` compared comes last, each number beside its limit
    assert list(result)[-1] == "compared" and result["compared"]
    assert all(value <= limit for value, limit in
               result["compared"].values())
    for metric in result["metrics"].values():
        assert isinstance(metric["value"], float) and metric["unit"]
    return result, obs


@pytest.mark.parametrize("cell,headlines", [
    ("tiny.tiny-train", ["train_tokens_per_s_per_chip"]),
    ("tiny.tiny-train4", ["train_tokens_per_s_per_chip"]),
    ("tiny.tiny-closed", ["serve_output_tokens_per_s"]),
    ("tiny.tiny-open", ["serve_tpot_p50_ms"]),
])
def test_kind_end_to_end_on_the_cpu(tree, cpu_peaks, cell, headlines):
    result, obs = _measure(tree, cell, trace=0)
    assert set(result["metrics"]) == {*headlines, "setup_s"}
    assert all(result["metrics"][h]["value"] > 0 for h in headlines)
    assert result["device"]["count"] == (4 if "train4" in cell else 1)
    if "train" in cell:
        # the step's first loss and gradient norm against the float32
        # reference's, as on the chip
        assert obs["loss_gap"] < 2e-3
        assert obs["grad_norm_gap"] < 2e-2
        assert max(obs["grad_leaf_gaps"].values()) < 0.1
    else:
        assert len(obs["logit_gaps"]) == 4


def test_a_cut_configuration_with_its_own_reference_is_files_alone(
        tree, cpu_peaks):
    """A serve cell of ``tiny-cut``: one layer of its source's two, a
    reference module dropped in beside it, program fields passed through
    — found by name, run through ``run.measure``, checked by ITS
    reference; and the spec check of the real ``BENCHMARK.json`` passes on
    the tree that holds it."""
    from benchmarks.lib import program
    from benchmarks.tests.test_yardstick import names_lead_to_files

    bench, benchmark_json = tree
    names_lead_to_files(os.path.dirname(benchmark_json))
    result, obs = _measure(tree, "tiny-cut.tiny-closed", trace=0)
    assert set(result["metrics"]) == {"serve_output_tokens_per_s",
                                      "setup_s"}
    cell = obs["cell"]
    assert cell.reference.__file__ == os.path.join(
        bench, "references", "tiny_cut_reference.py")
    assert cell.reference.CALLS["teacher_forced_gap"] == len(
        obs["logit_gaps"]) == 4
    fields = program.llama_fields(cell.config)
    assert (fields["n_layers"], fields["attention_impl"],
            fields["scan_unroll"]) == (1, "dot", 2)


def test_traced_run_reports_per_layer_metrics_and_a_new_one(tree,
                                                            cpu_peaks):
    """``--trace 1`` on the open-loop kind, on a cell made of a NEW
    config, traffic mix and metric."""
    result, obs = _measure(tree, "tiny.tiny-open", trace=1, seconds=5.0)
    metrics = result["metrics"]
    assert metrics["tiny_requests_measured"]["value"] == \
        result["attempted"]
    assert {"chat.ttft_p50_ms", "ttft_p90_ms", "loadgen_lag_p99_ms",
            "setup_cache_fetch_s", "window_compiles"} <= set(metrics)
    assert metrics["window_compiles"]["value"] == 0.0
    # no end-to-end metric in a traced run, and nothing a CPU cannot know
    assert "serve_tpot_p50_ms" not in metrics
    assert not any("roofline" in m or "device" in m for m in metrics)
    assert "breakdown" in result
    assert {"busy_s", "window_s"} <= set(result["device"])


# One layer of one decode step and of a prefill of the toy family, in
# instruction texts of the kind a v5e trace holds (``lib/moe_names.py``),
# microseconds, and the scope the program's map gives each.
_FAMILY_STEP = [
    ("%fusion.1 = bf16[4,64]{1,0} fusion(bf16[4,16] %latent)", 100.0,
     "mla_absorb"),
    ("%fusion.2 = f32[4,2,128]{2,1,0} fusion(bf16[4,2,8] %qi)", 150.0,
     "indexer"),
    ("%fusion.3 = u32[4]{0} fusion(u32[4,128] %scores)", 50.0,
     "index_select"),
    ("%mla_decode_attention.3 = bf16[4,4,16]{2,1,0} custom-call(bf16[4,4,24]"
     " %q), custom_call_target=\"tpu_custom_call\"", 200.0, "decode_attention"),
    ("%fusion.4 = f32[4,16]{1,0} fusion(bf16[4,64] %h)", 40.0, "router"),
    ("%fusion.5 = bf16[8,64]{1,0} fusion(bf16[4,64] %h, s32[8] %order)",
     60.0, "expert_dispatch"),
    ("%ragged-dot-none.2 = f32[8,32]{1,0} custom-call(bf16[8,64] %rows, "
     "bf16[4,64,32] %w), custom_call_target=\"tpu_custom_call\"", 300.0,
     "expert_ffn"),
    ("%fusion.6 = bf16[4,64]{1,0} fusion(bf16[4,64] %h)", 100.0,
     "shared_expert"),
]
_FAMILY_PREFILL = [
    ("%fusion.7 = f32[1,64,2,64]{3,2,1,0} fusion(bf16[1,64,2,8] %qi)",
     300.0, "indexer"),
    ("%fusion.8 = s32[1,64]{1,0} fusion(u32[1,64,64] %scores)", 200.0,
     "index_select"),
    ("%fusion.9 = bf16[1,64,64]{2,1,0} fusion(bf16[1,64,64] %x)", 500.0,
     "ffn"),
]


def _traced_run_of_the_family(cell):
    """What ``run.measure`` would hand the readers after a traced run of
    the cell: five whole decode launches of 16 steps x 2 expert layers
    and a prefill on a chip's trace, the program's scope map, its
    ``serve.chunk`` spans and the generator's log."""
    from benchmarks.lib import program_spans, trace_reduce
    from ray_tpu.observability.device import instruction_key

    ops, modules, t = [], [], 0.0
    for _launch in range(5):
        start = t
        for _ in range(16 * 2):
            for name, us, _scope in _FAMILY_STEP:
                ops.append((t, t + us * 1e-6, name))
                t += us * 1e-6
        modules.append((start, t, "jit_decode_k(7)"))
        t += 1e-4
    start = t
    for name, us, _scope in _FAMILY_PREFILL:
        ops.append((t, t + us * 1e-6, name))
        t += us * 1e-6
    modules.append((start, t, "jit_prefill(9)"))
    scopes = {module: {instruction_key(name): (scope, "forward")
                       for name, _us, scope in rows}
              for module, rows in (("jit_decode_k", _FAMILY_STEP),
                                   ("jit_prefill", _FAMILY_PREFILL))}
    # 4 rows decoding, 40 positions each at the span's middle: 16 kept
    records = [types.SimpleNamespace(
        ok=True, got_tokens=21, sent=0.0, ttft_ms=0.0, done=2.0,
        prompt_tokens=29) for _ in range(4)]
    chunk = {"k": 16, "active": 4, "expert_rows": 16 * 2 * 2,
             "expert_rows_elsewhere": 16 * 2 * 6,
             "experts_touched": 16 * 3, "expert_rows_max": 16 * 1,
             "kv_positions_present": 16 * 4 * 40,
             "kv_positions_attended": 16 * 4 * 16}
    return {
        "trace": trace_reduce.Trace(
            [trace_reduce.DeviceTrace(0, ops, modules)], [], 0.0, t),
        "cell": cell, "decode_chunk": 16, "trace_span": [0.9, 1.1],
        "scope_map": scopes, "chips": 1, "window_compiles": 0,
        "program_window_compiles": 0,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        "log": types.SimpleNamespace(records=records),
        "program_spans": program_spans.ProgramSpans([], [chunk] * 3, [])}


def test_a_new_family_joins_by_files_and_list_entries(tree):
    """A configuration with experts behind a leading dense layer, a held
    range of them, a latent row and an ``index_topk`` among its program's
    fields, dropped into the tree as a configuration file, a cell file
    and ``lib/tiny_latent_select_flops.py``, its cell's name appended to
    twelve entries' lists (``tree`` asserts that no file that was there
    changed): the step roofline's one reader divides ITS floor, the
    experts' entries read its scopes and kernels at its own width over
    the experts it HOLDS, the selection's and the latent path's read its
    scopes and spans -- each a number, from the readers the real cells
    use."""
    bench, benchmark_json = tree
    cell = spec.Cell(TINY_FAMILY_CELL, bench, benchmark_json)
    # (and the one entry every cell reports, which has no list)
    assert {e["name"] for e, _ in cell.readers("per_layer")} \
        == {*TINY_FAMILY_JOINS[:-1], "window_compiles",
            "tiny_absorb_time_share"}
    obs = _traced_run_of_the_family(cell)
    reads = {entry["name"]: read(obs)
             for entry, read in cell.readers("per_layer")}
    assert all(isinstance(v, float) for v in reads.values()), reads
    step_us = 2 * sum(us for _n, us, _s in _FAMILY_STEP)         # 2,000
    layer_us = step_us / 2
    # its own floor: 3 touched experts of 3 x 64 x 32, 4 rows x 16 kept
    # latent rows of 48 bytes a layer -- over a whole launch's step
    floor = (2 * 3 * 3 * 64 * 32 + 3 * 4 * 16 * 48) / 819e9
    assert reads["batch.decode_step_roofline"] == pytest.approx(
        100 * floor / (step_us * 1e-6))
    assert reads["moe_expert_ffn_time_share"] == pytest.approx(
        100 * 300 / layer_us)
    assert reads["moe_routing_time_share"] == pytest.approx(
        100 * (40 + 60) / layer_us)
    experts = 2 * (3 * 3 * 64 * 32 + 4 * (3 * 64 + 3 * 32)) / 819e9
    assert reads["moe_expert_matmul_roofline"] == pytest.approx(
        100 * experts / (2 * 300e-6))
    # the busiest of the 2 layers x 4 HELD experts against their mean
    assert reads["moe_expert_load_imbalance"] == pytest.approx(
        1 / (4 / (2 * 4)))
    assert reads["dsa_indexer_time_share"] == pytest.approx(
        100 * 150 / layer_us)
    assert reads["dsa_select_time_share"] == pytest.approx(
        100 * 50 / layer_us)
    assert reads["dsa_prefill_selection_time_share"] == pytest.approx(
        100 * 500 / 1000)
    assert reads["dsa_selected_share"] == pytest.approx(100 * 16 / 40)
    assert reads["mla_absorb_time_share"] == pytest.approx(
        100 * 100 / layer_us)
    assert reads["moe_shared_expert_time_share"] == pytest.approx(
        100 * 100 / layer_us)
    # its own entry's reader file, dropped in beside the others
    assert reads["tiny_absorb_time_share"] \
        == reads["mla_absorb_time_share"]
    assert reads["mla_held_rows_share"] == pytest.approx(100 * 2 / 8)


def test_the_appended_entries_pass_the_table_wide_checks(tree):
    """The tree holds three configurations, six cells and two ``per_layer``
    entries more than the repo's: the yardstick's check of every name, the
    table's one limit and that every reader file has an entry, and the
    scope readers' check, all pass on it."""
    from benchmarks.tests.test_scope_names import scope_entries_hold
    from benchmarks.tests.test_yardstick import (benchmark_at,
                                                 names_lead_to_files)

    root = os.path.dirname(tree[1])
    names_lead_to_files(root)
    assert "tiny_absorb_time_share" in scope_entries_hold(root)
    assert len(benchmark_at(root)["per_layer"]) \
        == len(benchmark_at(spec.ROOT)["per_layer"]) + 2


@pytest.mark.parametrize("module", CELL_TESTS)
def test_the_appended_entries_fail_no_cell_tests_entry_assertions(
        tree, module):
    """Each cell test's assertions about the entries ITS cell reports hold
    on the tree that a later PR's configuration, cells and entries were
    appended to: none counts the table or says what another family's names
    are (PRs 57 and 61 could enter none of their readers because three
    did)."""
    import importlib

    cell_test = importlib.import_module(f"benchmarks.tests.{module}")
    cell_test.the_cells_entries(os.path.dirname(tree[1]))


def test_every_cell_test_with_entry_assertions_is_rehearsed():
    here = os.path.dirname(os.path.abspath(__file__))
    with_assertions = set()
    for name in os.listdir(here):
        if name.startswith("test_") and name.endswith("_cell.py"):
            with open(os.path.join(here, name)) as f:
                text = f.read()
            if "def the_cells_entries(" in text:
                with_assertions.add(name[:-3])
            # no cell test counts the table's entries
            assert 'len(cell.benchmark["per_layer"])' not in text, name
            assert 'benchmark["per_layer"]) ==' not in text, name
    assert with_assertions == set(CELL_TESTS)


def test_command_refuses_without_a_chip(tree):
    from benchmarks.lib import runtime

    with pytest.raises(runtime.BenchmarkRefused):
        bench_run.measure(
            ["--workload", "smollm2-360m.train-1chip", "--seed", "1",
             "--seconds", "1", "--trace", "0"])
    assert bench_run.main(
        ["--workload", "smollm2-360m.train-1chip", "--seed", "1",
         "--seconds", "1", "--trace", "0"]) == 2


def test_the_command_says_what_it_compared_last(monkeypatch, capsys):
    """``main`` ends standard error with each number ``correct`` compared
    beside its limit, and standard output with the result line, whose last
    key holds the same."""
    result = {"correct": False, "attempted": 3, "failed": 0, "metrics": {},
              "device": {}, "compared": {"loss_gap": [0.5, 0.002],
                                         "window_compiles": [0, 0]}}
    monkeypatch.setattr(bench_run, "measure", lambda argv: (result, {}))
    assert bench_run.main([]) == 0
    out, err = capsys.readouterr()
    assert json.loads(out.splitlines()[-1]) == result
    assert err.splitlines()[-3:] == [
        "compared loss_gap 0.5 limit 0.002",
        "compared window_compiles 0 limit 0", "correct False"]


@pytest.mark.parametrize("tied", [False, True])
def test_reference_gradient_matches_the_program_at_tiny_size(tied):
    """The reference's layer-by-layer backward (what the on-chip check
    holds the step's ``grad_norm`` to) against ``value_and_grad`` of the
    program's loss, float32, same weights; and the whole gradient, leaf
    by leaf, through ``jax.grad`` of the reference's logits."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmarks.lib import program
    from benchmarks.references import dense_decoder
    from ray_tpu.models import llama

    tiny = dict(TINY, tie_word_embeddings=tied)
    cfg = program.llama_config(tiny, dtype=jnp.float32,
                               attention_impl="dot", remat=False)
    params = llama.init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (4, 32), 0, 256)

    def ref_loss(p):
        lg = dense_decoder.logits(p, tokens, tiny)
        logp = jax.nn.log_softmax(lg[:, :-1], -1)
        return -jnp.mean(jnp.take_along_axis(
            logp, tokens[:, 1:, None], -1))

    loss, ours = jax.value_and_grad(llama.loss_fn)(
        params, {"tokens": tokens}, cfg)
    theirs = jax.grad(ref_loss)(params)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6)
    ref, layered = dense_decoder.loss_and_grads(
        params, np.asarray(tokens), tiny, rows_at_a_time=2)
    assert ref == pytest.approx(float(loss), rel=1e-5)
    assert dense_decoder.global_norm(layered) == pytest.approx(
        float(optax.global_norm(ours)), rel=1e-5)
    gaps = dense_decoder.gradient_gaps(ours, layered)
    assert set(gaps) == set(params["layers"]) | (set(params) - {"layers"})
    assert max(gaps.values()) < 1e-4
    # a backward that loses dq moves the norm by a few percent (1% at
    # smollm2-360m's sizes) and wq's own gradient by all of it
    ours["layers"]["wq"] = 0.0 * ours["layers"]["wq"]
    assert optax.global_norm(ours) == pytest.approx(
        dense_decoder.global_norm(layered), rel=0.05)
    assert dense_decoder.gradient_gaps(ours, layered)["wq"] == 1.0
