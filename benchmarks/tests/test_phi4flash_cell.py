"""The decoder-hybrid-decoder's part of the benchmark:
``lib/sambay_flops.py`` against hand-worked numbers and the program's own
trees; the widest programs the cell's engine warms compiled at the REAL
widths for a v5e that is described, not attached; a CPU rehearsal of a toy
of the same shape through ``run.measure`` with ``phi4flash_decoder`` as its
reference and of ``tools/sambay_check.py``; and the ``sambay_*`` readers'
arithmetic on a split that is given.
"""

import json
import os
import shutil
import time
import types

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import (program, program_spans, sambay_flops,
                            sambay_names, scope_names, spec, swa_names)
from benchmarks.tests import test_rehearsal
# ``topo`` is described inside that file's fixture (never at import);
# ``compiled_kernels`` keeps these compiles out of the persistent cache.
from benchmarks.tests.test_aot_real_widths import (  # noqa: F401
    _json, _on, compiled_kernels, kernels_by_name_and_scope, one_chip, topo)

os.environ.setdefault("TPU_LOG_DIR", "disabled")
CONFIG = "phi-4-mini-flash-reasoning"
CELL = "phi-4-mini-flash-reasoning.serve-long-prompt"


# ------------------------------------------------------------------ flops
def test_operations_and_bytes_by_hand():
    c = _json("configs", CONFIG)
    assert c["reduced"] == [] and c["assumed"]
    assert sambay_flops.layer_counts(c) == {
        "mamba": 9, "window": 8, "attention": 1, "gmu": 7, "cross": 7}
    kinds = c["program_fields"]["layer_types"]
    assert kinds == ["mamba1", "window"] * 8 + ["mamba1", "attention"] \
        + ["gmu", "cross"] * 7
    mlp, norms = 3 * 2560 * 10240, 2 * 2 * 2560
    assert (mlp, norms) == (78_643_200, 10_240)
    mamba = 2560 * 10240 + (4 + 1) * 5120 + 5120 * 192 + 160 * 5120 + 5120 \
        + 16 * 5120 + 5120 + 5120 * 2560
    attention = 2560 * 5120 + 5120 + 2560 * 2560 + 2560 + 4 * 64 + 128
    cross = 2 * (2560 * 2560 + 2560) + 4 * 64 + 128
    gmu = 2 * 2560 * 5120
    assert (mamba, attention, cross, gmu) == (
        41_241_600, 19_668_864, 13_112_704, 26_214_400)
    per, small = (sambay_flops.mixer_matmul_params(c),
                  sambay_flops.mixer_small_params(c))
    assert {k: per[k] + small[k] for k in per} == {
        "mamba": mamba, "window": attention, "attention": attention,
        "cross": cross, "gmu": gmu}
    layers = 9 * mamba + 9 * attention + 7 * cross + 7 * gmu \
        + 32 * (mlp + norms)
    assert layers == 3_340_393_984
    assert sambay_flops.parameters(c) == layers + 200_064 * 2560 + 5120 \
        == 3_852_562_944 == c["parameters"]
    # a slot of 16,384 positions
    assert sambay_flops.slot_bytes(c, 16384) == {
        "kv_full": 83_886_080, "kv_window": 20_971_520, "ssm": 2_949_120,
        "conv": 276_480}
    # a decode step over 48 rows of 6,500 positions: the one pool, eight
    # times, is most of the bytes
    lengths = [6500] * 48
    assert sambay_flops.kv_row_bytes(c) == 5120
    shared = sambay_flops.shared_kv_bytes(c, lengths)
    assert shared == 8 * 5120 * 48 * 6500 == 12_779_520_000
    weights = 2 * sambay_flops.matmul_params(c)
    assert sambay_flops.decode_step_bytes(c, lengths) == weights + shared \
        + 8 * 5120 * 48 * 512 + 2 * 48 * (2_949_120 + 276_480)
    assert sambay_flops.decode_step_bytes(c, lengths) / 819e9 \
        > 5 * sambay_flops.decode_step_flops(c, lengths) / 197e12
    # a median prompt: the skip halves the prefill's matmuls
    assert 22e12 < sambay_flops.prefill_flops(c, 6144) < 24e12
    assert 46e12 < sambay_flops.prefill_flops(c, 6144, skip=False) < 48e12


def test_the_programs_trees_are_what_the_yardstick_counts():
    import jax

    from ray_tpu.models import llama, llama_serve

    c = _json("configs", CONFIG)
    engine = _json("workloads", CELL)["engine"]
    cfg = program.llama_config(c, max_seq_len=engine["max_len"])
    assert cfg.kv_layer == 17 and not cfg.plain_decoder
    pools = llama_serve.cache_pools(cfg, engine["max_slots"],
                                    engine["max_len"])
    per_slot = sambay_flops.slot_bytes(c, engine["max_len"])
    assert sum(per_slot.values()) == 108_083_200
    assert {k: v[0] for k, v in pools.items()} \
        == {k: engine["max_slots"] * v for k, v in per_slot.items()}
    assert (pools["ssm"][1], pools["kv_full"][1]) == ("float32", "bfloat16")
    shapes = jax.eval_shape(lambda k: llama.init_params(k, cfg, cfg.dtype),
                            jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == c["parameters"]


# ------------------------------------------- the real widths, for the chip
def test_the_widest_programs_fit_one_chip(one_chip):
    """The decode program at the whole 16,384 positions and the prefill of
    a 12,288 bucket compile for one 16 GB chip at the cell's slots: the
    decode step through THREE Mosaic calls (a window layer's ring, the K/V
    layer's pool, a cross layer's read of it: a call a scan body), with no
    scratch to speak of; the prefill through the banded flash forward."""
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

    decode = llama_serve.build_decode_k(cfg).lower(
        params, cache, arr(jnp.int32, slots), arr(jnp.int32, slots),
        arr(jnp.int32, slots), arr(jnp.int32, slots), arr(jnp.bool_, slots),
        arr(jnp.bool_, slots), k=16, s_active=max_len).compile()
    held = 2 * sambay_flops.parameters(_json("configs", CONFIG)) \
        + slots * 108_083_200
    memory = decode.memory_analysis()
    assert memory.argument_size_in_bytes < held + (1 << 20)
    assert memory.temp_size_in_bytes < 1 << 30
    # the one decode kernel under each scope the cell's readers sum: the
    # reads of the shared pool and the window layers' reads of their rings
    kernels = kernels_by_name_and_scope(decode.as_text())
    assert kernels["decode_attention", "cross_attention"] >= 1
    assert kernels["decode_attention", "decode_attention"] >= 1
    bucket = max(engine["prefill_buckets"])
    prefill = llama_serve.build_prefill(cfg).lower(
        params, cache, arr(jnp.int32, 1, bucket), arr(jnp.int32, 1),
        arr(jnp.int32, 1)).compile()
    # (the compiler raises RESOURCE_EXHAUSTED if the program does not fit;
    # the donated cache is argument and result at once)
    assert prefill.memory_analysis().temp_size_in_bytes < 3 << 30
    kernels = kernels_by_name_and_scope(prefill.as_text())
    assert kernels["flash_prefill_attention", "flash_attention.fwd"] >= 1


def test_the_dtype_block_is_what_the_programs_carry():
    """On the chip ``correct`` cannot hold the ``dtype`` block: stream,
    state and subtraction all in bfloat16 read 0.124 against a limit of
    0.25 (PERF.md section 2).  The programs' own types can: at the
    published widths the state pool is float32 and every other pool the
    serving type, and the stream every layer scan of the decode and
    prefill programs carries is float32."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama, llama_serve

    config = _json("configs", CONFIG)
    assert config["dtype"] == {"serve": "bfloat16", "residual": "float32",
                               "ssm_state": "float32"}
    cfg = program.llama_config(config, max_seq_len=1024)
    slots, bucket, H = 8, 512, config["hidden_size"]
    params = jax.eval_shape(
        lambda k: llama.init_params(k, cfg, cfg.dtype), jax.random.key(0))
    cache = jax.eval_shape(lambda: llama_serve.init_cache(cfg, slots, 1024))
    assert {name: leaf.dtype.name for name, leaf in cache.items()} == {
        "k": "bfloat16", "v": "bfloat16", "wk": "bfloat16",
        "wv": "bfloat16", "conv": "bfloat16", "ssm": "float32"}

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    def carried(jaxpr, shape, found):
        """dtypes of the values of ``shape`` that a scan carries."""
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan":
                first = eqn.params["num_consts"]
                found += [v.aval.dtype.name for v in eqn.invars[
                    first:first + eqn.params["num_carry"]]
                    if v.aval.shape == shape]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                carried(sub, shape, found)
        return found

    decode = jax.make_jaxpr(lambda *a: llama_serve.build_decode_k(cfg)(
        *a, k=16, s_active=1024))(
        params, cache, *[arr(jnp.int32, slots)] * 4,
        *[arr(jnp.bool_, slots)] * 2)
    prefill = jax.make_jaxpr(llama_serve.build_prefill(cfg))(
        params, cache, arr(jnp.int32, 1, bucket), arr(jnp.int32, 1),
        arr(jnp.int32, 1))
    # the self-decoder, the K/V layer, the cross-decoder: a scan each (in
    # a prefill the last two at one position a row)
    for jaxpr, shape, scans in ((decode, (slots, 1, H), 3),
                                (prefill, (1, bucket, H), 1),
                                (prefill, (1, 1, H), 2)):
        streams = carried(jaxpr.jaxpr, shape, [])
        assert len(streams) >= scans and set(streams) == {"float32"}


# ------------------------------------------------- a rehearsal on the CPU
TINY = {
    "name": "tiny-sambay", "source": "none (test, decoder-hybrid-decoder)",
    "reference": "phi4flash_decoder", "roofline": "sambay_flops",
    "vocab_size": 256,
    "hidden_size": 64, "num_hidden_layers": 8, "num_attention_heads": 8,
    "num_key_value_heads": 4, "head_dim": 8, "intermediate_size": 128,
    "mb_per_layer": 2, "sliding_window": 8, "layer_norm_eps": 1e-5,
    "rms_norm_eps": 1e-5, "mamba_d_state": 16, "mamba_d_conv": 4,
    "mamba_expand": 2, "mamba_dt_rank": 4, "max_position_embeddings": 256,
    "rope_theta": 10000, "tie_word_embeddings": True, "hidden_act": "silu",
    "reduced": [], "assumed": ["test"],
    "dtype": {"serve": "float32", "ssm_state": "float32"},
    # float32 throughout: a request's gap against the reference is then the
    # order of float32 sums, whichever requests a short window completes
    "program_fields": {
        "layer_types": ["mamba1", "window", "mamba1", "window", "mamba1",
                        "attention", "gmu", "cross"],
        "window_size": 8, "rope": False, "diff_attention": True,
        "layer_norm": True, "attn_bias": True, "ssm_inner": 128,
        "ssm_state": 16, "ssm_dt_rank": 4, "ssm_conv": 4, "ssm_chunk": 4,
        "ssm_state_dtype": "float32", "stream_dtype": "float32",
        "dtype": "float32"},
}
TINY_CELL = "tiny-sambay.tiny-closed"


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark with a toy of the same shape dropped in and
    its cell appended wherever the real one is."""
    root = tmp_path_factory.mktemp("bench_sambay")
    bench = str(root / "benchmarks")
    shutil.copytree(spec.BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "out", "__pycache__", "tests"))

    def drop(rel, payload):
        path = os.path.join(bench, rel)
        assert not os.path.exists(path), f"{rel} would be an edit"
        with open(path, "w") as f:
            json.dump(payload, f)

    drop("configs/tiny-sambay.json", TINY)
    drop("traffic/tiny-closed.json", test_rehearsal.TRAFFIC["tiny-closed"])
    drop(f"workloads/{TINY_CELL}.json",
         dict(test_rehearsal.SERVE, name=TINY_CELL, config="tiny-sambay",
              traffic="tiny-closed", why="test"))
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    benchmark["configs"].append(
        {"name": "tiny-sambay", "source": TINY["source"], "reduced": [],
         "file": "benchmarks/configs/tiny-sambay.json", "why": "test"})
    benchmark["workloads"].append(
        {"name": TINY_CELL, "config": "tiny-sambay",
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
    assert cell.workload["engine"]["max_len"] == 16384
    assert cell.workload["engine"]["prefill_buckets"] == [4096, 8192, 12288]
    names = {m["name"] for m in cell.metric_entries("per_layer")}
    assert {n for n in names if n.startswith("sambay_")} == {
        "sambay_shared_kv_attention_time_share",
        "sambay_shared_kv_attention_roofline",
        "sambay_window_attention_time_share",
        "sambay_window_attention_roofline", "sambay_ssm_scan_time_share",
        "sambay_ssm_state_update_time_share", "sambay_gmu_time_share",
        "sambay_diff_combine_time_share", "sambay_prefill_skipped_share"}
    assert {"batch.slot_wait_p50_ms", "batch.decode_kv_read_share",
            "batch.prefill_unscoped_time_share"} <= names
    assert not {n for n in names if n.startswith(
        ("swa_", "ssm_", "moe_", "dsa_", "mla_", "lfm2_"))}
    # the step's floor is the file's: lib/sambay_flops.py counts the eight
    # reads of one pool, the rings and the Mamba-1 states
    assert cell.config["roofline"] == "sambay_flops"
    assert "batch.decode_step_roofline" in names
    assert {m["name"] for m in cell.metric_entries("end_to_end")} \
        == {"serve_output_tokens_per_s", "setup_s"}


def test_the_cells_names_lead_to_files_and_join_the_serve_metrics():
    the_cells_entries()


def test_a_toy_decoder_hybrid_decoder_runs_end_to_end_on_the_cpu(
        tree, cpu_peaks):
    """One traced run of the toy cell through ``run.measure``: ``correct``
    against ``phi4flash_decoder`` with the harness's own limit and no
    allowance, nothing failed, the metrics the cell joins and the
    program's own count of skipped positions are there; what only a device
    trace knows is left out on a CPU, not invented."""
    bench, benchmark_json = tree
    result, obs = bench_run.measure(
        ["--workload", TINY_CELL, "--seed", "2147486230", "--seconds", "3",
         "--trace", "1"],
        allow_platforms=("cpu",), bench_dir=bench,
        benchmark_json=benchmark_json, t_process=time.perf_counter())
    assert result["correct"] is True, obs["checks"]
    assert result["failed"] == 0 < result["attempted"]
    assert obs["cell"].reference.__name__.endswith("phi4flash_decoder")
    assert len(obs["logit_gaps"]) == 4 and obs["logit_gap_max"] < 1e-2
    metrics = result["metrics"]
    assert {"batch.slot_wait_p50_ms", "batch.token_burst_gap_p50_ms",
            "batch.decode_slot_utilization", "batch.decode_kv_read_share",
            "batch.prefill_padding_share", "window_compiles",
            "sambay_prefill_skipped_share"} <= set(metrics)
    # 2 of 8 layers at one position a row: under 25%, by the padding's share
    assert 20.0 < metrics["sambay_prefill_skipped_share"]["value"] < 25.0
    assert not {name for name in metrics if name.startswith("sambay_")
                and name != "sambay_prefill_skipped_share"}
    chunk = next(c for c in program_spans.collect(obs).chunks
                 if c.get("kv_positions_attended"))
    assert chunk["shared_kv_positions_attended"] \
        == 2 * chunk["kv_positions_attended"]


def test_the_published_width_check_rehearsed_at_toy_size(tree, capsys):
    """``tools/sambay_check.py`` end to end on the toy: the intact engine
    within rounding of the reference in float32 arithmetic, lambda on the
    wrong half far off it."""
    from benchmarks.tools import sambay_check

    bench, _ = tree
    assert sambay_check.main([
        "--config", "tiny-sambay", "--seed", "2147486231", "--bench-dir",
        bench, "--variants", "intact,lambda_wrong_half",
        "--prompts", "1,8,9,31", "--new-tokens", "24", "--max-len", "128",
        "--buckets", "8,16,32"]) == 0
    done = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(done["intact"]) == {"1", "8", "9", "31", "reused_slot"}
    assert max(g for g, _ in done["intact"].values()) < 1e-3
    assert max(g for g, _ in done["lambda_wrong_half"].values()) > 0.25


@pytest.mark.parametrize("variant,correct", [
    ("intact", True), ("lambda_wrong_half", False),
    ("float8_weights", False)])
def test_a_fault_goes_through_the_harness_own_correct(tree, cpu_peaks, capsys,
                                                      variant, correct):
    """``sambay_check.py --cell``: the toy cell's own run by
    ``run.measure`` -- its traffic, its ``correct``, the harness's limit
    -- with a variant patched in for the length of the run: lambda on the
    wrong half and a reference that reads its weights in float8's mantissa
    are judged not correct, the intact engine correct, and nothing stays
    patched."""
    from benchmarks.lib import program
    from benchmarks.tools import sambay_check
    from ray_tpu.models import llama

    bench, benchmark_json = tree
    before = (program.llama_fields, llama.diff_combine, spec.load_module)
    assert sambay_check.through_the_cell(
        TINY_CELL, variant, 2147486232, 3.0, allow_platforms=("cpu",),
        bench_dir=bench, benchmark_json=benchmark_json,
        t_process=time.perf_counter()) == 0
    assert (program.llama_fields, llama.diff_combine,
            spec.load_module) == before
    said = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert said["event"] == "cell_control" and said["variant"] == variant
    assert said["correct"] is correct, said
    assert said["failed"] == 0 < said["attempted"]
    assert (max(said["logit_gaps"]) > 0.25) is not correct


# ----------------------------------------------------------- the readers
def test_the_readers_arithmetic_on_a_given_split(monkeypatch):
    """A decode program's seconds by scope as ``scope_names.split`` would
    hand them, 48 rows of 6,500 positions in flight: the shares are the
    scopes' own seconds over the module's, the shared pool's roofline its
    12.8 GB at the HBM peak over its 20 ms a step; a configuration of
    another family reads nothing."""
    c = _json("configs", CONFIG)
    by = {("cross_attention", "forward"): 0.20, ("gmu", "forward"): 0.02,
          ("decode_attention", "forward"): 0.03, ("attention", "forward"): 0.01,
          ("mamba1_state_update", "forward"): 0.04,
          ("diff_combine", "forward"): 0.01, ("ffn", "forward"): 0.30}
    monkeypatch.setattr(
        scope_names, "split",
        lambda obs, which: scope_names.Split(by, 1.0, []) if which == "decode"
        else scope_names.Split({("mamba1_scan", "forward"): 0.25}, 1.0, []))
    monkeypatch.setattr(sambay_names.readers, "decode_step_device_ms",
                        lambda obs: 100.0)
    monkeypatch.setattr(sambay_names, "_traced_lengths",
                        lambda obs: [6500.0] * 48)
    monkeypatch.setattr(swa_names, "_traced_lengths",
                        lambda obs: [6500.0] * 48)
    step_roofline = spec.load_module("metrics", "decode_step_roofline").read
    obs = {"cell": types.SimpleNamespace(config=c, bench_dir=spec.BENCH_DIR,
                                         name=CELL),
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    assert sambay_names.shared_kv_attention_time_share(obs) \
        == pytest.approx(20.0)
    assert sambay_names.window_attention_time_share(obs) \
        == pytest.approx(4.0)
    assert sambay_names.ssm_state_update_time_share(obs) \
        == pytest.approx(4.0)
    assert sambay_names.gmu_time_share(obs) == pytest.approx(2.0)
    assert sambay_names.diff_combine_time_share(obs) == pytest.approx(1.0)
    assert sambay_names.ssm_scan_time_share(obs) == pytest.approx(25.0)
    assert sambay_names.shared_kv_attention_roofline(obs) == pytest.approx(
        100 * 12_779_520_000 / 819e9 / 0.020)
    # 8 rings of 512 live keys x 5,120 B a row, 48 rows, over their 4 ms
    assert sambay_names.window_attention_roofline(obs) == pytest.approx(
        100 * 8 * 512 * 5120 * 48 / 819e9 / 0.004)
    assert step_roofline(obs) == pytest.approx(
        100 * sambay_flops.decode_step_bytes(c, [6500.0] * 48) / 819e9 / 0.1)
    other = {**obs, "cell": types.SimpleNamespace(
        config=_json("configs", "granite-4.0-h-micro"))}
    assert sambay_names.shared_kv_attention_time_share(other) is None
    assert sambay_names.window_attention_roofline(other) is None
    assert sambay_names.prefill_skipped_share({"program_spans": None}) is None
