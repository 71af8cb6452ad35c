"""The join of a device trace with the program's scope map
(``lib/scope_names.py``): its arithmetic on a trace recorded on the chip
and a hand-made map, its readers with and without a map, and -- at the
real widths, compiled for a described v5e -- that every executed fusion,
convolution and Mosaic call of cell 1's step and cell 3's ``decode_k`` and
widest ``prefill`` that carries an ``op_name`` lands in a scope of the
program's vocabulary, and that a scope moves nothing but metadata."""

import contextlib
import os
import re
import types

import pytest

from benchmarks.lib import scope_names, trace_reduce
from benchmarks.tests.test_aot_real_widths import (  # noqa: F401 - fixtures
    MOSAIC, _engine_programs, _train_step, compiled_kernels, one_chip, topo)

HERE = os.path.dirname(os.path.abspath(__file__))
NAMED_FIXTURE = os.path.join(HERE, "fixtures", "named_flash_tpu.xplane.pb")
STEP = r"^jit_step\b"


# ------------------------------------------- a recorded trace, a made map
def _made_map(trace):
    """A map as ``device.program_scopes()`` gives it for the fixture's
    events: the flash kernels under their scopes, every third other
    fusion ``ffn`` forward or backward, copies left out."""
    from ray_tpu.observability.device import instruction_key

    table, n = {}, 0
    for name in trace.op_seconds():
        key = instruction_key(name)
        if name.startswith("%flash_attention_"):
            kernel = name[len("%flash_attention_"):].split(".")[0]
            table[key] = [f"flash_attention.{kernel}",
                          "forward" if kernel == "fwd" else "backward"]
        elif trace_reduce.opcode(name) == "fusion":
            n += 1
            table[key] = [("ffn", "optimizer", "unscoped")[n % 3],
                          ("forward", "backward")[n % 2]]
    return {"jit_step": table, "jit_other": {"%x fusion f32[1]": ["ffn",
                                                                 "forward"]}}


def test_the_shares_add_up_to_the_modules_own_time():
    trace = trace_reduce.read(NAMED_FIXTURE)
    scopes = _made_map(trace)
    by, module_s, events = scope_names.split_by_scope(trace, STEP, scopes)
    runs = trace.module_runs(STEP)
    assert module_s == pytest.approx(sum(e - s for s, e, _ in runs))
    # every op of the fixture runs inside a step: the split is the chip's
    # own seconds, scope by scope, what no scope reaches included
    own = sum(trace.op_seconds().values())
    assert sum(by.values()) == pytest.approx(own, rel=1e-9)
    assert own <= module_s
    assert sum(s for _, scope, _, s in events if scope == "unscoped") \
        == pytest.approx(sum(s for (scope, _), s in by.items()
                             if scope == "unscoped"))
    # the kernels' seconds are what the readers by name make of them
    from benchmarks.lib import flash_names

    for kernel in ("fwd", "dq", "dkdv"):
        seconds = sum(s for (scope, _), s in by.items()
                      if scope == f"flash_attention.{kernel}")
        assert seconds == pytest.approx(
            flash_names.kernel_seconds(trace, kernel), rel=1e-9)
    assert scope_names.split_by_scope(trace, r"^jit_decode_k\b",
                                      scopes) is None
    assert scope_names.split_by_scope(trace, STEP, {"jit_prefill": {}}) \
        is None


def _obs(tmp_path, trace):
    cell = types.SimpleNamespace(bench_dir=str(tmp_path), name="a-cell")
    return {"trace": trace, "cell": cell}


def test_readers_with_a_map_and_without(tmp_path, monkeypatch):
    from ray_tpu.observability import device

    trace = trace_reduce.read(NAMED_FIXTURE)
    scopes = _made_map(trace)
    monkeypatch.setattr(device, "program_scopes", lambda: scopes,
                        raising=False)
    monkeypatch.setattr(device, "registered_programs", lambda: ["train.step"],
                        raising=False)
    obs = _obs(tmp_path, trace)
    ffn = scope_names.time_share("train", "ffn")(obs)
    optimizer = scope_names.time_share("train", "optimizer")(obs)
    unscoped = scope_names.time_share("train", "unscoped")(obs)
    everything = scope_names.time_share("train")(obs)
    backward = scope_names.time_share("train", None, "backward")(obs)
    forward = scope_names.time_share("train", None, "forward")(obs)
    assert 0 < ffn < 100 and 0 < optimizer < 100 and 0 < unscoped < 100
    assert forward + backward == pytest.approx(everything)
    by, module_s, _ = obs["scope_split.train"]
    flash = 100.0 * sum(s for (scope, _), s in by.items()
                        if scope.startswith("flash_")) / module_s
    assert ffn + optimizer + unscoped + flash == pytest.approx(everything)
    assert 90.0 < everything <= 100.0     # the rest: gaps inside a step
    assert obs["scope_map_cost"]["programs"] == 1
    assert os.path.isfile(tmp_path / "out" / "a-cell" / "scopes.json")
    # no decode or prefill program ran in this trace
    assert scope_names.time_share("decode", "ffn")(obs) is None
    # a program that registers nothing, one that has no such function
    # (the parent), a run that was not traced: the metric is left out
    monkeypatch.setattr(device, "program_scopes", lambda: {}, raising=False)
    assert scope_names.time_share("train", "ffn")(_obs(tmp_path, trace)) \
        is None
    monkeypatch.delattr(device, "program_scopes", raising=False)
    assert scope_names.time_share("train", "ffn")(_obs(tmp_path, trace)) \
        is None
    assert scope_names.time_share("train", "ffn")(_obs(tmp_path, None)) \
        is None


def scope_entries_hold(root):
    """Every entry of ``<root>/BENCHMARK.json`` whose reader file is built
    on ``scope_names`` directly is a share of device time read from a trace
    -- however many there are: a later PR appends its own (the rehearsal
    calls this on a tree that one was appended to)."""
    from benchmarks.tests.test_yardstick import benchmark_at, reader_at

    entries = [m for m in benchmark_at(root)["per_layer"]
               if hasattr(reader_at(root, m["name"]), "scope_names")]
    assert entries
    for entry in entries:
        assert callable(reader_at(root, entry["name"]).read), entry["name"]
        assert (entry["unit"], entry["source"]) == ("%", "device_trace")
    return {e["name"] for e in entries}


def test_every_new_entry_has_its_reader_and_the_families_are_scopes():
    from benchmarks.lib import spec
    from ray_tpu.observability import device

    assert {"train_ffn_time_share", "batch.decode_ffn_time_share",
            "chat.prefill_unscoped_time_share"} <= scope_entries_hold(
        spec.ROOT)
    for family, words in scope_names.FAMILIES.items():
        assert set(words) <= set(device.SCOPES) | {scope_names.UNSCOPED}


# --------------------------------------------------- at the real widths
_NAME = re.compile(r"%[\w.\-]+")


def _canonical(text):
    """A compiled module's text without what a scope may move: metadata,
    the location tables (of the module and inside a Mosaic call's
    payload) and the numbers that make instruction names unique."""
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    lines = []
    for line in text.splitlines():
        if line.startswith(("FileNames", "FunctionNames", "FileLocations",
                            "StackFrames")) or re.match(r"^\d+ ", line):
            continue
        if MOSAIC in line:
            line = re.sub(r"backend_config=\{.*$", "", line)
        lines.append(line)
    names = {}
    return [_NAME.sub(lambda m: names.setdefault(m.group(0),
                                                 f"%n{len(names)}"), line)
            for line in lines]


@contextlib.contextmanager
def _no_scopes():
    """The program traced as if it named nothing: every scope of the
    vocabulary dropped from the name stack.  (What jax has cached of an
    inner jitted helper, ``jax.nn.silu``, keeps the stack it was first
    traced under: a few ``qkv_proj`` / ``ffn`` may survive.)"""
    from jax._src import source_info_util

    from ray_tpu.observability import device

    real = source_info_util.extend_name_stack

    class Nothing(contextlib.ContextDecorator):
        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    def extend(name):
        return Nothing() if name in device.SCOPES else real(name)

    source_info_util.extend_name_stack = extend
    try:
        yield
    finally:
        source_info_util.extend_name_stack = real


def _executed_without_a_scope(text):
    """Executed fusions, convolutions and custom calls of a compiled text
    that carry an ``op_name`` and land in no scope of the vocabulary."""
    from ray_tpu.observability import device

    named = {device.instruction_key(line)
             for line in text.splitlines() if 'op_name="' in line}
    return [key for key, (scope, _phase)
            in device.scopes_of_text(text).items()
            if scope == "unscoped" and key in named
            and key.split()[1] in ("fusion", "convolution", "custom-call")]


def _cell3_programs(one_chip):
    wanted = ("decode_k s_active=512", "prefill group=8 bucket=256")
    return [(label, compile_it) for label, compile_it in _engine_programs(
        "internlm2-1.8b.serve-batch-decode", one_chip) if label in wanted]


def test_cell_1s_step_is_named_and_a_scope_moves_only_metadata(topo):
    from ray_tpu.observability import device

    text = _train_step("smollm2-360m", "train-1chip", None,
                       topo.devices).as_text()
    assert _executed_without_a_scope(text) == []
    scopes = device.scopes_of_text(text)
    held = {tuple(v) for v in scopes.values()}
    assert {("optimizer", "forward"), ("head_loss", "backward"),
            ("ffn", "remat"), ("qkv_proj", "backward"),
            ("layer_scan", "backward"), ("flash_attention.fwd", "forward"),
            ("flash_attention.dq", "backward"),
            ("flash_attention.dkdv", "backward")} <= held
    with _no_scopes():
        bare = _train_step("smollm2-360m", "train-1chip", None,
                           topo.devices).as_text()
    assert not {"optimizer", "head_loss", "attn_out", "layer_scan"} & {
        v[0] for v in device.scopes_of_text(bare).values()}
    assert _canonical(bare) == _canonical(text)


def test_cell_3s_programs_are_named_and_a_scope_moves_only_metadata(
        one_chip):
    from ray_tpu.observability import device

    programs = _cell3_programs(one_chip)
    assert len(programs) == 2
    # a program traces once: the bare ones are an engine's of their own
    for (label, compile_it), (_, compile_bare) in zip(
            programs, _cell3_programs(one_chip)):
        text = compile_it().as_text()
        assert _executed_without_a_scope(text) == [], label
        held = {v[0] for v in device.scopes_of_text(text).values()}
        assert {"qkv_proj", "attn_out", "ffn", "head", "kv_write",
                "layer_scan", "embed"} <= held, label
        with _no_scopes():
            bare = compile_bare().as_text()
        assert not {"attn_out", "layer_scan", "sample"} & {
            v[0] for v in device.scopes_of_text(bare).values()}, label
        assert _canonical(bare) == _canonical(text), label
