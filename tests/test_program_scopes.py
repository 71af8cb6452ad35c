"""Device seconds by scope (``observability/device.py``'s fifth surface):
``scope_of`` on the forms an ``op_name`` takes, programs registered at
warm-up and at a train step's first dispatch, ``program_scopes()`` keyed
by instructions of the compiled text, ``scopes.json`` in a captured
bundle, and nothing at all with tracing off."""

import io
import json
import re
import zipfile

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import llama
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.observability import device, tracing
from ray_tpu.serve import llm


@pytest.fixture(autouse=True)
def fresh_registry():
    device.clear_programs()
    yield
    device.clear_programs()


@pytest.mark.parametrize("op_name,expected", [
    ("jit(f)/jvp(ffn)/dot_general", ("ffn", "forward")),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "dot_general", (None, "backward")),
    ("jit(step)/transpose(jvp(layer_scan))/while/body/closed_call/"
     "checkpoint/ffn/dot_general", ("ffn", "backward")),
    ("jit(step)/transpose(jvp(layer_scan))/while/body/closed_call/"
     "checkpoint/rematted_computation/ffn/dot_general", ("ffn", "remat")),
    ("jit(step)/jvp(layer_scan)/while/body/dynamic_slice",
     ("layer_scan", "forward")),
    ("jit(step)/transpose(jvp(attention))/flash_attention.dq/"
     "flash_attention_dq", ("flash_attention.dq", "backward")),
    ("jit(decode_k)/sample/while/body/layer_scan/while/body/closed_call/"
     "attention/decode_attention/decode_attention",
     ("decode_attention", "forward")),
    # a word that keeps what lies inside it: one model's name for a use of
    # a kernel that carries a word of its own
    ("jit(decode_k)/sample/while/body/layer_scan/while/body/closed_call/"
     "cross_attention/decode_attention/decode_attention",
     ("cross_attention", "forward")),
    ("jit(prefill)/layer_scan/while/body/cross_attention/attention/"
     "dot_general", ("cross_attention", "forward")),
    ("jit(prefill)/head/head/dot_general", ("head", "forward")),
    ("jit(f)/optimizer/sub", ("optimizer", "forward")),
    ("jit(step)/head_loss/reduce_sum", ("head_loss", "forward")),
    ("jit(step)/header/mul", (None, "forward")),       # whole words only
    # what XLA lowers and names itself: ``jax.lax.ragged_dot``
    ("ragged-dot-none", ("expert_ffn", "forward")),
    ("ragged-dot-metadata", ("expert_dispatch", "forward")),
    ("jit(step)/jvp()/broadcast_in_dim", (None, "forward")),
    ("", (None, "forward")),
    (None, (None, "forward")),
])
def test_scope_of(op_name, expected):
    assert device.scope_of(op_name) == expected


def test_the_vocabulary_is_one_tuple_of_distinct_words():
    assert len(set(device.SCOPES)) == len(device.SCOPES)
    assert {"ffn", "qkv_proj", "optimizer", "head_loss", "kv_write",
            "ssm_state_update", "expert_dispatch"} <= set(device.SCOPES)


def test_instruction_key_is_what_text_and_trace_agree_on():
    text = ('  ROOT %fusion.3 = (f32[960]{0:T(1024)}, bf16[8,2048,960]'
            '{1,2,0:T(8,128)(2,1)}) fusion(%p.1, %p.2), kind=kOutput, '
            'calls=%fused_computation.3, metadata={op_name="jit(f)/ffn/'
            'dot_general" stack_frame_id=3}, backend_config={"x":{}}')
    event = ('%fusion.3 = (f32[960]{0:T(1024)S(1)}, bf16[8,2048,960]'
             '{1,2,0:T(8,128)(2,1)}) fusion(f32[8]{0} %p.1, f32[8]{0} '
             '%p.2), kind=kOutput, calls=%fused_computation.3')
    key = "%fusion.3 fusion (f32[960], bf16[8,2048,960])"
    assert device.instruction_key(text) == key
    assert device.instruction_key(event) == key
    assert device.instruction_key("HloModule jit_step") is None


HLO = """HloModule jit_f, entry_computation_layout={()->f32[4]{0}}

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %m = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(f)/ffn/mul"}
  %a = f32[4]{0} add(%m, %p), metadata={op_name="jit(f)/ffn/add"}
  ROOT %c = f32[4]{0} copy(%a), metadata={op_name="jit(f)/copy"}
}

%region (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %s = f32[] add(%x, %y), metadata={op_name="jit(f)/head/reduce_sum"}
}

%body (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[], f32[4]{0}) parameter(0)
  %i = s32[] get-tuple-element(%t), index=0
  %v = f32[4]{0} get-tuple-element(%t), index=1
  %dynamic-update-slice.5 = f32[4]{0} dynamic-update-slice(%v, %v, %i)
  %add.7 = s32[] add(%i, %i), metadata={op_name="jit(f)/sample/add"}
  ROOT %tuple.9 = (s32[], f32[4]{0}) tuple(%add.7, %dynamic-update-slice.5)
}

%cond (t.1: (s32[], f32[4])) -> pred[] {
  %t.1 = (s32[], f32[4]{0}) parameter(0)
  %i.1 = s32[] get-tuple-element(%t.1), index=0
  ROOT %compare.3 = pred[] compare(%i.1, %i.1), direction=LT
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a.1 = f32[4]{0} parameter(0)
  %tuple.1 = (s32[], f32[4]{0}) tuple(%a.1, %a.1)
  %while.2 = (s32[], f32[4]{0}) while(%tuple.1), condition=%cond, body=%body, metadata={op_name="jit(f)/kv_write/scatter"}
  %fusion = f32[4]{0} fusion(%a.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/copy"}
  %fusion.1 = f32[4]{0} fusion(%fusion), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/transpose(jvp(optimizer))/mul"}
  %copy.2 = f32[4]{0} copy(%fusion.1)
  ROOT %reduce = f32[]{:T(128)} reduce(%copy.2, %a.1), dimensions={0}, to_apply=%region, metadata={op_name="jit(f)/head/reduce_sum"}
}
"""


def test_scopes_of_text_votes_inherits_and_skips_what_is_no_event():
    assert device.scopes_of_text(HLO) == {
        # its own op_name has no word of the vocabulary: the majority of
        # its fused computation
        "%fusion fusion f32[4]": ["ffn", "forward"],
        "%fusion.1 fusion f32[4]": ["optimizer", "backward"],
        "%copy.2 copy f32[4]": ["unscoped", "forward"],
        "%reduce reduce f32[]": ["head", "forward"],
        # a scatter the compiler expanded: the loop keeps the op_name,
        # its body has none and takes the loop's
        "%while.2 while (s32[], f32[4])": ["kv_write", "forward"],
        "%dynamic-update-slice.5 dynamic-update-slice f32[4]":
            ["kv_write", "forward"],
        "%compare.3 compare pred[]": ["kv_write", "forward"],
        "%add.7 add s32[]": ["sample", "forward"],
    }


# --------------------------------------------------- registering programs
def _dot_generals(text):
    """(op_name, its scope) of every instruction of a compiled text that
    came from a ``dot_general``."""
    names = re.findall(r'op_name="([^"]*/dot_general)"', text)
    return [(n, device.scope_of(n)[0]) for n in names]


def _toy_train_step():
    cfg = LlamaConfig.debug(remat=True, remat_policy="attn")
    step = llama.make_train_step(cfg, fused=True)
    state = llama.init_train_state(jax.random.key(0), cfg, fused=True)
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32)}
    return cfg, step, state, batch


def test_a_train_step_registers_at_its_first_dispatch_and_no_later():
    _cfg, step, state, batch = _toy_train_step()
    assert device.registered_programs() == []
    state, _ = step(state, batch)
    state, _ = step(state, batch)
    assert device.registered_programs() == ["train.step"]
    scopes = device.program_scopes()
    assert list(scopes) == ["jit_step"]
    found = {tuple(v) for v in scopes["jit_step"].values()}
    assert {("optimizer", "forward"), ("head_loss", "forward"),
            ("head_loss", "backward"), ("ffn", "backward"),
            ("ffn", "remat"), ("qkv_proj", "backward"),
            ("embed", "backward")} <= found
    # keyed by instructions the compiled text holds, every dot_general
    # of it in a scope of the vocabulary
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), (state, batch))
    text = step.lower(*shapes).compile().as_text()
    keys = {device.instruction_key(line) for line in text.splitlines()}
    assert set(scopes["jit_step"]) <= keys
    dots = _dot_generals(text)
    assert len(dots) >= 10 and all(scope for _, scope in dots), dots
    # a second call returns what the first read, and lowers nothing
    step._jitted = None
    assert device.program_scopes() == scopes


def test_a_sharded_step_lowers_again_under_the_mesh_it_ran_with():
    """The step's trace reads ``current_mesh()``: the registry keeps the
    mesh and rules of the first dispatch, and the map is read outside
    them."""
    from ray_tpu.parallel import MeshSpec, use_mesh

    cfg = LlamaConfig.debug()
    with use_mesh(MeshSpec(fsdp=4).build(jax.devices()[:4])):
        step = llama.make_train_step(cfg, fused=True)
        state = llama.init_train_state(jax.random.key(0), cfg, fused=True)
        step(state, {"tokens": jnp.zeros((4, 16), jnp.int32)})
    table = device.program_scopes()["jit_step"]
    assert any(key.split()[1].startswith(("all-gather", "all-reduce",
                                          "reduce-scatter"))
               for key in table), "lowered without its mesh"
    assert {"ffn", "optimizer", "qkv_proj"} <= {v[0] for v in table.values()}


@pytest.mark.parametrize("preset,must_hold", [
    ("debug", {"qkv_proj", "attn_out", "ffn", "head", "sample",
               "kv_write", "embed", "layer_scan"}),
    ("moe_debug", {"router", "expert_dispatch", "expert_ffn", "ffn"}),
    ("hybrid_debug", {"ssm_proj", "ssm_conv", "ssm_scan",
                      "ssm_state_update", "ssm_out", "kv_write"}),
])
def test_an_engine_registers_what_it_warms(preset, must_hold):
    server = llm.LLMServer(model_preset=preset, max_slots=4, max_len=64,
                           prefill_buckets=(16,), decode_chunk=4)
    try:
        names = device.registered_programs()
        shapes = llm.prefill_shapes(server.prefill_groups, server.buckets,
                                    server.max_slots)
        assert names.count("serve.prefill") == len(shapes)
        assert names.count("serve.decode_k") == len(server.decode_buckets)
        assert names.count("serve.seat") == len({g for g, _ in shapes})
        scopes = device.program_scopes()
        assert set(scopes) == {"jit_prefill", "jit_decode_k", "jit_seat"}
        assert {v[0] for v in scopes["jit_seat"].values()} <= {
            "sample", "unscoped"}
        held = {v[0] for table in scopes.values() for v in table.values()}
        assert must_hold <= held, held
        assert held <= set(device.SCOPES) | {"unscoped"}
        group = jax.ShapeDtypeStruct((1,), jnp.int32)
        params, cache = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            (server.params, server.cache))
        text = server._prefill.lower(
            params, cache, jax.ShapeDtypeStruct((1, 16), jnp.int32), group,
            group).compile().as_text()
        keys = {device.instruction_key(line) for line in text.splitlines()}
        assert keys & set(scopes["jit_prefill"])
        dots = _dot_generals(text)
        assert dots and all(scope for _, scope in dots), dots
    finally:
        server.shutdown()


def test_a_captured_bundle_holds_the_scope_map():
    _cfg, step, state, batch = _toy_train_step()
    state, _ = step(state, batch)
    art = device.capture_device_trace(0.05)
    zf = zipfile.ZipFile(io.BytesIO(art["data"]))
    assert "scopes.json" in zf.namelist()
    assert art["files"] == len(zf.namelist())
    scopes = json.loads(zf.read("scopes.json"))
    assert scopes == device.program_scopes()
    assert ["optimizer", "forward"] in scopes["jit_step"].values()


def test_with_tracing_off_nothing_is_registered_and_nothing_lowers(
        monkeypatch):
    """The start, launch and harvest paths with ``RAY_TPU_TRACING=0``:
    no registry entry, no second lowering, no ``scopes.json``."""
    monkeypatch.setattr(tracing, "_enabled", False)
    _cfg, step, state, batch = _toy_train_step()
    state, _ = step(state, batch)         # compiles before lower is barred
    server = llm.LLMServer(model_preset="debug", max_slots=2, max_len=32,
                           prefill_buckets=(16,), decode_chunk=2,
                           warmup=False)

    def barred(*_a, **_k):
        raise AssertionError("lowered with tracing off")

    lowerable = [step._jitted, server._prefill, server._decode_k]
    monkeypatch.setattr(type(lowerable[0]), "lower", barred, raising=False)
    for program in lowerable:
        with pytest.raises(AssertionError, match="tracing off"):
            program.lower()
    try:
        server._warmup()
        state, _ = step(state, batch)
        assert device.registered_programs() == []
        assert device.program_scopes() == {}
        art = device.capture_device_trace(0.05)
        names = zipfile.ZipFile(io.BytesIO(art["data"])).namelist()
        assert "scopes.json" not in names
    finally:
        server.shutdown()
