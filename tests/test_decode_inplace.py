"""The decode step writes one K/V row per slot in place and attends the
cache where it lies.

Two halves.  On the CPU, at toy size, the step shared by ``_decode_k``
and ``_draft_propose`` is held to the formulation it replaced, kept only
here (the attended prefix sliced out, every layer rebuilt through a
masked select as a scan's xs/ys, ``llama._cache_attend``, the slice
written back): the tokens equal, the cache and the carries to a bf16
tolerance (the attention is a Pallas kernel since PR 29: the same keys
and precisions in another order of summation), the rows layer 0 wrote
bit for bit (they depend on the tokens alone), and every row that no
step wrote untouched.  For the chip, with no chip: the real-width
programs of the two serve cells are compiled for a described v5e and
their memory and their while bodies are looked at (what a run would cost
is ``PERF.md``'s business, not a test's).
"""

import os
import re

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

_SLOTS, _MAX_LEN = 4, 256
_ENGINE = dict(model_preset="debug", max_slots=_SLOTS, max_len=_MAX_LEN,
               prefill_buckets=(16,), decode_chunk=16,
               prefill_groups=(4,), warmup=False)


# ------------------------------------------------ the reference, test-local
def _reference_chunk(cfg, params, cache, tok, lens, active, k, s_active):
    """``k`` greedy steps the way the dense plane ran them before: slice,
    scan with the slice as carry, layers as xs/ys, ``jnp.where`` write,
    write-back."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama

    key_pos = jnp.arange(s_active, dtype=jnp.int32)
    scale = cfg.head_dim ** -0.5

    def step(carry, _):
        ck, cv, tok, lens = carry
        x = params["embed_tokens"].astype(cfg.dtype)[tok][:, None]
        sin, cos = llama.rope_table(lens[:, None], cfg.head_dim,
                                    cfg.rope_theta)
        writemask = ((key_pos[None, :] == lens[:, None])
                     & active[:, None])[:, :, None, None]

        def body(x, layer_and_cache):
            layer, ck_l, cv_l = layer_and_cache
            q, kk, vv = llama._qkv_rope(x, layer, sin, cos, cfg)
            ck_l = jnp.where(writemask, kk.astype(ck_l.dtype), ck_l)
            cv_l = jnp.where(writemask, vv.astype(cv_l.dtype), cv_l)
            attn = llama._cache_attend(q, ck_l, cv_l, lens[:, None],
                                       scale)
            return llama.attn_out_ffn(x, attn, layer, cfg)[0], (ck_l, cv_l)

        x, (ck, cv) = jax.lax.scan(body, x, (params["layers"], ck, cv))
        x = llama.rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = (params["embed_tokens"].astype(cfg.dtype).T
                if cfg.tie_embeddings
                else params["lm_head"].astype(cfg.dtype))
        nxt = jnp.argmax(llama.matmul(x, head)[:, 0],
                         axis=-1).astype(jnp.int32)
        nxt = jnp.where(active, nxt, tok)
        return (ck, cv, nxt, lens + active.astype(jnp.int32)), nxt

    ck = jax.lax.slice_in_dim(cache["k"], 0, s_active, axis=2)
    cv = jax.lax.slice_in_dim(cache["v"], 0, s_active, axis=2)
    (ck, cv, tok, lens), toks = jax.lax.scan(
        step, (ck, cv, tok, lens), None, length=k)
    cache = {
        "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], ck, 0, axis=2),
        "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], cv, 0, axis=2),
    }
    return cache, toks, tok, lens


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint16) if x.dtype.itemsize == 2 else x


def _random_cache(cfg, seed):
    """A cache with something in every row, so a stray write shows."""
    import jax

    from ray_tpu.models import llama

    shape = llama.init_kv_cache(cfg, _SLOTS, _MAX_LEN)["k"].shape
    kk, kv = jax.random.split(jax.random.key(seed))
    return {"k": jax.random.normal(kk, shape, cfg.dtype),
            "v": jax.random.normal(kv, shape, cfg.dtype)}


@pytest.fixture(params=[None, 2048], ids=["one_block", "several_blocks"])
def engine(request, monkeypatch):
    """Builds an engine that has traced nothing yet.  A toy slot's whole
    cache is one block of the kernel; 2 KiB makes a block 32 positions
    (64 B a position), so that rows end inside, at the edge of and
    blocks past their first, as the real widths' rows do on the chip."""
    from ray_tpu.ops import decode_attention
    from ray_tpu.serve import llm

    if request.param is not None:
        monkeypatch.setattr(decode_attention, "_BLOCK_BYTES",
                            request.param)
    servers = []

    def build(**kw):
        servers.append(llm.LLMServer(**_ENGINE, **kw))
        return servers[-1]

    yield build
    for server in servers:
        server.shutdown()


# Layers past the first see the kernel's attention: sums of values of
# order one that differ in their last bf16 bits (2**-8 each), and a
# chunk's later rows are computed from the earlier ones.
_CACHE_TOL = 5e-2
_NO_OVERRIDE = dict(ov_tok=(0, 0, 0, 0), ov_len=(0, 0, 0, 0),
                    ov_mask=(False,) * 4)

# lens, active, k, s_active (+ overrides): one row per property held.
_CASES = {
    "all_active_smallest_bucket": dict(
        lens=(5, 17, 40, 1), active=(True,) * 4, k=16, s_active=64),
    "mixed_active_inactive": dict(
        lens=(5, 100, 40, 77), active=(True, False, True, False), k=16,
        s_active=128),
    "lens_reaches_s_active_mid_chunk": dict(
        lens=(61, 64, 3, 70), active=(True,) * 4, k=16, s_active=64),
    "override_token_and_length": dict(
        lens=(5, 17, 40, 1), active=(True,) * 4, k=16, s_active=128,
        ov_tok=(0, 201, 0, 7), ov_len=(0, 90, 0, 33),
        ov_mask=(False, True, False, True),
        # the cache this case drew by default leaves slot 1's eighth
        # step a near tie between two tokens, which another order of
        # summation in the attention flips
        seed=1),
    "one_step": dict(
        lens=(5, 17, 40, 1), active=(True, True, False, True), k=1,
        s_active=64),
    "largest_bucket": dict(
        lens=(239, 130, 0, 255), active=(True,) * 4, k=16,
        s_active=_MAX_LEN),
    "nothing_active": dict(
        lens=(5, 17, 40, 1), active=(False,) * 4, k=16, s_active=64),
    "last_position_and_position_zero": dict(
        lens=(0, 63, 62, 127), active=(True, True, True, False), k=2,
        s_active=64),
}


@pytest.mark.parametrize("case", list(_CASES) + ["draft_propose"])
def test_row_write_and_kernel_match_masked_select(case, engine):
    import jax
    import jax.numpy as jnp

    spec = {**_NO_OVERRIDE,
            **_CASES.get(case, _CASES["mixed_active_inactive"])}
    lens0 = jnp.asarray(spec["lens"], jnp.int32)
    tok0 = jnp.asarray((3, 99, 250, 42), jnp.int32)
    active = jnp.asarray(spec["active"], bool)
    ov_tok = jnp.asarray(spec["ov_tok"], jnp.int32)
    ov_len = jnp.asarray(spec["ov_len"], jnp.int32)
    ov_mask = jnp.asarray(spec["ov_mask"], bool)
    k, s_active = spec["k"], spec["s_active"]

    if case == "draft_propose":
        server = engine(paged=True, block_size=16, spec_k=4, draft_layers=1)
        cfg, params = server.draft_cfg, server.draft_params
        assert cfg.n_layers == 1       # its own cache, not the target's
        before = _random_cache(cfg, seed=7)
        got_cache, got_toks = server._draft_propose(
            params, jax.tree.map(jnp.copy, before), tok0, lens0, active,
            k=k, s_active=s_active)
        got = (got_cache, got_toks)
        want = jax.jit(_reference_chunk, static_argnums=(0, 6, 7))(
            cfg, params, before, tok0, lens0, active, k, s_active)[:2]
    else:
        dense = engine()
        cfg, params = dense.cfg, dense.params
        before = _random_cache(cfg, seed=spec.get("seed", len(case)))
        got = dense._decode_k(
            params, jax.tree.map(jnp.copy, before), jnp.copy(tok0),
            jnp.copy(lens0), ov_tok, ov_len, ov_mask, active, k=k,
            s_active=s_active)
        want = jax.jit(_reference_chunk, static_argnums=(0, 6, 7))(
            cfg, params, before, jnp.where(ov_mask, ov_tok, tok0),
            jnp.where(ov_mask, ov_len, lens0), active, k, s_active)

    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype
        if g.dtype.kind == "i":                 # tokens, carries
            np.testing.assert_array_equal(g, w)
        else:                                   # the cache
            np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(w, np.float32),
                atol=_CACHE_TOL, rtol=_CACHE_TOL)
    # Layer 0's rows come from the tokens' embeddings alone.
    for name in ("k", "v"):
        np.testing.assert_array_equal(_bits(got[0][name][0]),
                                      _bits(want[0][name][0]))

    # What the reference implies, said outright: a slot that is not
    # active, or is past the attended prefix, keeps every row it had.
    lens_in = np.where(spec["ov_mask"], spec["ov_len"], spec["lens"])
    for slot in range(_SLOTS):
        wrote = [p for p in range(lens_in[slot], lens_in[slot] + k)
                 if spec["active"][slot] and p < s_active]
        keep = np.ones(_MAX_LEN, bool)
        keep[wrote] = False
        for name in ("k", "v"):
            np.testing.assert_array_equal(
                _bits(got[0][name][:, slot])[:, keep],
                _bits(before[name][:, slot])[:, keep])
            if wrote:
                assert (_bits(got[0][name][:, slot])[:, wrote]
                        != _bits(before[name][:, slot])[:, wrote]).any()


# ------------------------------------------- the real widths, for the chip
@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_for_the_chip(monkeypatch):
    """The backend here is the CPU but the target is the chip: kernels
    are steered to Mosaic as ``benchmarks/tests/test_aot_real_widths.py``
    steers them (the one function every kernel of ``ray_tpu.ops`` asks),
    and a compile for a described chip is kept out of the persistent
    cache, which cannot read it back without one."""
    import importlib

    import jax

    flash = importlib.import_module("ray_tpu.ops.flash_attention")
    monkeypatch.setattr(flash, "_use_interpret", lambda: False)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)


_DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4,
                "pred": 1, "s8": 1, "u8": 1}
_VIEWS = ("parameter", "get-tuple-element", "tuple", "bitcast", "while")


def _while_body_results(hlo):
    """(instruction, opcode, root opcode of the fusion it calls, arrays)
    for every instruction of every while body of an optimised HLO module
    that makes something; an array is (dims, bytes)."""
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
    bodies = {m.group(1) for lines in comps.values() for line in lines
              for m in re.finditer(r"\bbody=%([^,\s)]+)", line)}
    assert bodies, "no while loop in the program"
    out = []
    for body in bodies:
        for line in comps[body]:
            m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*?) ([\w-]+)\(", line)
            if not m or m.group(3) in _VIEWS:
                continue
            inst, result, opcode = m.groups()
            arrays = []
            for dtype, dims in re.findall(r"(\w+)\[([\d,]*)\]", result):
                dims = tuple(int(d) for d in dims.split(",") if d)
                arrays.append((dims, int(np.prod(dims, dtype=np.int64))
                               * _DTYPE_BYTES.get(dtype, 4)))
            root = ""
            called = re.search(r"calls=%([^,\s)]+)", line)
            if called:
                root = next((r.group(1) for r in (
                    re.match(r"\s*ROOT %\S+ = .*? ([\w-]+)\(", f)
                    for f in comps[called.group(1)]) if r), "")
            out.append((inst, opcode, root, arrays))
    return out


@pytest.mark.parametrize("cell,more_slots,refused_before", [
    ("internlm2-1.8b.serve-batch-decode", 128, 256),
    ("internlm2-1.8b.serve-chat-busy", 48, 1024),
])
def test_decode_k_at_real_widths_updates_the_cache_in_place(
        one_chip, compiled_for_the_chip, cell, more_slots, refused_before):
    from benchmarks.tests.test_aot_real_widths import (_engine_programs,
                                                       _json)

    engine = _json("workloads", cell)["engine"]
    slots, max_len = engine["max_slots"], engine["max_len"]
    layers, kv_heads, head_dim = 24, 8, 128           # internlm2-1.8b
    cache_bytes = 2 * layers * slots * max_len * kv_heads * head_dim * 2
    programs = dict(_engine_programs(cell, one_chip))
    compiled = programs[f"decode_k s_active={max_len}"]()

    # (a) no second cache: the slice-out and its ~1.76x of scratch went
    assert compiled.memory_analysis().temp_size_in_bytes < cache_bytes / 4

    # (b) what the token loop and the layer loop make that has K/V's
    # shape: the two row scatters, which XLA runs in place on the loops'
    # carry.  Nothing else: no copy, select, slice or fusion makes the
    # cache, a layer of it, a layer's prefix or a group of slots' (the
    # attended K and V that PR 24's step staged in VMEM: ``staged``),
    # because the attention is ONE Mosaic call a layer that reads the
    # carry where it lies.  (c) Nor is a stack of layer weights moved in
    # a loop: at a 16 MiB group XLA parked wk in VMEM and took it out and
    # back in every layer.
    scatters = staged = 0
    kernels = []
    for inst, opcode, root, arrays in _while_body_results(
            compiled.as_text()):
        if opcode == "custom-call":
            kernels.append(inst)
        for dims, nbytes in arrays:
            if dims == (layers, slots, max_len, kv_heads, head_dim):
                assert (opcode, root) == ("fusion", "scatter"), inst
                scatters += 1
            elif dims[-2:] == (kv_heads, head_dim) and nbytes >= 1 << 20:
                staged += 1
            elif dims[:1] == (layers,):
                assert nbytes < 16 << 20, (inst, opcode, dims)
    assert scatters == 2 and staged == 0                   # K and V
    assert len(kernels) == 1 and kernels[0].startswith("decode_attention")
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 1

    # (d) the slot count the compiler refused before now compiles, at the
    # bucket it was refused at and at the largest
    more = dict(_engine_programs(cell, one_chip, max_slots=more_slots))
    for s_active in (refused_before, max_len):
        more[f"decode_k s_active={s_active}"]()


def test_decode_k_of_a_hybrid_updates_the_recurrent_state_in_place(
        one_chip, compiled_for_the_chip):
    """The state's case of "nothing of the cache's shape is copied in a
    loop", at granite-4.0-h-micro's widths (80 slots x 512): what the
    token loop and the period loop make that has the stacked recurrent
    state's shape -- (36, slots, 128, 4096) float32, 6.0 GB -- is the
    result of the ``ssm_state_update`` kernel alone, one Mosaic call a
    Mamba layer of the period, whose state operand is aliased to it;
    nothing makes a layer's or a slot's worth of it; the conv windows are
    written by fusions on the carry; and K/V keep their two row scatters
    an attention layer."""
    from benchmarks.tests.test_granite_cell import CELL, _engine_programs
    from benchmarks.tests.test_aot_real_widths import _json

    engine = _json("workloads", CELL)["engine"]
    slots, max_len = engine["max_slots"], engine["max_len"]
    state = (36, slots, 128, 4096)
    programs = dict(_engine_programs(one_chip))
    compiled = programs[f"decode_k s_active={max_len}"]()
    state_bytes = int(np.prod(state, dtype=np.int64)) * 4
    assert compiled.memory_analysis().temp_size_in_bytes < state_bytes / 4
    kernels, scatters = [], 0
    for inst, opcode, root, arrays in _while_body_results(
            compiled.as_text()):
        for dims, nbytes in arrays:
            if dims == state:
                assert opcode == "custom-call", (inst, opcode, root)
                kernels.append(inst)
            elif dims == (4, slots, max_len * 4, 128):   # two heads a row
                assert (opcode, root) == ("fusion", "scatter"), inst
                scatters += 1
            else:
                assert dims[-2:] != state[-2:], (inst, opcode, dims)
    assert len(kernels) == 9 and all(
        k.startswith("ssm_state_update") for k in kernels)
    assert scatters == 2                          # K and V
