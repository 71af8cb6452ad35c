"""A prefill group's K/V rows are written into the cache where they lie.

Two halves.  On the CPU, at toy size, ``llama_serve._insert_rows`` over
the dense plane's 5-D leaves is held to the formulation it replaced, kept
only here (a one-hot projection that spreads the group over every slot,
a slice of the cache's first ``bucket`` positions, a select, an update of
that whole prefix): it multiplied by exactly 1 and added exact zeros, so
the caches are equal bit for bit, and so are the tokens of every engine
that shares the insert.  For the chip, with no chip: the prefill programs
of the dense serve cells are compiled for a described v5e at their real
widths and their scratch and their optimised HLO are looked at (what a
launch costs is ``PERF.md``'s business, not a test's).
"""

import os
import re

import numpy as np
import pytest

import family

os.environ.setdefault("TPU_LOG_DIR", "disabled")

SLOTS, MAX_LEN = 10, 32


# ------------------------------------------------ the reference, test-local
def _onehot_insert(pool, new, slots):
    """``llama.insert_prefill`` as it was before PR 33, one leaf of it."""
    import jax
    import jax.numpy as jnp

    B, P = pool.shape[1], new.shape[2]
    onehot = slots[:, None] == jnp.arange(B, dtype=jnp.int32)[None, :]
    written = onehot.any(axis=0)[None, :, None, None, None]
    spread = jnp.einsum("gb,lgphd->lbphd", onehot.astype(pool.dtype),
                        new.astype(pool.dtype))
    cur = jax.lax.slice_in_dim(pool, 0, P, axis=2)
    return jax.lax.dynamic_update_slice_in_dim(
        pool, jnp.where(written, spread, cur), 0, axis=2)


def _normal(seed, shape, dtype):
    """Something in every row, made on the host: ``jax.random.normal``
    is a program a shape, and a filler's shape is nothing a test reads."""
    import jax.numpy as jnp

    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        shape, np.float32).astype(dtype))


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint16) if x.dtype.itemsize == 2 else x


def _assert_same_bits(got, want):
    import jax

    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                    strict=True):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(_bits(g), _bits(w))


def _slots(group, padding):
    """Distinct slots for a group of ``group`` members, out of order, with
    the padding of a rung (-1) where ``padding`` says."""
    slots = [4, 1, 9, 0, 7, 2, 8, 5][:group]
    if padding == "first":
        slots[0] = -1
    elif padding == "last":
        slots[-1] = -1
    elif padding == "most":
        slots[1:] = [-1] * (group - 1)
    return slots


# ------------------------------------------------------- the insert alone
@pytest.mark.parametrize("padding", ["none", "first", "last", "most"])
@pytest.mark.parametrize("kv_heads", [2, 4], ids=["gqa", "mha"])
@pytest.mark.parametrize("bucket", [16, MAX_LEN], ids=["short", "max_len"])
@pytest.mark.parametrize("group", [1, 2, 4, 8])
def test_rows_land_where_the_one_hot_insert_put_them(group, bucket,
                                                     kv_heads, padding):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama_serve

    layers, d = 3, 16
    seed = group * 100 + bucket
    # something in every row, so that a stray write shows
    pool = _normal(seed, (layers, SLOTS, MAX_LEN, kv_heads, d), jnp.bfloat16)
    new = _normal(seed + 1, (layers, group, bucket, kv_heads, d),
                  jnp.bfloat16)
    slots = _slots(group, padding)
    got = jax.jit(llama_serve._insert_rows)(
        pool, new, jnp.asarray(slots, jnp.int32))
    want = jax.jit(_onehot_insert)(pool, new, jnp.asarray(slots, jnp.int32))
    _assert_same_bits(got, want)

    # What the reference implies, said outright.
    written = {slot: g for g, slot in enumerate(slots) if slot >= 0}
    assert len(written) == sum(slot >= 0 for slot in slots)
    for slot in range(SLOTS):
        if slot in written:
            np.testing.assert_array_equal(
                _bits(got[:, slot, :bucket]),
                _bits(new[:, written[slot]]))
            np.testing.assert_array_equal(_bits(got[:, slot, bucket:]),
                                          _bits(pool[:, slot, bucket:]))
        else:
            np.testing.assert_array_equal(_bits(got[:, slot]),
                                          _bits(pool[:, slot]))


@pytest.mark.parametrize("group,padding", [(1, "none"), (2, "first"),
                                           (4, "last"), (8, "most")])
def test_a_pool_stored_as_rows_takes_the_same_rows(group, padding):
    """A windowed model's pools are ``(layers, B, positions x Hkv, D)``:
    the same function, the same shape rule, the same reference (applied
    to the pool seen by position, which on the CPU is only a view)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama_serve

    layers, kv_heads, d, bucket = 3, 4, 16, 16
    by_position = (layers, SLOTS, MAX_LEN, kv_heads, d)
    pool = _normal(group, by_position, jnp.float32)
    new = _normal(group + 10, (layers, group, bucket, kv_heads, d),
                  jnp.float32)
    slots = jnp.asarray(_slots(group, padding), jnp.int32)
    got = jax.jit(llama_serve._insert_rows)(
        pool.reshape(layers, SLOTS, MAX_LEN * kv_heads, d), new, slots)
    assert got.shape == (layers, SLOTS, MAX_LEN * kv_heads, d)
    np.testing.assert_array_equal(
        np.asarray(got.reshape(by_position)),
        np.asarray(jax.jit(_onehot_insert)(pool, new, slots)))


# ------------------------------------------------- the programs that use it
def _random_like(tree, seed):
    import jax

    leaves, treedef = jax.tree.flatten(tree)
    return treedef.unflatten([_normal(seed + i, x.shape, x.dtype)
                              for i, x in enumerate(leaves)])


@pytest.mark.parametrize("group,padding", [(1, "none"), (2, "first"),
                                           (4, "last"), (8, "most")])
@pytest.mark.parametrize("preset,kw", [
    ("debug", {}),                                        # GQA 4 / 2
    ("moe_debug", dict(n_kv_heads=4, tie_embeddings=False)),    # MHA
    ("hybrid_debug", {}),               # + ``insert_states`` beside it
    ("draft", {}),                      # ``build_draft_prefill``
])
def test_prefill_programs_leave_the_cache_the_one_hot_insert_left(
        preset, kw, group, padding, monkeypatch):
    """``build_prefill`` and ``build_draft_prefill`` on a cache with
    something in every row: the whole tree afterwards -- K, V and a
    hybrid's recurrent and conv states -- and the first tokens are what
    the same program gives with the parent's insert in it."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama_serve
    from ray_tpu.models.llama import LlamaConfig

    draft = preset == "draft"
    cfg = getattr(LlamaConfig, "debug" if draft else preset)(**kw)
    build = (llama_serve.build_draft_prefill if draft
             else llama_serve.build_prefill)
    params = family.init_params(jax.random.key(1), cfg, cfg.dtype)
    before = _random_like(llama_serve.init_cache(cfg, SLOTS, MAX_LEN), 2)
    bucket = 16
    rng = np.random.default_rng(group)
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, (group, bucket)),
                         jnp.int32)
    lengths = jnp.asarray(rng.integers(1, bucket + 1, group), jnp.int32)
    slots = jnp.asarray(_slots(group, padding), jnp.int32)

    got = build(cfg)(params, jax.tree.map(jnp.copy, before), tokens,
                     lengths, slots)
    with monkeypatch.context() as patched:
        patched.setattr(llama_serve, "_insert_rows", _onehot_insert)
        want = build(cfg)(params, jax.tree.map(jnp.copy, before), tokens,
                          lengths, slots)
    _assert_same_bits(got, want)

    cache = got if draft else got[0]
    assert set(cache) == set(before)
    untouched = [s for s in range(SLOTS) if s not in set(slots.tolist())]
    for name, leaf in cache.items():
        slot_axis = 2 if name == "conv" else 1
        np.testing.assert_array_equal(
            _bits(jnp.take(leaf, jnp.asarray(untouched), axis=slot_axis)),
            _bits(jnp.take(before[name], jnp.asarray(untouched),
                           axis=slot_axis)))
        if name in ("k", "v"):
            np.testing.assert_array_equal(_bits(leaf[:, :, bucket:]),
                                          _bits(before[name][:, :, bucket:]))
            wrote = [s for s in slots.tolist() if s >= 0]
            assert (_bits(leaf[:, wrote, :bucket])
                    != _bits(before[name][:, wrote, :bucket])).any()


# ----------------------------------------------------- through ``LLMServer``
_PLANES = {
    "dense": ("debug", {}),
    "experts": ("moe_debug", {}),
    "hybrid": ("hybrid_debug", {}),
    "speculative": ("debug", dict(paged=True, block_size=8, spec_k=2,
                                  draft_layers=1)),
}


def _requests(seed, count=14):
    rng = np.random.default_rng(seed)
    return [{"prompt": rng.integers(1, 256, int(rng.integers(2, 65))
                                    ).tolist(),
             "max_new_tokens": int(rng.integers(2, 9))}
            for _ in range(count)]


def _generate(server, requests):
    return [r["tokens"] for r in family.generate(server, requests)]


@pytest.mark.parametrize("plane", sorted(_PLANES))
def test_engine_tokens_are_the_one_hot_inserts(plane, monkeypatch):
    """The same seeded requests through an engine as it is and through
    one whose programs were traced with the parent's insert: the same
    tokens, on every plane whose prefill shares the insert (the paged
    target of a speculative engine scatters blocks; its DRAFT's dense
    cache takes the insert).  The waves hold groups of several rows and
    rungs with padding members."""
    from ray_tpu.models import llama_serve
    from ray_tpu.observability import timeline
    from ray_tpu.serve import llm

    preset, args = _PLANES[plane]

    def tokens():
        server = llm.LLMServer(
            model_preset=preset, max_slots=8, max_len=96,
            prefill_buckets=(16, 32, 64), decode_chunk=4, warmup=False,
            **args)
        try:
            return [_generate(server, _requests(seed)) for seed in (5, 6)]
        finally:
            server.shutdown()

    timeline.clear()
    got = tokens()
    groups = [e["args"] for e in timeline.export_timeline()
              if e.get("ph") == "X" and e["name"] == "serve.prefill_group"]
    assert any(g["rows_padded"] > 1 for g in groups)
    assert any(g["rows"] < g["rows_padded"] for g in groups)
    with monkeypatch.context() as patched:
        patched.setattr(llama_serve, "_insert_rows", _onehot_insert)
        want = tokens()
    assert got == want
    assert all(len(t) == r["max_new_tokens"]
               for t, r in zip(got[0], _requests(5)))


# ------------------------------------------- the real widths, for the chip
# ``topo`` is described inside that file's fixture (never at import), as
# ``benchmarks/tests/test_olmoe_cell.py`` takes it; its autouse
# ``compiled_kernels`` is NOT taken: the toy engines above run their
# kernels interpreted.
from benchmarks.tests.test_aot_real_widths import one_chip, topo  # noqa: E402,F401


@pytest.fixture
def compiled_for_the_chip(monkeypatch):
    """The backend here is the CPU but the target is the chip: kernels
    are steered to Mosaic as ``benchmarks/tests/test_aot_real_widths.py``
    steers them, and a compile for a described chip is kept out of the
    persistent cache, which cannot read it back without one."""
    import importlib

    import jax

    flash = importlib.import_module("ray_tpu.ops.flash_attention")
    monkeypatch.setattr(flash, "_use_interpret", lambda: False)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)


_VIEWS = ("parameter", "get-tuple-element", "tuple", "bitcast")


def _made_with_dims(hlo, shapes):
    """(instruction, opcode, root opcode of the fusion it calls) of every
    instruction of an optimised HLO module -- entry, fused computations
    and loop bodies alike -- that MAKES an array whose dims are among
    ``shapes`` (a view of one makes nothing)."""
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
    roots = {}
    for comp, lines in comps.items():
        for line in lines:
            root = re.match(r"\s*ROOT %\S+ = .*? ([\w-]+)\(", line)
            if root:
                roots[comp] = root.group(1)
    out = []
    for lines in comps.values():
        for line in lines:
            m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*?) ([\w-]+)\(", line)
            if not m or m.group(3) in _VIEWS:
                continue
            inst, result, opcode = m.groups()
            dims = {tuple(int(d) for d in dims.split(",") if d)
                    for _dtype, dims in re.findall(r"(\w+)\[([\d,]*)\]",
                                                   result)}
            if dims & set(shapes):
                called = re.search(r"calls=%([^,\s)]+)", line)
                out.append((inst, opcode,
                            roots.get(called.group(1), "") if called
                            else ""))
    return out


# Scratch of the compiled prefill programs, GB (AOT for a described v5e):
# the parent's, read while PR 33 was written, and the limit held here.
# The change's own: 0.000 / 0.05 / 0.22 and 0.10 / 0.94 / 1.98.
_SCRATCH = {
    "internlm2-1.8b.serve-batch-decode": {       # 120 slots x 512
        1: (0.013, 0.0135), 4: (3.22, 0.5), 8: (3.42, 0.5)},
    "internlm2-1.8b.serve-chat-busy": {          # 40 slots x 1,280
        1: (0.202, 0.2025), 4: (4.83, 1.2), 8: (5.64, 2.4)},
}


@pytest.mark.parametrize("cell", sorted(_SCRATCH))
def test_prefill_at_real_widths_makes_nothing_of_the_caches_shape(
        one_chip, compiled_for_the_chip, cell):
    from benchmarks.tests.test_aot_real_widths import (_engine_programs,
                                                       _json)

    engine = _json("workloads", cell)["engine"]
    slots, max_len = engine["max_slots"], engine["max_len"]
    bucket = engine["prefill_buckets"][-1]
    layers, kv_heads, head_dim = 24, 8, 128           # internlm2-1.8b
    forbidden = [(layers, slots, positions, kv_heads, head_dim)
                 for positions in (max_len, bucket)]
    programs = dict(_engine_programs(cell, one_chip))
    for group, (_before, limit) in _SCRATCH[cell].items():
        compiled = programs[f"prefill group={group} bucket={bucket}"]()
        # (a) no copy of the cache's first ``bucket`` positions, whatever
        # the group: the scratch left is the forward pass's own
        scratch = compiled.memory_analysis().temp_size_in_bytes
        assert scratch < limit * 1e9, (group, scratch)
        # (b) what has the cache's or its prefix's shape is the result
        # of an update in place: a row written per member into K and
        # into V.  No slice, copy, select, dot or other fusion makes one.
        made = _made_with_dims(compiled.as_text(), forbidden)
        updates = [m for m in made if m[1] == "dynamic-update-slice"]
        assert len(updates) == 2 * group, made
        for inst, opcode, root in made:
            assert "dynamic-update-slice" in (opcode, root), (group, inst,
                                                              opcode, root)


def test_cell_4s_engine_compiles_at_twice_its_slots(one_chip,
                                                    compiled_for_the_chip):
    """80 slots x 1,280: every program the engine warms compiles, where
    the 4-row and the 8-row prefill were refused beside the copy ("Used
    20.77G of 15.75G", "21.14G"; AOT, PR 33, the parent).  The cell file
    keeps 40 until a ``benchmark`` PR re-cuts it."""
    from benchmarks.tests.test_aot_real_widths import _engine_programs

    labels = []
    for label, compile_it in _engine_programs(
            "internlm2-1.8b.serve-chat-busy", one_chip, max_slots=80):
        compile_it()       # the compiler raises RESOURCE_EXHAUSTED if not
        labels.append(label)
    assert {"prefill group=4 bucket=1024",
            "prefill group=8 bucket=1024"} <= set(labels)


# OLMoE's copy was most of its scratch: label -> (the parent's, the limit
# held), GB.
_OLMOE = {"prefill group=4 bucket=256": (2.149, 0.25),
          "prefill group=8 bucket=256": (2.283, 0.6)}
# Granite's K/V is 0.34 GB of four layers, so its insert never was what
# its scratch held; the yardstick is the SAME program with no insert in
# it at all, compiled beside it: 0.833 / 1.733 / 1.762 GB against 0.808 /
# 1.717 / 1.711 with the insert (AOT, PR 33).  The parent's 0.751 / 1.650
# / 1.544 lay under both by what its one-hot insert let XLA's scheduler
# reorder in the forward pass, not by anything an insert needs: cell 6's
# reserved HBM on the chip is the parent's (PERF.md section 6, PR 33).
_GRANITE = ("prefill 4x256", "prefill 8x64", "prefill 8x256")


def test_multi_row_scratch_of_the_expert_engine(one_chip,
                                                compiled_for_the_chip):
    from benchmarks.tests.test_aot_real_widths import _engine_programs
    from benchmarks.tests.test_olmoe_cell import CELL

    programs = dict(_engine_programs(CELL, one_chip))
    for label, (_before, limit) in _OLMOE.items():
        scratch = programs[label]().memory_analysis().temp_size_in_bytes
        assert scratch < limit * 1e9, (label, scratch)


@pytest.mark.parametrize("label", _GRANITE)
def test_the_hybrid_engines_insert_adds_no_scratch(
        one_chip, compiled_for_the_chip, monkeypatch, label):
    """A multi-row prefill of cell 6's engine takes the scratch of the
    program with no insert in it, or less (a hundredth of room: how
    tightly the scheduler packs either differs by shape)."""
    from benchmarks.tests.test_granite_cell import _engine_programs
    from ray_tpu.models import llama_serve

    scratch = dict(_engine_programs(one_chip))[
        label]().memory_analysis().temp_size_in_bytes
    # a new ``build_prefill`` each: nothing traced above is found again
    monkeypatch.setattr(llama_serve, "_insert_rows",
                        lambda pool, new, slots: pool)
    bare = dict(_engine_programs(one_chip))[
        label]().memory_analysis().temp_size_in_bytes
    assert scratch <= 1.01 * bare, (label, scratch, bare)
    assert scratch < 1.75e9        # and under 1.75 GB whichever way


@pytest.mark.parametrize("tool,argv", [
    ("tools.prefill_times", ["internlm2-1.8b.serve-batch-decode"]),
    ("tools.flash_sweep", [])])
def test_the_timing_tools_refuse_anything_but_the_chip(tool, argv):
    """A time is the chip's or it is none: off a TPU the kernels run
    interpreted, so the tools exit before they print a column."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    run = subprocess.run(
        [sys.executable, "-m", tool, *argv], cwd=root, text=True,
        capture_output=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert run.returncode != 0
    assert "tpu only" in run.stderr
    assert run.stdout == ""
