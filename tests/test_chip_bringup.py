"""What the chip bring-up (ISSUE 21) fixed, held on the CPU: nothing on
the main path hides the device, nothing takes the chip unasked, the
compile cache can be placed from outside, and ``chip_smoke.py``'s
phases pass at ``debug()`` size."""

import importlib
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_argv, env=None, cwd=None, timeout=120):
    argv = ([sys.executable, "-c", code_or_argv]
            if isinstance(code_or_argv, str) else code_or_argv)
    return subprocess.run(argv, capture_output=True, text=True,
                          timeout=timeout, cwd=cwd,
                          env={**os.environ, "PYTHONPATH": REPO,
                               **(env or {})})


# ----------------------------------------------------- compile cache
_PLACE = ("from ray_tpu.compile_cache import place_compile_cache; "
          "print(place_compile_cache())")


def test_compile_cache_left_alone_when_placed_from_outside(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the helper sets nothing — jax's
    own config already holds the variable's value, and stays there."""
    placed = str(tmp_path / "cache")
    proc = _run(
        "import os, jax\n"
        "from ray_tpu.compile_cache import place_compile_cache\n"
        "seen = []\n"
        "real = jax.config.update\n"
        "jax.config.update = lambda *a: (seen.append(a), real(*a))\n"
        "print(place_compile_cache())\n"
        "assert not seen, seen\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "print(os.environ['JAX_COMPILATION_CACHE_DIR'])\n",
        env={"JAX_COMPILATION_CACHE_DIR": placed, "JAX_PLATFORMS": "tpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [placed] * 3


def test_compile_cache_default_is_one_in_checkout_path(tmp_path):
    """Unset: the same in-checkout directory from different processes
    and working directories, exported so children inherit it."""
    env = {"JAX_COMPILATION_CACHE_DIR": "", "JAX_PLATFORMS": "tpu,cpu"}
    outs = []
    for cwd in (str(tmp_path), REPO):
        proc = _run(
            _PLACE + "; import os; "
            "print(os.environ['JAX_COMPILATION_CACHE_DIR'])",
            env=env, cwd=cwd)
        assert proc.returncode == 0, proc.stderr[-2000:]
        outs.append(proc.stdout.split())
    expected = os.path.join(REPO, ".jax_cache")
    assert outs == [[expected, expected]] * 2
    ignored = subprocess.run(["git", "check-ignore", "-q", ".jax_cache/x"],
                             cwd=REPO)
    assert ignored.returncode == 0, ".jax_cache must be git-ignored"


def test_compile_cache_not_placed_in_a_cpu_held_process():
    proc = _run(_PLACE, env={"JAX_COMPILATION_CACHE_DIR": "",
                             "JAX_PLATFORMS": "cpu"})
    assert proc.stdout.split() == ["None"], proc.stderr[-2000:]


# ------------------------------------------------ one process per chip
def test_worker_process_forced_to_cpu_overrides_inherited_platform(
        monkeypatch):
    """The chip machine's environment names a platform; a setdefault
    there sent every Cluster child after the chip its parent holds."""
    from ray_tpu.core import node

    seen = {}

    def fake_popen(cmd, env=None, **_kw):
        seen.update(env)
        return object()

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(node.subprocess, "Popen", fake_popen)
    node.start_worker_process("127.0.0.1:1", force_cpu_platform=True)
    assert seen["JAX_PLATFORMS"] == "cpu"
    node.start_worker_process("127.0.0.1:1", force_cpu_platform=False)
    assert seen["JAX_PLATFORMS"] == "tpu"


def test_chip_count_needs_no_jax(monkeypatch):
    import glob

    from ray_tpu.core import resources

    def fake_glob(pattern):
        return {"/dev/accel[0-9]*": [],
                "/dev/vfio/[0-9]*": ["/dev/vfio/0", "/dev/vfio/1"]}[pattern]

    monkeypatch.setattr(glob, "glob", fake_glob)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    assert resources.detect_node_resources(num_cpus=1)["TPU"] == 2.0
    # A process held to the CPU advertises none, whatever the host has.
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert "TPU" not in resources.detect_node_resources(num_cpus=1)


def test_dryrun_multichip_decides_from_the_environment(monkeypatch):
    import __graft_entry__ as graft

    flag = "--xla_force_host_platform_device_count"
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", f"--foo {flag}=8")
    assert graft._env_is_cpu_dryrun(8) and graft._env_is_cpu_dryrun(4)
    assert not graft._env_is_cpu_dryrun(16)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    assert not graft._env_is_cpu_dryrun(4)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", "")
    assert not graft._env_is_cpu_dryrun(2)


# ------------------------------------------------- no silent fallback
def test_interpret_mode_is_decided_by_inclusion(monkeypatch):
    import jax

    # (ray_tpu.ops re-exports the function under the module's name.)
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    assert fa._use_interpret() is True  # this suite runs on cpu
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert fa._use_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        fa._use_interpret()


def test_untileable_shape_raises_on_tpu_instead_of_einsum(monkeypatch):
    import jax.numpy as jnp

    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    q = jnp.ones((1, 100, 2, 128), jnp.bfloat16)
    # Interpreted (cpu): any shape tiles.
    assert fa.flash_attention(q, q, q, causal=False).shape == q.shape
    monkeypatch.setattr(fa, "_use_interpret", lambda: False)
    with pytest.raises(ValueError, match="cannot tile"):
        fa.flash_attention(q, q, q, causal=False)
    assert not hasattr(fa, "_einsum_fallback")


# ------------------------------------------------------- chip_smoke.py
def test_chip_smoke_exits_nonzero_naming_a_cpu_platform():
    proc = _run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                env={"JAX_PLATFORMS": "cpu"}, cwd=REPO)
    assert proc.returncode != 0
    assert "platform is 'cpu', not 'tpu'" in proc.stdout
    assert '"ok"' not in proc.stdout


def test_mosaic_calls_reads_result_shapes():
    import chip_smoke

    hlo = (
        '  %custom-call.1 = (bf16[2,8,2048,128]{3,2,1,0:T(8,128)(2,1)}, '
        'f32[2,8,2048,1]{3,2,1,0}) custom-call(%a, %b, %c), '
        'custom_call_target="tpu_custom_call", backend_config={...}\n'
        '  %custom-call.2 = f32[2,8,2048,128]{3,2,1,0} custom-call(%a), '
        'custom_call_target="tpu_custom_call"\n'
        '  %custom-call.3 = f32[2,8,2048,128]{3,2,1,0} custom-call(%d), '
        'custom_call_target="tpu_custom_call"\n'
        '  %cc = f32[4]{0} custom-call(%a), custom_call_target="Sharding"\n')
    assert chip_smoke._mosaic_calls(hlo) == [
        "bf16[2,8,2048,128] f32[2,8,2048,1]", "f32[2,8,2048,128]"]


_FLASH = dict(attention_impl="flash", remat=True, remat_policy="attn")


@pytest.fixture
def clean_runtime():
    import ray_tpu

    ray_tpu.shutdown()
    yield
    ray_tpu.shutdown()


@pytest.mark.parametrize("mesh", [None, "fsdp4_tensor2"])
def test_chip_smoke_train_phase_at_debug_size(clean_runtime, mesh):
    """JaxTrainer → data pipeline → fused step with the flash kernel
    (interpreted here), one device and the 8-device virtual mesh; under
    the mesh the state is born sharded, batches land split over the
    batch axes, and the step keeps both."""
    import chip_smoke
    from ray_tpu.parallel import MeshSpec

    spec = MeshSpec(fsdp=4, tensor=2) if mesh else None
    out = chip_smoke.train_phase(
        preset="debug", batch=8, seq=128, warmup=1, steps=2,
        cfg_overrides=_FLASH, mesh=spec)
    assert len(out["losses"]) == 3
    if mesh:
        assert "fsdp" in out["batch_sharding"]


@pytest.mark.parametrize("paged", [False, True])
def test_chip_smoke_serve_phase_at_debug_size(clean_runtime, paged):
    import chip_smoke

    engine = dict(model_preset="debug", max_slots=4, max_len=64,
                  prefill_buckets=(16,), decode_chunk=8,
                  prefill_groups=(4,))
    if paged:
        engine.update(block_size=8, num_blocks=33)
    out = chip_smoke.serve_phase(paged=paged, engine=engine,
                                 n_concurrent=3, prompt_len=8,
                                 max_new_tokens=8)
    assert out["requests"] == 4


def test_chip_smoke_serve_hybrid_phase(clean_runtime):
    """``serve_hybrid``: the toy hybrid (a recurrent state beside the K/V
    cache) through ``serve.run`` on the dense plane, as the phase runs it
    on the chip but with fewer slots."""
    import chip_smoke

    engine = dict(chip_smoke.HYBRID_ENGINE, max_slots=4, max_len=64,
                  prefill_buckets=(16,), decode_chunk=8,
                  prefill_groups=(4,))
    out = chip_smoke.serve_phase(paged=False, engine=engine,
                                 n_concurrent=3, prompt_len=8,
                                 max_new_tokens=8)
    assert out["requests"] == 4
