"""Test fixtures.

Parallelism tests run on a simulated 8-device CPU mesh
(XLA_FLAGS=--xla_force_host_platform_device_count=8), mirroring the
reference's in-process multi-node simulation strategy
(SURVEY.md §4.3 ray_start_cluster / cluster_utils.Cluster).
"""

import atexit
import os
import shutil
import tempfile

# Must be set before the CPU backend initializes.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags += " --xla_force_host_platform_device_count=8"
os.environ["XLA_FLAGS"] = _flags.strip()

# A program is compiled once a RUN.  Three quarters of a model file's CPU
# is XLA compiling toys, and many of them are one program met again: in
# another test, another worker, a subprocess (``benchmarks/run.py`` in
# the cell rehearsals).  The first process of a run -- the xdist
# controller, or the one process of a run without xdist -- makes a
# directory for jax's persistent compilation cache and names it in the
# environment before jax is imported; the workers and every subprocess the
# suite starts inherit the variable and read it as jax does.  The
# directory is removed when that first process ends, so a run never reads
# what an earlier run wrote and its time does not depend on the run
# before.  An environment that already names a cache is left alone.  A
# test that asserts a compile happened takes a directory of its own
# (``own_compile_cache`` below).
_RUN_CACHE = None
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _RUN_CACHE = tempfile.mkdtemp(prefix="ray_tpu_tests_jax_cache_")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _RUN_CACHE
    # keep every program however small or quick: a toy's are all both
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    # removed when this process ends, however it ends short of a signal
    # that kills it
    atexit.register(shutil.rmtree, _RUN_CACHE, ignore_errors=True)

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _worker_nodes_keep_xla_quiet():
    """XLA:CPU writes two ERROR lines about machine features for EVERY
    executable it loads from a cache (``cpu_aot_loader.cc``; the machine
    that compiled is this one).  A worker NODE writes them into a pipe
    nobody reads (``core/node.start_worker_process``: ROADMAP D10c), and
    after 64 KB of them it blocks for good.  So the nodes the tests start
    -- and only they: a test's own process keeps its diagnostics -- are
    given ``TF_CPP_MIN_LOG_LEVEL=3`` unless the caller's ``env`` says
    otherwise.  Goes when the pipe is drained."""
    from ray_tpu.core import node

    start = node.start_worker_process

    def quiet(head_address, *, env=None, **kw):
        return start(head_address,
                     env={"TF_CPP_MIN_LOG_LEVEL": "3", **(env or {})}, **kw)

    node.start_worker_process = quiet
    yield
    node.start_worker_process = start

# Hard per-test hang guard for fault-injection tests: the failure mode
# under test IS the hang (wedged ring readers), so a chaos-marked test
# that exceeds this budget must die loudly instead of stalling the
# whole tier-1 run.  SIGALRM fires in the main thread regardless of
# what worker threads are blocked on.
CHAOS_HARD_TIMEOUT_S = int(os.environ.get(
    "RAY_TPU_CHAOS_TEST_TIMEOUT_S", "180"))


class ChaosHangGuardTimeout(BaseException):
    """BaseException on purpose: the framework's retry loops catch
    (ConnectionError, TimeoutError) — an Exception-typed guard fired
    inside one of those try blocks would be swallowed as a routine
    retry, and SIGALRM is one-shot."""


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    """Where the run's time went, on every run: the ten slowest tests,
    the ten files with the most test-seconds, and the total -- so that
    every PR's log says what it added to a suite whose time limit is its
    guard.  A report, never a check: a time assertion would flap with the
    box."""
    rows, by_file = [], {}
    for key in ("passed", "failed"):
        for rep in terminalreporter.stats.get(key, ()):
            if getattr(rep, "when", "") == "call":
                rows.append((rep.duration, rep.nodeid))
                path = rep.nodeid.split("::", 1)[0]
                seconds, cases = by_file.get(path, (0.0, 0))
                by_file[path] = (seconds + rep.duration, cases + 1)
    if not rows:
        return
    rows.sort(reverse=True)
    terminalreporter.write_sep("-", "slowest 10 tests")
    for duration, nodeid in rows[:10]:
        terminalreporter.write_line(f"{duration:8.2f}s  {nodeid}")
    total = sum(seconds for seconds, _ in by_file.values())
    terminalreporter.write_sep(
        "-", f"10 files with the most test-seconds of {total:.0f} "
             f"in {len(rows)} tests")
    slowest = sorted(by_file.items(), key=lambda kv: kv[1], reverse=True)
    for path, (seconds, cases) in slowest[:10]:
        terminalreporter.write_line(
            f"{seconds:8.2f}s  {cases:4d} tests  {path}")


def pytest_collection_modifyitems(config, items):
    # ``stress`` implies ``slow``: the virtual-cluster soaks run
    # hundreds of simulated nodes for tens of seconds — tier-1
    # (-m 'not slow') must skip them without every soak needing two
    # markers by hand.
    for item in items:
        if item.get_closest_marker("stress") is not None:
            item.add_marker(pytest.mark.slow)


@pytest.fixture(autouse=True)
def _chaos_hang_guard(request):
    # overload, net, and stress tests share the guard: their failure
    # mode is ALSO a hang (a shed point that never fires leaves
    # waiters queued forever under sustained load; a wedged collective
    # ring blocks every member on a recv that never lands; a vcluster
    # soak whose head never recovers blocks every load thread).
    # tsdb cluster tests poll shipped history with bounded deadlines;
    # the guard catches the same failure mode (a wedged flush/standby
    # pump blocking the poll loop forever).
    # postmortem tests kill -9 real worker subprocesses and then wait
    # on supervisor-shipped reports: their failure mode is the same
    # wait-forever hang.
    if request.node.get_closest_marker("chaos") is None and \
            request.node.get_closest_marker("overload") is None and \
            request.node.get_closest_marker("net") is None and \
            request.node.get_closest_marker("tsdb") is None and \
            request.node.get_closest_marker("device") is None and \
            request.node.get_closest_marker("postmortem") is None and \
            request.node.get_closest_marker("stress") is None:
        yield
        return
    import signal

    def _on_alarm(_signum, _frame):
        raise ChaosHangGuardTimeout(
            f"chaos test exceeded its {CHAOS_HARD_TIMEOUT_S}s hard "
            f"timeout (hang guard) — a recovery path is wedged")

    old = signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(CHAOS_HARD_TIMEOUT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


_BOX_FACTOR = None


def box_speed_factor() -> float:
    """Measured per-run capacity probe for the box-speed-sensitive
    tests (disagg flat-TTFT soak, dag perf comparison, vcluster
    smoke): one small single-thread compute loop plus a burst of
    thread round-trips, compared against the reference fast box.
    Returns >= 1.0 (1.0 = reference speed or better, clamped at 8x);
    perf-sensitive bars SCALE their absolute constants by it so a
    loaded 1-core CI container passes the same assertions a fast box
    does, instead of each test carrying hand-tuned slack.

    Measured once per pytest run (module cache): probing inside each
    test would itself be load-sensitive noise."""
    global _BOX_FACTOR
    if _BOX_FACTOR is None:
        import threading
        import time

        import numpy as np

        best = float("inf")
        for _ in range(2):  # best-of-2: absorb one scheduling hiccup
            a = np.random.default_rng(0).standard_normal((256, 256))
            t0 = time.perf_counter()
            for _ in range(30):
                a = np.tanh(a @ a.T * 1e-3)
            for _ in range(100):
                ev = threading.Event()
                threading.Thread(target=ev.set).start()
                ev.wait()
            best = min(best, time.perf_counter() - t0)
        _BOX_FACTOR = min(8.0, max(1.0, best / 0.02))
    return _BOX_FACTOR


@pytest.fixture
def box_factor() -> float:
    return box_speed_factor()


@pytest.fixture
def ray_start_regular():
    """Fresh runtime per test (reference: conftest.py:463)."""
    import ray_tpu

    ray_tpu.shutdown()
    rt = ray_tpu.init(num_cpus=8, num_tpus=0)
    yield rt
    ray_tpu.shutdown()


@pytest.fixture
def traced():
    """Tracing on and an empty timeline, whatever ran before on this
    worker (a test that turned tracing off, another engine's spans);
    tracing is left as it was found."""
    from ray_tpu.observability import timeline, tracing

    was = tracing.enabled()
    tracing.enable()
    timeline.clear()
    yield timeline
    if not was:
        tracing.disable()


@pytest.fixture
def own_compile_cache(tmp_path):
    """An empty compilation cache for a test that asserts a COMPILE
    happened: in the run's shared one (top of this file) the program may
    be there already, written by another test or worker that compiled the
    same text, and jax then reports a fetch and no compile."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "jax"))
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


@pytest.fixture
def shutdown_only():
    import ray_tpu

    ray_tpu.shutdown()
    yield None
    ray_tpu.shutdown()


