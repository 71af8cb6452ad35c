"""Device-plane telemetry tests (observability/device.py, ISSUE 15):
HBM sampler (CPU live-arrays fallback), XLA compile tracking, the
recompile-storm default alert, device-trace artifact round-trip, and
the `ray_tpu top` / `status` device surfaces.

Acceptance (CPU backend): a 2-worker gang's HBM series answer
`last(ray_tpu_device_hbm_bytes_used) by (node_id)` with CLI/RPC/
dashboard parity; a forced-recompile loop fires (then clears) the
xla-recompile-storm default alert; a device-trace capture round-trips
through the head artifact store."""

import gzip
import io
import json
import subprocess
import sys
import threading
import time
import urllib.parse
import urllib.request
import zipfile

import pytest

import ray_tpu
from ray_tpu.observability import device as device_mod
from ray_tpu.observability import metrics as metrics_mod
from ray_tpu.observability import timeline as timeline_mod
from ray_tpu.observability import tsdb as tsdb_mod

pytestmark = pytest.mark.device


# ------------------------------------------------------------- sampler
class TestSampler:
    def test_cpu_fallback_attributes_live_arrays(self):
        import jax
        import jax.numpy as jnp

        dev = jax.local_devices()[1]
        arr = jax.device_put(jnp.ones((256, 256), jnp.float32), dev)
        arr.block_until_ready()
        samples = device_mod.sample_once()
        assert samples is not None
        by_dev = {s["device"]: s for s in samples}
        assert str(dev) in by_dev
        got = by_dev[str(dev)]
        assert got["used"] >= arr.nbytes
        assert got["live_buffers"] >= 1
        assert got["peak"] >= got["used"]
        # The gauges landed in the registry (this is what the
        # EventShipper snapshots onto the head TSDB).
        summ = metrics_mod.metrics_summary()
        assert summ["ray_tpu_device_hbm_bytes_used"][str(dev)] \
            >= arr.nbytes
        del arr

    def test_fallback_limit_env_drives_utilization(self, monkeypatch):
        import jax
        import jax.numpy as jnp

        monkeypatch.setattr(device_mod, "_FALLBACK_LIMIT", 1 << 20)
        dev = jax.local_devices()[2]
        arr = jax.device_put(jnp.ones((128, 128), jnp.float32), dev)
        arr.block_until_ready()
        device_mod.sample_once()
        summ = metrics_mod.metrics_summary()
        util = summ["ray_tpu_device_hbm_utilization"][str(dev)]
        assert util == pytest.approx(arr.nbytes / (1 << 20), rel=0.5)
        limit = summ["ray_tpu_device_hbm_bytes_limit"][str(dev)]
        assert limit == float(1 << 20)
        del arr

    def test_disable_no_ops_the_plane(self):
        device_mod.disable()
        try:
            assert device_mod.sample_once() is None
            ann = device_mod.annotation("x")
            assert ann is device_mod._NULL_CTX
        finally:
            device_mod.enable()

    def test_sampler_thread_install_idempotent(self):
        device_mod.install()
        first = device_mod._sampler_stop
        device_mod.install()
        assert device_mod._sampler_stop is first
        assert any(t.name == "device-sampler"
                   for t in threading.enumerate())


    def test_sampler_never_initialises_a_backend(self):
        """A Runtime process that imports jax but leaves it unused must
        stay backend-uninitialised (on the chip the sampler would
        otherwise take the TPU away from a child, and it broke
        jax.distributed.initialize); once the PROGRAM brings a backend
        up, the gauges appear.  Fresh process: this one's backend is
        long initialised."""
        code = """
import time
import ray_tpu
ray_tpu.init(num_cpus=1, num_tpus=0)
import jax
from jax._src import xla_bridge
from ray_tpu.observability import metrics
time.sleep(0.3)  # six sampler periods
assert not xla_bridge.backends_are_initialized(), "sampler took the backend"
assert not metrics.metrics_summary().get("ray_tpu_device_hbm_bytes_used")
x = jax.numpy.ones((64, 64)).block_until_ready()  # the program's own init
deadline = time.monotonic() + 10
while not metrics.metrics_summary().get("ray_tpu_device_hbm_bytes_used"):
    assert time.monotonic() < deadline, "no gauges after backend init"
    time.sleep(0.05)
ray_tpu.shutdown()
print("ok")
"""
        import os

        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=120,
            env={**os.environ, "RAY_TPU_DEVICE_SAMPLE_S": "0.05"})
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert proc.stdout.strip().endswith("ok")


# ---------------------------------------------------- compile tracking
class TestCompileTracking:
    def test_forced_recompiles_count_and_span(self):
        import jax
        import jax.numpy as jnp

        device_mod.sample_once()  # installs the listener
        before = metrics_mod.metrics_summary().get(
            "ray_tpu_xla_compiles_total", {}).get(
            "backend_compile", 0.0)
        n = 3
        for i in range(n):
            # Fresh lambda + fresh shape per round: every call is a
            # guaranteed new compile.
            jax.jit(lambda v, i=i: v * (i + 2))(
                jnp.ones(i + 3)).block_until_ready()
        after = metrics_mod.metrics_summary()[
            "ray_tpu_xla_compiles_total"]["backend_compile"]
        assert after - before >= n
        spans = [e for e in timeline_mod.export_timeline(None)
                 if e["name"] == "xla_compile"]
        assert len(spans) >= n
        assert spans[-1]["dur"] > 0
        assert spans[-1]["tid"] == "xla-compile"

    def test_compile_span_carries_ambient_trace_id(self):
        import jax
        import jax.numpy as jnp

        from ray_tpu.observability import tracing

        device_mod.sample_once()
        with tracing.span("test.compile") as sp:
            jax.jit(lambda v: v - 41.5)(
                jnp.ones(17)).block_until_ready()
            trace_id = sp.trace_id
        spans = [e for e in timeline_mod.export_timeline(None)
                 if e["name"] == "xla_compile"
                 and e.get("args", {}).get("trace_id") == trace_id]
        assert spans, "compile span did not inherit the ambient trace"

    def test_compile_histogram_observes_durations(self):
        import jax
        import jax.numpy as jnp

        device_mod.sample_once()
        jax.jit(lambda v: v + 13)(jnp.ones(23)).block_until_ready()
        hist = metrics_mod._registry["ray_tpu_xla_compile_seconds"]
        assert sum(hist.buckets()) >= 1


# ------------------------------------------------------- trace capture
class TestDeviceTrace:
    def test_capture_produces_loadable_zip_with_annotations(self):
        import jax.numpy as jnp

        from ray_tpu.observability import tracing

        stop = threading.Event()

        def work():
            while not stop.is_set():
                with tracing.span("devtrace.work"):
                    with device_mod.annotation("serve.decode_chunk"):
                        (jnp.ones((64, 64))
                         @ jnp.ones((64, 64))).block_until_ready()
                time.sleep(0.01)

        t = threading.Thread(target=work, daemon=True)
        t.start()
        try:
            art = device_mod.capture_device_trace(0.6)
        finally:
            stop.set()
            t.join()
        assert art["files"] >= 1 and len(art["data"]) > 0
        zf = zipfile.ZipFile(io.BytesIO(art["data"]))
        names = zf.namelist()
        assert any(n.endswith(".xplane.pb") for n in names)
        tj = [n for n in names if n.endswith("trace.json.gz")]
        assert tj, names
        body = gzip.decompress(zf.read(tj[0])).decode(
            errors="replace")
        # The hot-loop annotation — WITH its ambient trace id — shows
        # up in the device trace: that id is the correlation key back
        # into the cluster timeline.
        assert "serve.decode_chunk#trace=" in body


# ------------------------------------------------- model-plane gauges
class TestModelPlane:
    def test_record_train_step_sets_gauges(self):
        device_mod.record_train_step(8192, 0.5, n_params=1_000_000,
                                     device_kind="TPU v4")
        summ = metrics_mod.metrics_summary()
        assert summ["ray_tpu_train_tokens_per_s"][""] == \
            pytest.approx(16384.0)
        assert summ["ray_tpu_train_step_seconds"][""] == \
            pytest.approx(0.5)
        # v4 roofline: 16384 tok/s * 6e6 flop/tok / 275e12
        assert summ["ray_tpu_train_mfu"][""] == pytest.approx(
            16384.0 * 6e6 / 275e12)

    def test_record_train_step_skips_mfu_on_unknown_roofline(self):
        metrics_mod.reset_metrics()
        device_mod.record_train_step(100, 1.0, n_params=1000,
                                     device_kind="TFRT_CPU")
        summ = metrics_mod.metrics_summary()
        assert summ["ray_tpu_train_tokens_per_s"][""] == 100.0
        assert summ["ray_tpu_train_mfu"] == {}

    def test_program_ema_gauge(self):
        device_mod.record_program_ema("llm", "decode_chunk", 0.012)
        device_mod.record_program_ema("llm", "prefill", 0.034)
        summ = metrics_mod.metrics_summary()
        got = summ["ray_tpu_serve_program_seconds"]
        assert got["llm,decode_chunk"] == pytest.approx(0.012)
        assert got["llm,prefill"] == pytest.approx(0.034)

    def test_peak_table(self):
        assert device_mod.peak_bf16_flops("TPU v4") == 275e12
        assert device_mod.peak_bf16_flops("TPU v5e") == 197e12
        assert device_mod.peak_bf16_flops("TFRT_CPU_0") is None
        assert device_mod.peak_bf16_flops("cpu") is None
        # No guessed peak: a chip missing from the table is an error,
        # and bare "v5" no longer silently means v5p.
        for kind in ("TPU v9", "TPU v5"):
            with pytest.raises(ValueError, match="no bf16 peak"):
                device_mod.peak_bf16_flops(kind)


# ------------------------------------------------------- top rendering
class TestTopRender:
    def test_render_top_pure(self):
        from ray_tpu.scripts.cli import render_top

        snap = {
            "nodes": [
                {"node_id": "aaaa1111", "name": "worker-0",
                 "alive": True},
                {"node_id": "bbbb2222", "name": "", "alive": False},
            ],
            "actors": {"aaaa1111": 3},
            "hbm_used": {"aaaa1111": 2.5e9},
            "hbm_limit": {"aaaa1111": 16e9},
            "bufs": {"aaaa1111": 42.0},
            "xla": {"aaaa1111": 7.0},
            "occupancy": {},
            "qdepth": {"aaaa1111": 5.0},
            "train_tps": {},
        }
        out = render_top(snap)
        assert "NODE" in out and "HBM USED/LIMIT" in out
        assert "worker-0" in out and "bbbb2222" in out
        assert "2.50G/16.00G" in out
        assert "DEAD" in out and "ALIVE" in out
        assert "1/2 nodes alive" in out

    def test_render_top_empty_cluster(self):
        from ray_tpu.scripts.cli import render_top

        out = render_top({"nodes": [], "actors": {}, "hbm_used": {},
                          "hbm_limit": {}, "bufs": {}, "xla": {},
                          "occupancy": {}, "qdepth": {},
                          "train_tps": {}})
        assert "NODE" in out and "0/0 nodes alive" in out


# -------------------------------------------------- cluster acceptance
class TestClusterAcceptance:
    def test_two_worker_gang_hbm_series_all_surfaces(
            self, shutdown_only):
        """Acceptance: two worker processes hold device arrays; their
        samplers ship HBM gauges through the EventShipper into the
        head TSDB, and `last(ray_tpu_device_hbm_bytes_used)[60s] by
        (node_id)` answers for BOTH workers — identically via the
        RPC, the CLI (own operator process), and the dashboard.  The
        `status` and `top --once` device surfaces render the same
        series."""
        from ray_tpu.cluster.cluster_utils import Cluster
        from ray_tpu.dashboard import start_dashboard, stop_dashboard

        c = Cluster()
        env = {"RAY_TPU_EVENT_FLUSH_S": "0.2",
               "RAY_TPU_DEVICE_SAMPLE_S": "0.1"}
        c.add_node(num_cpus=2, resources={"d0": 10}, env=env)
        c.add_node(num_cpus=2, resources={"d1": 10}, env=env)
        rt = c.connect(num_cpus=2)
        expr = ("last(ray_tpu_device_hbm_bytes_used)[60s] "
                "by (node_id)")
        try:
            @ray_tpu.remote
            class DeviceHog:
                def __init__(self, mb: int):
                    import jax.numpy as jnp

                    self.block = jnp.ones((mb, 1 << 18),
                                          jnp.float32)  # mb MiB

                def nbytes(self):
                    return int(self.block.nbytes)

            hogs = [DeviceHog.options(resources={"d0": 1}).remote(4),
                    DeviceHog.options(resources={"d1": 1}).remote(4)]
            assert all(n == 4 << 20 for n in
                       ray_tpu.get([h.nbytes.remote() for h in hogs]))

            workers = {n["NodeID"] for n in ray_tpu.nodes()
                       if n["NodeID"] != rt.cluster.node_id}
            deadline = time.monotonic() + 40.0
            while True:
                out = tsdb_mod.query_cluster(rt.cluster, expr)
                got = {r["labels"].get("node_id"): r["value"]
                       for r in out["rows"]}
                if workers <= set(got) and all(
                        got[w] >= 4 << 20 for w in workers):
                    break
                assert time.monotonic() < deadline, \
                    f"hbm rows incomplete: {got} vs {workers}"
                time.sleep(0.3)

            # Dashboard route.
            dash = start_dashboard(port=0)
            try:
                url = (dash.url + "/api/metrics/query?q="
                       + urllib.parse.quote(expr))
                body = json.loads(urllib.request.urlopen(
                    url, timeout=15).read().decode())
                dash_nodes = {r["labels"].get("node_id")
                              for r in body["rows"]}
                assert workers <= dash_nodes
            finally:
                stop_dashboard()

            # CLI route (real operator process).
            proc = subprocess.run(
                [sys.executable, "-m", "ray_tpu", "metrics",
                 "query", expr, "--address", c.head_address,
                 "--json"],
                capture_output=True, text=True, timeout=60)
            assert proc.returncode == 0, proc.stderr
            cli_nodes = {r["labels"].get("node_id")
                         for r in json.loads(proc.stdout)["rows"]}
            assert workers <= cli_nodes

            # `status` grows the per-node device summary column...
            proc = subprocess.run(
                [sys.executable, "-m", "ray_tpu", "status",
                 "--address", c.head_address],
                capture_output=True, text=True, timeout=60)
            assert proc.returncode == 0, proc.stderr
            assert "device hbm" in proc.stdout
            assert "hbm " in proc.stdout
            # ... and `top --once` renders one frame with the same
            # numbers (non-interactive CI surface).
            proc = subprocess.run(
                [sys.executable, "-m", "ray_tpu", "top",
                 "--address", c.head_address, "--once"],
                capture_output=True, text=True, timeout=60)
            assert proc.returncode == 0, proc.stderr
            assert "HBM USED/LIMIT" in proc.stdout
            assert "nodes alive" in proc.stdout
            assert "4.2M" in proc.stdout or "M/" in proc.stdout \
                or "G/" in proc.stdout
        finally:
            ray_tpu.shutdown()
            c.shutdown()

    def test_recompile_storm_alert_fires_and_clears(
            self, shutdown_only, monkeypatch):
        """Acceptance: the SHIPPED xla-recompile-storm rule fires
        under a forced-recompile loop — compile counts travel
        jax.monitoring listener → registry → EventShipper → head TSDB
        → alert loop → pubsub — and CLEARS once the storm ages out of
        the (env-shrunk) window."""
        monkeypatch.setenv("RAY_TPU_ALERT_EVAL_S", "0.2")
        monkeypatch.setenv("RAY_TPU_ALERT_XLA_WINDOW_S", "5")
        monkeypatch.setenv("RAY_TPU_ALERT_XLA_COMPILES", "3")
        monkeypatch.setenv("RAY_TPU_EVENT_FLUSH_S", "0.2")
        import jax
        import jax.numpy as jnp

        from ray_tpu.cluster.cluster_utils import Cluster

        ray_tpu.shutdown()
        c = Cluster()
        rt = c.connect(num_cpus=4)
        try:
            device_mod.sample_once()  # listener installed
            for i in range(8):
                jax.jit(lambda v, i=i: v * (i - 0.5))(
                    jnp.ones(i + 40)).block_until_ready()
            head = rt.cluster.head
            cursor = 0
            deadline = time.monotonic() + 40.0
            fired = None
            while fired is None:
                assert time.monotonic() < deadline, \
                    "xla-recompile-storm never fired"
                out = head.call("pubsub_poll", {
                    "cursors": {"alerts": cursor}, "timeout_s": 1.0})
                ch = (out or {}).get("alerts")
                if not ch:
                    continue
                cursor = ch["seq"]
                for ev in ch["events"]:
                    if (ev["rule"] == "xla-recompile-storm"
                            and ev["state"] == "firing"):
                        fired = ev
            assert fired["value"] >= 3.0
            # Clears once the compiles age out of the 5s window.
            deadline = time.monotonic() + 40.0
            cleared = None
            while cleared is None:
                assert time.monotonic() < deadline, \
                    "xla-recompile-storm never cleared"
                out = head.call("pubsub_poll", {
                    "cursors": {"alerts": cursor}, "timeout_s": 1.0})
                ch = (out or {}).get("alerts")
                if not ch:
                    continue
                cursor = ch["seq"]
                for ev in ch["events"]:
                    if (ev["rule"] == "xla-recompile-storm"
                            and ev["state"] == "cleared"):
                        cleared = ev
            st = head.call("alerts_status", {})
            assert not [a for a in st["active"]
                        if a["rule"] == "xla-recompile-storm"]
        finally:
            ray_tpu.shutdown()
            c.shutdown()

    def test_device_trace_artifact_roundtrip(self, shutdown_only):
        """Acceptance: the node `device_trace` RPC captures, zips,
        and ships the artifact to the head's bounded store; `list
        artifacts` sees it, `get_artifact` returns the identical
        bytes, and the dashboard serves it as a zip download."""
        import jax.numpy as jnp

        from ray_tpu.cluster.cluster_utils import Cluster
        from ray_tpu.dashboard import start_dashboard, stop_dashboard

        ray_tpu.shutdown()
        c = Cluster()
        rt = c.connect(num_cpus=2)
        try:
            stop = threading.Event()

            def work():
                while not stop.is_set():
                    (jnp.ones((32, 32))
                     @ jnp.ones((32, 32))).block_until_ready()
                    time.sleep(0.01)

            t = threading.Thread(target=work, daemon=True)
            t.start()
            try:
                reply = rt.cluster.pool.get(rt.cluster.address).call(
                    "device_trace", {"duration_s": 0.4},
                    timeout=60.0)
            finally:
                stop.set()
                t.join()
            assert reply["shipped"] and reply["bytes"] > 0
            name = reply["name"]

            listing = rt.cluster.head.call("list_artifacts", {})
            entry = [a for a in listing if a["name"] == name]
            assert entry and entry[0]["kind"] == "device_trace"
            assert entry[0]["node_id"] == rt.cluster.node_id

            art = rt.cluster.head.call("get_artifact",
                                       {"name": name})
            assert art["found"] and len(art["data"]) == \
                reply["bytes"]
            zf = zipfile.ZipFile(io.BytesIO(art["data"]))
            assert any(n.endswith(".xplane.pb")
                       for n in zf.namelist())

            dash = start_dashboard(port=0)
            try:
                url = (dash.url + "/api/profile?device=1&artifact="
                       + urllib.parse.quote(name))
                resp = urllib.request.urlopen(url, timeout=30)
                body = resp.read()
                assert resp.headers["Content-Type"] == \
                    "application/zip"
                assert body == art["data"]
            finally:
                stop_dashboard()
        finally:
            ray_tpu.shutdown()
            c.shutdown()

    def test_artifact_store_byte_cap_drops_oldest(self,
                                                  monkeypatch):
        from ray_tpu.cluster.head import HeadServer
        from ray_tpu.cluster.rpc import RpcClient

        monkeypatch.setenv("RAY_TPU_HEAD_ARTIFACT_BYTES", "1000")
        head = HeadServer("127.0.0.1", 0)
        cl = RpcClient(head.address)
        try:
            for i in range(5):
                cl.call("put_artifact", {
                    "name": f"a{i}", "data": b"x" * 400,
                    "meta": {"kind": "device_trace"}})
            names = [a["name"] for a in
                     cl.call("list_artifacts", {})]
            # 1000-byte cap holds 2 of the 400-byte artifacts;
            # the NEWEST survive.
            assert names == ["a3", "a4"]
            assert not cl.call("get_artifact",
                               {"name": "a0"})["found"]
            assert cl.call("get_artifact",
                           {"name": "a4"})["found"]
        finally:
            cl.close()
            head.shutdown()


# ----------------------------------------------- serve engine plumbing
class TestServeEngineSeries:
    def test_program_emas_exported_by_engine(self):
        """The debug-preset engine's prefill/decode EMAs land as
        ray_tpu_serve_program_seconds gauges — the feasibility
        estimator's numbers, continuously queryable."""
        import asyncio

        from ray_tpu.serve.llm import LLMServer

        eng = LLMServer(model_preset="debug", max_slots=2,
                        max_len=64, prefill_buckets=(16,),
                        decode_chunk=8, prefill_groups=(2,))
        try:
            out = asyncio.run(eng.generate(
                {"prompt": [1, 2, 3], "max_new_tokens": 6}))
            assert len(out["tokens"]) == 6
            summ = metrics_mod.metrics_summary()
            got = summ.get("ray_tpu_serve_program_seconds", {})
            assert got.get("llm,prefill", 0) > 0
            assert got.get("llm,decode_chunk", 0) > 0
        finally:
            eng.shutdown()


# ------------------------------------------ where a start's seconds go
def _spans(since=0.0, names=None):
    return [e for e in timeline_mod.export_timeline(None)
            if e.get("ph") == "X" and e["ts"] >= since * 1e6
            and (names is None or e["name"] in names)]


XLA_SPANS = ("xla_trace", "xla_lower", "xla_compile")


@pytest.mark.usefixtures("own_compile_cache")
class TestCompilePhases:
    def test_first_call_writes_one_span_a_phase_under_the_ambient_span(
            self):
        import jax
        import jax.numpy as jnp

        from ray_tpu.observability import tracing

        device_mod.install_compile_listener()

        @jax.jit
        def inner(v):
            return jnp.where(v > 0, v, 0.0) * 3.25

        @jax.jit
        def phases_probe(v):     # nested jits, jnp functions: one trace
            return jnp.sum(inner(v) + jnp.einsum("ij,jk->ik", v, v))

        x = jnp.ones((5, 5))
        x.block_until_ready()
        t0 = time.time()
        with tracing.span("test.phases") as sp:
            phases_probe(x).block_until_ready()
            mine = _spans(t0, XLA_SPANS)
            phases_probe(x).block_until_ready()   # cached: nothing
            assert _spans(t0, XLA_SPANS) == mine
        assert [e["name"] for e in mine] == list(XLA_SPANS)
        assert [e["args"]["fun_name"] for e in mine] == \
            ["phases_probe", "jit(phases_probe)", "jit(phases_probe)"]
        outer, = _spans(t0, ("test.phases",))
        end = 0.0
        for e in mine:
            assert e["tid"] == "xla-compile"
            assert e["args"]["trace_id"] == sp.trace_id
            assert e["args"]["parent_span_id"] == sp.span_id
            assert e["args"]["duration_s"] == pytest.approx(
                e["dur"] * 1e-6, abs=1e-4)
            # jax's own start and end: inside the ambient span, one
            # phase after the other (1 ms: time.time() beside the ring's
            # perf_counter clock)
            assert e["ts"] >= max(end, outer["ts"]) - 1e3
            end = e["ts"] + e["dur"]
        assert end <= outer["ts"] + outer["dur"] + 1e3
        assert mine[2]["args"]["cache_hit"] is False
        assert mine[2]["args"]["cache_fetch_s"] == 0.0

    def test_one_compile_span_a_compilation_and_the_sums_are_the_backends(
            self):
        import jax
        import jax.numpy as jnp

        device_mod.install_compile_listener()
        x = jnp.ones(29)
        x.block_until_ready()

        def read():
            s = metrics_mod.metrics_summary()
            return (s.get("ray_tpu_xla_compiles_total", {}).get(
                        "backend_compile", 0.0),
                    sum(s.get("ray_tpu_xla_compile_seconds", {}).values()),
                    dict(s.get("ray_tpu_xla_phase_seconds", {})))

        count0, seconds0, phases0 = read()
        t0 = time.time()
        for i in range(3):
            jax.jit(lambda v, i=i: v * (i + 7.5))(x).block_until_ready()
        count1, seconds1, phases1 = read()
        spans = _spans(t0, XLA_SPANS)
        by_name = {n: [e for e in spans if e["name"] == n]
                   for n in XLA_SPANS}
        assert [len(by_name[n]) for n in XLA_SPANS] == [3, 3, 3]
        assert count1 - count0 == 3
        # the histogram sums the backend's seconds, as it always has:
        # neither trace nor lowering nor the cache's fetch is added
        assert seconds1 - seconds0 == pytest.approx(
            sum(e["dur"] for e in by_name["xla_compile"]) * 1e-6,
            abs=1e-3)
        for phase in ("trace", "lower"):
            assert phases1[phase] - phases0.get(phase, 0.0) == \
                pytest.approx(sum(e["dur"] for e in by_name[
                    "xla_" + phase]) * 1e-6, abs=1e-3)
        assert set(phases1) <= {"trace", "lower", "cache_fetch"}

    def test_a_cache_fetch_is_kept_for_the_compile_span(self):
        """The cache's events fire inside the backend's phase, on its
        thread: the span that closes it says what they said."""
        device_mod.install_compile_listener()
        t0 = time.time() - 60.0     # (spans of the past: none leaks on)
        before = metrics_mod.metrics_summary().get(
            "ray_tpu_xla_phase_seconds", {}).get("cache_fetch", 0.0)
        device_mod._on_xla_event(device_mod._CACHE_FETCH_EVENT, 0.125)
        device_mod._on_xla_event(device_mod._COMPILE_EVENT, 0.25)
        device_mod._on_xla_span(device_mod._COMPILE_EVENT, t0, t0 + 0.25,
                                fun_name="jit(fetched)")
        device_mod._on_xla_span(device_mod._COMPILE_EVENT, t0 + 0.5,
                                t0 + 0.75, fun_name="jit(compiled)")
        fetched, compiled = [
            e for e in _spans(t0, ("xla_compile",))
            if e["args"]["fun_name"] in ("jit(fetched)", "jit(compiled)")]
        assert (fetched["args"]["cache_hit"],
                fetched["args"]["cache_fetch_s"]) == (True, 0.125)
        assert (compiled["args"]["cache_hit"],
                compiled["args"]["cache_fetch_s"]) == (False, 0.0)
        assert metrics_mod.metrics_summary()[
            "ray_tpu_xla_phase_seconds"]["cache_fetch"] - before == 0.125

    @pytest.mark.parametrize("off", ["tracing", "telemetry"])
    def test_gates(self, off, monkeypatch):
        """Spans under tracing, series under the device plane."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.observability import tracing

        device_mod.install_compile_listener()
        x = jnp.ones(31)
        x.block_until_ready()
        monkeypatch.setattr(
            *{"tracing": (tracing, "_enabled", False),
              "telemetry": (device_mod, "_enabled", False)}[off])
        before = metrics_mod.metrics_summary()
        t0 = time.time()
        jax.jit(lambda v: v * 11.25 - 2)(x).block_until_ready()
        assert _spans(t0, XLA_SPANS) == []
        after = metrics_mod.metrics_summary()
        grew = after["ray_tpu_xla_compiles_total"]["backend_compile"] \
            - before["ray_tpu_xla_compiles_total"]["backend_compile"]
        assert grew == (1 if off == "tracing" else 0)
        assert (after["ray_tpu_xla_phase_seconds"]
                != before["ray_tpu_xla_phase_seconds"]) == (off == "tracing")


START_SPANS = ("serve.engine_start", "serve.engine_build", "serve.warmup",
               "serve.warm_program", "serve.warm_wait")
START_ENGINE = dict(model_preset="debug", max_slots=4, max_len=64,
                    prefill_buckets=(16,), decode_chunk=8,
                    prefill_groups=(2, 4))
START_PLANES = {"dense": {}, "paged": dict(paged=True, block_size=8)}


def _inside(child, parent, slack_us=1e3):
    return (child["ts"] >= parent["ts"] - slack_us
            and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + slack_us)


class TestEngineStartSpans:
    @pytest.mark.parametrize("plane", sorted(START_PLANES))
    def test_a_start_is_a_tree_of_spans(self, plane):
        from ray_tpu.serve import llm

        t0 = time.time()
        eng = llm.LLMServer(**START_ENGINE, **START_PLANES[plane])
        try:
            spans = _spans(t0, START_SPANS + XLA_SPANS)
            shapes = llm.prefill_shapes(eng.prefill_groups, eng.buckets,
                                        eng.max_slots)
            rungs = sorted({g for g, _ in shapes})
            if plane == "paged":
                warmed = [(name, g, b) for g, b in shapes for name in (
                    "serve.prefill_cold", "serve.prefill_warm")]
                warmed += [("serve.decode_paged", None, None)
                           ] * len(eng._nb_buckets)
            else:
                warmed = [("serve.prefill", g, b) for g, b in shapes]
                warmed += [("serve.decode_k", None, None)
                           ] * len(eng.decode_buckets)
            warmed += [("serve.seat", None, None)] * len(rungs)
        finally:
            eng.shutdown()
        one = {n: [e for e in spans if e["name"] == n] for n in START_SPANS}
        start, = one["serve.engine_start"]
        build, = one["serve.engine_build"]
        warmup, = one["serve.warmup"]
        wait, = one["serve.warm_wait"]
        programs = one["serve.warm_program"]
        assert [(e["args"]["program"], e["args"].get("rows"),
                 e["args"].get("bucket")) for e in programs] == warmed
        decode = [e["args"] for e in programs
                  if e["args"]["program"].startswith("serve.decode")]
        assert all(a["k"] == 8 for a in decode)
        if plane == "dense":
            assert [a["s_active"] for a in decode] == \
                list(eng.decode_buckets)
        # one trace, children name their parents, inside them on the clock
        assert {e["args"]["trace_id"] for e in spans} == \
            {start["args"]["trace_id"]}
        assert "parent_span_id" not in start["args"]
        for child, parent in [(build, start), (warmup, start),
                              (wait, warmup)] + [(p, warmup)
                                                 for p in programs]:
            assert child["args"]["parent_span_id"] == \
                parent["args"]["span_id"]
            assert _inside(child, parent)
        assert build["ts"] + build["dur"] <= warmup["ts"] + 1e3
        # how the engine's K/V lie and what attends them, fixed at the
        # build (the toy: 2 heads of 16 by position; off the chip the
        # kernel is interpreted)
        assert {key: build["args"].get(key) for key in (
            "kv_row_heads", "kv_row_dim", "decode_attention")} == {
            "kv_row_heads": 2, "kv_row_dim": 16,
            "decode_attention": "kernel"}
        by_id = {e["args"]["span_id"]: e for e in spans
                 if e["name"] in START_SPANS}
        phases = [e for e in spans if e["name"] in XLA_SPANS]
        for e in phases:    # every compilation of the start has a home
            assert _inside(e, by_id[e["args"]["parent_span_id"]])
        for p in programs:  # each warmed call: its three phases, once
            mine = [e["name"] for e in phases
                    if e["args"]["parent_span_id"] == p["args"]["span_id"]]
            assert mine == list(XLA_SPANS), (p["args"], mine)
            assert p["dur"] >= sum(
                e["dur"] for e in phases
                if e["args"]["parent_span_id"] == p["args"]["span_id"])

    @pytest.mark.parametrize("plane", sorted(START_PLANES))
    def test_tracing_off_is_the_same_start_with_no_span(self, plane,
                                                       monkeypatch):
        """Warm-up calls the same programs with the same shapes in the
        same order, and waits once, whether tracing is on or off; off,
        nothing is written and nothing registered."""
        import jax

        from ray_tpu.observability import tracing
        from ray_tpu.serve import llm

        calls, waits = [], []
        real_wait = jax.block_until_ready
        monkeypatch.setattr(
            jax, "block_until_ready",
            lambda x: (waits.append(len(calls)), real_wait(x))[1])

        def counted(name, program):
            def call(*args, **static):
                calls.append((name, tuple(
                    getattr(a, "shape", None) for a in args[2:]),
                    tuple(sorted(static.items()))))
                return program(*args, **static)
            return call

        def start(traced):
            monkeypatch.setattr(tracing, "_enabled", traced)
            device_mod.clear_programs()
            eng = llm.LLMServer(**{**START_ENGINE, "max_slots": 2,
                                   "prefill_groups": (2,)},
                                **START_PLANES[plane], warmup=False)
            for attr in ("_prefill", "_decode_k", "_prefill_cold",
                         "_prefill_warm", "_decode_paged", "_inject",
                         "_seat"):
                if getattr(eng, attr, None) is not None:
                    setattr(eng, attr, counted(attr, getattr(eng, attr)))
            del calls[:], waits[:]
            t0 = time.time()
            try:
                eng._warmup()
                return (list(calls), list(waits),
                        _spans(t0, START_SPANS + XLA_SPANS),
                        device_mod.registered_programs())
            finally:
                eng.shutdown()   # (waits once more)

        on_calls, on_waits, on_spans, on_registered = start(True)
        off_calls, off_waits, off_spans, off_registered = start(False)
        assert off_calls == on_calls and len(off_calls) >= 3
        # the one wait, after the last decode program and before the seats
        assert off_waits == on_waits and len(off_waits) == 1
        assert off_spans == [] and off_registered == []
        assert len([e for e in on_spans
                    if e["name"] == "serve.warm_program"]) == \
            len(on_registered) == len(
                [c for c in on_calls if c[0] != "_inject"])


class TestWarmupStackRoom:
    """Warm-up runs under one frame with room (``llm._with_stack_room``):
    CPython frees a 16 KB chunk of frames when its first frame returns,
    so a hot call at a chunk's end maps and unmaps memory every time."""

    def test_it_calls_and_hands_back(self):
        from ray_tpu.serve import llm

        assert llm._with_stack_room(lambda: 7) == 7
        with pytest.raises(KeyError):
            llm._with_stack_room(lambda: {}["x"])

    @pytest.mark.skipif(sys.implementation.name != "cpython",
                        reason="CPython's data-stack chunks")
    def test_no_depth_under_it_meets_a_chunks_end(self):
        from ray_tpu.serve import llm

        class Key:      # a dict lookup calls back into Python, as jax's do
            def __hash__(self):
                # a frame larger than at_depth's: one depth a chunk then
                # has room for lookups' frame and none for this one
                a = b = c = d = e = f = g = h = 1
                return a

        key = Key()
        table = {key: 0}

        def lookups():
            t = time.perf_counter()
            for _ in range(2000):
                table[key]
            return time.perf_counter() - t

        def at_depth(n):
            return at_depth(n - 1) if n else min(lookups(), lookups())

        # 300 of at_depth's frames are two chunks and more: without room
        # one of these depths pays the chunk at every lookup (20-100x)
        plain = max(at_depth(n) for n in range(300))
        roomy = max(llm._with_stack_room(lambda n=n: at_depth(n))
                    for n in range(300))
        assert roomy < 0.5 * plain

    def test_warm_up_runs_under_it(self, monkeypatch):
        from ray_tpu.serve import llm

        under = []
        real = llm._with_stack_room
        monkeypatch.setattr(
            llm, "_with_stack_room",
            lambda fn: (under.append(fn.__name__), real(fn))[1])
        eng = llm.LLMServer(**START_ENGINE)
        eng.shutdown()
        assert under == ["_warmup"]


class TestTrainerStartSpan:
    @pytest.mark.parametrize("takes_config", [True, False])
    def test_worker_start_ends_where_the_loop_begins(self, shutdown_only,
                                                     takes_config):
        from ray_tpu.train import JaxTrainer, ScalingConfig

        entered = []
        if takes_config:
            def loop(config):
                entered.append((time.time(), config["x"]))
        else:
            def loop():
                entered.append((time.time(), 5))
        t0 = time.time()
        JaxTrainer(loop, train_loop_config={"x": 5},
                   scaling_config=ScalingConfig(num_workers=1)).fit()
        (t_loop, x), = entered
        assert x == 5
        span, = _spans(t0, ("train.worker_start",))
        assert span["args"]["trace_id"] and span["args"]["span_id"]
        assert t0 * 1e6 - 1e3 <= span["ts"]
        assert span["ts"] + span["dur"] == pytest.approx(
            t_loop * 1e6, abs=50e3)

    def test_no_span_with_tracing_off(self, shutdown_only, monkeypatch):
        from ray_tpu.observability import tracing
        from ray_tpu.train import JaxTrainer, ScalingConfig

        monkeypatch.setattr(tracing, "_enabled", False)
        entered = []
        t0 = time.time()
        JaxTrainer(lambda: entered.append(1),
                   scaling_config=ScalingConfig(num_workers=1)).fit()
        assert entered == [1]
        assert _spans(t0, ("train.worker_start",)) == []
