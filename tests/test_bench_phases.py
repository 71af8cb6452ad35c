"""CPU-shaped smokes of the model-plane bench phases (ISSUE 13).

The real numbers come from the TPU BENCH round; these gates make sure
the phase HARNESSES keep working on CI — a broken phase should fail a
PR here, not silently emit ``*_error`` keys at the next BENCH round.
Every engine is debug-preset sized so the whole file stays in tier-1
budget."""

import pytest

from ray_tpu import serve

# Debug-shaped engine reused by every phase smoke: tiny compile
# matrix (one prefill bucket, one group size).
_ENGINE = dict(model_preset="debug", max_slots=4, max_len=64,
               prefill_buckets=(16,), decode_chunk=8, paged=True,
               block_size=8, prefill_groups=(4,))


@pytest.fixture
def serve_session(ray_start_regular):
    yield
    serve.shutdown()


def test_serve_bench_spec_phase_smoke(serve_session):
    """The spec-decode phase emits its throughput key AND the accept
    rate pulled from the replica's own counters."""
    from bench import _serve_bench

    out = _serve_bench(
        n_requests=6, paged=True, suffix="_spec", vocab=256,
        engine_kw=dict(_ENGINE, spec_k=3, draft_layers=1))
    assert out["serve_decode_tok_per_s_spec"] > 0
    assert out["spec_decode_k"] == 3
    assert 0.0 <= out["spec_decode_accept_rate"] <= 1.0


def test_kv_quant_bench_phase_smoke(serve_session):
    """The kv-quant phase's capacity math holds (same pool bytes buy
    ~2x the int8 blocks) and both engines decode."""
    from bench import _kv_quant_bench

    out = _kv_quant_bench(n_requests=6, engine_kw=dict(_ENGINE),
                          base_blocks=9, vocab=256)
    # 8 usable bf16 blocks re-cut as int8: 2D/(D+4) ≈ 1.6x at the
    # debug preset's head_dim 16 (per-row scales cost 4/D; ~1.94x at
    # the bench model's head_dim 128).
    assert out["kv_quant_blocks_int8"] >= int(1.5 * (9 - 1))
    assert out["serve_decode_tok_per_s_int8"] > 0
    assert out["kv_quant_decode_ratio"] > 0


def test_bench_module_imports_without_side_effects():
    """``import bench`` starts nothing (the phase smokes above import
    from it).  That bench.py refuses to RUN without the chip is held
    by tests/test_chip_bringup.py."""
    import subprocess
    import sys

    assert subprocess.run(
        [sys.executable, "-c", "import bench"],
        capture_output=True).returncode == 0


def test_device_telemetry_overhead_phase_smoke():
    """The device-plane overhead phase runs the paired-adjacent
    harness end to end at smoke size and emits its keys (the <5
    guard is asserted on the full-size BENCH run)."""
    from bench import _device_telemetry_overhead_bench

    out = _device_telemetry_overhead_bench(n_pairs=6)
    assert "device_telemetry_overhead_pct" in out
    assert out["device_on_roundtrip_us"] > 0
    assert out["device_off_roundtrip_us"] > 0
    assert -50.0 < out["device_telemetry_overhead_pct"] < 100.0


def test_tsdb_bench_phase_smoke():
    """The TSDB phase emits its query latency + ingest-overhead keys
    from a real head RPC round (small sizes — the real numbers come
    from the BENCH round's full run)."""
    from bench import _tsdb_bench

    out = _tsdb_bench(n_nodes=2, n_flushes=25, n_queries=8,
                      n_pairs=10)
    assert out["metrics_query_us"] > 0
    assert out["tsdb_series"] > 0
    assert out["tsdb_bytes_per_sample"] > 0
    # The overhead key exists and is a sane percentage; the <5 guard
    # is asserted on the full-size BENCH run, not a 10-pair smoke.
    assert -50.0 < out["tsdb_ingest_overhead_pct"] < 100.0


def test_shuffle_bench_phase_smoke():
    """The shuffle phase runs both paths (push + materialized) end to
    end at smoke size and emits its keys.  The >=1.5x push speedup is
    asserted on the full-size BENCH run — at smoke size the fixed
    actor/ring setup cost dominates and the ratio is meaningless."""
    from bench import _shuffle_bench

    out = _shuffle_bench(n_blocks=8, rows_per_block=512, width=32)
    assert out["shuffle_gbytes_per_s"] > 0
    assert out["shuffle_gbytes_per_s_materialized"] > 0
    assert out["shuffle_push_speedup"] > 0
    from ray_tpu.experimental.channel import channels_available
    if channels_available():
        # Same-host soak: fragments must ride the shm rings.
        assert out["shuffle_shm_bytes"] > 0


def test_raylint_bench_phase_smoke():
    """The raylint phase lints the real package twice (cold parse,
    then AST-memo-served) and reports wall clock + parse-cache hit
    rate; the package itself must stay finding-free."""
    from bench import _raylint_bench

    out = _raylint_bench()
    assert out["raylint_wall_clock_s"] > 0
    assert out["raylint_warm_wall_clock_s"] > 0
    # Second run re-reads identical bytes: every parse is memo-served,
    # so the process-lifetime hit rate lands at ~50% for two runs.
    assert out["raylint_parse_cache_hit_rate"] >= 0.4
    assert out["raylint_findings"] == 0


def test_flightrec_overhead_phase_smoke():
    """The flight-recorder overhead phase runs the paired-adjacent
    harness end to end at smoke size and emits its keys (the <5
    guard is asserted on the full-size BENCH run)."""
    from bench import _flightrec_overhead_bench

    out = _flightrec_overhead_bench(n_pairs=6)
    assert "flightrec_overhead_pct" in out
    assert out["flightrec_on_roundtrip_us"] > 0
    assert out["flightrec_off_roundtrip_us"] > 0
    assert -50.0 < out["flightrec_overhead_pct"] < 100.0
