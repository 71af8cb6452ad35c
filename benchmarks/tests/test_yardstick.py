"""The yardstick's own arithmetic: FLOP and byte counts against
hand-worked numbers, the load generator's determinism, the trace
reduction on a recorded TPU trace, and that BENCHMARK.json's names all
lead to files."""

import json
import os
import threading
import time
import types

import numpy as np
import pytest

from benchmarks.lib import (flops, loadgen, program_spans, readers, spec,
                            trace_reduce)

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixtures", "tiny_tpu.xplane.pb")


def _config(name):
    with open(os.path.join(spec.BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


# ------------------------------------------------------------------ flops
@pytest.mark.parametrize("name,params,matmul", [
    # 24 x (2048*2048 + 2*2048*1024 + 2048*2048 + 3*2048*8192 + 2*2048)
    #   + 2 * 92544*2048 + 2048 ; matmul = the same less norms/embedding
    ("internlm2-1.8b", 1_889_110_016, 1_699_479_552),
    # 32 x (960*960 + 2*960*320 + 960*960 + 3*960*2560 + 2*960)
    #   + 49152*960 + 960 (tied head)
    ("smollm2-360m", 361_821_120, 361_758_720),
])
def test_parameter_counts_by_hand(name, params, matmul):
    c = _config(name)
    assert flops.param_count(c) == params == c["parameters"]
    assert flops.matmul_params(c) == matmul


def test_train_flops_per_token_by_hand():
    c = _config("smollm2-360m")
    # forward: 2 FLOPs a weight a token; causal attention of one 2048-token
    # sequence: QK^T and PV, 2*2*S*S*heads*D, halved, over 32 layers
    attn = 32 * (2 * 2 * 2048 * 2048 * 15 * 64) / 2 / 2048
    assert flops.train_flops_per_token(c, 2048) == pytest.approx(
        3 * (2 * 361_758_720 + attn))
    assert flops.train_flops_per_token(c, 2048) == pytest.approx(
        2.548e9, rel=1e-3)
    c = _config("internlm2-1.8b")
    assert flops.train_flops_per_token(c, 4096) == pytest.approx(
        11.405e9, rel=1e-3)


def test_decode_bytes_by_hand():
    c = _config("internlm2-1.8b")
    # K and V, 24 layers x 8 heads x 128 x 2 bytes = 98,304 bytes a position
    assert flops.kv_bytes_per_token(c) == 98_304
    assert flops.weight_bytes(c) == 2 * 1_699_479_552
    assert flops.decode_step_bytes(c, 10_000) == \
        2 * 1_699_479_552 + 10_000 * 98_304
    # flash: forward 1 + backward 2.5 of the causal forward's FLOPs
    assert flops.flash_train_flops(c, 4, 4096) == pytest.approx(
        3.5 * 4 * flops.attention_flops_fwd(c, 4096))


# ---------------------------------------------------------------- loadgen
TRAFFIC = {
    "generator": "requests",
    "arrivals": {"process": "poisson", "rate_per_s": 200.0,
                 "lead_in_s": 0.2, "drain_s": 5.0},
    "prompt_tokens": {"dist": "lognormal", "median": 20, "sigma": 0.8,
                      "min": 4, "max": 64, "stratified": 8},
    "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.7,
                      "min": 2, "max": 32, "stratified": 8},
}


def _stream(seed, n=50):
    source = loadgen.RequestSource(TRAFFIC, seed, 0, 1000)
    return [source.next() for _ in range(n)]


def test_same_seed_same_schedule_and_requests():
    assert _stream(7) == _stream(7)
    assert _stream(7) != _stream(8)
    a = loadgen.arrival_offsets(TRAFFIC["arrivals"], 7, 2.0)
    assert np.array_equal(a, loadgen.arrival_offsets(
        TRAFFIC["arrivals"], 7, 2.0))
    assert a[0] >= -0.2 and a[-1] < 2.0 and np.all(np.diff(a) > 0)
    assert len(a) == pytest.approx(2.2 * 200, rel=0.2)
    for r in _stream(7):
        assert 4 <= len(r["prompt"]) <= 64
        assert 2 <= r["max_new_tokens"] <= 32
    rows = loadgen.token_batches(
        {"batch": 2, "seq_len": 16, "distinct_batches": 3}, 5, 100)
    assert rows.shape == (6, 16) and rows.max() < 100
    assert np.array_equal(rows, loadgen.token_batches(
        {"batch": 2, "seq_len": 16, "distinct_batches": 3}, 5, 100))


class _FakeResponse:
    def __init__(self, request, delay):
        self.request, self.delay = request, delay
        self.t0 = time.perf_counter()

    def result(self, timeout=None):
        time.sleep(max(0.0, self.t0 + self.delay - time.perf_counter()))
        return {"tokens": [1] * self.request["max_new_tokens"],
                "ttft_ms": 1.0}


def test_open_loop_times_from_due_and_reports_its_own_lateness():
    """A sender that stalls 30 ms inside ``send`` falls behind a 200/s
    schedule; the log shows it as ``sent - due``, and every request due
    in the window is awaited."""
    def slow_send(request):
        time.sleep(0.03)
        return _FakeResponse(request, 0.01)

    gen = loadgen.LoadGenerator(TRAFFIC, 3, 1000, slow_send)
    log = gen.run(0.5)
    measured = log.measured()
    assert measured and all(r.ok and r.done >= r.sent for r in measured)
    assert all(log.t_open <= r.due < log.t_close for r in measured)
    lag = max(r.sent - r.due for r in measured)
    assert lag > 0.1, lag      # the generator was late, and says so


def test_closed_loop_keeps_callers_busy_and_counts_completions():
    traffic = dict(TRAFFIC, arrivals={"process": "closed", "callers": 4,
                                      "lead_in_s": 0.1})
    in_flight, peak = [0], [0]
    lock = threading.Lock()

    def send(request):
        with lock:
            in_flight[0] += 1
            peak[0] = max(peak[0], in_flight[0])

        class R(_FakeResponse):
            def result(self, timeout=None):
                out = super().result(timeout)
                with lock:
                    in_flight[0] -= 1
                return out
        return R(request, 0.02)

    gen = loadgen.LoadGenerator(traffic, 3, 1000, send)
    log = gen.run(0.4)
    assert gen.join(5.0) == 0
    assert peak[0] == 4
    # every request in the system during the window, each one finished
    measured = log.measured()
    assert all(r.ok and r.sent < log.t_close and r.done >= log.t_open
               for r in measured)
    assert 40 <= len(measured) <= 100      # 4 callers x 0.4 s / 20 ms
    # tokens are counted where they were produced: 4 callers, a token
    # every 20 ms / request length, so the window's count is the rate
    # times its length whatever straddles its edges
    per_s = sum(r.got_tokens for r in measured) / sum(
        r.done - r.sent for r in measured) * 4
    assert log.tokens_in_window() == pytest.approx(0.4 * per_s, rel=0.1)


def test_stratified_lengths_and_fixed_counts_are_the_same_work():
    spec_ = {"dist": "lognormal", "median": 100, "sigma": 0.7, "min": 8,
             "max": 400, "stratified": 16}
    sums = set()
    for seed in range(5):
        lengths = loadgen.Lengths(spec_, np.random.default_rng(seed))
        draws = [lengths.draw() for _ in range(64)]
        sums.add(sum(draws))
        assert min(draws) >= 8 and max(draws) <= 400
    assert len(sums) == 1          # every seed: the same lengths
    assert loadgen.quantile(spec_, 0.5) == 100
    offsets = [loadgen.arrival_offsets(TRAFFIC["arrivals"], seed, 2.0)
               for seed in range(5)]
    # the lead-in's count and the window's, each: the same load every seed
    assert {(len(o), int((o >= 0).sum())) for o in offsets} == {(440, 400)}


# ----------------------------------------------------------- trace_reduce
def test_interval_algebra():
    assert trace_reduce.union([(0, 1), (0.5, 2), (3, 4)]) == \
        [(0, 2), (3, 4)]
    assert trace_reduce.subtract([(0, 10)], [(1, 2), (3, 4)]) == \
        [(0, 1), (2, 3), (4, 10)]
    # a while spans its body: its own time is what the body leaves
    assert trace_reduce.self_times(
        [(0, 10, "while"), (1, 3, "a"), (3, 6, "b"), (11, 12, "a")]) == \
        {"a": 3.0, "b": 3.0, "while": 5.0}


def test_collectives_exposed_is_what_no_other_op_covers():
    ag = "%all-gather.1 = bf16[8] all-gather(bf16[2] %p), dimensions={0}"
    mm = "%fusion.1 = bf16[8] fusion(bf16[8] %x), kind=kOutput"
    wh = "%while.1 = (s32[]) while((s32[]) %t), body=%b"
    dev = trace_reduce.DeviceTrace(
        0, ops=[(0.0, 10.0, wh), (1.0, 3.0, mm), (6.0, 8.0, mm)],
        modules=[], async_ops=[(2.0, 7.0, ag)])
    trace = trace_reduce.Trace([dev], [], 0.0, 10.0)
    total, exposed = trace.collective_seconds()
    assert total == pytest.approx(5.0)
    assert exposed == pytest.approx(3.0)     # 3..6; the while is no cover
    assert trace_reduce.short_name(ag) == "%all-gather.1 all-gather bf16[8]"


def test_reduction_of_a_recorded_tpu_trace():
    """Recorded on a v5e (PR 22): three runs of one jitted program — a
    4-step scan of matmuls, then a Mosaic flash forward and backward —
    each launched under a ``train.step`` annotation, with a 2 ms sleep
    under ``serve.harvest_chunk`` between them."""
    trace = trace_reduce.read(FIXTURE)
    assert [d.index for d in trace.devices] == [0]
    runs = trace.module_runs(r"^jit_step\b")
    assert len(runs) == 3
    module_s = sum(e - s for s, e, _ in runs)
    # busy is the union of op intervals: inside the modules' time, and the
    # while is not counted on top of its body
    assert 0.5 * module_s < trace.busy_s <= module_s * 1.001
    assert trace.window_s > 0.004          # two sleeps of 2 ms
    assert 0.9 < trace.idle_share() < 1.0
    ops = trace.op_seconds()
    assert sum(ops.values()) == pytest.approx(trace.busy_s, rel=0.02)
    mosaic = trace.seconds_matching(
        'custom_call_target="tpu_custom_call"')
    assert 0 < mosaic < trace.busy_s
    assert trace.collective_seconds() == (0, 0)
    # the idle time lies under the annotation the host had open
    gaps = dict(trace.idle_gaps(names=("train.step",
                                       "serve.harvest_chunk")))
    assert gaps["serve.harvest_chunk"] > 0.9 * sum(gaps.values())
    names = [n for n, _ in trace.top_ops(top=5)]
    assert any("custom-call[mosaic]" in n for n in names), names
    assert all(len(n) <= 120 for n in names)


# ------------------------------------------------- a whole launch's step
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
_OP = "%fusion.1 = bf16[8] fusion(bf16[8] %x), kind=kLoop"


def _launches(*runs, per_ms=10.0):
    """A chip's trace of ``(module name, steps held)`` runs back to back:
    a step is ``per_ms`` long and four ops; a run that holds fewer than
    its program's 16 steps is the piece an edge of the trace left."""
    ops, modules, t = [], [], 0.0
    for name, steps in runs:
        start = t
        for _ in range(steps * 4):
            ops.append((t, t + per_ms * 0.25e-3, _OP))
            t += per_ms * 0.25e-3
        modules.append((start, t, name))
        t += 1e-4
    return trace_reduce.Trace(
        [trace_reduce.DeviceTrace(0, ops, modules)], [], 0.0, t)


DECODE, OTHER, PREFILL = ("jit_decode_k(7)", "jit_decode_k(8)",
                          "jit_prefill(9)")


@pytest.mark.parametrize("runs,whole,step_ms", [
    # both edges cut: the three launches between them are the step
    ([(DECODE, 5), (DECODE, 16), (DECODE, 16), (DECODE, 16), (DECODE, 3)],
     3, 10.0),
    # PR 52's seed 2147481012: one whole launch and what an edge left of
    # another, whichever edge (the median of the two read 182% of a floor)
    ([(PREFILL, 9), (DECODE, 16), (DECODE, 5)], 1, 10.0),
    ([(DECODE, 5), (DECODE, 16), (PREFILL, 9)], 1, 10.0),
    # an edge launch that is whole (as many ops as its program's others)
    # counts; so does every launch between a prefill at either edge
    ([(DECODE, 16), (DECODE, 16), (DECODE, 16)], 3, 10.0),
    ([(PREFILL, 9), (DECODE, 16), (DECODE, 16), (PREFILL, 9)], 2, 10.0),
    # nothing whole to hold an edge launch against: not a step's time --
    # two pieces, a lone launch, another program's launches
    ([(DECODE, 9), (DECODE, 9)], 0, None),
    ([(DECODE, 16)], 0, None),
    ([(OTHER, 16), (DECODE, 16), (DECODE, 16), (OTHER, 7)], 2, 10.0),
    ([(OTHER, 16), (DECODE, 16), (OTHER, 16)], 1, 10.0),
])
def test_a_step_is_a_whole_launchs(runs, whole, step_ms):
    """``decode_step_device_ms`` and ``train_step_device_ms`` take the
    median of the launches the traced slice holds WHOLE, and read None
    where it holds none."""
    trace = _launches(*runs)
    assert len(trace.whole_runs(readers.DECODE_MODULE)) == whole
    got = readers.decode_step_device_ms({"trace": trace, "decode_chunk": 16})
    assert got == (None if step_ms is None else pytest.approx(step_ms))
    # the same trace as a trainer's: a step a launch
    train = trace_reduce.Trace(
        [trace_reduce.DeviceTrace(
            0, trace.devices[0].ops,
            [(s, e, n.replace("jit_decode_k", "jit_step"))
             for s, e, n in trace.devices[0].modules])], [], 0.0, trace.hi)
    got = readers.train_step_device_ms({"trace": train})
    assert got == (None if step_ms is None
                   else pytest.approx(16 * step_ms))
    assert readers.decode_step_device_ms({"trace": None}) is None


@pytest.mark.parametrize("steps", [
    [16, 16, 16, 16],           # whole steps alone
    [7, 16, 16, 16, 5],         # the slice's edges cut the first and last
])
def test_the_flash_roofline_is_a_whole_steps_too(steps):
    """The kernels' seconds are held against their share of the steps'
    time x a WHOLE step, not divided by the count of runs: a step that the
    trace's edge cut holds its part of the kernels and of the time."""
    kernel = ("%flash_attention_fwd.3 = bf16[8,15,2048,64]{3,2,1,0} "
              "custom-call(bf16[8,15,2048,64] %q), "
              "custom_call_target=\"tpu_custom_call\"")
    ops, modules, t = [], [], 0.0
    for held in steps:                     # 16 x (1 ms kernel + 3 ms rest)
        start = t
        for _ in range(held):
            ops += [(t, t + 1e-3, kernel), (t + 1e-3, t + 4e-3, _OP)]
            t += 4e-3
        modules.append((start, t, "jit_step(3)"))
    trace = trace_reduce.Trace(
        [trace_reduce.DeviceTrace(0, ops, modules)], [], 0.0, t)
    c = _config("smollm2-360m")
    obs = {"trace": trace, "peaks": PEAKS, "chips": 1, "batch": 8,
           "seq_len": 2048, "cell": types.SimpleNamespace(config=c)}
    least = max(flops.flash_train_flops(c, 8, 2048) / 197e12,
                flops.flash_train_bytes(c, 8, 2048) / 819e9)
    assert readers.train_step_device_ms(obs) == pytest.approx(64.0)
    assert readers.flash_attention_roofline(obs) == pytest.approx(
        100 * least / 16e-3)


# ------------------------------------------- a configuration's own floor
def fixed_observations(config, cell_name):
    """What a decode step's floor is a function of, fixed: twelve
    requests decoding at the traced span's middle with prompts of 300 to
    3,600, five chunks' own counts of their experts and states, four
    whole launches of 160 ms."""
    with open(os.path.join(spec.BENCH_DIR, "workloads",
                           cell_name + ".json")) as f:
        workload = json.load(f)
    cell = types.SimpleNamespace(config=config, workload=workload,
                                 bench_dir=spec.BENCH_DIR, name=cell_name)
    records = [types.SimpleNamespace(
        ok=True, got_tokens=101 + 10 * i, sent=0.0, ttft_ms=100.0 + 10 * i,
        done=2.0 + 0.1 * i, prompt_tokens=300 * (i + 1)) for i in range(12)]
    chunks = [{"k": 16, "active": 12, "expert_rows": 16 * (500 + 8 * j),
               "experts_touched": 16 * (150 + j),
               "expert_rows_max": 16 * (9 + j),
               "expert_rows_elsewhere": 16 * 700,
               "state_rows_updated": 16 * (11 + j % 2)} for j in range(5)]
    return {"cell": cell, "peaks": PEAKS, "decode_chunk": 16,
            "trace": _launches(*[(DECODE, 16)] * 4),
            "trace_span": [0.9, 1.1],
            "log": types.SimpleNamespace(records=records),
            "program_spans": program_spans.ProgramSpans([], chunks, [])}


@pytest.mark.parametrize("config,cell,module,parent_s", [
    # the seconds the PARENT's family reader (085977a: ``readers``,
    # ``moe_names``, ``ssm_names``, ``swa_names``, ``mla_names``,
    # ``lfm2_names``, ``dsa_names``, ``sambay_names``
    # ``.decode_step_roofline``) priced these observations at, from its
    # share of a 10 ms step, before it was deleted
    ("internlm2-1.8b", "internlm2-1.8b.serve-batch-decode", "flops",
     0.007037739286253819),
    ("olmoe-1b-7b", "olmoe-1b-7b.serve-batch-decode", "moe_flops",
     0.004842262918552607),
    ("granite-4.0-h-micro", "granite-4.0-h-micro.serve-batch-decode",
     "ssm_flops", 0.01008460215175436),
    ("smallthinker-21b-a3b", "smallthinker-21b-a3b.serve-long-prompt",
     "swa_flops", 0.0040333348822633045),
    ("deepseek-v2", "deepseek-v2.serve-long-prompt", "mla_flops",
     0.011998633714570113),
    ("lfm2-8b-a1b", "lfm2-8b-a1b.serve-batch-decode-wide", "lfm2_flops",
     0.005342080536892694),
    ("keye-vl-2.0-30b-a3b", "keye-vl-2.0-30b-a3b.serve-long-prompt",
     "dsa_flops", 0.003130993063897268),
    ("phi-4-mini-flash-reasoning",
     "phi-4-mini-flash-reasoning.serve-long-prompt", "sambay_flops",
     0.01100107968493337),
    # as 5e17fcb's own floor modules priced them (PR 64: cell 14's grouped
    # matmuls and state update are since counted by ``moe_flops`` /
    # ``ssm_flops`` at the shapes its file states, its experts' load by
    # ``moe_names.chunk_medians``)
    ("solar-open2-250b", "solar-open2-250b.serve-long-prompt", "kda_flops",
     0.008027047273679378),
    ("nemotron-3-super-120b-a12b",
     "nemotron-3-super-120b-a12b.serve-reasoning-decode", "nemotron_flops",
     0.005115520792778819),
])
def test_a_configurations_floor_is_its_familys_of_the_parent(
        config, cell, module, parent_s):
    """The module a served configuration names under ``roofline`` prices
    fixed observations as the family's own reader did, to 1e-9, and
    ``batch.decode_step_roofline``'s one reader divides it by a whole
    launch's step."""
    c = _config(config)
    assert c["roofline"] == module
    obs = fixed_observations(c, cell)
    floor = spec.load_module("lib", module)
    assert floor.decode_step_least_s(obs) == pytest.approx(
        parent_s, rel=1e-9)
    read = spec.load_module("metrics", "decode_step_roofline").read
    assert read(obs) == pytest.approx(100 * parent_s / 10e-3, rel=1e-9)
    assert read(dict(obs, trace=None)) is None
    assert read(dict(obs, trace_span=[None, None])) is None
    unnamed = dict(c)
    del unnamed["roofline"]
    assert read(dict(obs, cell=types.SimpleNamespace(
        **{**vars(obs["cell"]), "config": unnamed}))) is None
    with pytest.raises(spec.SpecError):
        read(dict(obs, cell=types.SimpleNamespace(
            **{**vars(obs["cell"]), "config": dict(c, roofline="none")})))


# ------------------------------------------------------------------- spec
def benchmark_at(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_at(root, name):
    """The cell of ``<root>/BENCHMARK.json`` over ``<root>/benchmarks``: a
    cell test's entry assertions run on the repo's tree and, from the
    rehearsal, on a tree that a later PR's files and entries were added
    to."""
    return spec.Cell(name, os.path.join(root, "benchmarks"),
                     os.path.join(root, "BENCHMARK.json"))


def reader_at(root, name):
    return spec.load_module("metrics", name.rsplit(".", 1)[-1],
                            os.path.join(root, "benchmarks"))


def names_lead_to_files(root):
    """Every name in ``<root>/BENCHMARK.json`` against the files under
    ``<root>/benchmarks`` (the rehearsal calls this on its throw-away
    tree, which holds a configuration that is cut)."""
    benchmark_json = os.path.join(root, "BENCHMARK.json")
    bench_dir = os.path.join(root, "benchmarks")
    with open(benchmark_json) as f:
        bench = json.load(f)
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        cell = spec.Cell(w["name"], bench_dir, benchmark_json)
        assert cell.chips == w["chips"] and len(w["why"]) <= 200
        assert callable(cell.kind.run)
        assert callable(cell.reference.logits)
        assert callable(cell.reference.teacher_forced_gap)
        for group in ("end_to_end", "per_layer"):
            readers = cell.readers(group)
            assert readers, (w["name"], group)
            for entry, read in readers:
                assert callable(read)
                if group == "per_layer":
                    # reported only where the metric it moves is
                    moved = [m for m in cell.metric_entries("end_to_end")
                             if m["name"] == entry["moves"]]
                    assert moved, (w["name"], entry["name"])
        assert "setup_s" in {e["name"] for e, _ in
                             cell.readers("end_to_end")}
    for m in bench["per_layer"]:
        assert m["moves"] in end_to_end
    # one entry a question: the table has room (the ONE count of its
    # entries that any test holds: a later PR appends its own), no reader
    # waits beside it without an entry, and no two entries are one reader
    # of one end-to-end metric in the same cells
    assert len(bench["per_layer"]) <= 128
    entered = {m["name"].rsplit(".", 1)[-1]
               for group in ("end_to_end", "per_layer")
               for m in bench[group]}
    files = {f[:-3] for f in os.listdir(os.path.join(bench_dir, "metrics"))
             if f.endswith(".py") and f != "__init__.py"}
    assert files == entered, (sorted(files - entered),
                              sorted(entered - files))
    asked = [(m["name"].rsplit(".", 1)[-1], m["moves"],
              tuple(m.get("workloads", ()))) for m in bench["per_layer"]]
    assert len(set(asked)) == len(asked)
    served = {w["config"] for w in bench["workloads"]
              if any(w["name"] in m.get("workloads", ())
                     and m["name"].endswith("decode_step_roofline")
                     for m in bench["per_layer"])}
    for c in bench["configs"]:
        with open(os.path.join(root, c["file"])) as f:
            cfg = json.load(f)
        # a served configuration names the module that counts ITS step
        if c["name"] in served:
            floor = spec.load_module("lib", cfg["roofline"], bench_dir)
            assert callable(floor.decode_step_least_s), c["name"]
        assert cfg["source"] == c["source"] and cfg["assumed"]
        # a cut is written out: BENCHMARK.json names the keys, the file
        # says from what to what and why (lib/spec.py's header)
        assert [cut["key"] for cut in cfg["reduced"]] == c["reduced"]
        for cut in cfg["reduced"]:
            assert set(cut) == {"key", "published", "here", "why"}
            assert cfg[cut["key"]] == cut["here"] != cut["published"]
            assert cut["why"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) \
        <= max(1, len(bench["workloads"]) // 4)


def test_every_name_in_benchmark_json_leads_to_its_file():
    names_lead_to_files(spec.ROOT)


def test_unknown_chip_is_an_error_not_a_default():
    from benchmarks.lib import runtime

    assert runtime.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(runtime.BenchmarkRefused):
        runtime.load_peaks("TPU v9 imaginary")
    with pytest.raises(runtime.BenchmarkRefused):
        runtime.load_peaks("_source")
