"""How a prefill wave is cut into padded groups (``serve/llm.py``
``cut_prefill_wave``, ``prefill_shapes``, ``_launch_prefills``): pure
host arithmetic, so everything here runs without a model — on waves drawn
from the benchmark's own traffic files and for the benchmark's own engine
blocks (both read, neither edited)."""

import json
import pathlib
import time
import types

import numpy as np
import pytest

from benchmarks.lib import loadgen
from ray_tpu.serve import llm

BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
LADDER = llm.PREFILL_GROUPS
# a serve cell's traffic, its engine's buckets and the mean number of
# prompts a wave of it held at the parent commit (PERF.md section 6)
TRAFFIC = {"serve-batch-decode": ((64, 128, 256), 12.5),
           "serve-chat-busy": ((64, 128, 256, 512, 1024), 3.4)}
SERVE_CELLS = sorted(p.stem for p in (BENCH / "workloads").glob("*.json")
                     if "engine" in json.loads(p.read_text()))


def _old_rule(lengths, buckets, rungs=(4, 32)):
    """The rule this cut replaced: per length bucket, the smallest rung
    that holds the rows left."""
    by_bucket = {}
    for n in lengths:
        by_bucket.setdefault(next(b for b in buckets if n <= b),
                             []).append(n)
    groups = []
    for bucket, members in by_bucket.items():
        i = 0
        while i < len(members):
            rung = next((r for r in rungs if r >= len(members) - i),
                        rungs[-1])
            groups.append((rung, bucket, members[i:i + rung]))
            i += rung
    return groups


def _waves(traffic, seed, count):
    """Seeded waves of the traffic file's prompt lengths, Poisson sizes."""
    spec = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    lengths = loadgen.Lengths(spec["prompt_tokens"],
                              np.random.default_rng([seed, 1]))
    sizes = np.random.default_rng([seed, 2]).poisson(TRAFFIC[traffic][1],
                                                     count)
    return [[lengths.draw() for _ in range(n)] for n in sizes if n]


def _positions(groups):
    return sum(rows * bucket for rows, bucket, _members in groups)


def _cost(groups):
    return _positions(groups) + llm._LAUNCH_POSITIONS * len(groups)


@pytest.mark.parametrize("rungs", [LADDER, (4,), (2, 4), (1, 2, 4, 8, 16, 32)])
@pytest.mark.parametrize("seed", range(4))
def test_every_entry_lands_in_one_group_that_holds_it(seed, rungs):
    rng = np.random.default_rng(seed)
    buckets = (16, 64, 256, 1024)
    for n in (1, 2, 3, 5, 9, 17, 40, 120):
        lengths = [int(x) for x in rng.integers(1, 1025, n)]
        groups = llm.cut_prefill_wave(lengths, buckets, rungs)
        assert sorted(i for _r, _b, members in groups for i in members) \
            == list(range(n))
        shapes = llm.prefill_shapes(rungs, buckets, n)
        for rows, bucket, members in groups:
            assert (rows, bucket) in shapes
            assert rows == rungs[0] \
                or rows * bucket <= llm._GROUP_POSITIONS
            assert 0 < len(members) <= rows
            assert max(lengths[i] for i in members) <= bucket
            # no emptier rung would have held them, no shorter bucket
            assert rows == min(r for r in rungs if r >= len(members))
            assert bucket == min(b for b in buckets if b >= max(
                lengths[i] for i in members))


def _partitions(items):
    if not items:
        yield []
        return
    head, rest = items[0], items[1:]
    for part in _partitions(rest):
        yield [[head]] + part
        for i in range(len(part)):
            yield part[:i] + [[head] + part[i]] + part[i + 1:]


@pytest.mark.parametrize("seed", range(6))
def test_no_grouping_at_all_is_cheaper(seed):
    """The table looks at runs of the sorted wave only; held against every
    partition of a small wave into groups of any members, each of a shape
    that warm-up holds."""
    rng = np.random.default_rng(seed)
    buckets, rungs = (64, 128, 256, 512, 1024), LADDER
    lengths = [int(x) for x in rng.integers(1, 1025, 7)]
    shapes = set(llm.prefill_shapes(rungs, buckets, len(lengths)))

    def padded(part):
        return [(min(r for r in rungs if r >= len(g)),
                 min(b for b in buckets if b >= max(lengths[i] for i in g)),
                 g) for g in part]

    least = min(_cost(padded(part))
                for part in _partitions(list(range(len(lengths))))
                if all(len(g) <= rungs[-1] for g in part)
                and {(r, b) for r, b, _g in padded(part)} <= shapes)
    assert _cost(llm.cut_prefill_wave(lengths, buckets, rungs)) == least


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("traffic", sorted(TRAFFIC))
def test_less_padding_than_the_old_rule_on_the_cells_traffic(traffic, seed):
    buckets = TRAFFIC[traffic][0]
    tokens = old = new = 0
    for lengths in _waves(traffic, seed, 400):
        was = _old_rule(lengths, buckets)
        now = llm.cut_prefill_wave(lengths, buckets, LADDER)
        # a wave may trade a few positions for a launch saved, never
        # more than the launches it saves are charged
        assert _positions(now) <= _positions(was) + llm._LAUNCH_POSITIONS \
            * max(0, len(was) - len(now)), lengths
        tokens += sum(lengths)
        old += _positions(was)
        new += _positions(now)
    assert 1 - tokens / old > 0.70      # the record: 79.3%, 73.6%
    assert 1 - tokens / new <= 0.45
    assert new < 0.5 * old


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_warm_up_holds_every_shape_the_cut_emits(cell):
    engine = json.loads(
        (BENCH / "workloads" / f"{cell}.json").read_text())["engine"]
    rungs = tuple(engine.get("prefill_groups", LADDER))
    buckets = tuple(sorted(b for b in engine["prefill_buckets"]
                           if b <= engine["max_len"]))
    warmed = set(llm.prefill_shapes(rungs, buckets, engine["max_slots"]))
    # every bucket alone in a row; more rows up to _GROUP_POSITIONS
    assert warmed == {(r, b) for r in rungs for b in buckets
                      if r == rungs[0] or r * b <= 2048}
    # (4,096 and up: a row a launch, whatever the ladder; 512 / 1,024 /
    # 2,048: a row a launch and four rows of 512)
    assert len(warmed) == (len(buckets) if buckets[0] > 2048
                           else 4 if buckets == (512, 1024, 2048)
                           else {3: 9, 5: 12}[len(buckets)])
    rng = np.random.default_rng(7)
    emitted = set()
    for n in list(range(1, 33)) + [engine["max_slots"]] * 8:
        n = min(n, engine["max_slots"])
        for top in (buckets[0], buckets[-1]):   # short waves, mixed waves
            lengths = [int(x) for x in rng.integers(1, top + 1, n)]
            emitted |= {(rows, bucket) for rows, bucket, _m in
                        llm.cut_prefill_wave(lengths, buckets, rungs)}
    assert emitted <= warmed
    # every rung that some bucket admits is reached
    assert {rows for rows, _b in emitted} == {rows for rows, _b in warmed}


@pytest.mark.parametrize("rungs, max_slots, rows", [
    (LADDER, 120, [1, 4, 8]), (LADDER, 5, [1, 4, 8]), (LADDER, 4, [1, 4]),
    (LADDER, 2, [1, 4]), (LADDER, 1, [1]), ((4,), 2, [4]),
    ((2, 4), 64, [2, 4]), ((4, 32), 16, [4, 32])])
def test_no_shape_is_warmed_that_no_wave_can_reach(rungs, max_slots, rows):
    shapes = llm.prefill_shapes(rungs, (32, 64), max_slots)
    assert shapes == [(r, b) for r in rows for b in (32, 64)]
    # and a full wave of the shortest prompts stays inside them
    groups = llm.cut_prefill_wave([1] * max_slots, (32, 64), rungs)
    assert {(r, b) for r, b, _m in groups} <= set(shapes)


@pytest.mark.parametrize("paged, spec_k", [(False, 0), (True, 0), (True, 2)])
def test_warm_and_cold_entries_never_share_a_group(paged, spec_k):
    """``_launch_prefills`` on a stand-in engine: a warm entry (paged
    plane, prefix-cache hit) is cut by its suffix, apart from the cold
    ones; the dense plane has no warm entries whatever pos0 says; a
    speculative engine's draft gets a cut of its own, by whole prompts."""
    launched, drafted = [], []
    buckets = (16, 32, 64)
    engine = types.SimpleNamespace(
        paged=paged, spec_k=spec_k, buckets=buckets, prefill_groups=LADDER,
        _jnp=None,
        _launch_prefill_group=lambda g, bucket, warm, group, jnp:
        launched.append((g, bucket, warm, group)),
        _launch_draft_prefill=lambda g, bucket, group, jnp:
        drafted.append((g, bucket, group)))
    rng = np.random.default_rng(3)
    wave = []
    for slot in range(11):
        pos0 = int(rng.choice([0, 0, 16, 32]))
        n = int(rng.integers(1, 65 - pos0))
        request = types.SimpleNamespace(prompt=[1] * (pos0 + n), slot=slot)
        wave.append((slot, request, n, pos0))
    llm.LLMServer._launch_prefills(engine, wave)
    by_slot = sorted(wave, key=lambda e: e[0])
    assert sorted((e for _g, _b, _w, group in launched for e in group),
                  key=lambda e: e[0]) == by_slot
    shapes = llm.prefill_shapes(LADDER, buckets, len(wave))
    for g, bucket, warm, group in launched:
        assert len(group) <= g and (g, bucket) in shapes
        assert all((paged and pos0 > 0) == warm
                   for _s, _r, _n, pos0 in group)
        assert max(n for _s, _r, n, _p in group) <= bucket
    assert any(warm for _g, _b, warm, _group in launched) == paged
    if not spec_k:
        assert not drafted
        return
    assert sorted((e for _g, _b, group in drafted for e in group),
                  key=lambda e: e[0]) == by_slot
    for g, bucket, group in drafted:
        assert len(group) <= g and (g, bucket) in shapes
        assert max(len(r.prompt) for _s, r, _n, _p in group) <= bucket


def test_a_full_wave_is_cut_in_well_under_a_decode_chunk():
    """n = max_slots = 120 (cell 3); a decode chunk is ~0.28 s on the
    chip.  The limit is generous so that the case is steady under the
    suite's six workers."""
    rng = np.random.default_rng(0)
    lengths = [int(x) for x in rng.integers(1, 1025, 120)]
    buckets = (64, 128, 256, 512, 1024)
    llm.cut_prefill_wave(lengths, buckets, LADDER)
    t0 = time.perf_counter()
    for _ in range(5):
        groups = llm.cut_prefill_wave(lengths, buckets, LADDER)
    assert (time.perf_counter() - t0) / 5 < 0.2
    assert sum(len(m) for _r, _b, m in groups) == 120


def test_the_ladder_and_the_widest_group():
    assert LADDER[0] == 1 and LADDER == tuple(sorted(set(LADDER)))
    assert LADDER[-1] == 8
    # the least rung carries any bucket; nothing wider than the limit
    shapes = llm.prefill_shapes(LADDER, (64, 512, 1024, 4096), 64)
    assert [b for r, b in shapes if r == 1] == [64, 512, 1024, 4096]
    assert max(r * b for r, b in shapes if r > 1) <= llm._GROUP_POSITIONS
    assert (4, 512) in shapes and (8, 512) not in shapes
    # eight long prompts: eight launches of one row, not one of 8 x 1,024
    groups = llm.cut_prefill_wave([1000] * 8, (64, 512, 1024), LADDER)
    assert [(r, b) for r, b, _m in groups] == [(1, 1024)] * 8
