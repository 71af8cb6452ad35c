"""Head-resident metrics time-series store + windowed query engine.

PR 3 gave every process a metrics registry and the head a
*point-in-time* aggregation (`cluster_metrics` = each node's latest
``export_state`` snapshot).  This module adds the **history** between
those snapshots, in the mold of the Gorilla / Monarch in-memory TSDBs:

- every ``push_events`` flush lands its timestamped snapshot here, one
  bounded **compressed series** per (metric, tagset, node): timestamps
  delta-of-delta encoded, values XOR-encoded (Gorilla §4.1) — a
  counter ticking every second costs ~1–2 bytes/sample instead of 16;
- retention is a **window, not a ledger**: sealed chunks age out past
  ``RAY_TPU_TSDB_RETAIN_S`` and the series dimension is capped
  (``RAY_TPU_TSDB_MAX_SERIES``, drop-new + counted) so cardinality
  bugs cost a counter, not head memory;
- counters are **reset-aware**: each snapshot carries its process's
  incarnation id (``metrics.INCARNATION``), so a restarted worker's
  counter restarting from zero is recorded as a reset point and
  ``rate()`` adds the post-restart value instead of a huge negative
  delta (value-drop detection is the fallback for legacy snapshots);
- a small **windowed query engine** answers
  ``fn(metric{label=value})[window] by (label)`` — ``rate`` /
  ``increase`` over counters, ``avg/min/max/sum_over_time`` / ``last``
  over gauges, ``p50``/``p9x`` quantiles interpolated from histogram
  bucket series — exposed as the head RPC ``metrics_query``, the
  dashboard ``/api/metrics/query``, and ``ray_tpu metrics query``.

The windowed-read surface is the input plane for the alert/SLO rules
(observability/alerts.py) and the contract the metrics-driven
autoscaler consumes next (ROADMAP item 1).
"""

from __future__ import annotations

import math
import os
import re
import struct
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Tuple

DEFAULT_RETAIN_S = float(os.environ.get("RAY_TPU_TSDB_RETAIN_S", "600"))
DEFAULT_MAX_SERIES = int(os.environ.get(
    "RAY_TPU_TSDB_MAX_SERIES", "20000"))
# Samples per chunk before it seals: retention evicts whole sealed
# chunks, so this bounds both the eviction granularity and the open
# chunk's decode cost per query.
CHUNK_SAMPLES = 120

_enabled = True


def enable() -> None:
    """(Re-)enable ingest process-wide."""
    global _enabled
    _enabled = True


def disable() -> None:
    """Disable ingest process-wide: ``TSDB.ingest`` becomes a no-op.
    Queries still answer from already-stored history."""
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


# ---------------------------------------------------------------- bits
class _BitWriter:
    __slots__ = ("_buf", "_bits", "_nbits")

    def __init__(self):
        self._buf = bytearray()
        self._bits = 0      # pending bits, MSB-first accumulator
        self._nbits = 0

    def write(self, value: int, nbits: int) -> None:
        self._bits = (self._bits << nbits) | (value & ((1 << nbits) - 1))
        self._nbits += nbits
        while self._nbits >= 8:
            self._nbits -= 8
            self._buf.append((self._bits >> self._nbits) & 0xFF)
        self._bits &= (1 << self._nbits) - 1

    def getvalue(self) -> bytes:
        """Byte-aligned copy (trailing partial byte zero-padded)."""
        out = bytes(self._buf)
        if self._nbits:
            out += bytes([(self._bits << (8 - self._nbits)) & 0xFF])
        return out

    def __len__(self) -> int:
        return len(self._buf) + (1 if self._nbits else 0)


class _BitReader:
    __slots__ = ("_data", "_pos")

    def __init__(self, data: bytes):
        self._data = data
        self._pos = 0  # bit position

    def read(self, nbits: int) -> int:
        pos = self._pos
        end = pos + nbits
        first = pos >> 3
        last = (end + 7) >> 3
        chunk = int.from_bytes(self._data[first:last], "big")
        chunk >>= (last << 3) - end
        self._pos = end
        return chunk & ((1 << nbits) - 1)


def _f2b(v: float) -> int:
    return struct.unpack("<Q", struct.pack("<d", v))[0]


def _b2f(b: int) -> float:
    return struct.unpack("<d", struct.pack("<Q", b))[0]


class GorillaChunk:
    """Append-only compressed block of (timestamp, float) samples.

    Timestamps are stored at millisecond resolution, delta-of-delta
    encoded with the paper's variable-length buckets; values XOR
    against the previous value with the leading/meaningful-bit window
    reuse trick.  Chunks seal at ``CHUNK_SAMPLES`` — retention evicts
    sealed chunks whole."""

    __slots__ = ("start_ts", "end_ts", "count", "_w",
                 "_prev_tms", "_prev_delta", "_prev_bits",
                 "_prev_lead", "_prev_mlen")

    def __init__(self):
        self.start_ts = 0.0
        self.end_ts = 0.0
        self.count = 0
        self._w = _BitWriter()
        self._prev_tms = 0
        self._prev_delta = 0
        self._prev_bits = 0
        self._prev_lead = -1
        self._prev_mlen = -1

    @property
    def full(self) -> bool:
        return self.count >= CHUNK_SAMPLES

    def nbytes(self) -> int:
        return len(self._w)

    def append(self, ts: float, value: float) -> None:
        tms = int(round(ts * 1000.0))
        bits = _f2b(value)
        w = self._w
        if self.count == 0:
            self.start_ts = ts
            w.write(tms, 64)
            w.write(bits, 64)
            self._prev_delta = 0
        else:
            delta = tms - self._prev_tms
            dod = delta - self._prev_delta
            if dod == 0:
                w.write(0, 1)
            elif -63 <= dod <= 64:
                w.write(0b10, 2)
                w.write(dod + 63, 7)
            elif -255 <= dod <= 256:
                w.write(0b110, 3)
                w.write(dod + 255, 9)
            elif -2047 <= dod <= 2048:
                w.write(0b1110, 4)
                w.write(dod + 2047, 12)
            else:
                w.write(0b1111, 4)
                w.write(dod & ((1 << 64) - 1), 64)
            self._prev_delta = delta
            xor = bits ^ self._prev_bits
            if xor == 0:
                w.write(0, 1)
            else:
                lead = min(31, 64 - xor.bit_length())
                trail = (xor & -xor).bit_length() - 1
                mlen = 64 - lead - trail
                if (self._prev_lead >= 0 and lead >= self._prev_lead
                        and trail >= 64 - self._prev_lead
                        - self._prev_mlen):
                    # Fits the previous meaningful window: reuse it.
                    w.write(0b10, 2)
                    shift = 64 - self._prev_lead - self._prev_mlen
                    w.write(xor >> shift, self._prev_mlen)
                else:
                    w.write(0b11, 2)
                    w.write(lead, 5)
                    w.write(mlen - 1, 6)
                    w.write(xor >> trail, mlen)
                    self._prev_lead = lead
                    self._prev_mlen = mlen
        self._prev_tms = tms
        self._prev_bits = bits
        self.end_ts = ts
        self.count += 1

    def samples(self) -> List[Tuple[float, float]]:
        if self.count == 0:
            return []
        r = _BitReader(self._w.getvalue())
        tms = r.read(64)
        bits = r.read(64)
        out = [(tms / 1000.0, _b2f(bits))]
        delta = 0
        lead = mlen = 0
        for _ in range(self.count - 1):
            if r.read(1):
                if r.read(1):
                    if r.read(1):
                        if r.read(1):
                            dod = r.read(64)
                            if dod >= 1 << 63:
                                dod -= 1 << 64
                        else:
                            dod = r.read(12) - 2047
                    else:
                        dod = r.read(9) - 255
                else:
                    dod = r.read(7) - 63
            else:
                dod = 0
            delta += dod
            tms += delta
            if r.read(1):
                if r.read(1):
                    lead = r.read(5)
                    mlen = r.read(6) + 1
                xor = r.read(mlen) << (64 - lead - mlen)
                bits ^= xor
            out.append((tms / 1000.0, _b2f(bits)))
        return out


# -------------------------------------------------------------- series
_KIND_COUNTER = "counter"
_KIND_GAUGE = "gauge"


class Series:
    """One (metric, tagset) sample stream: sealed Gorilla chunks plus
    a STAGED open tail (plain tuples, batch-encoded only when it
    reaches CHUNK_SAMPLES — Gorilla's own open-block design).  The
    per-flush ingest cost is a list append; the encode cost amortizes
    over a whole chunk; and queries over the hot tail skip decode
    entirely.  Counter reset points (incarnation change / value drop)
    are recorded at ingest."""

    __slots__ = ("name", "kind", "labels", "chunks", "open",
                 "last_ts", "last_value", "resets", "birth_ts",
                 "incarnation")

    def __init__(self, name: str, kind: str, labels: Dict[str, str]):
        self.name = name
        self.kind = kind
        self.labels = labels
        self.chunks: List[GorillaChunk] = []     # sealed, oldest first
        self.open: List[Tuple[float, float]] = []
        self.last_ts = float("-inf")
        self.last_value: Optional[float] = None
        self.resets: List[float] = []
        # Incarnation of the LAST append, tracked per series (not per
        # node): a counter created lazily — absent from the restarted
        # process's first flush, present in a later one — still gets
        # its reset point the first time the new incarnation touches
        # it, even when it has re-accumulated past the old value.
        self.incarnation = ""
        # First-ever sample time (plain float — survives chunk
        # eviction): a counter BORN inside a query window contributes
        # its first value to increase/rate, so the famous "first
        # increment is invisible to rate()" gotcha doesn't eat e.g.
        # the first stuck-detector snapshot an alert watches for.
        self.birth_ts: Optional[float] = None

    def append(self, ts: float, value: float,
               incarnation: str = "") -> None:
        # Quantize to the chunk encoder's ms grid up front, so staged
        # and decoded samples compare identically.
        ts = int(round(ts * 1000.0)) / 1000.0
        if ts <= self.last_ts:
            return  # duplicate / out-of-order flush: drop, keep order
        if self.kind == _KIND_COUNTER and self.last_value is not None \
                and ((incarnation and self.incarnation
                      and incarnation != self.incarnation)
                     or value < self.last_value):
            self.resets.append(ts)
        if incarnation:
            self.incarnation = incarnation
        self.open.append((ts, float(value)))
        if len(self.open) >= CHUNK_SAMPLES:
            self._seal()
        if self.birth_ts is None:
            self.birth_ts = ts
        self.last_ts = ts
        self.last_value = value

    def _seal(self) -> None:
        chunk = GorillaChunk()
        for t, v in self.open:
            chunk.append(t, v)
        self.chunks.append(chunk)
        self.open = []

    def samples_between(self, t0: float, t1: float,
                        anchor: bool = False
                        ) -> List[Tuple[float, float]]:
        """Samples with t0 < ts <= t1; with ``anchor`` also the single
        newest sample at or before t0 (the rate/increase baseline)."""
        out: List[Tuple[float, float]] = []
        anchor_sample: Optional[Tuple[float, float]] = None
        # Chunks are time-ordered: only the NEWEST chunk wholly
        # before t0 can hold the anchor — decode from there, not from
        # the head of retention (the alert loop queries every series
        # every tick; a full-retention decode per query is ~5x the
        # needed work at the default window/retention ratio).
        start = 0
        for i, chunk in enumerate(self.chunks):
            if chunk.end_ts <= t0:
                start = i if anchor else i + 1
            else:
                break
        for chunk in self.chunks[start:]:
            if chunk.start_ts > t1:
                break
            for s in chunk.samples():
                if s[0] <= t0:
                    anchor_sample = s
                elif s[0] <= t1:
                    out.append(s)
        for s in self.open:
            if s[0] <= t0:
                anchor_sample = s
            elif s[0] <= t1:
                out.append(s)
        if anchor and anchor_sample is not None:
            out.insert(0, anchor_sample)
        return out

    def evict_before(self, cutoff: float) -> None:
        """Drop sealed chunks wholly older than ``cutoff`` (the open
        tail always stays — it is bounded at CHUNK_SAMPLES)."""
        while self.chunks and self.chunks[0].end_ts < cutoff:
            self.chunks.pop(0)
        if self.resets and self.resets[0] < cutoff:
            self.resets = [t for t in self.resets if t >= cutoff]

    def nbytes(self) -> int:
        return (sum(c.nbytes() for c in self.chunks)
                + 16 * len(self.open))

    def sample_count(self) -> int:
        return sum(c.count for c in self.chunks) + len(self.open)


# --------------------------------------------------------------- query
_QUERY_RE = re.compile(
    r"""^\s*(?P<fn>[a-z][a-z0-9_]*)\s*\(\s*
        (?P<metric>[A-Za-z_:][A-Za-z0-9_:]*)\s*
        (?:\{(?P<matchers>[^}]*)\})?\s*\)\s*
        \[\s*(?P<num>\d+(?:\.\d+)?)\s*(?P<unit>ms|s|m|h)\s*\]\s*
        (?:by\s*\(\s*(?P<by>[A-Za-z0-9_,\s]*)\)\s*)?$""",
    re.VERBOSE)
_MATCHER_RE = re.compile(
    r"""\s*(?P<label>[A-Za-z_][A-Za-z0-9_]*)\s*=\s*
        (?:"(?P<q>[^"]*)"|'(?P<sq>[^']*)'|(?P<raw>[^,]*?))\s*
        (?:,|$)""", re.VERBOSE)
_UNIT_S = {"ms": 0.001, "s": 1.0, "m": 60.0, "h": 3600.0}
_OVER_TIME_FNS = {"avg_over_time", "min_over_time", "max_over_time",
                  "sum_over_time", "last"}
_COUNTER_FNS = {"rate", "increase"}


class QueryError(ValueError):
    """Malformed query expression (bad grammar, unknown function)."""


class Query:
    __slots__ = ("fn", "metric", "matchers", "window_s", "by",
                 "quantile", "expr")

    def __init__(self, fn: str, metric: str,
                 matchers: Dict[str, str], window_s: float,
                 by: Tuple[str, ...], quantile: Optional[float],
                 expr: str):
        self.fn = fn
        self.metric = metric
        self.matchers = matchers
        self.window_s = window_s
        self.by = by
        self.quantile = quantile
        self.expr = expr


def parse_query(expr: str) -> Query:
    """``fn(metric{label=value,...})[window] by (label, ...)`` —
    fn ∈ rate | increase | avg/min/max/sum_over_time | last | p50/p9x
    (pNN → the NN-th percentile from histogram buckets); window is
    ``<num><ms|s|m|h>``."""
    m = _QUERY_RE.match(expr)
    if m is None:
        raise QueryError(
            f"malformed query {expr!r}: expected "
            f"fn(metric{{label=value}})[window] by (label)")
    fn = m.group("fn")
    quantile = None
    pm = re.fullmatch(r"p(\d{1,3})", fn)
    if pm is not None:
        digits = pm.group(1)
        quantile = int(digits) / (10 ** len(digits))
        if not 0.0 < quantile < 1.0:
            raise QueryError(f"quantile out of range in {fn!r}")
    elif fn not in _OVER_TIME_FNS | _COUNTER_FNS:
        raise QueryError(
            f"unknown function {fn!r} (rate, increase, "
            f"avg/min/max/sum_over_time, last, p50..p999)")
    matchers: Dict[str, str] = {}
    raw = m.group("matchers")
    if raw and raw.strip():
        pos = 0
        while pos < len(raw.rstrip()):
            mm = _MATCHER_RE.match(raw, pos)
            if mm is None:
                raise QueryError(f"malformed matcher list {raw!r}")
            value = mm.group("q")
            if value is None:
                value = mm.group("sq")
            if value is None:
                value = (mm.group("raw") or "").strip()
            matchers[mm.group("label")] = value
            pos = mm.end()
    window_s = float(m.group("num")) * _UNIT_S[m.group("unit")]
    if window_s <= 0:
        raise QueryError("window must be positive")
    by_raw = m.group("by")
    by = tuple(s.strip() for s in by_raw.split(",")
               if s.strip()) if by_raw else ()
    return Query(fn, m.group("metric"), matchers, window_s, by,
                 quantile, expr)


def _delta_sum(samples: List[Tuple[float, float]],
               resets: List[float]) -> Optional[float]:
    """Reset-aware increase over an ordered sample run: a pair with a
    recorded reset between it (or a value drop) contributes the NEW
    value — everything the restarted process accumulated — instead of
    a negative delta."""
    if len(samples) < 2:
        return None
    total = 0.0
    ri = 0
    for (t0, v0), (t1, v1) in zip(samples, samples[1:]):
        while ri < len(resets) and resets[ri] <= t0:
            ri += 1
        reset_between = ri < len(resets) and t0 < resets[ri] <= t1
        if reset_between or v1 < v0:
            total += v1
        else:
            total += v1 - v0
    return total


def _window_increase(s: "Series", t0: float,
                     t1: float) -> Optional[float]:
    """Reset-aware counter increase over (t0, t1], birth-aware: a
    series whose FIRST-EVER sample lands inside the window counts
    that value too (it rose 0 → v since birth) — unlike Prometheus,
    the store ingests continuously and knows birth from a mere
    retention gap, so the first increment is never invisible."""
    samples = s.samples_between(t0, t1, anchor=True)
    if not samples:
        return None
    born_in_window = (s.birth_ts is not None and s.birth_ts > t0
                      and samples[0][0] == s.birth_ts)
    inc = _delta_sum(samples, s.resets)
    if inc is None:
        if not born_in_window:
            return None   # lone mid-life sample: baseline unknown
        inc = 0.0
    if born_in_window:
        inc += samples[0][1]
    return inc


class TSDB:
    """The label-indexed series store (one per head)."""

    def __init__(self, retain_s: Optional[float] = None,
                 max_series: Optional[int] = None):
        self.retain_s = (DEFAULT_RETAIN_S if retain_s is None
                         else float(retain_s))
        self.max_series = (DEFAULT_MAX_SERIES if max_series is None
                           else int(max_series))
        self._series: Dict[Tuple[str, Tuple[Tuple[str, str], ...]],
                           Series] = {}
        self._by_name: Dict[str, List[Series]] = {}
        # Ingest fast path: (node_id, metric, raw tagset key) →
        # Series.  Every flush re-presents the same identities; this
        # skips rebuilding + sorting the label dict per sample
        # (measured ~3x on the ingest-overhead bench).  Invalidated
        # by eviction (cleared wholesale — rebuilt in one flush).
        self._fast: Dict[Tuple, Optional[Series]] = {}
        self._lock = threading.Lock()
        self.dropped_series = 0   # cardinality-cap drops
        self.ingested_samples = 0
        self._last_evict = 0.0
        self._max_ts = 0.0

    # ------------------------------------------------------- ingest
    def _get_series(self, name: str, kind: str,
                    labels: Dict[str, str]) -> Optional[Series]:
        key = (name, tuple(sorted(labels.items())))
        s = self._series.get(key)
        if s is None:
            if len(self._series) >= self.max_series:
                self.dropped_series += 1
                return None
            s = Series(name, kind, labels)
            self._series[key] = s
            self._by_name.setdefault(name, []).append(s)
        return s

    def ingest(self, node_id: str, state: Dict[str, Dict],
               ts: Optional[float] = None,
               incarnation: str = "") -> int:
        """Fold one node's ``metrics.export_state()`` snapshot into
        the series index.  ``incarnation`` identifies the shipping
        process; each counter series records a reset point the first
        time a NEW incarnation touches it (per series, not per flush
        — lazily-created counters absent from the restarted process's
        first flush still get their reset marker later)."""
        if not _enabled or not state:
            return 0
        ts = time.time() if ts is None else float(ts)
        appended = 0
        with self._lock:
            fast = self._fast
            miss = object()
            for name, entry in state.items():
                kind = entry.get("kind", _KIND_GAUGE)
                tag_keys = None
                skind = (_KIND_COUNTER if kind == "counter"
                         else _KIND_GAUGE)
                for key, value in (entry.get("values") or {}).items():
                    fk = (node_id, name, key)
                    s = fast.get(fk, miss)
                    if s is miss:
                        if tag_keys is None:
                            tag_keys = tuple(
                                entry.get("tag_keys") or ())
                        labels = {"node_id": node_id}
                        labels.update((k, v) for k, v in
                                      zip(tag_keys, key) if v)
                        if kind == "histogram":
                            # values holds per-tagset observation
                            # SUMS for histograms.
                            s = self._get_series(
                                name + "_sum", _KIND_COUNTER, labels)
                        else:
                            s = self._get_series(name, skind, labels)
                        fast[fk] = s
                    if s is not None:
                        s.append(ts, float(value), incarnation)
                        appended += 1
                if kind == "histogram":
                    bounds = entry.get("boundaries") or []
                    for key, counts in (entry.get("counts")
                                        or {}).items():
                        fk = (node_id, name, key, "buckets")
                        row = fast.get(fk, miss)
                        if row is miss:
                            if tag_keys is None:
                                tag_keys = tuple(
                                    entry.get("tag_keys") or ())
                            base = {"node_id": node_id}
                            base.update((k, v) for k, v in
                                        zip(tag_keys, key) if v)
                            row = [self._get_series(
                                name + "_bucket", _KIND_COUNTER,
                                {**base, "le": repr(float(b))})
                                for b in bounds]
                            row.append(self._get_series(
                                name + "_bucket", _KIND_COUNTER,
                                {**base, "le": "+Inf"}))
                            row.append(self._get_series(
                                name + "_count", _KIND_COUNTER,
                                base))
                            fast[fk] = row
                        cum = 0
                        for c, s in zip(counts, row):
                            cum += c
                            if s is not None:
                                s.append(ts, float(cum), incarnation)
                                appended += 1
                        for s in row[len(bounds) + 1:]:
                            if s is not None:
                                s.append(ts, float(cum), incarnation)
                                appended += 1
            self.ingested_samples += appended
            # Eviction runs against the newest INGESTED timestamp, not
            # the wall clock: the sample stream defines the window
            # (and replayed history — boot-time ring rescans, tests
            # with synthetic clocks — must not age itself out).
            if ts > self._max_ts:
                self._max_ts = ts
            if (self._max_ts - self._last_evict
                    >= max(1.0, self.retain_s / 16)):
                self._evict_locked(self._max_ts)
        return appended

    def _evict_locked(self, now: float) -> None:
        self._last_evict = now
        cutoff = now - self.retain_s
        dead = []
        for key, s in self._series.items():
            s.evict_before(cutoff)
            if s.last_ts < cutoff:
                dead.append(key)
        for key in dead:
            s = self._series.pop(key)
            peers = self._by_name.get(s.name)
            if peers is not None:
                try:
                    peers.remove(s)
                except ValueError:
                    pass
                if not peers:
                    self._by_name.pop(s.name, None)
        if dead:
            # The ingest fast path may hold evicted Series objects;
            # drop it wholesale — one flush rebuilds it.
            self._fast.clear()

    # -------------------------------------------------------- query
    def _matching(self, name: str,
                  matchers: Dict[str, str]) -> List[Series]:
        out = []
        for s in self._by_name.get(name, ()):
            if all(s.labels.get(k) == v for k, v in matchers.items()):
                out.append(s)
        return out

    @staticmethod
    def _series_value(q: Query, s: Series, t0: float,
                      t1: float) -> Optional[float]:
        if q.fn in _COUNTER_FNS:
            inc = _window_increase(s, t0, t1)
            if inc is None:
                return None
            return inc / q.window_s if q.fn == "rate" else inc
        values = [v for _t, v in s.samples_between(t0, t1)]
        if not values:
            return None
        if q.fn == "avg_over_time":
            return sum(values) / len(values)
        if q.fn == "min_over_time":
            return min(values)
        if q.fn == "max_over_time":
            return max(values)
        if q.fn == "sum_over_time":
            return sum(values)
        return values[-1]  # last

    @staticmethod
    def _group_labels(q: Query, labels: Dict[str, str]
                      ) -> Tuple[Dict[str, str], Tuple]:
        if q.by:
            sub = {k: labels.get(k, "") for k in q.by}
        else:
            sub = {k: v for k, v in labels.items() if k != "le"}
        return sub, tuple(sorted(sub.items()))

    def query(self, expr, now: Optional[float] = None
              ) -> Dict[str, Any]:
        """Evaluate one expression; returns ``{"expr", "fn",
        "window_s", "rows": [{"labels", "value"}, ...]}``.  Rows are
        per matching series, or per ``by``-group (grouped rates/
        increases/sums SUM across the group; avg averages, min/max
        fold; quantiles merge bucket increments before
        interpolating)."""
        q = expr if isinstance(expr, Query) else parse_query(expr)
        t1 = time.time() if now is None else float(now)
        t0 = t1 - q.window_s
        rows: List[Dict[str, Any]] = []
        with self._lock:
            if q.quantile is not None:
                rows = self._quantile_rows_locked(q, t0, t1)
            else:
                groups: Dict[Tuple, Dict[str, Any]] = {}
                for s in self._matching(q.metric, q.matchers):
                    v = self._series_value(q, s, t0, t1)
                    if v is None:
                        continue
                    sub, gkey = self._group_labels(q, s.labels)
                    g = groups.setdefault(
                        gkey, {"labels": sub, "values": []})
                    g["values"].append(v)
                for g in groups.values():
                    vals = g.pop("values")
                    if q.fn == "avg_over_time":
                        g["value"] = sum(vals) / len(vals)
                    elif q.fn == "min_over_time":
                        g["value"] = min(vals)
                    elif q.fn == "max_over_time":
                        g["value"] = max(vals)
                    else:  # rate / increase / sum_over_time / last
                        g["value"] = sum(vals)
                    rows.append(g)
        rows.sort(key=lambda r: sorted(r["labels"].items()))
        return {"expr": q.expr, "fn": q.fn, "window_s": q.window_s,
                "rows": rows}

    def _quantile_rows_locked(self, q: Query, t0: float,
                              t1: float) -> List[Dict[str, Any]]:
        """pNN: per-bucket window increments merged per group, then a
        Prometheus-style linear interpolation inside the bucket the
        rank lands in (+Inf clamps to the highest finite bound)."""
        buckets: Dict[Tuple, Dict[str, Any]] = {}
        for s in self._matching(q.metric + "_bucket", q.matchers):
            le_raw = s.labels.get("le", "")
            le = math.inf if le_raw == "+Inf" else float(le_raw)
            inc = _window_increase(s, t0, t1)
            if inc is None:
                continue
            sub, gkey = self._group_labels(q, s.labels)
            g = buckets.setdefault(
                gkey, {"labels": sub, "les": {}})
            g["les"][le] = g["les"].get(le, 0.0) + inc
        rows = []
        for g in buckets.values():
            les = sorted(g["les"].items())
            total = g["les"].get(math.inf, 0.0)
            if total <= 0:
                continue
            rank = q.quantile * total
            cum_prev = 0.0
            bound_prev = 0.0
            value = None
            finite = [b for b, _ in les if b != math.inf]
            for bound, cum in les:
                if cum >= rank:
                    if bound == math.inf:
                        value = finite[-1] if finite else math.nan
                    elif cum == cum_prev:
                        value = bound
                    else:
                        lo = bound_prev if cum_prev > 0 or bound > 0 \
                            else min(0.0, bound)
                        value = lo + (bound - lo) * (
                            (rank - cum_prev) / (cum - cum_prev))
                    break
                cum_prev, bound_prev = cum, bound
            if value is not None and not math.isnan(value):
                rows.append({"labels": g["labels"],
                             "value": float(value)})
        return rows

    # --------------------------------------------------------- misc
    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "series": len(self._series),
                "bytes": sum(s.nbytes()
                             for s in self._series.values()),
                "dropped_series": self.dropped_series,
                "ingested_samples": self.ingested_samples,
                "retain_s": self.retain_s,
            }

    def series_names(self) -> List[str]:
        with self._lock:
            return sorted(self._by_name)


def query_cluster(client, expr: str,
                  timeout: float = 30.0) -> Dict[str, Any]:
    """The head-RPC query surface (`metrics_query`) — same rows the
    dashboard's ``/api/metrics/query`` and the CLI print."""
    return client.head.call("metrics_query", {"expr": expr},
                            timeout=timeout)
