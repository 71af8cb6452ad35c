"""The one general load generator.  A traffic mix is a data file
(``benchmarks/traffic/<name>.json``); this module turns it and a seed
into inputs, sends them, and keeps a log.  The program under test
receives only the generated inputs.

``generator: "requests"`` — serving traffic:

    arrivals       {"process": "closed", "callers": N, "lead_in_s": s,
                    "drain_s": s}
                   {"process": "poisson", "rate_per_s": r, "lead_in_s": s,
                    "drain_s": s}
    prompt_tokens  {"dist": "lognormal", "median", "sigma", "min", "max",
                    "stratified": n}
    output_tokens  the same

A fixed amount of work from the seed: Poisson gaps are scaled so that
exactly rate x span requests fall in the lead-in and in the window,
each (that IS the process, given its counts); a closed loop's callers
start part-way through their first request, as in a steady state; a
length distribution with ``"stratified": n`` is sampled n values at a time at
evenly spaced quantiles, shuffled — the same distribution, every seed
with the same amount of work, only order and timing left to chance.
Token ids are uniform from the seed.

``generator: "token_batches"`` — training input:

    batch, seq_len, distinct_batches

No cell sets anything else, so nothing else is here: bursty arrivals,
shared prefixes and other length distributions come with the cell that
needs them (PERF.md section 7).

Times are ``time.perf_counter()`` of this process.  An open loop sends
on its schedule whatever the system does and times each request from
when it was DUE; how late the generator itself ran is in the log
(``sent - due``).  A closed loop sends a caller's next request when the
last one returns.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional

import numpy as np

_NORMAL = statistics.NormalDist()


# ------------------------------------------------------------ drawing
def quantile(spec: Dict[str, Any], u: float) -> int:
    """The length at quantile ``u`` of ``{"dist": "lognormal", "median",
    "sigma", "min", "max"}``, clipped to [min, max]."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = spec["median"] * math.exp(
        spec["sigma"] * _NORMAL.inv_cdf(min(max(u, 1e-9), 1 - 1e-9)))
    return int(min(max(round(x), spec["min"]), spec["max"]))


class Lengths:
    """Draws from a length distribution ``"stratified": n`` at a time: at
    the quantiles (i + 0.5) / n in a seeded order, so every n draws hold
    the same lengths."""

    def __init__(self, spec: Dict[str, Any], rng: np.random.Generator):
        self.spec, self.rng = spec, rng
        self.block: List[int] = []

    def draw(self) -> int:
        if not self.block:
            n = int(self.spec["stratified"])
            self.block = [quantile(self.spec, (i + 0.5) / n)
                          for i in self.rng.permutation(n)]
        return self.block.pop()


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *map(int, stream)])


def token_batches(traffic: Dict[str, Any], seed: int,
                  vocab: int) -> np.ndarray:
    """(distinct_batches * batch, seq_len) int32 token ids."""
    rows = traffic["distinct_batches"] * traffic["batch"]
    return _rng(seed, 0).integers(
        0, vocab, (rows, traffic["seq_len"])).astype(np.int32)


class RequestSource:
    """The seeded stream of requests of one caller (closed loop) or of
    the whole schedule (open loop, ``stream`` 0)."""

    def __init__(self, traffic: Dict[str, Any], seed: int, stream: int,
                 vocab: int):
        self.vocab = vocab
        self.rng = _rng(seed, 1, stream)
        self.prompt_len = Lengths(traffic["prompt_tokens"],
                                  _rng(seed, 5, stream))
        self.output_len = Lengths(traffic["output_tokens"],
                                  _rng(seed, 6, stream))

    def next(self, output_scale: float = 1.0) -> Dict[str, Any]:
        n_prompt = self.prompt_len.draw()
        n_out = self.output_len.draw()
        if output_scale != 1.0:
            n_out = max(1, int(round(n_out * output_scale)))
        prompt = self.rng.integers(1, self.vocab, n_prompt)
        return {"prompt": prompt.tolist(), "max_new_tokens": n_out}


def arrival_offsets(arrivals: Dict[str, Any], seed: int,
                    seconds: float) -> np.ndarray:
    """Open loop: seconds relative to the window's opening at which each
    request is due, from ``-lead_in_s`` up to ``seconds``."""
    rate = float(arrivals["rate_per_s"])
    lead = float(arrivals.get("lead_in_s", 0.0))
    if arrivals["process"] != "poisson":
        raise ValueError(f"not an open-loop process: "
                         f"{arrivals['process']!r}")
    rng = _rng(seed, 3)

    def stretch(span: float) -> np.ndarray:
        # rate x span arrivals in the span: the next one is the first
        # past its end
        due = np.cumsum(rng.exponential(1.0 / rate,
                                        int(round(span * rate)) + 1))
        return due[:-1] * (span / due[-1])

    # The lead-in and the window hold their counts each: every seed
    # offers the window the same load, not 97-103% of it.
    return np.concatenate([stretch(lead) - lead, stretch(seconds)])


# ------------------------------------------------------------- sending
@dataclasses.dataclass
class Record:
    """One request as the generator saw it."""
    index: int
    caller: int
    prompt_tokens: int
    asked_tokens: int
    due: Optional[float]      # open loop only
    sent: float = 0.0
    done: float = 0.0
    ok: bool = False
    error: str = ""
    got_tokens: int = 0
    tokens_valid: bool = False
    ttft_ms: float = float("nan")   # as the engine's response states it


@dataclasses.dataclass
class Log:
    records: List[Record]
    t_open: float
    t_close: float
    closed_loop: bool

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def measured(self) -> List[Record]:
        """The window's requests: an open loop is judged on those DUE in
        the window, a closed loop on those in the system during any part
        of it (sent before it closed, not finished before it opened).
        All of them are awaited after the window closes."""
        if self.closed_loop:
            return [r for r in self.records
                    if r.sent < self.t_close
                    and not (r.done and r.done < self.t_open)]
        return [r for r in self.records
                if self.t_open <= r.due < self.t_close]

    def tokens_in_window(self) -> float:
        """Output tokens produced inside the window: the first token at
        sent + ttft, the others spread evenly from there to completion
        (the engine steps every running sequence together), each counted
        where it falls.  Needs every measured request to have finished."""
        total = 0.0
        for r in self.measured():
            if not r.ok or r.got_tokens < 1:
                continue
            first = r.sent + r.ttft_ms * 1e-3
            total += self.t_open <= first < self.t_close
            if r.got_tokens > 1 and r.done > first:
                inside = min(r.done, self.t_close) - max(first, self.t_open)
                total += (r.got_tokens - 1) * max(0.0, inside) \
                    / (r.done - first)
        return total


Send = Callable[[Dict[str, Any]], Any]   # -> object with .result(timeout)


class LoadGenerator:
    def __init__(self, traffic: Dict[str, Any], seed: int, vocab: int,
                 send: Send, request_timeout_s: float = 120.0):
        if traffic["generator"] != "requests":
            raise ValueError("LoadGenerator sends 'requests' traffic")
        self.traffic = traffic
        self.arrivals = traffic["arrivals"]
        self.seed = seed
        self.vocab = vocab
        self.send = send
        self.timeout = request_timeout_s
        self.records: List[Record] = []
        # record index -> (prompt, tokens returned): what the kind's
        # correctness check reads back through the reference
        self.exchanges: Dict[int, Any] = {}
        self._lock = threading.Lock()
        self._stop = threading.Event()

    # -- one request ------------------------------------------------------
    def _new_record(self, caller: int, request: Dict[str, Any],
                    due: Optional[float]) -> Record:
        with self._lock:
            rec = Record(len(self.records), caller, len(request["prompt"]),
                         request["max_new_tokens"], due)
            self.records.append(rec)
        return rec

    def _await(self, rec: Record, request, response) -> None:
        try:
            out = response.result(timeout=self.timeout)
            toks = out["tokens"]
            self.exchanges[rec.index] = (request["prompt"], list(toks))
            rec.got_tokens = len(toks)
            rec.tokens_valid = all(0 <= int(t) < self.vocab for t in toks)
            rec.ttft_ms = float(out["ttft_ms"])
            rec.ok = True
        except Exception as e:  # noqa: BLE001 - a failed request is data
            rec.error = f"{type(e).__name__}: {e}"[:200]
        rec.done = time.perf_counter()

    # -- closed loop ------------------------------------------------------
    def _caller(self, caller: int) -> None:
        source = RequestSource(self.traffic, self.seed, caller, self.vocab)
        # A steady-state start: the first request of a caller is part-way
        # through, so completions are spread from the beginning instead of
        # arriving in the waves of a synchronised start.
        scale = float(_rng(self.seed, 4, caller).uniform(0.05, 1.0))
        while not self._stop.is_set():
            request = source.next(scale)
            scale = 1.0
            rec = self._new_record(caller, request, None)
            rec.sent = time.perf_counter()
            try:
                response = self.send(request)
            except Exception as e:  # noqa: BLE001
                rec.error = f"{type(e).__name__}: {e}"[:200]
                rec.done = time.perf_counter()
                continue
            self._await(rec, request, response)

    def _run_closed(self, seconds: float, on_open, on_close) -> Log:
        threads = [threading.Thread(target=self._caller, args=(i,),
                                    daemon=True, name=f"caller-{i}")
                   for i in range(int(self.arrivals["callers"]))]
        for t in threads:
            t.start()
        time.sleep(float(self.arrivals.get("lead_in_s", 0.0)))
        t_open = on_open()
        time.sleep(max(0.0, t_open + seconds - time.perf_counter()))
        t_close = on_close()
        self._stop.set()
        self._threads = threads
        # Callers send nothing new; what is in flight is waited for, so
        # the log knows when every token of the window was produced.
        self.join(float(self.arrivals.get("drain_s", 60.0)))
        return Log(self.records, t_open, t_close, closed_loop=True)

    # -- open loop --------------------------------------------------------
    def _run_open(self, seconds: float, on_open, on_close) -> Log:
        offsets = arrival_offsets(self.arrivals, self.seed, seconds)
        source = RequestSource(self.traffic, self.seed, 0, self.vocab)
        requests = [source.next() for _ in offsets]
        lead = float(self.arrivals.get("lead_in_s", 0.0))
        pool = ThreadPoolExecutor(max_workers=512,
                                  thread_name_prefix="waiter")
        waits = []
        t_open_planned = time.perf_counter() + lead + 0.05
        t_open = None
        for offset, request in zip(offsets, requests):
            due = t_open_planned + float(offset)
            if t_open is None and offset >= 0.0:
                time.sleep(max(0.0, t_open_planned - time.perf_counter()))
                t_open = on_open()
            time.sleep(max(0.0, due - time.perf_counter()))
            rec = self._new_record(0, request, due)
            rec.sent = time.perf_counter()
            try:
                response = self.send(request)
            except Exception as e:  # noqa: BLE001
                rec.error = f"{type(e).__name__}: {e}"[:200]
                rec.done = time.perf_counter()
                continue
            waits.append(pool.submit(self._await, rec, request,
                                     response))
        if t_open is None:
            t_open = on_open()
        time.sleep(max(0.0, t_open_planned + seconds - time.perf_counter()))
        t_close = on_close()
        # Every request due in the window is awaited, so the tails hold
        # the slowest ones too.
        deadline = time.perf_counter() + float(
            self.arrivals.get("drain_s", 60.0))
        for w in waits:
            try:
                w.result(timeout=max(0.1, deadline - time.perf_counter()))
            except Exception:  # noqa: BLE001 - left as not ok in the log
                pass
        pool.shutdown(wait=False, cancel_futures=True)
        # The window is the schedule's, whenever on_open got to run.
        return Log(self.records, t_open_planned, t_open_planned + seconds,
                   closed_loop=False)

    def run(self, seconds: float, on_open: Callable[[], float] = None,
            on_close: Callable[[], float] = None) -> Log:
        """Lead in, open the window (``on_open()`` returns its time),
        measure ``seconds``, close it, then wait (``drain_s`` at most)
        for every request still in flight."""
        on_open = on_open or time.perf_counter
        on_close = on_close or time.perf_counter
        if self.arrivals["process"] == "closed":
            return self._run_closed(seconds, on_open, on_close)
        return self._run_open(seconds, on_open, on_close)

    def join(self, timeout: float = 30.0) -> int:
        """Wait for closed-loop callers to end; returns how many are
        still alive (their requests stay in the log as not ok)."""
        deadline = time.perf_counter() + timeout
        alive = 0
        for t in getattr(self, "_threads", []):
            t.join(max(0.0, deadline - time.perf_counter()))
            alive += t.is_alive()
        return alive
