"""Kind ``serve_llm_even``: ``serve_llm`` — the same deployment, window,
``correct`` and observations, from ``kinds/serve_llm.py``'s own ``run`` —
under a closed loop whose callers draw from ONE stratified stream.

Why: ``lib/loadgen.py`` promises "every seed with the same amount of work,
only order and timing left to chance" by drawing a length distribution
``"stratified": n`` values at a time, but it does so per CALLER.  Where a
request is thousands of tokens a caller sends two or three a run, never n,
so its draws are as good as independent and the seed decides how much
prefill a window holds per token it emits: cell 8 read 408–449 tokens/s on
one program, each seed repeating to 0.3% (PERF.md section 6, PR 37).
Here the k-th request the loop creates, whichever caller sends it, takes
the k-th lengths of one stream over the same strata, and the callers'
part-way starts are the evenly spaced values of their range in a seeded
order instead of one uniform draw each.  The traffic file, its
distributions and every request's marginal are ``serve_llm``'s; a window
holds the same lengths whatever the seed.

The same seed gives the same requests: the k-th request's lengths and
token ids are functions of (seed, k) alone, and the first request of each
caller is made in caller order before any is sent.  Which caller carries
a later one, and when, is the engine's timing, as it is in ``serve_llm``.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, Tuple

from benchmarks.kinds import serve_llm
from benchmarks.lib import loadgen

START_SCALE = (0.05, 1.0)   # ``LoadGenerator._caller``'s part-way start


class SharedSource:
    """The seeded stream of requests of a whole closed loop."""

    def __init__(self, traffic: Dict[str, Any], seed: int, vocab: int):
        self.seed, self.vocab = seed, vocab
        self.prompt_len = loadgen.Lengths(traffic["prompt_tokens"],
                                          loadgen._rng(seed, 5))
        self.output_len = loadgen.Lengths(traffic["output_tokens"],
                                          loadgen._rng(seed, 6))
        self.created = 0
        self._lock = threading.Lock()

    def lengths(self) -> Tuple[int, int, int]:
        """(k, prompt tokens, output tokens) of the next request."""
        with self._lock:
            k, self.created = self.created, self.created + 1
            return k, self.prompt_len.draw(), self.output_len.draw()

    def next(self, output_scale: float = 1.0) -> Dict[str, Any]:
        k, n_prompt, n_out = self.lengths()
        if output_scale != 1.0:
            n_out = max(1, int(round(n_out * output_scale)))
        prompt = loadgen._rng(self.seed, 1, k).integers(
            1, self.vocab, n_prompt)
        return {"prompt": prompt.tolist(), "max_new_tokens": n_out}


def start_scales(seed: int, callers: int):
    """How far into its first request each caller starts: the ``callers``
    evenly spaced values of START_SCALE, in a seeded order."""
    lo, hi = START_SCALE
    return [lo + (hi - lo) * (i + 0.5) / callers
            for i in loadgen._rng(seed, 4).permutation(callers)]


class EvenLoadGenerator(loadgen.LoadGenerator):
    """``LoadGenerator`` for a closed loop, the callers sharing one
    ``SharedSource``.  An open loop already holds its counts."""

    def __init__(self, traffic, seed, vocab, send, **kwargs):
        super().__init__(traffic, seed, vocab, send, **kwargs)
        if self.arrivals["process"] != "closed":
            raise ValueError("serve_llm_even is for a closed loop; an open "
                             "loop's schedule holds its counts already")
        callers = int(self.arrivals["callers"])
        self.source = SharedSource(traffic, seed, vocab)
        self._first = [self.source.next(scale)
                       for scale in start_scales(seed, callers)]

    def _caller(self, caller: int) -> None:
        request = self._first[caller]
        while not self._stop.is_set():
            rec = self._new_record(caller, request, None)
            rec.sent = time.perf_counter()
            try:
                response = self.send(request)
            except Exception as e:  # noqa: BLE001 - a failed request is data
                rec.error = f"{type(e).__name__}: {e}"[:200]
                rec.done = time.perf_counter()
            else:
                self._await(rec, request, response)
            request = self.source.next()


def run(ctx) -> Dict[str, Any]:
    # ``serve_llm.run`` names its generator through the module: the one
    # seam it has, and this kind changes nothing else of it.
    held = loadgen.LoadGenerator
    loadgen.LoadGenerator = EvenLoadGenerator
    try:
        return serve_llm.run(ctx)
    finally:
        loadgen.LoadGenerator = held
