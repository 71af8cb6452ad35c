"""Dataset facade: lazy logical plan + consumption APIs.

Reference: python/ray/data/dataset.py:146 (``Dataset`` — lazy plan,
``iter_batches`` :3935, ``materialize`` :4897) and
``streaming_split`` → output_splitter (used by
train/_internal/data_config.py for per-worker shards).

TPU-first notes: batches are dict[str, np.ndarray] — exactly what a jit
train step takes; ``iter_batches(device_put=True)`` overlaps host→HBM
transfer of batch N+1 with the consumer's step N (the reference's
prefetching batcher + GPU pinning, block_batching/).
"""

from __future__ import annotations

import builtins
import os

import itertools
import threading
from collections import deque
from typing import (Any, Callable, Dict, Iterator, List, Optional,
                    Sequence, Union)

import numpy as np

from .block import (Block, BlockAccessor, BlockMetadata,
                    group_boundaries, hash_partition_indices,
                    sort_by_key)
from .context import DataContext
from .datasource import (BlocksDatasource, Datasource, ItemsDatasource,
                         RangeDatasource, csv_datasource, json_datasource,
                         numpy_datasource, parquet_datasource)
from .executor import (ActorMapBlocks, ActorPoolStrategy, AllToAll,
                       Exchange, Limit, LogicalOp, MapBlocks, PlanStats,
                       Read, UnionOp, ZipOp, execute_streaming)


class Dataset:
    """Lazy, immutable pipeline of blocks.  Every transform returns a new
    Dataset sharing the prefix of the plan (reference dataset.py:146)."""

    def __init__(self, ops: List[LogicalOp]):
        self._ops = ops
        self._last_stats: Optional[PlanStats] = None

    # -- transforms ---------------------------------------------------------
    def _with(self, op: LogicalOp) -> "Dataset":
        return Dataset(self._ops + [op])

    def map_batches(self, fn, *,
                    batch_size: Optional[int] = None,
                    compute: Optional[ActorPoolStrategy] = None,
                    fn_constructor_args: tuple = (),
                    fn_constructor_kwargs: Optional[dict] = None
                    ) -> "Dataset":
        """Apply ``fn`` to batches (reference dataset.map_batches).
        With ``batch_size=None`` the fn sees whole blocks (zero-copy);
        otherwise blocks are re-chunked to exactly ``batch_size`` rows
        inside the task.

        ``compute=ActorPoolStrategy(size=n)`` makes this a stateful
        actor-pool stage (reference actor_pool_map_operator.py:34):
        ``fn`` must be a CLASS, instantiated once per pool actor with
        ``fn_constructor_args``; each batch goes through
        ``instance(batch)``."""
        if compute is not None:
            if not callable(fn) or not isinstance(fn, type):
                raise TypeError(
                    "compute=ActorPoolStrategy requires fn to be a "
                    "class (instantiated once per pool actor)")
            return self._with(ActorMapBlocks(
                fn.__name__, fn, tuple(fn_constructor_args),
                dict(fn_constructor_kwargs or {}), batch_size, compute))
        if batch_size is None:
            def tf(block: Block) -> List[Block]:
                return [BlockAccessor.validate(fn(block))]
        else:
            def tf(block: Block) -> List[Block]:
                out = []
                n = BlockAccessor.num_rows(block)
                for lo in builtins.range(0, n, batch_size):
                    piece = BlockAccessor.slice(block, lo,
                                                min(lo + batch_size, n))
                    out.append(BlockAccessor.validate(fn(piece)))
                return out
        return self._with(MapBlocks("MapBatches", tf))

    def map(self, fn: Callable[[Dict[str, Any]], Dict[str, Any]]
            ) -> "Dataset":
        def tf(block: Block) -> List[Block]:
            rows = [fn(r) for r in BlockAccessor.to_rows(block)]
            return [BlockAccessor.from_rows(rows)]
        return self._with(MapBlocks("Map", tf))

    def flat_map(self, fn: Callable[[Dict[str, Any]], Sequence[Dict]]
                 ) -> "Dataset":
        def tf(block: Block) -> List[Block]:
            rows: List[Dict[str, Any]] = []
            for r in BlockAccessor.to_rows(block):
                rows.extend(fn(r))
            return [BlockAccessor.from_rows(rows)] if rows else []
        return self._with(MapBlocks("FlatMap", tf))

    def filter(self, fn: Callable[[Dict[str, Any]], bool]) -> "Dataset":
        def tf(block: Block) -> List[Block]:
            keep = np.fromiter(
                (bool(fn(r)) for r in BlockAccessor.to_rows(block)),
                dtype=bool, count=BlockAccessor.num_rows(block))
            return [BlockAccessor.take(block, np.nonzero(keep)[0])]
        return self._with(MapBlocks("Filter", tf))

    def limit(self, n: int) -> "Dataset":
        return self._with(Limit(n))

    def repartition(self, num_blocks: int) -> "Dataset":
        """Distributed exchange: each input splits into ``num_blocks``
        row ranges (partition tasks), one merge task concatenates each
        range (reference: planner/exchange/ — no block values cross the
        driver)."""
        def partition(block: Block, n: int, spec, offset: int):
            # Exact global row ranges from the sampled total: output
            # partition j covers global rows [bounds[j], bounds[j+1]).
            total = spec["total"]
            bounds = np.linspace(0, total, n + 1).astype(np.int64)
            rows = BlockAccessor.num_rows(block)
            out = []
            for j in builtins.range(n):
                lo = max(int(bounds[j]) - offset, 0)
                hi = min(int(bounds[j + 1]) - offset, rows)
                if hi > lo:
                    out.append((j, BlockAccessor.slice(block, lo, hi)))
            return out

        def merge(blocks: List[Block], _spec, _idx) -> List[Block]:
            return [BlockAccessor.concat(blocks)] if blocks else []

        return self._with(Exchange("Repartition", partition, merge,
                                   n_out=num_blocks,
                                   needs_offsets=True))

    def random_shuffle(self, *, seed: Optional[int] = None) -> "Dataset":
        """Distributed shuffle (reference: push-based shuffle,
        push_based_shuffle_task_scheduler.py:590): partition tasks deal
        rows to random output partitions; each merge task concatenates
        its parts and permutes locally.  Values move node-to-node."""
        def partition(block: Block, n: int, _spec, offset: int):
            rows = BlockAccessor.num_rows(block)
            # Fold the global offset into the stream so blocks don't
            # share one assignment pattern under a fixed seed.
            rng = np.random.default_rng(
                None if seed is None else (seed, offset))
            assign = rng.integers(0, n, rows)
            return [(j, BlockAccessor.take(block,
                                           np.nonzero(assign == j)[0]))
                    for j in builtins.range(n)]

        def merge(blocks: List[Block], _spec, part_idx) -> List[Block]:
            if not blocks:
                return []
            whole = BlockAccessor.concat(blocks)
            # Fold the merge partition index into the seed so output
            # partitions don't share one permutation pattern.
            rng = np.random.default_rng(
                None if seed is None else (seed, part_idx))
            perm = rng.permutation(BlockAccessor.num_rows(whole))
            return [BlockAccessor.take(whole, perm)]

        return self._with(Exchange("RandomShuffle", partition, merge))

    def sort(self, key: str, *, descending: bool = False) -> "Dataset":
        """Distributed range sort (reference SortTaskSpec,
        sort_task_spec.py:94): sample tasks pick range bounds, partition
        tasks split by range, merge tasks sort each range locally; the
        ordered ranges concatenate into the global order."""
        def sample(blocks: List[Block]):
            vals = np.concatenate([np.asarray(b[key]) for b in blocks]) \
                if blocks else np.asarray([])
            if len(vals) > 100:
                idx = np.linspace(0, len(vals) - 1, 100).astype(np.int64)
                vals = np.sort(vals)[idx]
            return vals

        def bounds(samples, n: int):
            allv = np.sort(np.concatenate(
                [np.asarray(s) for s in samples if len(s)]))
            if len(allv) == 0:
                return np.asarray([])
            qs = np.linspace(0, len(allv) - 1, n + 1).astype(np.int64)
            return allv[qs[1:-1]]

        def partition(block: Block, n: int, spec, _offset: int):
            spec = spec["spec"]
            vals = np.asarray(block[key])
            idx = np.searchsorted(spec, vals, side="right") \
                if len(spec) else np.zeros(len(vals), np.int64)
            if descending:
                idx = (n - 1) - idx
            return [(j, BlockAccessor.take(block,
                                           np.nonzero(idx == j)[0]))
                    for j in builtins.range(n)]

        def merge(blocks: List[Block], _spec, _idx) -> List[Block]:
            if not blocks:
                return []
            whole = BlockAccessor.concat(blocks)
            order = np.argsort(np.asarray(whole[key]), kind="stable")
            if descending:
                order = order[::-1]
            return [BlockAccessor.take(whole, order)]

        return self._with(Exchange("Sort", partition, merge,
                                   sample_fn=sample, bounds_fn=bounds))

    # -- relational ops (push exchange) --------------------------------------
    def groupby(self, key: str) -> "GroupedData":
        """Hash-partition rows by ``key`` for aggregation (reference:
        Dataset.groupby → GroupedData).  All NaN keys form one group;
        output groups are key-sorted within each output partition but
        partitions are in hash order, not key order."""
        return GroupedData(self, key)

    def aggregate(self, *aggs) -> Optional[Dict[str, Any]]:
        """Whole-dataset aggregation (reference: Dataset.aggregate):
        ``ds.aggregate(Sum("x"), ("mean", "y"), "count")`` → one dict
        of results, or None on an empty dataset."""
        from .aggregate import resolve_aggregate

        resolved = [resolve_aggregate(a) for a in aggs]
        if not resolved:
            raise ValueError("aggregate() needs at least one aggregate")
        rows = _aggregate_exchange(self, None, resolved).take_all()
        if not rows:
            return None
        return dict(rows[0])

    def zip(self, other: "Dataset") -> "Dataset":
        """Column-concatenate with ``other``, position-aligned
        (reference: Dataset.zip).  Row counts must match —
        :class:`~ray_tpu.exceptions.ZipLengthMismatchError` otherwise;
        colliding column names from ``other`` get a ``_1`` suffix."""
        return self._with(ZipOp(list(other._ops)))

    def union(self, *others: "Dataset") -> "Dataset":
        """Append the other datasets' blocks after this one's
        (reference: Dataset.union).  Column sets must agree —
        :class:`~ray_tpu.exceptions.UnionSchemaError` otherwise."""
        if not others:
            return self
        return self._with(UnionOp([list(o._ops) for o in others]))

    # -- execution ----------------------------------------------------------
    def iter_blocks(self) -> Iterator[Block]:
        self._last_stats = PlanStats()
        return execute_streaming(self._ops, stats=self._last_stats)

    def iter_batches(self, *, batch_size: int = 256,
                     drop_last: bool = False,
                     batch_format: str = "numpy",
                     prefetch_batches: Optional[int] = None,
                     device_put: bool = False,
                     local_shuffle_buffer_size: Optional[int] = None,
                     local_shuffle_seed: Optional[int] = None
                     ) -> Iterator[Any]:
        """Stream exact-size batches (reference dataset.py:3935 +
        _internal/batcher.py).  ``device_put=True`` moves each batch to
        the default jax device one batch ahead of the consumer.
        ``local_shuffle_buffer_size`` permutes rows through a rolling
        buffer of at least that many rows before batching — the cheap
        within-shard decorrelation Train ingestion uses between full
        shuffled epochs (a ``random_shuffle()`` exchange)."""
        ctx = DataContext.get_current()
        depth = (ctx.prefetch_batches if prefetch_batches is None
                 else prefetch_batches)
        return _assemble_batches(
            self.iter_blocks(), batch_size=batch_size,
            drop_last=drop_last, batch_format=batch_format,
            prefetch=depth, device_put=device_put,
            local_shuffle_buffer_size=local_shuffle_buffer_size,
            local_shuffle_seed=local_shuffle_seed)

    def iter_rows(self) -> Iterator[Dict[str, Any]]:
        for block in self.iter_blocks():
            yield from BlockAccessor.to_rows(block)

    def take(self, n: int = 20) -> List[Dict[str, Any]]:
        return list(itertools.islice(self.limit(n).iter_rows(), n))

    def take_all(self) -> List[Dict[str, Any]]:
        return list(self.iter_rows())

    def count(self) -> int:
        return sum(BlockAccessor.num_rows(b) for b in self.iter_blocks())

    def schema(self) -> Optional[Dict[str, np.dtype]]:
        for block in self.iter_blocks():
            return BlockAccessor.schema(block)
        return None

    def materialize(self) -> "Dataset":
        """Execute now; the result re-reads from memory
        (reference dataset.py:4897)."""
        blocks = list(self.iter_blocks())
        return Dataset([Read(BlocksDatasource(blocks))])

    def stats(self) -> str:
        if self._last_stats is None:
            return "(dataset not executed yet)"
        return self._last_stats.summary()

    # -- writers (reference: Dataset.write_* → one file per block) ----------
    def _write_files(self, path: str, ext: str, write_block) -> List[str]:
        os.makedirs(path, exist_ok=True)
        out = []
        for i, block in enumerate(self.iter_blocks()):
            fp = os.path.join(path, f"{i:06d}.{ext}")
            write_block(fp, block)
            out.append(fp)
        return out

    def write_parquet(self, path: str) -> List[str]:
        import pyarrow as pa
        import pyarrow.parquet as pq

        def w(fp, block):
            pq.write_table(pa.table(
                {k: np.asarray(v) for k, v in block.items()}), fp)

        return self._write_files(path, "parquet", w)

    def write_csv(self, path: str) -> List[str]:
        def w(fp, block):
            BlockAccessor.to_pandas(block).to_csv(fp, index=False)

        return self._write_files(path, "csv", w)

    def write_json(self, path: str) -> List[str]:
        import json

        def w(fp, block):
            with open(fp, "w") as f:
                for row in BlockAccessor.to_rows(block):
                    f.write(json.dumps(
                        {k: (v.tolist() if hasattr(v, "tolist") else v)
                         for k, v in row.items()}) + "\n")

        return self._write_files(path, "jsonl", w)

    def write_tfrecords(self, path: str) -> List[str]:
        from .tfrecords import write_tfrecords_file

        def w(fp, block):
            write_tfrecords_file(fp, [block])

        return self._write_files(path, "tfrecords", w)

    def to_random_access_dataset(self, key: str, *,
                                 num_workers: int = 2):
        """Keyed O(log n) lookup structure over the sorted dataset
        (reference: Dataset.to_random_access_dataset)."""
        from .random_access import RandomAccessDataset

        return RandomAccessDataset(self, key, num_workers=num_workers)

    # -- splitting (Train integration) --------------------------------------
    def streaming_split(self, n: int, *, equal: bool = True
                        ) -> List["DataIterator"]:
        """N per-consumer iterators over ONE shared execution
        (reference: Dataset.streaming_split → output_splitter op, the
        API train/_internal/data_config.py shards datasets with).
        ``equal=True`` slices every block into n row-balanced pieces
        (shards stay within ±1 row of each other, keeping a lockstep
        training gang in sync); ``equal=False`` deals whole blocks
        round-robin.  Consumers advance epochs in lockstep.
        """
        router = _SplitRouter(self, n, equal=equal)
        return [DataIterator(router, i) for i in builtins.range(n)]

    def split(self, n: int) -> List["Dataset"]:
        """Materializing split into n row-balanced datasets."""
        blocks = list(self.iter_blocks())
        whole = BlockAccessor.concat(blocks)
        rows = BlockAccessor.num_rows(whole)
        bounds = np.linspace(0, rows, n + 1).astype(np.int64)
        return [Dataset([Read(BlocksDatasource(
            [BlockAccessor.slice(whole, int(lo), int(hi))]))])
                for lo, hi in zip(bounds[:-1], bounds[1:])]

    def __repr__(self):
        names = [getattr(op, "name", type(op).__name__)
                 for op in self._ops]
        return f"Dataset({' -> '.join(names)})"


class GroupedData:
    """Deferred groupby (reference: grouped_data.py GroupedData): the
    aggregate/map_groups call appends the push-exchange op to the
    plan.  Aggregations combine INCREMENTALLY on the reducers (partial
    state per distinct key, never raw rows); ``map_groups`` ships raw
    rows and applies the fn per key-run after the shuffle."""

    def __init__(self, ds: Dataset, key: str):
        if not isinstance(key, str):
            raise TypeError(
                f"groupby key must be a column name, got {key!r}")
        self._ds = ds
        self._key = key

    def aggregate(self, *aggs) -> Dataset:
        from .aggregate import resolve_aggregate

        resolved = [resolve_aggregate(a) for a in aggs]
        if not resolved:
            raise ValueError("aggregate() needs at least one aggregate")
        return _aggregate_exchange(self._ds, self._key, resolved)

    def count(self) -> Dataset:
        from .aggregate import Count

        return self.aggregate(Count())

    def sum(self, on: str) -> Dataset:
        from .aggregate import Sum

        return self.aggregate(Sum(on))

    def min(self, on: str) -> Dataset:
        from .aggregate import Min

        return self.aggregate(Min(on))

    def max(self, on: str) -> Dataset:
        from .aggregate import Max

        return self.aggregate(Max(on))

    def mean(self, on: str) -> Dataset:
        from .aggregate import Mean

        return self.aggregate(Mean(on))

    def std(self, on: str, ddof: int = 0) -> Dataset:
        from .aggregate import Std

        return self.aggregate(Std(on, ddof=ddof))

    def map_groups(self, fn: Callable[[Block], Any]) -> Dataset:
        """Apply ``fn`` to each whole group (a Block of that key's
        rows); it returns a Block of any shape (reference:
        GroupedData.map_groups)."""
        key = self._key

        def partition(block: Block, n: int, _spec, _offset: int):
            idx = hash_partition_indices(block, key, n)
            return [(j, BlockAccessor.take(block,
                                           np.nonzero(idx == j)[0]))
                    for j in builtins.range(n)]

        def merge(blocks: List[Block], _spec, _idx) -> List[Block]:
            if not blocks:
                return []
            sb = sort_by_key(BlockAccessor.concat(blocks), key)
            bounds = group_boundaries(sb[key])
            outs: List[Block] = []
            for s, e in zip(bounds[:-1], bounds[1:]):
                res = BlockAccessor.validate(
                    fn(BlockAccessor.slice(sb, int(s), int(e))))
                if BlockAccessor.num_rows(res):
                    outs.append(res)
            return outs

        return self._ds._with(
            Exchange(f"MapGroups({key})", partition, merge))


def _aggregate_exchange(ds: Dataset, key: Optional[str],
                        aggs) -> Dataset:
    from .aggregate import AggCombine, make_agg_partition

    return ds._with(Exchange(
        f"GroupBy({key})" if key is not None else "Aggregate",
        make_agg_partition(key, aggs), None,
        n_out=1 if key is None else -1,
        combine=AggCombine(key, aggs)))


class _SplitRouter:
    """Routes blocks of one shared streaming execution to n consumers,
    round-robin by block index.  Epoch-aware: a consumer that finishes
    epoch e and starts epoch e+1 blocks until every consumer has
    finished epoch e, then the plan re-executes (reference
    DataIterators are re-iterable; training loops advance epochs in
    lockstep)."""

    _END = object()

    def __init__(self, ds: Dataset, n: int, equal: bool = True):
        self._n = n
        self._equal = equal
        self._cond = threading.Condition()
        self._queues: List[deque] = [deque() for _ in builtins.range(n)]
        self._source: Optional[Iterator[Block]] = None
        self._ds = ds
        self._next = 0
        self._done = False
        self._finished: set = set()
        self._epoch = 0

    def _deal(self, block: Block):
        if not self._equal:
            self._queues[self._next].append(block)
            self._next = (self._next + 1) % self._n
            return
        # Row-balanced: slice the block into n contiguous pieces,
        # rotating which shard gets the (possibly longer) first piece
        # so remainders even out across blocks.
        rows = BlockAccessor.num_rows(block)
        bounds = np.linspace(0, rows, self._n + 1).astype(np.int64)
        for j in builtins.range(self._n):
            lo, hi = int(bounds[j]), int(bounds[j + 1])
            if hi > lo:
                shard = (j + self._next) % self._n
                self._queues[shard].append(
                    BlockAccessor.slice(block, lo, hi))
        self._next = (self._next + 1) % self._n

    def next_block(self, shard: int, epoch: int) -> Any:
        """Next block for ``shard`` in ``epoch``, or ``_END`` at the end
        of that shard's epoch."""
        with self._cond:
            while epoch > self._epoch:
                # This consumer is ahead; wait for laggards to finish
                # the current epoch.
                self._cond.wait(timeout=1.0)
            if epoch < self._epoch:
                # The epoch this iterator belongs to is over.
                return self._END
            while not self._queues[shard]:
                if self._done:
                    if shard not in self._finished:
                        self._finished.add(shard)
                        if len(self._finished) == self._n:
                            # Everyone finished: rearm for next epoch.
                            self._source = None
                            self._done = False
                            self._finished = set()
                            self._next = 0
                            self._epoch += 1
                            self._cond.notify_all()
                    return self._END
                if self._source is None:
                    self._source = self._ds.iter_blocks()
                try:
                    block = next(self._source)
                except StopIteration:
                    self._done = True
                    continue
                self._deal(block)
                self._cond.notify_all()
            return self._queues[shard].popleft()


class DataIterator:
    """Per-worker view of a streaming_split (reference:
    data/iterator.py DataIterator)."""

    def __init__(self, router: _SplitRouter, shard: int):
        self._router = router
        self._shard = shard
        self._epoch = 0

    def iter_blocks(self) -> Iterator[Block]:
        epoch = self._epoch
        self._epoch += 1
        while True:
            block = self._router.next_block(self._shard, epoch)
            if block is _SplitRouter._END:
                return
            yield block

    def iter_batches(self, *, batch_size: int = 256,
                     drop_last: bool = False,
                     batch_format: str = "numpy",
                     prefetch_batches: int = 1,
                     device_put: bool = False,
                     local_shuffle_buffer_size: Optional[int] = None,
                     local_shuffle_seed: Optional[int] = None
                     ) -> Iterator[Any]:
        return _assemble_batches(
            self.iter_blocks(), batch_size=batch_size,
            drop_last=drop_last, batch_format=batch_format,
            prefetch=prefetch_batches, device_put=device_put,
            local_shuffle_buffer_size=local_shuffle_buffer_size,
            local_shuffle_seed=local_shuffle_seed)

    def iter_rows(self) -> Iterator[Dict[str, Any]]:
        for block in self.iter_blocks():
            yield from BlockAccessor.to_rows(block)


# --------------------------------------------------------------------------
# Batching / prefetch plumbing
# --------------------------------------------------------------------------
def _assemble_batches(blocks: Iterator[Block], *, batch_size: int,
                      drop_last: bool, batch_format: str,
                      prefetch: int, device_put: bool,
                      local_shuffle_buffer_size: Optional[int] = None,
                      local_shuffle_seed: Optional[int] = None
                      ) -> Iterator[Any]:
    """Batcher → optional device_put → optional prefetch thread →
    format-on-consumer.  Formatting (e.g. pandas DataFrame build) runs
    on the caller's thread, never the prefetch daemon: pandas' lazy
    native init on a short-lived thread corrupts later pyarrow calls
    on other fresh threads (segfault observed under the test suite)."""
    if device_put and batch_format != "numpy":
        raise ValueError("device_put requires batch_format='numpy'")
    if local_shuffle_buffer_size is not None:
        if local_shuffle_buffer_size < 1:
            raise ValueError(
                "local_shuffle_buffer_size must be >= 1, got "
                f"{local_shuffle_buffer_size}")
        blocks = _local_shuffle_iter(blocks, local_shuffle_buffer_size,
                                     local_shuffle_seed)
    it = _batch_iterator(blocks, batch_size, drop_last)
    if device_put:
        # Resolved HERE, on the consumer's thread: the active mesh is
        # thread-local and the pump below runs on its own thread.
        it = _device_put_iter(it, _batch_sharding())
    if prefetch > 0:
        it = _prefetch_iter(it, prefetch)
    if batch_format == "numpy":
        return it
    return (_format_batch(b, batch_format) for b in it)


def _batch_iterator(blocks: Iterator[Block], batch_size: int,
                    drop_last: bool) -> Iterator[Block]:
    """Re-chunk a block stream into exact-size numpy batches
    (reference: _internal/batcher.py).  Batches are numpy views into
    the merged buffer (an offset walks the block; only the sub-batch
    tail is ever copied into the next merge), so a single huge block
    costs O(rows), not O(rows²/batch_size)."""
    merged: Block = {}
    offset = 0
    for block in blocks:
        if not merged or offset >= BlockAccessor.num_rows(merged):
            merged, offset = block, 0
        else:
            tail = BlockAccessor.slice(merged, offset,
                                       BlockAccessor.num_rows(merged))
            merged, offset = BlockAccessor.concat([tail, block]), 0
        while BlockAccessor.num_rows(merged) - offset >= batch_size:
            yield BlockAccessor.slice(merged, offset,
                                      offset + batch_size)
            offset += batch_size
    leftover = (BlockAccessor.num_rows(merged) - offset
                if merged else 0)
    if leftover > 0 and not drop_last:
        yield BlockAccessor.slice(merged, offset, offset + leftover)


def _local_shuffle_iter(blocks: Iterator[Block], buffer_rows: int,
                        seed: Optional[int]) -> Iterator[Block]:
    """Rolling within-shard shuffle (reference: iter_batches
    ``local_shuffle_buffer_size`` → ShufflingBatcher): rows pool into
    a buffer until it holds at least ``buffer_rows``, then the pooled
    rows are permuted and the surplus beyond half a buffer is emitted
    — every emitted row was mixed across a window of at least
    ``buffer_rows`` rows, at memcpy cost instead of an exchange."""
    rng = np.random.default_rng(seed)
    hold: Optional[Block] = None
    for block in blocks:
        hold = block if hold is None else \
            BlockAccessor.concat([hold, block])
        n = BlockAccessor.num_rows(hold)
        if n >= buffer_rows:
            hold = BlockAccessor.take(hold, rng.permutation(n))
            keep = buffer_rows // 2
            yield BlockAccessor.slice(hold, 0, n - keep)
            hold = dict(BlockAccessor.slice(hold, n - keep, n)) \
                if keep else None
    if hold is not None and BlockAccessor.num_rows(hold):
        n = BlockAccessor.num_rows(hold)
        yield BlockAccessor.take(hold, rng.permutation(n))


def _format_batch(batch: Block, batch_format: str) -> Any:
    if batch_format == "numpy":
        return batch
    if batch_format == "pandas":
        return BlockAccessor.to_pandas(batch)
    raise ValueError(f"unknown batch_format {batch_format!r}")


def _batch_sharding():
    """Where ``device_put=True`` batches go: split along the row
    dimension over the active mesh's batch axes, so each device
    receives only its own rows — or None (jax's default device) when
    no mesh is active.  A mesh spanning several processes is left to
    the caller: each process holds only its own rows there."""
    from ray_tpu.parallel.sharding import current_mesh, logical_sharding

    mesh = current_mesh()
    if mesh is None or mesh.size == 1 or mesh.is_multi_process:
        return None
    return logical_sharding(("batch",), mesh)


def _device_put_iter(batches: Iterator[Block], sharding=None
                     ) -> Iterator[Any]:
    """Move batches to the device(s), one ahead of the consumer
    (host→HBM transfer overlaps the consumer's current step)."""
    import jax

    pending = None
    for batch in batches:
        nxt = jax.device_put(batch, sharding)
        if pending is not None:
            yield pending
        pending = nxt
    if pending is not None:
        yield pending


def _prefetch_iter(it: Iterator[Any], depth: int) -> Iterator[Any]:
    """Run the upstream iterator in a daemon thread with a bounded
    queue (reference: block_batching prefetcher).  An abandoned
    consumer (break / GC) stops the pump via the stop flag, so no
    thread stays blocked holding device batches."""
    import queue as _queue

    q: "_queue.Queue[Any]" = _queue.Queue(maxsize=depth)
    stop = threading.Event()
    _END = object()

    def put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except _queue.Full:
                continue
        return False

    def pump():
        try:
            for item in it:
                if not put(item):
                    return
            put(_END)
        except BaseException as e:  # noqa: BLE001 — surface to consumer
            put(e)

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    try:
        while True:
            item = q.get()
            if item is _END:
                return
            if isinstance(item, BaseException):
                raise item
            yield item
    finally:
        stop.set()
        try:
            while True:
                q.get_nowait()
        except _queue.Empty:
            pass


# --------------------------------------------------------------------------
# Read API (reference: read_api.py)
# --------------------------------------------------------------------------
def read_datasource(source: Datasource, *, parallelism: int = -1
                    ) -> Dataset:
    return Dataset([Read(source, parallelism)])


def range(n: int, *, parallelism: int = -1) -> Dataset:  # noqa: A001
    return read_datasource(RangeDatasource(n), parallelism=parallelism)


def from_items(items: Sequence[Any]) -> Dataset:
    return read_datasource(ItemsDatasource(items))


def from_blocks(blocks: List[Block]) -> Dataset:
    return read_datasource(BlocksDatasource(blocks))


def from_numpy(arrays: Union[np.ndarray, Dict[str, np.ndarray]]) -> Dataset:
    if isinstance(arrays, dict):
        return from_blocks([arrays])
    return from_blocks([{"data": np.asarray(arrays)}])


def from_pandas(df) -> Dataset:
    return from_blocks([BlockAccessor.from_pandas(df)])


def from_arrow(table) -> Dataset:
    return from_blocks([BlockAccessor.from_arrow(table)])


def read_parquet(paths, *, columns=None, parallelism: int = -1) -> Dataset:
    return read_datasource(parquet_datasource(paths, columns=columns),
                           parallelism=parallelism)


def read_csv(paths, *, parallelism: int = -1, **kw) -> Dataset:
    return read_datasource(csv_datasource(paths, **kw),
                           parallelism=parallelism)


def read_json(paths, *, parallelism: int = -1) -> Dataset:
    return read_datasource(json_datasource(paths),
                           parallelism=parallelism)


def read_numpy(paths, *, parallelism: int = -1) -> Dataset:
    return read_datasource(numpy_datasource(paths),
                           parallelism=parallelism)


def read_tfrecords(paths, *, parallelism: int = -1) -> Dataset:
    """TFRecord files of tf.train.Example protos (reference:
    read_api.read_tfrecords; codec is native — data/tfrecords.py)."""
    from .datasource import tfrecords_datasource

    return read_datasource(tfrecords_datasource(paths),
                           parallelism=parallelism)


def read_images(paths, *, size=None, mode=None,
                parallelism: int = -1) -> Dataset:
    """Image files → rows {"image": HWC array, "path"} (reference:
    read_api.read_images).  ``size=(w, h)`` resizes; ``mode`` converts
    (e.g. "RGB")."""
    from .datasource import image_datasource

    return read_datasource(image_datasource(paths, size=size, mode=mode),
                           parallelism=parallelism)
