"""Find a cell's files by name.  Nothing here knows any cell, model,
traffic mix or metric: a later PR adds one by adding a file and an entry
in ``BENCHMARK.json``.

    cell     benchmarks/workloads/<cell>.json
    config   benchmarks/configs/<config>.json    (+ references/<reference>.py)
    traffic  benchmarks/traffic/<traffic>.json
    kind     benchmarks/kinds/<kind>.py          run(ctx) -> observations
    metric   benchmarks/metrics/<reader>.py      read(obs) -> number | None

A metric's name is ``<reader>`` or ``<group>.<reader>``.  An entry of
``BENCHMARK.json`` has ONE ``moves``, so a reader that is reported where
it moves tokens/s and where it moves a latency is two entries
(``batch.decode_step_device_ms``, ``chat.decode_step_device_ms``) that
share the one file ``metrics/decode_step_device_ms.py``.  A later cell
joins an entry's ``workloads`` or brings an entry under a group name of
its own; neither needs a file.

What a ``model_config`` PR brings, all of it new files and appended
entries (``benchmarks/tests/test_rehearsal.py`` does exactly this, on
the CPU):

    configs/<config>.json        the published keys, ``reference``,
                                 ``assumed`` (non-empty), ``reduced``,
                                 ``program_fields`` (fields of the
                                 program's config that no published key
                                 spells, passed through verbatim)
    references/<reference>.py    ``logits`` and ``teacher_forced_gap``
                                 (serving), ``loss_and_grads``,
                                 ``global_norm``, ``gradient_gaps``
                                 (training), if no reference here is the
                                 configuration's mathematics
    workloads/<cell>.json        and traffic/<traffic>.json unless the
                                 cell runs a mix that is here
    lib/<family>_flops.py        where its decode step's floor is its own:
                                 ``decode_step_least_s(obs)`` beside its
                                 operation and byte counts, named by
                                 ``"roofline"`` in the configuration's file
    metrics/<reader>.py          for what no reader here measures: its own
                                 kernels and layers
    BENCHMARK.json               the configuration, the cell, its own
                                 metrics; the cell's name appended to the
                                 ``workloads`` of the entries it joins

One entry a question, and a cell joins by what its program does.  A served
configuration joins ``batch.decode_step_roofline`` (one reader,
``readers.decode_step_roofline``, over the floor its file names) and brings
no entry for its step.  What lets a cell join the counted entries is said
in its configuration's file:

    ``moe_*``                    the program's ``router`` / ``expert_dispatch``
                                 / ``expert_ffn`` / ``shared_expert`` scopes,
                                 the ``%ragged-dot-none*`` kernels and the
                                 ``serve.chunk`` spans, at an expert's width
                                 (``moe_intermediate_size``) and the layers
                                 and range ``program_fields`` gives the
                                 program (``first_dense_layers``,
                                 ``moe_experts``, ``moe_held``); where an
                                 expert is not three matrices of stream x
                                 width in all but the leading layers,
                                 ``expert_shape`` {``matrices``,
                                 ``row_width``, ``layers``}
                                 (``lib/moe_flops.py``, ``lib/moe_names.py``)
    ``ssm_*``                    ``layer_types`` with ``"mamba"`` and the
                                 ``mamba_*`` keys, or ``ssm_shape`` {``heads``,
                                 ``head_dim``, ``state``, ``groups``, ``conv``,
                                 ``layers``}, with ``"ops": "scopes"`` where
                                 the program traces ``ssm_state_update`` /
                                 ``ssm_scan`` (``lib/ssm_flops.py``,
                                 ``lib/ssm_names.py``)
    ``train_mfu``,               ``train_counts``: the module of ``lib/`` with
    ``flash_attention_roofline`` ``train_flops_per_token``,
                                 ``flash_train_flops``, ``flash_train_bytes``
                                 of ITS train step (none named:
                                 ``lib/flops.py``, a dense full-causal
                                 decoder); ``train_expert_*`` /
                                 ``train_routing_*`` read the step's scopes
                                 and its ``expert_rows`` metric as ``moe_*``
                                 do a served step's
    ``dsa_*`` selection          an ``index_topk`` among ``program_fields``
    ``mla_*`` scopes and spans   a ``kv_lora_rank``

It brings entries only for kernels and layers of its own, appended to
``per_layer`` (``benchmarks/tests/test_yardstick.py`` holds the table's one
limit, 128, and that every file under ``metrics/`` is an entry's reader; no
other test counts the entries, and no cell's test says what another
family's names are).

A configuration that is cut says so twice: ``reduced`` in
``BENCHMARK.json`` lists the keys, and ``reduced`` in the file has one
``{"key", "published", "here", "why"}`` per key, in the same order
(``[]`` where nothing is cut).
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class SpecError(Exception):
    """The benchmark's own files do not fit together."""


def _load_json(kind: str, name: str, bench_dir: str) -> Dict[str, Any]:
    if not _NAME.match(name):
        raise SpecError(f"{kind} name {name!r} is not a name")
    path = os.path.join(bench_dir, kind, name + ".json")
    if not os.path.isfile(path):
        raise SpecError(f"no {kind} file {path}")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """``benchmarks/<kind>/<name>.py`` as a module, or None if absent."""
    if not _NAME.match(name):
        raise SpecError(f"{kind} name {name!r} is not a name")
    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.isfile(path):
        return None
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name.replace('-', '_').replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """One entry of ``BENCHMARK.json``'s ``workloads`` with everything
    its names lead to."""

    def __init__(self, name: str, bench_dir: str = BENCH_DIR,
                 benchmark_json: Optional[str] = None):
        self.bench_dir = bench_dir
        path = benchmark_json or os.path.join(
            os.path.dirname(bench_dir), "BENCHMARK.json")
        with open(path) as f:
            self.benchmark = json.load(f)
        entries = [w for w in self.benchmark["workloads"]
                   if w["name"] == name]
        if not entries:
            raise SpecError(f"{name!r} is not a workload of {path}")
        self.entry = entries[0]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.workload = _load_json("workloads", name, bench_dir)
        self.config = _load_json("configs", self.entry["config"], bench_dir)
        self.traffic = _load_json("traffic", self.entry["traffic"],
                                  bench_dir)
        for key in ("config", "traffic", "chips"):
            if self.workload[key] != self.entry[key]:
                raise SpecError(
                    f"{name}: workloads/{name}.json says {key}="
                    f"{self.workload[key]!r}, BENCHMARK.json says "
                    f"{self.entry[key]!r}")
        self.kind = load_module("kinds", self.workload["kind"], bench_dir)
        if self.kind is None:
            raise SpecError(f"no kind {self.workload['kind']!r}")
        self.reference = load_module("references",
                                     self.config["reference"], bench_dir)
        if self.reference is None:
            raise SpecError(
                f"no reference {self.config['reference']!r}")

    def metric_entries(self, group: str) -> List[Dict[str, Any]]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell
        reports: those with no ``workloads`` key, or that list it."""
        return [m for m in self.benchmark[group]
                if self.name in m.get("workloads", [self.name])]

    def readers(self, group: str):
        """(entry, read function) per metric of the cell."""
        out = []
        for entry in self.metric_entries(group):
            reader = entry["name"].rsplit(".", 1)[-1]
            module = load_module("metrics", reader, self.bench_dir)
            if module is None:
                raise SpecError(f"metric {entry['name']!r} has no "
                                f"benchmarks/metrics/{reader}.py")
            out.append((entry, module.read))
        return out
