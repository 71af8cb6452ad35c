"""Cross-process pipeline parallelism: GPipe over stage gangs.

The missing DCN half of the parallelism story (SURVEY §5.8, §7): the
in-jit schedule (parallel/pipeline.py) covers pipe stages WITHIN one
mesh/ICI domain; this module pipelines ACROSS processes — each stage is
an actor owning one slice's mesh and its layer block, activations ride
the object plane between stages (the compiled-DAG channel role,
reference substrate python/ray/dag/dag_node_operation.py:506-539), and
the head places one stage per TPU slice (SLICE_SPREAD,
cluster/head.py), so only stage boundaries cross DCN.

Schedule: per step, M microbatches flow all-forward then all-backward
(GPipe).  Every call is an async actor call chained by object refs, so
stage i runs microbatch m while stage i+1 runs m-1 — the pipeline
overlap comes from per-actor FIFO execution + dataflow, with no central
tick loop.  Backward is stage-granular recomputation: a stage keeps
only its INPUT per in-flight microbatch and re-runs its forward under
``jax.vjp`` when the output cotangent arrives.

Stage boundaries where BOTH adjacent stages live on this host ride the
native channel data plane (experimental.channel shm rings, one forward
+ one backward ring per boundary, M+1 slots deep so a full GPipe wave
never blocks a producer): activations and cotangents move
writer→reader at memcpy speed with no per-microbatch object minting.
Cross-host boundaries (stages placed on other slices) keep riding the
object plane exactly as before — the decision is per-edge.

Optimizer parity with the single-process step (llama.default_optimizer:
global-norm clip 1.0 + adamw) is kept exactly: stages accumulate
microbatch grads, the driver sums the per-stage squared norms into the
TRUE global norm, and each stage applies the same clip scale.
"""

from __future__ import annotations

import logging
from typing import Any, Dict, List, Optional

import numpy as np

import ray_tpu
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.parallel.mesh import MeshSpec

_log = logging.getLogger("ray_tpu.train")

PyTree = Any


@ray_tpu.remote
class _StageWorker:
    """One pipeline stage: owns its parameter slice, mesh, and the
    jitted fwd / fwd-loss / vjp programs."""

    def __init__(self, stage: int, n_stages: int, config: LlamaConfig,
                 mesh_spec: Optional[MeshSpec], seed: int,
                 learning_rate: float, weight_decay: float,
                 clip_norm: float):
        import jax
        import optax

        from ray_tpu.models import llama, llama_pipeline
        from ray_tpu.parallel.mesh import build_mesh
        from ray_tpu.parallel.sharding import use_mesh

        self._jax = jax
        self.stage, self.n = stage, n_stages
        self.cfg = config
        self.first = stage == 0
        self.last = stage == n_stages - 1
        self.clip_norm = clip_norm
        self._mesh = (build_mesh(mesh_spec, jax.devices())
                      if mesh_spec is not None else None)
        self._use_mesh = use_mesh

        # Identical init numerics to the single-process model: build the
        # full tree from the same key, keep this stage's slice.
        full = llama.init_params(jax.random.key(seed), config)
        self.params = llama_pipeline.stage_slice(full, stage, n_stages)
        del full
        self._opt = optax.adamw(learning_rate,
                                weight_decay=weight_decay)
        self.opt_state = self._opt.init(self.params)

        fwd = llama_pipeline.make_stage_fwd(config, self.first)
        self._fwd = jax.jit(fwd)
        if self.last:
            fwd_loss = llama_pipeline.make_stage_fwd_loss(config)

            def bwd_last(sl, h_in, tokens):
                loss, vjp = jax.vjp(
                    lambda p, h: fwd_loss(p, h, tokens), sl, h_in)
                gp, gh = vjp(jax.numpy.ones((), jax.numpy.float32))
                return loss, gp, gh

            # h_in is a per-microbatch staging buffer, dead after the
            # call, and shape-matches gh: donate it.  tokens is dead
            # too, but int32 can alias no float output — donating it
            # only buys an XLA unusable-buffer warning.
            self._bwd = jax.jit(bwd_last, donate_argnums=(1,))
        elif self.first:
            def bwd_first(sl, tokens, g):
                _, vjp = jax.vjp(lambda p: fwd(p, tokens), sl)
                (gp,) = vjp(g)
                return gp

            # No donation: the only outputs are param-shaped grads;
            # neither tokens (int32) nor g ([B,T,D]) can alias them,
            # so donation would be pure warning noise.
            self._bwd = jax.jit(bwd_first)
        else:
            def bwd_mid(sl, h_in, g):
                _, vjp = jax.vjp(fwd, sl, h_in)
                gp, gh = vjp(g)
                return gp, gh

            # gh can alias exactly one [B,T,D] input: donate h_in (g
            # would be a second, unusable donation).
            self._bwd = jax.jit(bwd_mid, donate_argnums=(1,))

        self._inputs: Dict[int, Any] = {}   # mb_idx -> stage input
        self._grad_acc: Optional[PyTree] = None
        self._losses: List[Any] = []  # device scalars until apply_update
        self._n_mb = 0

    # ------------------------------------------------------------ helpers
    def device_info(self) -> Dict[str, Any]:
        """This stage's accelerator identity for the driver's MFU
        roofline: chip kind + process-qualified device ids (the
        driver dedups across stages — colocated in-process stages
        share one device set and must not double-count it)."""
        import os

        devs = self._jax.local_devices()
        return {"kind": devs[0].device_kind if devs else "",
                "devices": [f"{os.getpid()}:{d}" for d in devs]}

    def _run(self, fn, *args):
        if self._mesh is not None:
            with self._use_mesh(self._mesh):
                return fn(*args)
        return fn(*args)

    def _acc(self, gp: PyTree):
        jnp = self._jax.numpy
        if self._grad_acc is None:
            self._grad_acc = self._jax.tree.map(
                lambda g: g.astype(jnp.float32), gp)
        else:
            self._grad_acc = self._jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32),
                self._grad_acc, gp)
        self._n_mb += 1

    def _to_host(self, x):
        return np.asarray(self._jax.device_get(x))

    # ------------------------------------------------------------ schedule
    def forward(self, mb_idx: int, inp: np.ndarray) -> np.ndarray:
        """Stage 0..K-2 forward; keeps the input for recompute-bwd."""
        jnp = self._jax.numpy
        inp = jnp.asarray(inp)
        self._inputs[mb_idx] = inp
        return self._to_host(self._run(self._fwd, self.params, inp))

    def fwd_bwd_last(self, mb_idx: int, h_in: np.ndarray,
                     tokens: np.ndarray) -> np.ndarray:
        """Last stage: loss forward + backward in one call (its output
        cotangent is available immediately)."""
        jnp = self._jax.numpy
        # raylint: disable=missing-donation -- h_in IS donated at the bwd_last build; tokens is int32 and can alias no float output
        loss, gp, gh = self._run(self._bwd, self.params,
                                 jnp.asarray(h_in), jnp.asarray(tokens))
        self._acc(gp)
        # Keep the loss on device: one blocking materialization per
        # optimizer step in apply_update instead of one per microbatch.
        self._losses.append(loss)
        return self._to_host(gh)

    def backward(self, mb_idx: int, g_out: np.ndarray) -> np.ndarray:
        """Middle stage: recompute forward under vjp, return the input
        cotangent for the upstream stage."""
        jnp = self._jax.numpy
        h_in = self._inputs.pop(mb_idx)
        # raylint: disable=missing-donation -- h_in IS donated at the bwd_mid build; gh can alias only one [B,T,D] input, so donating g_out too would be unusable
        gp, gh = self._run(self._bwd, self.params, h_in,
                           jnp.asarray(g_out))
        self._acc(gp)
        return self._to_host(gh)

    def backward_first(self, mb_idx: int, g_out: np.ndarray) -> bool:
        jnp = self._jax.numpy
        tokens = self._inputs.pop(mb_idx)
        # raylint: disable=missing-donation -- bwd_first's only outputs are param-shaped grads; neither int32 tokens nor [B,T,D] g_out can alias them
        gp = self._run(self._bwd, self.params, tokens,
                       jnp.asarray(g_out))
        self._acc(gp)
        return True

    # ------------------------------------------------------------ update
    def grad_sqnorm(self) -> float:
        """Σ g² of the microbatch-averaged grads (driver sums stages
        into the true global norm)."""
        jnp = self._jax.numpy
        m = float(max(self._n_mb, 1))
        return float(sum(
            jnp.sum(jnp.square(g / m))
            for g in self._jax.tree.leaves(self._grad_acc)))

    def reset_accum(self) -> bool:
        """Recovery: drop partial microbatch state from an aborted
        step so the retried step starts clean."""
        self._grad_acc = None
        self._losses = []
        self._n_mb = 0
        self._inputs.clear()
        return True

    def apply_update(self, global_sqnorm: float) -> Dict[str, float]:
        jax, jnp = self._jax, self._jax.numpy
        m = float(max(self._n_mb, 1))
        gnorm = float(np.sqrt(global_sqnorm))
        scale = 1.0 if gnorm <= self.clip_norm or gnorm == 0.0 \
            else self.clip_norm / gnorm
        grads = jax.tree.map(lambda g: (g / m) * scale, self._grad_acc)
        updates, self.opt_state = self._opt.update(
            grads, self.opt_state, self.params)
        import optax

        self.params = optax.apply_updates(self.params, updates)
        out = {"grad_norm": gnorm}
        if self._losses:
            out["loss"] = float(np.mean(self._losses))
        self._grad_acc = None
        self._losses = []
        self._n_mb = 0
        self._inputs.clear()
        return out


class CrossSlicePipeline:
    """Driver handle: K stage actors, one per slice.

    ``resources_per_stage`` places stages through a placement group
    with the given strategy (default SLICE_SPREAD — one stage per TPU
    slice; unlabeled nodes degrade to one stage per node).  Without
    resources the actors schedule wherever capacity exists (single-
    process tests).
    """

    def __init__(self, config: LlamaConfig, n_stages: int,
                 num_microbatches: int, *,
                 mesh_spec: Optional[MeshSpec] = None,
                 resources_per_stage: Optional[Dict[str, float]] = None,
                 placement_strategy: str = "SLICE_SPREAD",
                 seed: int = 0, learning_rate: float = 3e-4,
                 weight_decay: float = 0.1, clip_norm: float = 1.0):
        from ray_tpu.models.llama_pipeline import check_pipeline_config

        check_pipeline_config(config, n_stages)
        self.n_stages = n_stages
        self.num_microbatches = num_microbatches
        self.config = config
        self._n_params: Optional[int] = None  # lazy (model-plane MFU)
        self._gang_devices = None             # lazy (kind, chip count)
        self._pg = None
        opts_per_stage: List[Dict[str, Any]] = [{} for _ in range(n_stages)]
        if resources_per_stage:
            from ray_tpu.core.task_spec import (
                PlacementGroupSchedulingStrategy)
            from ray_tpu.util.placement_group import placement_group

            self._pg = placement_group(
                [dict(resources_per_stage) for _ in range(n_stages)],
                strategy=placement_strategy)
            self._pg.wait(timeout_seconds=60)
            for i in range(n_stages):
                res = dict(resources_per_stage)
                opts_per_stage[i] = {
                    "scheduling_strategy": PlacementGroupSchedulingStrategy(
                        placement_group=self._pg,
                        placement_group_bundle_index=i),
                    "num_cpus": res.pop("CPU", None),
                    "num_tpus": res.pop("TPU", None),
                    "resources": res or None,
                }
        self.stages = [
            _StageWorker.options(**opts_per_stage[i]).remote(
                i, n_stages, config, mesh_spec, seed, learning_rate,
                weight_decay, clip_norm)
            for i in range(n_stages)]
        self._plan_channels()

    def _plan_channels(self):
        """One fwd + one bwd shm ring per adjacent SAME-HOST stage
        pair; cross-host pairs stay on the object plane (per-edge
        decision, so a pipeline straddling slices still benefits on
        its local boundaries)."""
        from ray_tpu.experimental import channel as chx

        n = self.n_stages
        self._fwd_ch: List[Optional[str]] = [None] * max(0, n - 1)
        self._bwd_ch: List[Optional[str]] = [None] * max(0, n - 1)
        self._ch_nodes: Dict[str, set] = {}
        # M microbatches can sit in a ring while a downstream stage
        # works; M+1 slots keep the all-forward wave non-blocking.
        self._ch_slots = self.num_microbatches + 1
        if not chx.channels_available():
            return
        locs = [chx.channel_location(s) for s in self.stages]
        for i in range(n - 1):
            if locs[i] is not None and locs[i + 1] is not None \
                    and locs[i][0] == locs[i + 1][0]:
                self._fwd_ch[i] = chx.channel_path(f"pp-fwd{i}")
                self._bwd_ch[i] = chx.channel_path(f"pp-bwd{i}")
                # Endpoint-hosting nodes (None = this process) so
                # shutdown can reach rings living in worker processes.
                nodes = {locs[i][1], locs[i + 1][1]}
                self._ch_nodes[self._fwd_ch[i]] = nodes
                self._ch_nodes[self._bwd_ch[i]] = nodes

    def _call(self, stage_idx: int, method: str, args, *,
              write: Optional[str] = None):
        """Submit a stage method; ``write`` tees its result into that
        ring (so the ref carries only a token), ``ChannelArg`` markers
        in ``args`` read from rings.  Falls through to a plain actor
        call on pure object-plane edges."""
        from ray_tpu.experimental import channel as chx

        uses_chan = write is not None or any(
            isinstance(a, chx.ChannelArg) for a in args)
        if not uses_chan:
            return getattr(self.stages[stage_idx], method).remote(*args)
        writes = ()
        if write is not None:
            writes = (chx.writer_spec(write, self._ch_slots),)
        return chx.submit_channel_call(
            self.stages[stage_idx], method, args, writes=writes,
            returns_value=write is None)

    def _edge_in(self, boundary: int, ref, forward: bool = True):
        """The consumer-side argument for a stage boundary: a channel
        marker when the boundary has a ring, else the producer ref.
        The marker carries the producing stage's actor id so the
        reader's liveness probing can name (and detect) a dead
        producer."""
        from ray_tpu.experimental import channel as chx

        path = (self._fwd_ch if forward else self._bwd_ch)[boundary]
        if path is None:
            return ref
        producer = self.stages[boundary if forward else boundary + 1]
        return chx.ChannelArg(
            path, producer=getattr(producer, "_actor_id", None))

    def train_step(self, tokens: np.ndarray) -> Dict[str, float]:
        """One GPipe step over ``tokens`` (B, S) int32.  B must divide
        by num_microbatches.

        Fault tolerance: the microbatch WAVE (forward/backward
        accumulation) is retried ONCE if it dies to a data-plane or
        actor fault (severed ring, stage killed mid-pass) — wait out
        any head-driven stage restart, drop the aborted wave's partial
        microbatch state on every surviving stage, tear down the stale
        rings and re-plan them against the stages' current endpoints.
        The wave is side-effect-free until ``apply_update``, so the
        retry is exact; the UPDATE phase is deliberately NOT retried
        (some stages may already have applied — re-running it would
        double-apply the optimizer step), its failures propagate
        typed.  A restarted stage re-runs its constructor (same seed →
        same init); a stage dead for good (no restart budget)
        re-raises the typed error."""
        import time as _time

        from ray_tpu.exceptions import (ActorError, ChannelError,
                                        ObjectLostError, TaskError)
        from ray_tpu.observability import device as _device_mod
        from ray_tpu.observability import tracing

        # One trace per train step: every microbatch task on every
        # stage (and the retried wave, if any) shares the trace id.
        t0 = _time.perf_counter()
        with tracing.span("train.step",
                          args={"stages": self.n_stages}) as span:
            # The annotation carries the step's trace id into any
            # device trace captured while the wave runs.
            with _device_mod.annotation("train.step"):
                try:
                    self._run_wave(tokens)
                except (ActorError, ChannelError, ObjectLostError,
                        TaskError) as e:
                    cause = e.cause if isinstance(e, TaskError) else e
                    if not isinstance(cause,
                                      (ActorError, ChannelError,
                                       ObjectLostError)):
                        raise
                    if not self._recover_stages():
                        raise
                    # The recovery that used to be only a counter is
                    # now a correlated log line: `logs --trace <step
                    # trace>` shows WHY this step was slow next to
                    # its spans.
                    _log.warning(
                        "train.step wave retried after %s trace=%s",
                        type(cause).__name__, span.trace_id)
                    self._run_wave(tokens)
                out = self._apply_updates()
            # Step time ends HERE — the roofline gather below is a
            # one-off gang RPC that must not pollute the first step's
            # tokens/s gauge.
            step_s = _time.perf_counter() - t0
            # Model-plane series: per-step tokens/s (+ MFU where the
            # chip roofline is known).  The roofline is the GANG's: kind + distinct chip
            # count come from the stage workers, not the driver (a
            # CPU driver orchestrating TPU stages would otherwise
            # never export MFU, and a multi-stage gang would report
            # it inflated by the stage count).
            kind, n_dev = self._gang_roofline()
            _device_mod.record_train_step(
                int(tokens.shape[0]) * (int(tokens.shape[1]) - 1),
                step_s, n_params=self._total_params(),
                device_kind=kind or None, n_devices=n_dev)
            return out

    def _total_params(self) -> Optional[int]:
        """Whole-model parameter count for the MFU gauge, computed
        once via shape-only eval (no weights materialize on the
        driver); None when jax is unavailable here."""
        if self._n_params is None:
            try:
                import jax

                from ray_tpu.models import llama

                self._n_params = llama.param_count(jax.eval_shape(
                    lambda: llama.init_params(jax.random.key(0),
                                              self.config)))
            except Exception:
                self._n_params = 0
        return self._n_params or None

    def _gang_roofline(self):
        """(device_kind, distinct device count) across the stage
        gang, gathered once: each stage reports process-qualified
        device ids, deduped here so colocated in-process stages
        (which share one device set) don't double-count chips."""
        if self._gang_devices is None:
            try:
                infos = ray_tpu.get(
                    [s.device_info.remote() for s in self.stages],
                    timeout=30.0)
                devs: set = set()
                kind = ""
                for info in infos:
                    devs.update(info["devices"])
                    kind = kind or info["kind"]
                self._gang_devices = (kind, max(1, len(devs)))
            except Exception:
                # Transient (a stage mid-restart): DON'T cache the
                # failure — the next step retries, else one bad first
                # step would disable MFU export for the pipeline's
                # whole lifetime.
                return "", 1
        return self._gang_devices

    def _recover_stages(self, timeout_s: float = 60.0) -> bool:
        """Wait for every stage to be ALIVE again (restarts included),
        reset their partial step state, and rebuild the boundary rings.
        False when some stage is dead for good."""
        import time as _time

        from ray_tpu.experimental.channel import (_producer_state,
                                                  destroy_channel_at)

        deadline = _time.monotonic() + timeout_s
        for stage in self.stages:
            aid = getattr(stage, "_actor_id", None)
            while True:
                state = _producer_state(aid)
                if state in (None, "ALIVE"):
                    break
                if state == "DEAD" or _time.monotonic() > deadline:
                    return False
                _time.sleep(0.2)
        # Destroy the stale rings BEFORE touching the stages: aborted
        # channel-step tasks may still sit in the stage FIFOs blocked
        # on these rings (a restarted-but-alive producer defeats the
        # liveness probe), and reset_accum queues behind them — the
        # destroy fails those reads immediately (ChannelClosed).
        for path in (self._fwd_ch + self._bwd_ch):
            if path is not None:
                destroy_channel_at(path, self._ch_nodes.get(path, ()))
        try:
            ray_tpu.get([s.reset_accum.remote() for s in self.stages],
                        timeout=timeout_s)
        except Exception:
            return False
        self._plan_channels()
        from ray_tpu.observability import metrics as _metrics

        _metrics.Counter(
            "ray_tpu_pipeline_recoveries_total",
            "cross-pipeline wave recoveries (stage restart + ring "
            "rebuild + retry)").inc()
        return True

    def _run_wave(self, tokens: np.ndarray) -> None:
        """The GPipe microbatch wave: all-forward then all-backward,
        grads ACCUMULATED on the stages (no parameter mutation — this
        whole phase is retryable after reset_accum)."""
        M = self.num_microbatches
        B = tokens.shape[0]
        if B % M:
            raise ValueError(f"batch {B} not divisible by {M} microbatches")
        mbs = np.split(np.asarray(tokens), M, axis=0)

        # All-forward: chained edges (shm ring where the boundary is
        # same-host, object refs otherwise); actor FIFO pipelines the
        # stages either way.
        K = self.n_stages
        h = [self._call(0, "forward", (i, mb), write=self._fwd_ch[0])
             for i, mb in enumerate(mbs)]
        for j in range(1, K - 1):
            h = [self._call(j, "forward",
                            (i, self._edge_in(j - 1, r)),
                            write=self._fwd_ch[j])
                 for i, r in enumerate(h)]
        # Last stage folds backward into forward; then all-backward
        # in reverse microbatch order (frees newest inputs first).
        g = [self._call(K - 1, "fwd_bwd_last",
                        (i, self._edge_in(K - 2, r), mbs[i]),
                        write=self._bwd_ch[K - 2])
             for i, r in enumerate(h)]
        for j in range(K - 2, 0, -1):
            g = [self._call(j, "backward",
                            (i, self._edge_in(j, r, forward=False)),
                            write=self._bwd_ch[j - 1])
                 for i, r in enumerate(g)]
        done = [self._call(0, "backward_first",
                           (i, self._edge_in(0, r, forward=False)))
                for i, r in enumerate(g)]
        ray_tpu.get(done)

    def _apply_updates(self) -> Dict[str, float]:
        """Two-phase clipped update over the accumulated grads.
        Mutates stage parameters — never retried (see train_step)."""
        sq = sum(ray_tpu.get(
            [s.grad_sqnorm.remote() for s in self.stages]))
        metrics = ray_tpu.get(
            [s.apply_update.remote(sq) for s in self.stages])
        out = dict(metrics[-1])  # last stage carries the loss
        out["grad_norm"] = metrics[0]["grad_norm"]
        return out

    def shutdown(self):
        for s in self.stages:
            try:
                ray_tpu.kill(s)
            except Exception:
                pass
        from ray_tpu.experimental.channel import destroy_channel_at

        for path in (self._fwd_ch + self._bwd_ch):
            if path is not None:
                destroy_channel_at(path, self._ch_nodes.get(path, ()))
        self._fwd_ch = [None] * len(self._fwd_ch)
        self._bwd_ch = [None] * len(self._bwd_ch)
        self._ch_nodes = {}
        if self._pg is not None:
            from ray_tpu.util.placement_group import (
                remove_placement_group)

            try:
                remove_placement_group(self._pg)
            except Exception:
                pass
        self.stages = []
