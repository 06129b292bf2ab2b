"""Compiled SPMD training step (the performance path).

Ref: the reference's fleet static-graph path (SURVEY.md §3.5) — one compiled
program per step. Here: jax.value_and_grad over the Layer's functional form +
the optimizer's pure update rule, jitted once with donated state. When a mesh
+ sharding specs are given, parameters/optimizer states are placed with
NamedShardings (TP from param.pspec, ZeRO from group_sharded), the batch is
dp-sharded, and XLA emits all collectives.
"""
from __future__ import annotations

import functools
import os
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import envs
from .. import observability
from ..distributed import sharding_utils
from ..nn.layer.layers import Layer
from ..tensor.tensor import Tensor
from .functional import functional_call, state_arrays


class TrainStep:
    """Owns the (possibly sharded) param/opt-state arrays; callable per batch.

    train_step = TrainStep(model, loss_fn, optimizer, mesh=hcg.mesh,
                           batch_spec=P('dp'))
    loss = train_step(x, y)
    """

    def __init__(self, model: Layer, loss_fn: Callable, optimizer,
                 mesh: Optional[Mesh] = None, batch_spec=None,
                 grad_accum: int = 1, donate: bool = True, rng_seed: int = 0,
                 grad_sync: Optional[str] = None,
                 grad_bucket_mb: Optional[float] = None,
                 param_prefetch: Optional[bool] = None,
                 param_bucket_mb: Optional[float] = None,
                 telemetry: Optional[bool] = None,
                 telemetry_dir: Optional[str] = None,
                 tokens_per_step: Optional[int] = None,
                 flight_recorder: Optional[bool] = None,
                 fleet=None, ledger=None, checkpoint=None):
        # rolling-checkpoint + preemption orchestration (PR 13): a
        # CheckpointManager instance or a root directory string. on_step
        # fires after every completed step; interval pacing and the
        # SIGTERM path live in the manager.
        if isinstance(checkpoint, str):
            from ..distributed.checkpoint.manager import CheckpointManager
            checkpoint = CheckpointManager(checkpoint)
        self.checkpoint = checkpoint
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.mesh = mesh
        self.batch_spec = batch_spec
        self.grad_accum = int(grad_accum)
        self._step_count = 0
        self._rng = jax.random.PRNGKey(rng_seed)

        params, buffers = state_arrays(model)
        self.param_objs = dict(model.named_parameters())
        trainable = {k: p for k, p in self.param_objs.items()
                     if not p.stop_gradient}
        self.trainable_keys = list(trainable)

        opt_states = {}
        for k in self.trainable_keys:
            opt_states[k] = optimizer._create_accumulators(self.param_objs[k])
        self.wd_map = {k: optimizer._weight_decay for k in self.trainable_keys}

        if mesh is not None:
            from ..distributed.fleet.meta_parallel.sharding.group_sharded \
                import mesh_resolved_spec
            # ZeRO specs attached by group_sharded_parallel are re-derived
            # here against the REAL mesh degree (divisibility enforced —
            # see mesh_resolved_spec); non-ZeRO pspecs pass through.
            gs_specs = {k: mesh_resolved_spec(p, mesh)
                        for k, p in self.param_objs.items()
                        if getattr(p, "opt_state_pspec", None) is not None}
            self.param_shardings = {}
            for k, p in self.param_objs.items():
                if getattr(p, "sharding_level", None) == "p_g_os" \
                        and gs_specs.get(k) is not None:
                    self.param_shardings[k] = NamedSharding(mesh, gs_specs[k])
                else:
                    self.param_shardings[k] = \
                        sharding_utils.param_sharding(p, mesh)
            params = {k: jax.device_put(v, self.param_shardings[k])
                      for k, v in params.items()}
            # ZeRO stage 1/2 (group_sharded 'os'/'os_g'): optimizer states
            # shard over the 'sharding' axis even when the param itself is
            # replicated — XLA then reduce-scatters grads into the update.
            opt_shardings = {}
            for k in self.trainable_keys:
                os_spec = gs_specs.get(k)
                opt_shardings[k] = (NamedSharding(mesh, os_spec)
                                    if os_spec is not None
                                    else self.param_shardings[k])
            opt_states = {
                k: jax.tree_util.tree_map(
                    lambda a, s=opt_shardings[k], nd=params[k].ndim:
                        jax.device_put(a, s if a.ndim == nd else
                                       NamedSharding(mesh, P())),
                    opt_states[k])
                for k in self.trainable_keys}
            buffers = {k: jax.device_put(v, NamedSharding(mesh, P()))
                       for k, v in buffers.items()}
            # the stage-1 vs stage-2 distinction (ZeRO): stage 1 keeps grads
            # replicated (one all-reduce, update gathers from sharded
            # states); stage 2/3 constrain grads onto the sharding axis, so
            # XLA lowers the grad sum to a reduce-scatter (half the grad
            # traffic — the reference's stage-2 win) and each rank updates
            # only its shard
            self.grad_shardings = {}
            for k in self.trainable_keys:
                p = self.param_objs[k]
                lvl = getattr(p, "sharding_level", None)
                os_spec = gs_specs.get(k)
                if lvl in ("os_g", "p_g_os") and os_spec is not None:
                    self.grad_shardings[k] = NamedSharding(mesh, os_spec)
                elif lvl == "os":
                    self.grad_shardings[k] = NamedSharding(mesh, P())
        else:
            self.grad_shardings = {}
        self.params = params
        self.buffers = buffers
        self.opt_states = opt_states

        param_shardings_ref = getattr(self, "param_shardings", None)
        grad_shardings_ref = self.grad_shardings
        clip = optimizer._grad_clip
        clip_norm = getattr(clip, "clip_norm", None) if clip is not None else None
        update_rule = optimizer._update
        wd_map = dict(self.wd_map)
        trainable_keys = list(self.trainable_keys)
        model_ref = model
        loss_ref = loss_fn
        mesh_ref = mesh
        bspec = batch_spec

        def compute_loss(train_params, frozen_params, buffers, batch, rng,
                         use_hints=True):
            all_params = {**frozen_params, **train_params}
            def run():
                out, new_buf = functional_call(model_ref, all_params,
                                               batch["inputs"], buffers=buffers,
                                               rng_key=rng, training=True)
                t_out = Tensor._from_data(out) if not isinstance(out, tuple) \
                    else tuple(Tensor._from_data(o) for o in out)
                labels = [Tensor._from_data(l) for l in batch["labels"]]
                loss = loss_ref(t_out, *labels)
                return loss._data.astype(jnp.float32), new_buf
            # hints are skipped inside the explicit-sync shard_map island:
            # with_sharding_constraint is meaningless on manual (per-shard)
            # values, and the island only activates when mp/pp/sep are trivial
            if mesh_ref is not None and use_hints:
                with _mesh_hints(mesh_ref):
                    return run()
            return run()

        accum = int(grad_accum)

        def accum_loss_grads(train_params, frozen_params, buffers, batch,
                             rng, use_hints=True):
            compute = functools.partial(compute_loss, use_hints=use_hints)
            """Gradient merge (ref: GradientMergeOptimizer / pipeline
            accumulate_steps): split the batch into `accum` microbatches on
            axis 0 and lax.scan them, summing grads in the carry (O(1) grad
            memory) and applying ONE optimizer update for the mean."""
            if accum <= 1:
                return jax.value_and_grad(compute, has_aux=True)(
                    train_params, frozen_params, buffers, batch, rng)

            def split(a):
                if a.ndim == 0 or a.shape[0] % accum:
                    raise ValueError(
                        f"grad_accum={accum} must divide batch dim "
                        f"{a.shape[:1]}")
                # STRIDED split (row i of microbatch m is global row
                # m + i*accum): under a dp-sharded batch each microbatch
                # keeps rows on every dp shard; a contiguous split would
                # park whole microbatches on one shard and force XLA to
                # reshard every scan step
                a = a.reshape((a.shape[0] // accum, accum) + a.shape[1:])
                return jnp.swapaxes(a, 0, 1)

            mb = jax.tree_util.tree_map(split, batch)
            rngs = jax.random.split(rng, accum)
            g0 = jax.tree_util.tree_map(jnp.zeros_like, train_params)

            def body(carry, xs):
                bufs, gsum, lsum = carry
                batch_i, rng_i = xs
                (l, new_bufs), g = jax.value_and_grad(
                    compute, has_aux=True)(train_params, frozen_params,
                                           bufs, batch_i, rng_i)
                gsum = jax.tree_util.tree_map(jnp.add, gsum, g)
                return (new_bufs, gsum, lsum + l), None

            (new_buffers, gsum, lsum), _ = jax.lax.scan(
                body, (buffers, g0, jnp.zeros((), jnp.float32)), (mb, rngs))
            grads = jax.tree_util.tree_map(lambda g: g / accum, gsum)
            return (lsum / accum, new_buffers), grads

        # --- explicit bucketed/per-param gradient sync (DataParallel /
        # GroupSharded stage-1/2). Instead of GSPMD's implicit per-parameter
        # grad reduces, a fully-manual shard_map island computes per-shard
        # grads and issues the reduces itself — one fused psum per size-capped
        # bucket, in reverse parameter order, so each bucket's collective
        # overlaps the rest of backward. Opt-in (grad_sync=/env); only
        # activates when every non-trivial mesh axis is a data axis (dp/
        # sharding) — hybrid mp/pp/sep keeps the GSPMD path.
        sync_mode = grad_sync or envs.get("PADDLE_TPU_GRAD_SYNC")
        reduce_axes = ()
        if sync_mode not in ("auto", "explicit", "bucketed"):
            raise ValueError(f"grad_sync must be auto/explicit/bucketed, "
                             f"got {sync_mode!r}")
        if sync_mode != "auto":
            if mesh is None or batch_spec is None:
                sync_mode = "auto"
            else:
                nontrivial = {ax for ax, sz in mesh.shape.items() if sz > 1}
                reduce_axes = tuple(ax for ax in ("dp", "sharding")
                                    if mesh.shape.get(ax, 1) > 1)
                if not reduce_axes or nontrivial - {"dp", "sharding"}:
                    sync_mode, reduce_axes = "auto", ()
        self.grad_sync_mode = sync_mode
        self.grad_buckets = None
        if sync_mode == "bucketed":
            if grad_bucket_mb is None:
                grad_bucket_mb = getattr(model, "_comm_buffer_mb", None)
            if grad_bucket_mb is None:
                grad_bucket_mb = envs.get("PADDLE_TPU_DP_BUCKET_MB")
            shapes = {k: (tuple(params[k].shape), params[k].dtype.itemsize)
                      for k in trainable_keys}
            self.grad_buckets = sharding_utils.plan_grad_buckets(
                shapes, int(float(grad_bucket_mb) * 2 ** 20))
        buckets_ref = self.grad_buckets
        sync_axes = reduce_axes

        # --- stage-3 (ZeRO-3) param-gather prefetch: bucket the sharded
        # params in FORWARD order (same planner as the grad buckets, not
        # reversed) and issue each bucket's all-gather one bucket ahead of
        # first use inside the compiled step (sharding_utils.
        # prefetch_param_gathers). Default follows the overlap switch
        # (PADDLE_TPU_TP_OVERLAP) like the ring matmuls; pure data movement,
        # loss is bit-identical to the non-prefetched stage 3.
        self.param_gather_buckets = None
        prefetch_shardings = {}
        pf_shapes = {}
        if mesh is not None and mesh.shape.get("sharding", 1) > 1:
            if param_prefetch is None:
                from ..parallel import collective_matmul as _cm
                param_prefetch = _cm.overlap_enabled()
            if param_prefetch:
                for k in trainable_keys:
                    p = self.param_objs[k]
                    if getattr(p, "sharding_level", None) != "p_g_os":
                        continue
                    full = getattr(p, "_pre_gs_pspec", None) or P()
                    if self.param_shardings[k].spec == full:
                        continue  # indivisible shape: never actually sharded
                    pf_shapes[k] = (tuple(params[k].shape),
                                    params[k].dtype.itemsize)
                    prefetch_shardings[k] = NamedSharding(mesh, full)
                if pf_shapes:
                    cap = (int(float(param_bucket_mb) * 2 ** 20)
                           if param_bucket_mb is not None
                           else int(getattr(model, "_gs_buffer_bytes",
                                            2 ** 23)))
                    self.param_gather_buckets = \
                        sharding_utils.plan_grad_buckets(
                            pf_shapes, cap, reverse=False)
        pf_buckets_ref = self.param_gather_buckets
        pf_shardings_ref = prefetch_shardings

        # --- step telemetry (observability.StepMetrics). Explicit arg wins,
        # else PADDLE_TPU_TELEMETRY. Nothing below adds host syncs: wall
        # times are perf_counter intervals around the ASYNC dispatch, FLOPs
        # are captured once per compile from the lowered program's cost
        # analysis, memory stats are host-side PJRT queries.
        self.telemetry = None
        self._flops_stale = True
        self._seen_cache_size = 0
        # failure flight recorder (observability.FlightRecorder): rings the
        # last N dispatch records host-side and dumps them to
        # PADDLE_TPU_TELEMETRY_DIR when a step raises or its wall time
        # spikes. Independent of the telemetry switch so post-mortems don't
        # depend on having had telemetry on.
        self.recorder = (
            observability.FlightRecorder(source="train_step")
            if observability.flight_recorder_enabled(flight_recorder)
            else None)
        if observability.telemetry_enabled(telemetry):
            self.telemetry = observability.StepMetrics(
                name="train_step", tokens_per_step=tokens_per_step,
                n_devices=(mesh.size if mesh is not None else 1))
            logdir = telemetry_dir or observability.telemetry_dir()
            if logdir:
                rank = observability.process_rank()
                self.telemetry.attach(observability.JsonlWriter(
                    os.path.join(logdir, f"steps_rank{rank:03d}.jsonl")))
            observability.set_active(self.telemetry)
            observability.set_counter(
                "grad_sync.mode." + sync_mode, 1)
        # fleet monitor (PR 15): cross-rank step/comm/memory aggregation,
        # one host-side allgather per reporting interval, nothing on the
        # step hot path. Accepts a shared FleetMonitor instance (the
        # multichip dryrun's), True/False, or None -> PADDLE_TPU_FLEET.
        if isinstance(fleet, observability.FleetMonitor):
            self.fleet = fleet
        elif observability.fleet_enabled(fleet if isinstance(fleet, bool)
                                         else None):
            logdir = telemetry_dir or observability.telemetry_dir()
            self.fleet = observability.FleetMonitor(
                recorder=self.recorder,
                out_path=(os.path.join(logdir, "fleet_health.jsonl")
                          if logdir else None))
        else:
            self.fleet = None
        # roofline ledger (PR 17): itemizes step time into named kernel
        # component lines from the cost_estimate FLOPs/bytes captured while
        # tracing. Accepts a shared RooflineLedger instance, True/False, or
        # None -> PADDLE_TPU_LEDGER. Measurement-only: the compiled program
        # is untouched, the only hot-path cost is one perf_counter read.
        if isinstance(ledger, observability.RooflineLedger):
            self.ledger = ledger
        elif observability.ledger_enabled(ledger if isinstance(ledger, bool)
                                          else None):
            self.ledger = observability.RooflineLedger(name="train_step")
        else:
            self.ledger = None
        if self.fleet is not None and self.telemetry is not None:
            try:
                self.telemetry.register_into(self.fleet.registry)
            except ValueError:
                pass  # shared monitor: an earlier TrainStep registered
        if self.grad_buckets is not None:
            sizes = sharding_utils.bucket_bytes(shapes, self.grad_buckets)
            observability.set_counter("grad_sync.n_buckets",
                                      len(self.grad_buckets))
            observability.set_counter("grad_sync.total_bytes", sum(sizes))
            for i, nbytes in enumerate(sizes):
                # .plan_bytes: the static bucket payload; the traced span
                # separately tallies .bytes per trace
                observability.set_counter(
                    f"grad_sync.bucket{i:02d}.plan_bytes", nbytes)
        if self.param_gather_buckets is not None:
            sizes = sharding_utils.bucket_bytes(pf_shapes,
                                                self.param_gather_buckets)
            observability.set_counter("param_gather.n_buckets",
                                      len(self.param_gather_buckets))
            observability.set_counter("param_gather.total_bytes", sum(sizes))
            for i, nbytes in enumerate(sizes):
                observability.set_counter(
                    f"param_gather.bucket{i:02d}.plan_bytes", nbytes)

        def island_loss_grads(train_params, frozen_params, buffers, batch,
                              rng):
            from jax import shard_map
            n_tot = 1
            for ax in sync_axes:
                n_tot *= mesh.shape[ax]

            def local(train_params, frozen_params, buffers, batch, rng):
                idx = lax.axis_index(sync_axes[0])
                for ax in sync_axes[1:]:
                    idx = idx * mesh.shape[ax] + lax.axis_index(ax)
                rng_local = jax.random.fold_in(rng, idx)
                (loss, new_buf), grads = accum_loss_grads(
                    train_params, frozen_params, buffers, batch, rng_local,
                    use_hints=False)
                if buckets_ref is not None:
                    grads = sharding_utils.bucketed_psum(
                        grads, buckets_ref, sync_axes)
                else:
                    grads = {k: lax.psum(g, sync_axes)
                             for k, g in grads.items()}
                grads = {k: g / n_tot for k, g in grads.items()}
                loss = lax.psum(loss, sync_axes) / n_tot
                new_buf = {k: lax.psum(v, sync_axes) / n_tot
                           for k, v in new_buf.items()}
                return loss, new_buf, grads

            bs = list(bspec)
            batch_specs = jax.tree_util.tree_map(
                lambda a: P(*(bs + [None] * (a.ndim - len(bs)))), batch)
            f = shard_map(local, mesh=mesh,
                          in_specs=(P(), P(), P(), batch_specs, P()),
                          out_specs=(P(), P(), P()),
                          axis_names=frozenset(mesh.axis_names),
                          check_vma=False)
            loss, new_buf, grads = f(train_params, frozen_params, buffers,
                                     batch, rng)
            return (loss, new_buf), grads

        def step_fn(train_params, opt_states, buffers, frozen_params, batch,
                    rng, lr):
            # stage-3 prefetch: hand the forward the GATHERED view (bucketed,
            # one ahead); the optimizer update below stays on the sharded
            # originals. Constraints are value-identity, so grads wrt the
            # gathered view equal grads wrt the originals bit-for-bit.
            fwd_params = train_params
            if pf_buckets_ref:
                fwd_params = sharding_utils.prefetch_param_gathers(
                    train_params, pf_buckets_ref, pf_shardings_ref)
            if sync_axes:
                (loss, new_buffers), grads = island_loss_grads(
                    fwd_params, frozen_params, buffers, batch, rng)
            else:
                (loss, new_buffers), grads = accum_loss_grads(
                    fwd_params, frozen_params, buffers, batch, rng)
            if grad_shardings_ref:
                grads = {
                    k: jax.lax.with_sharding_constraint(
                        g, grad_shardings_ref[k])
                    if k in grad_shardings_ref else g
                    for k, g in grads.items()}
            if clip_norm is not None:
                gnorm = jnp.sqrt(sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in jax.tree_util.tree_leaves(grads)))
                scale = clip_norm / jnp.maximum(gnorm, clip_norm)
                grads = jax.tree_util.tree_map(
                    lambda g: (g * scale).astype(g.dtype), grads)
            new_params = dict(train_params)
            new_states = dict(opt_states)
            for k in trainable_keys:
                p32 = train_params[k]
                new_p, new_s = update_rule(
                    p32.astype(jnp.float32) if p32.dtype != jnp.float32 else p32,
                    grads[k], opt_states[k], lr, wd_map[k], {})
                new_p = new_p.astype(train_params[k].dtype)
                if param_shardings_ref is not None:
                    # keep the param on its declared layout: replicated for
                    # ZeRO-1/2 (gathers the sharded update), sharded for
                    # ZeRO-3/TP — the reference's post-step broadcast
                    new_p = jax.lax.with_sharding_constraint(
                        new_p, param_shardings_ref[k])
                new_params[k] = new_p
                new_states[k] = new_s
            return new_params, new_states, new_buffers, loss

        donate_args = (0, 1, 2) if donate else ()
        self._compiled = jax.jit(step_fn, donate_argnums=donate_args)

    def _prepare(self, inputs, labels):
        """Shared __call__/compiled_hlo preamble: the batch pytree and the
        param split, exactly as the compiled step consumes them."""
        if labels is None:
            *inputs, labels = inputs
            labels = [labels]
        elif not isinstance(labels, (list, tuple)):
            labels = [labels]
        batch = {
            "inputs": tuple(self._place_batch(x) for x in inputs),
            "labels": [self._place_batch(l) for l in labels],
        }
        train_params = {k: self.params[k] for k in self.trainable_keys}
        frozen = {k: v for k, v in self.params.items()
                  if k not in set(self.trainable_keys)}
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        return batch, train_params, frozen, lr

    def __call__(self, *inputs, labels=None):
        batch, train_params, frozen, lr = self._prepare(list(inputs), labels)
        self._rng, sub = jax.random.split(self._rng)
        m = self.telemetry
        led = self.ledger
        captured = False
        if (m is not None or led is not None) and self._flops_stale:
            # once per (re)compile, BEFORE dispatch (donation hasn't consumed
            # the buffers yet): lower the step for this batch and read the
            # program's cost analysis — trace-time work, nothing per step.
            # The trace also fires every pallas_call cost_estimate= site, so
            # the ledger ingests exact per-kernel FLOPs/bytes for free.
            self._capture_cost(train_params, frozen, batch, sub, lr)
            captured = True
        rec = self.recorder
        fl = self.fleet
        timed = (m is not None or rec is not None or fl is not None
                 or led is not None)
        t0 = time.perf_counter() if timed else 0.0
        try:
            new_p, new_s, new_b, loss = self._compiled(
                train_params, self.opt_states, self.buffers, frozen, batch,
                sub, lr)
        except BaseException:
            # crash post-mortem: flush the last N dispatch records before
            # the exception propagates (no-op without a telemetry dir)
            if rec is not None:
                rec.dump("exception")
            raise
        if timed:
            dt = time.perf_counter() - t0
            is_compile = (self._note_compile()
                          if (m is not None or led is not None)
                          else self._step_count == 0)
            if is_compile and captured:
                # this dispatch paid trace+compile. A recompile marks FLOPs
                # stale (the program changed) — unless they were captured
                # for exactly this program a few lines up.
                self._flops_stale = False
            if m is not None:
                if is_compile:
                    # account it as compile time, not a step sample
                    m.record_compile(compile_s=dt, flops=m.flops_per_step)
                else:
                    m.step(tokens=self._batch_tokens(batch),
                           dispatch_ms=dt * 1e3)
            if rec is not None:
                if is_compile:
                    rec.record_compile("train_step", dt)
                else:
                    # dispatch wall time (async): in steady state with
                    # donation it tracks device step time; a spike means a
                    # recompile, host stall, or device-queue backup
                    rec.record({"iteration": self._step_count + 1,
                                "dispatch_ms": dt * 1e3,
                                "tokens": self._batch_tokens(batch)})
                    rec.check_step_time(dt)
            if fl is not None and not is_compile:
                # host float only — the monitor must never pull a device
                # value (that would be the sync this path avoids)
                fl.on_step(dt)
            if led is not None and not is_compile:
                led.on_step(dt)
                if observability.ledger_dir() \
                        and self._step_count % 64 == 0:
                    led.write()
        self.params.update(new_p)
        self.opt_states = new_s
        self.buffers = new_b
        self._step_count += 1
        if self.checkpoint is not None:
            # interval-paced async save (overlaps the next steps) and the
            # preemption hook: a pending SIGTERM raises Preempted here,
            # after the final sync save and flight-recorder dump
            self.checkpoint.on_step(self._step_count, self.state_dict,
                                    recorder=self.recorder)
        return Tensor._from_data(loss)

    def state_dict(self):
        """Checkpointable state: params, optimizer states, buffers and the
        step counter, as raw (possibly sharded) jax arrays. Restoring via
        CheckpointManager.restore reshards each leaf onto whatever
        sharding THIS TrainStep placed it with — the elastic-resume path
        when the mesh shape changed between save and restore."""
        return {"params": dict(self.params),
                "opt_states": self.opt_states,
                "buffers": dict(self.buffers),
                "step": self._step_count}

    def load_state_dict(self, state):
        """Adopt a (restored) state dict produced by :meth:`state_dict`."""
        self.params.update(state["params"])
        self.opt_states = state["opt_states"]
        self.buffers.update(state["buffers"])
        self._step_count = int(np.asarray(state["step"]))  # noqa: PTA006 -- restore boundary, once per resume: the step counter must become a host int

    def restore(self, checkpoint=None, step: Optional[int] = None) -> int:
        """Restore from `checkpoint` (defaults to the ctor's manager):
        fills a fresh state_dict() — current shardings as reshard targets —
        and adopts it. Returns the restored step number."""
        mgr = checkpoint if checkpoint is not None else self.checkpoint
        if mgr is None:
            raise ValueError("no CheckpointManager: pass checkpoint= to "
                             "restore() or the TrainStep constructor")
        state = self.state_dict()
        restored = mgr.restore(state, step=step)
        self.load_state_dict(state)
        return restored

    def _capture_cost(self, train_params, frozen, batch, sub, lr):
        """FLOPs-per-step from the lowered program's cost analysis (client-
        side HLO analysis; no extra XLA compile, no device work). Tracing
        also fires every pallas_call ``cost_estimate=`` site exactly as
        many times as the program calls it, so the window delta over the
        kernel-cost totals is this program's exact per-kernel cost — the
        roofline ledger's model-mode feed."""
        self._flops_stale = False
        try:
            from ..ops import _common as _opsc
            snap = _opsc.snapshot_kernel_costs()
            t0 = time.perf_counter()
            lowered = self._compiled.lower(train_params, self.opt_states,
                                           self.buffers, frozen, batch, sub,
                                           lr)
            trace_s = time.perf_counter() - t0
            if self.ledger is not None:
                self.ledger.ingest(_opsc.kernel_costs_since(snap))
            cost = lowered.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            flops = float((cost or {}).get("flops", 0.0))
            if self.telemetry is not None:
                self.telemetry.trace_time_s += trace_s
                if flops > 0:
                    self.telemetry.flops_per_step = flops
        except Exception:
            pass

    def _note_compile(self) -> bool:
        """Detect a fresh jit compile via the pjit cache size (True exactly
        when this call compiled); marks FLOPs stale on recompiles."""
        try:
            size = self._compiled._cache_size()
        except Exception:
            if self.telemetry is None:
                return not self._step_count
            return self.telemetry.compiles == 0 and not self._step_count
        if size != self._seen_cache_size:
            self._seen_cache_size = size
            self._flops_stale = True
            return True
        return False

    def _batch_tokens(self, batch) -> Optional[int]:
        """Tokens per step for throughput: [B, S] integer inputs count B*S
        (sequence ids), anything else counts batch rows. Override with the
        ``tokens_per_step`` ctor arg."""
        if self.telemetry is not None \
                and self.telemetry.tokens_per_step is not None:
            return self.telemetry.tokens_per_step
        try:
            x = batch["inputs"][0]
            if x.ndim == 2 and jnp.issubdtype(x.dtype, jnp.integer):
                return int(x.shape[0]) * int(x.shape[1])
            return int(x.shape[0])
        except Exception:
            return None

    def compiled_hlo(self, *inputs, labels=None) -> str:
        """Post-SPMD-partitioning HLO of the step (for inspecting which
        collectives XLA emitted — e.g. ZeRO stage-2's grad reduce-scatter)."""
        batch, train_params, frozen, lr = self._prepare(list(inputs), labels)
        lowered = self._compiled.lower(train_params, self.opt_states,
                                       self.buffers, frozen, batch,
                                       self._rng, lr)
        return lowered.compile().as_text()

    def _place_batch(self, x):
        arr = x._data if isinstance(x, Tensor) else jnp.asarray(np.asarray(x))  # noqa: PTA006 -- input boundary: stages the host batch, not a device pull
        if self.mesh is not None:
            if self.batch_spec is not None:
                spec = list(self.batch_spec) + \
                    [None] * (arr.ndim - len(self.batch_spec))
            else:
                # no dp sharding: the batch must still live on the MESH
                # (replicated) — mesh-sharded params + single-device
                # batch is an incompatible-devices error under jit
                spec = [None] * arr.ndim
            arr = jax.device_put(arr, NamedSharding(self.mesh, P(*spec)))
        return arr

    def sync_to_model(self):
        """Copy the (device, possibly sharded) params back into the Layer."""
        for k, p in self.param_objs.items():
            if k in self.params:
                p._data = self.params[k]
        for k, b in self.model.named_buffers():
            if b is not None and k in self.buffers:
                b._data = self.buffers[k]


class _mesh_hints:
    """Context activating sharding hints for the functional trace."""

    def __init__(self, mesh):
        self.mesh = mesh
        self._cm = None

    def __enter__(self):
        self._cm = sharding_utils.auto_shard(self.mesh)
        return self._cm.__enter__()

    def __exit__(self, *exc):
        return self._cm.__exit__(*exc)
