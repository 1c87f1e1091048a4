"""Training loop: grad-accumulation, checkpoint/restart, straggler watchdog.

``make_train_step`` builds the jit-able (params, opt_state, batch) -> ...
update (optionally scanning microbatches for gradient accumulation and
applying error-feedback int8 compression to the gradients that would cross
the pod axis).  ``Trainer`` owns the host-side loop: periodic async
checkpoints, resume-from-latest, deterministic data (stateless pipeline) and
a step-time EMA watchdog that flags stragglers, and names each step for the
profiler (``run_step``: a step annotation around the host spans ``data``,
``dispatch`` and ``sync``) and records recompiles.  The jitted step donates
params and optimizer state, so a failed step cannot be retried on the same
arguments: a device error propagates at once, and recovery is
``try_resume`` from the last checkpoint (or ``replan`` onto the survivors).
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro import tracing
from repro.core.plan import JointPlan, StrategyPlan
from repro.optim.adamw import OptConfig, apply_adamw, init_opt_state
from repro.optim.compress import compress_with_feedback, init_residuals
from repro.train.checkpoint import CheckpointManager

log = logging.getLogger("repro.train")


@dataclasses.dataclass(frozen=True)
class ElasticSpec:
    """How to rebuild the training computation on a RESIZED mesh — the
    trainer-side mirror of ``serving.engine.replan``'s derivation.

    ``make_loss(mesh, sharder, schedule) -> loss_fn`` rebuilds the loss for
    a new parallel triple (mesh may be None for the 1-device degenerate
    case).  ``solve_schedule(sp, topology) -> Schedule`` re-solves the DSP
    switching plan for a new SP degree on the resized fabric (called only
    for sp > 1; None skips planning and the mode-based Sharder defaults
    apply).  ``plan`` is the ``parallel.partition.ParallelPlan`` parameter
    placements are derived from on every mesh."""

    make_loss: Callable[..., Callable]
    solve_schedule: Optional[Callable] = None
    plan: Any = None


def place_tree(tree, mesh, plan):
    """Migrate a params-shaped pytree onto ``mesh`` per ``plan``
    (``param_pspecs``-derived shardings; the path rules see the same leaf
    names under ``m/``/``v/``/``master/`` prefixes, so AdamW moments and
    compression residuals reshard exactly like their params).  ``mesh=None``
    collapses to host-side single-device arrays."""
    if tree is None:
        return None
    if mesh is None:
        return jax.tree_util.tree_map(
            lambda x: jnp.asarray(jax.device_get(x)), tree)
    from jax.sharding import NamedSharding
    from repro.parallel.partition import param_pspecs
    specs = param_pspecs(tree, plan, axis_sizes=dict(mesh.shape))
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs)


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    total_steps: int = 100
    grad_accum: int = 1
    log_every: int = 10
    ckpt_every: int = 50
    straggler_factor: float = 3.0      # step slower than 3x EMA => flagged
    grad_compress: bool = False        # int8 EF compression (cross-pod)


def make_train_step(loss_fn: Callable, opt_cfg: OptConfig, *,
                    grad_accum: int = 1, grad_compress: bool = False):
    """loss_fn(params, batch) -> (scalar, metrics dict).

    With grad_accum > 1, ``batch`` leaves must carry a leading
    (grad_accum, micro...) dim; gradients average over microbatches via
    lax.scan (sequential, constant memory).
    """

    def grads_of(params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        return loss, metrics, grads

    def step(params, opt_state, batch, residuals=None):
        if grad_accum > 1:
            def micro(carry, mb):
                acc, loss_acc = carry
                loss, _, g = grads_of(params, mb)
                acc = jax.tree_util.tree_map(jnp.add, acc, g)
                return (acc, loss_acc + loss), None

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, loss_sum), _ = jax.lax.scan(
                micro, (zeros, jnp.zeros(())), batch)
            grads = jax.tree_util.tree_map(lambda g: g / grad_accum, gsum)
            loss = loss_sum / grad_accum
            metrics: Dict[str, Any] = {}
        else:
            loss, metrics, grads = grads_of(params, batch)

        if grad_compress:
            assert residuals is not None
            grads, residuals = compress_with_feedback(grads, residuals)

        params, opt_state, om = apply_adamw(params, grads, opt_state, opt_cfg)
        out_metrics = {"loss": loss, **metrics, **om}
        if grad_compress:
            return params, opt_state, residuals, out_metrics
        return params, opt_state, out_metrics

    return step


class Trainer:
    def __init__(self, *, loss_fn, params, opt_cfg: OptConfig,
                 cfg: TrainerConfig, data_fn: Callable[[int], Any],
                 ckpt_dir: Optional[str] = None,
                 schedule=None, mesh=None, topology=None,
                 elastic: Optional[ElasticSpec] = None):
        self.cfg = cfg
        self.data_fn = data_fn
        self.params = params
        self.opt_cfg = opt_cfg
        self.opt_state = init_opt_state(params, opt_cfg)
        self.residuals = (init_residuals(params) if cfg.grad_compress
                          else None)
        self.ckpt = (CheckpointManager(ckpt_dir) if ckpt_dir else None)
        self.start_step = 0
        self.straggler_events = []
        self.recompiles = []       # (step, programs compiled) after step 1
        self.metrics_history = []
        self.step_seconds = []     # host clock, step ended by block_until_ready
        # elastic state: the mesh/schedule the step runs on today, the
        # fabric template replan resizes, and the data-axis width an
        # elastic resize preserves when it still divides
        self.mesh = mesh
        self.step_fn = self._jit_step(loss_fn)
        self.schedule = schedule
        self.elastic = elastic
        self._topology_template = (
            topology if topology is not None
            else getattr(schedule, "topology", None))
        self._data_axis = (mesh.shape.get("data", 1)
                           if mesh is not None else 1)
        # planned communication of one training step, both legs: the solved
        # DSP Schedule (core.schedule) prices its forward AND its planned
        # backward — surfaced in the run() summary next to measured times
        self.plan_meta = self._plan_meta(schedule)

    def _jit_step(self, loss_fn):
        """The jitted train step.  Params and optimizer state are donated:
        the update writes in place, which is what lets a full-width model's
        step fit one chip (no second copy of the f32 master, m and v)."""
        kw = {}
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            kw["out_shardings"] = (self._pin_state()
                                   + (NamedSharding(self.mesh, P()),))
        return jax.jit(
            make_train_step(loss_fn, self.opt_cfg,
                            grad_accum=self.cfg.grad_accum,
                            grad_compress=self.cfg.grad_compress),
            donate_argnums=(0, 1), **kw)

    def _pin_state(self):
        """Put params, optimizer state (and residuals) on ``self.mesh`` —
        leaves not on it yet, such as the step counter, replicated — and
        return their shardings.  The step's outputs keep them, so the
        donated buffers are reused in place and every call runs the same
        executable (XLA's own choice of output sharding would differ from
        the placement and recompile the second step)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        rep = NamedSharding(self.mesh, P())

        def where(x):
            s = x.sharding
            return (s if isinstance(s, NamedSharding) and s.mesh == self.mesh
                    else rep)

        state = (self.params, self.opt_state)
        if self.cfg.grad_compress:
            state += (self.residuals,)
        shardings = jax.tree_util.tree_map(where, state)
        placed = jax.device_put(state, shardings)
        self.params, self.opt_state = placed[0], placed[1]
        if self.cfg.grad_compress:
            self.residuals = placed[2]
        return shardings

    @staticmethod
    def _plan_meta(schedule) -> Optional[Dict[str, Any]]:
        if schedule is None:
            return None
        meta: Dict[str, Any] = {
            "planned_switches": schedule.n_switches(),
            "bwd_mirrored": schedule.mirrored,
        }
        if schedule.topology is not None:
            rs = schedule.roundtrip_seconds()
            meta.update(planned_fwd_seconds=rs.fwd,
                        planned_bwd_seconds=rs.bwd,
                        planned_roundtrip_seconds=rs.total)
            log.info("planned comm: fwd %.3es + bwd %.3es per step "
                     "(bwd %s)", rs.fwd, rs.bwd,
                     "mirrors fwd" if schedule.mirrored else "planned "
                     "independently")
        return meta

    # -- fault tolerance -------------------------------------------------------
    def try_resume(self):
        if self.ckpt is None:
            return
        latest = self.ckpt.latest_step()
        if latest is None:
            return
        template = {"params": self.params, "opt": self.opt_state}
        if self.cfg.grad_compress and self.residuals is not None:
            template["residuals"] = self.residuals
        _, tree = self.ckpt.restore(template, latest)
        self.params, self.opt_state = tree["params"], tree["opt"]
        if "residuals" in template:
            self.residuals = tree["residuals"]
        self.start_step = latest
        log.info("resumed from step %d", latest)

    def _plan_record(self):
        """The solved plan the checkpoint manifest records — a
        ``StrategyPlan`` when the schedule carries strategies, a
        ``JointPlan`` when the backward was planned, the bare dim sequence
        otherwise (None without a schedule)."""
        sch = self.schedule
        if sch is None:
            return None
        if getattr(sch, "strategies", None) is not None:
            return StrategyPlan(tuple(sch.dims), tuple(sch.strategies))
        if getattr(sch, "bwd_dims", None) is not None:
            return JointPlan(tuple(sch.dims), tuple(sch.bwd_dims))
        return list(sch.dims)

    def _checkpoint(self, step: int, blocking: bool = False):
        if self.ckpt is None:
            return
        tree = {"params": self.params, "opt": self.opt_state}
        if self.cfg.grad_compress and self.residuals is not None:
            tree["residuals"] = self.residuals
        sch = self.schedule
        topo = (getattr(sch, "topology", None) if sch is not None else None)
        meta = None
        if sch is not None:
            meta = {"initial": sch.initial, "final": sch.final}
        with tracing.span(tracing.CHECKPOINT):
            self.ckpt.save(step, tree, blocking=blocking,
                           plan=self._plan_record(),
                           topology=topo if topo is not None
                           else self._topology_template,
                           meta=meta)

    # -- elastic resize --------------------------------------------------------
    def replan(self, n_devices: int, *, topology=None):
        """Re-solve and rebuild for ``n_devices`` — the training mirror of
        ``serving.engine.replan``.  Re-solves the switching plan on the
        resized fabric (``Topology.resized``, or an explicit override),
        rebuilds schedule/sharder/train-step through the ``ElasticSpec``,
        and migrates params + opt state (AdamW moments, master weights and
        compression residuals reshard with their params) onto the new mesh.
        Pure layout movement: an 8-to-4 resize keeps the loss curve
        bit-aligned with the uninterrupted run (pinned by the
        ``elastic_train_resize`` scenario)."""
        if self.elastic is None:
            raise ValueError("Trainer.replan needs an ElasticSpec "
                             "(elastic= at construction)")
        if self.ckpt is not None:
            self.ckpt.wait()      # never migrate under an in-flight save
        avail = jax.device_count()
        if n_devices > avail:
            raise ValueError(f"replan({n_devices}) exceeds the "
                             f"{avail} available devices")
        from repro.parallel.partition import ParallelPlan, make_sharder
        plan = self.elastic.plan or ParallelPlan(mode="dsp")
        if n_devices == 1:
            mesh, schedule, topo = None, None, None
            plan = ParallelPlan(mode="none")
            sharder = make_sharder(None, plan)
        else:
            from repro.launch.mesh import submesh
            data = (self._data_axis
                    if self._data_axis > 0 and
                    n_devices % max(self._data_axis, 1) == 0
                    and n_devices // self._data_axis >= 1 else 1)
            mesh = submesh(n_devices, data)
            sp = mesh.shape.get("model", 1)
            topo = topology
            if topo is None and self._topology_template is not None:
                topo = self._topology_template.resized(sp)
            schedule = (self.elastic.solve_schedule(sp, topo)
                        if self.elastic.solve_schedule is not None and sp > 1
                        else None)
            sharder = make_sharder(mesh, plan, schedule, topo)
        # migrate live state: moments/master/residuals follow their params;
        # the scalar step count is replicated everywhere
        self.params = place_tree(self.params, mesh, plan)
        self.opt_state = place_tree(self.opt_state, mesh, plan)
        self.residuals = place_tree(self.residuals, mesh, plan)
        self.mesh = mesh
        self.step_fn = self._jit_step(
            self.elastic.make_loss(mesh, sharder, schedule))
        self.schedule = schedule
        self.plan_meta = self._plan_meta(schedule)
        log.info("replanned onto %d device(s)%s", n_devices,
                 "" if schedule is None else
                 f" ({schedule.n_switches()} planned switches)")
        return self

    # -- loop -------------------------------------------------------------------
    def run_step(self, step: int):
        """One training step inside the profiler's step annotation and the
        host spans ``data``, ``dispatch`` and ``sync`` (``repro.tracing``);
        returns its metrics and host seconds (dispatch to the loss being
        ready).  Programs compiled, or loaded from the compile cache, by
        any step after the first are recorded in ``recompiles`` as
        ``(step, n)``."""
        with _Compiles() as compiles, jax.profiler.StepTraceAnnotation(
                tracing.STEP, step_num=step):
            with tracing.span(tracing.DATA):
                batch = self.data_fn(step)
            t0 = time.monotonic()
            with tracing.span(tracing.DISPATCH):
                if self.cfg.grad_compress:
                    (self.params, self.opt_state, self.residuals,
                     metrics) = self.step_fn(self.params, self.opt_state,
                                             batch, self.residuals)
                else:
                    self.params, self.opt_state, metrics = self.step_fn(
                        self.params, self.opt_state, batch)
            with tracing.span(tracing.SYNC):
                jax.block_until_ready(metrics["loss"])
            dt = time.monotonic() - t0
        if compiles.n and self.step_seconds:
            self.recompiles.append((step, compiles.n))
            log.warning("recompile: step %d compiled %d program(s)", step,
                        compiles.n)
        self.step_seconds.append(dt)
        return metrics, dt

    def run(self) -> Dict[str, Any]:
        ema = None
        step = self.start_step
        while step < self.cfg.total_steps:
            metrics, dt = self.run_step(step)
            if ema is None:
                ema = dt
            if dt > self.cfg.straggler_factor * ema and step > self.start_step + 2:
                self.straggler_events.append((step, dt, ema))
                log.warning("straggler: step %d took %.3fs (ema %.3fs)",
                            step, dt, ema)
            ema = 0.9 * ema + 0.1 * dt
            step += 1
            if step % self.cfg.log_every == 0:
                self.metrics_history.append(
                    (step, float(metrics["loss"])))
                log.info("step %d loss %.4f (%.3fs)", step,
                         float(metrics["loss"]), dt)
            if self.cfg.ckpt_every and step % self.cfg.ckpt_every == 0:
                self._checkpoint(step)
        self._checkpoint(step, blocking=True)
        out = {"final_step": step,
               "history": self.metrics_history,
               "step_seconds": self.step_seconds,
               "stragglers": self.straggler_events,
               "recompiles": self.recompiles}
        if self.plan_meta is not None:
            out["plan"] = self.plan_meta
        return out


class _Compiles:
    """Counts the programs JAX compiles or loads from the compile cache
    while it is open."""

    def __init__(self):
        self.n = 0

    def __call__(self, event, *_, **__):
        if "backend_compile" in event or "cache_retrieval" in event:
            self.n += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self)
        return False
