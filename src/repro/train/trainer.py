"""Fault-tolerant training loop.

Responsibilities beyond ``train_step``:
  * checkpoint/restart (exact resume: params + optimizer + data-iterator +
    step — the restart test asserts a bitwise-identical loss trajectory),
  * preemption (SIGTERM -> final checkpoint),
  * straggler monitoring (per-step wall-time EWMA; steps > mean + k*sigma are
    logged and counted — on a fleet this feeds the re-dispatch policy),
  * microbatch gradient accumulation (sequential ``lax.scan`` over
    microbatches — the standard way to hold global batch while scaling
    nodes down),
  * optional int8 gradient compression with error feedback (cross-pod DCN
    traffic; see dist/compression.py),
  * simulated failure injection for the fault-tolerance tests.
"""

from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager, install_sigterm_handler
from repro.core.gemm import current_log, gemm_context
from repro.data import SyntheticLMData
from repro.dist.compression import ErrorFeedback
from repro.utils.logging import get_logger
from repro.utils.timing import EWMA, Timer

log = get_logger("train")


def train_gemm_div(
    model, batch: Optional[int] = None, plan=None
) -> Dict[str, int]:
    """Per-array-aware ambient GEMM divisor table for the train path.

    ``ShardingPlan.gemm_div`` is mesh-level: it cannot see the per-array
    divisibility demotion ``spec_for`` applies (an odd vocab on a model=4
    mesh executes replicated while the mesh table still claims the split).
    ``serve_gemm_div`` closed that gap for serving; this is the same probe
    at the trainer call site — the other place the mesh-level table used to
    be threaded verbatim (ROADMAP item 6's leftover). Every parameter spec
    runs through the plan's own solver (:meth:`ShardingPlan.demoted_dims`);
    when any tensor-parallel weight dim would be demoted to replication the
    table's ``model`` entry drops to 1, and ``batch`` drops to 1 when the
    global batch is not divisible by the data-parallel factor — so train
    fingerprints never claim splits the arrays don't execute.

    ``plan`` defaults to the ambient :func:`~repro.dist.sharding.current_plan`;
    pass it explicitly when building the step before installing the plan.
    Returns ``{}`` when no plan is active (unsharded training)."""
    from repro.dist.sharding import current_plan

    if plan is None:
        plan = current_plan()
    if plan is None:
        return {}
    div = dict(plan.gemm_div())
    tp = div.get("model", 1)
    if tp > 1:
        offenders = plan.demoted_dims(model.param_specs(), mesh_axis="model")
        if offenders:
            shown = ", ".join(
                f"dim {d} ({ax or '?'}) of {sh}" for sh, ax, _, d in offenders[:3]
            )
            log.warning(
                "train fingerprints demote model divisor %d -> 1: %d weight "
                "dim(s) fail the plan's divisibility solver and execute "
                "replicated (e.g. %s); a mesh-level divisor would fingerprint "
                "local shapes the kernels never see",
                tp,
                len(offenders),
                shown,
            )
            div["model"] = 1
    db = div.get("batch", 1)
    if batch is not None and db > 1 and batch % db:
        log.warning(
            "train fingerprints demote batch divisor %d -> 1: global batch "
            "%d is not divisible, so activations execute replicated",
            db,
            batch,
        )
        div["batch"] = 1
    return div


@dataclass
class TrainerConfig:
    total_steps: int = 100
    log_every: int = 10
    ckpt_every: int = 50
    ckpt_dir: Optional[str] = None
    ckpt_keep: int = 3
    async_ckpt: bool = True
    microbatches: int = 1
    grad_compression: bool = False
    straggler_k: float = 3.0
    handle_sigterm: bool = False


def make_train_step(
    model,
    optimizer,
    *,
    div: Optional[Dict[str, int]] = None,
    microbatches: int = 1,
    grad_compression: bool = False,
    extra_shardings=None,
):
    """Build the jit'd train step: (state, batch) -> (state, metrics).

    state = {params, opt, step} (+ "ef" residuals when compression is on).
    With ``microbatches > 1`` the global batch is split on axis 0 and
    gradients are accumulated with a sequential scan.
    """

    def loss_fn(params, batch):
        # XLA's dot: the Pallas GEMMs have no custom_vjp, so cannot be differentiated
        with gemm_context(backend="xla", log=current_log()):
            return model.loss_fn(params, batch, div=div)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def compute_grads(params, batch):
        if microbatches == 1:
            (loss, metrics), grads = grad_fn(params, batch)
            return loss, metrics, grads
        split = lambda x: x.reshape(microbatches, x.shape[0] // microbatches, *x.shape[1:])
        mb = jax.tree.map(split, batch)

        def body(carry, b):
            acc, loss_acc = carry
            (loss, metrics), grads = grad_fn(params, b)
            acc = jax.tree.map(jnp.add, acc, grads)
            return (acc, loss_acc + loss), metrics

        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (gacc, loss_sum), metrics = jax.lax.scan(body, (zero, 0.0), mb)
        grads = jax.tree.map(lambda g: g / microbatches, gacc)
        metrics = jax.tree.map(lambda m: m[-1], metrics)
        return loss_sum / microbatches, metrics, grads

    def step_fn(state, batch):
        params = state["params"]
        loss, metrics, grads = compute_grads(params, batch)
        if grad_compression:
            grads, residuals = ErrorFeedback.apply(grads, state["ef"])
        new_params, opt_state, opt_metrics = optimizer.update(
            grads, state["opt"], params
        )
        new_state = {
            "params": new_params,
            "opt": opt_state,
            "step": state["step"] + 1,
        }
        if grad_compression:
            new_state["ef"] = residuals
        metrics = {**metrics, **opt_metrics, "loss": loss}
        return new_state, metrics

    return step_fn


def init_train_state(model, optimizer, params, grad_compression: bool = False):
    state = {
        "params": params,
        "opt": optimizer.init(params),
        "step": jnp.zeros((), jnp.int32),
    }
    if grad_compression:
        state["ef"] = ErrorFeedback.init(params)
    return state


@dataclass
class StragglerMonitor:
    ewma: EWMA = field(default_factory=EWMA)
    k: float = 3.0
    flagged: int = 0

    def observe(self, seconds: float) -> bool:
        outlier = self.ewma.is_outlier(seconds, self.k)
        self.ewma.update(seconds)
        if outlier:
            self.flagged += 1
            log.warning(
                "straggler step: %.3fs (mean %.3fs, std %.3fs)",
                seconds,
                self.ewma.mean,
                self.ewma.std,
            )
        return outlier


class Trainer:
    def __init__(
        self,
        model,
        optimizer,
        data: SyntheticLMData,
        cfg: TrainerConfig,
        *,
        div: Optional[Dict[str, int]] = None,
        jit: bool = True,
        failure_injector: Optional[Callable[[int], None]] = None,
    ):
        self.model = model
        self.optimizer = optimizer
        self.data = data
        self.cfg = cfg
        if div is None:
            # default to the probed ambient table (no-op when no plan is
            # installed) so direct Trainer users get the per-array demotion
            # without threading the table themselves
            div = train_gemm_div(model) or None
        self.div = div
        self.failure_injector = failure_injector
        step_fn = make_train_step(
            model,
            optimizer,
            div=div,
            microbatches=cfg.microbatches,
            grad_compression=cfg.grad_compression,
        )
        self.step_fn = jax.jit(step_fn, donate_argnums=(0,)) if jit else step_fn
        self.ckpt = CheckpointManager(cfg.ckpt_dir, cfg.ckpt_keep) if cfg.ckpt_dir else None
        self.monitor = StragglerMonitor(k=cfg.straggler_k)
        self.history: list = []

    # -- checkpoint plumbing ------------------------------------------------
    def _save(self, state, blocking=True):
        if not self.ckpt:
            return
        step = int(state["step"])
        self.ckpt.save(
            step,
            state,
            extra={"data": self.data.state_dict()},
            blocking=blocking,
        )

    def maybe_restore(self, state):
        if not self.ckpt or self.ckpt.latest_step() is None:
            return state, 0
        restored, step = self.ckpt.restore(state)
        extra = self.ckpt.read_extra(step)
        if "data" in extra:
            self.data.load_state_dict(extra["data"])
        log.info("resumed from checkpoint step %d", step)
        return restored, step

    # -- main loop --------------------------------------------------------------
    def fit(self, state):
        cfg = self.cfg
        state, start = self.maybe_restore(state)
        if cfg.handle_sigterm and self.ckpt:
            install_sigterm_handler(lambda: self._save(state, blocking=True))
        step = start
        while step < cfg.total_steps:
            batch = {k: jnp.asarray(v) for k, v in self.data.batch_at(step).items()}
            if self.failure_injector:
                self.failure_injector(step)  # may raise to simulate a crash
            with Timer() as t:
                state, metrics = self.step_fn(state, batch)
                jax.block_until_ready(metrics["loss"])
            self.monitor.observe(t.seconds)
            step += 1
            self.data.state.step = step
            loss = float(metrics["loss"])
            self.history.append(loss)
            if step % cfg.log_every == 0 or step == cfg.total_steps:
                log.info(
                    "step %d loss %.4f grad_norm %.3f (%.3fs)",
                    step,
                    loss,
                    float(metrics.get("grad_norm", 0.0)),
                    t.seconds,
                )
            if self.ckpt and (step % cfg.ckpt_every == 0 or step == cfg.total_steps):
                self._save(state, blocking=not cfg.async_ckpt)
        if self.ckpt:
            self.ckpt.wait()
        return state
