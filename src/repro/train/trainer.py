"""Training recipe loop: jitted TrainState step (loss + grad + clip +
AdamW/ZeRO-1 + schedule) driven by a tiny loop with composable callbacks
(logging, periodic eval, full-state async checkpointing — see
``train/callbacks.py``). The state itself (params, optimizer, step, RNG) is
the explicit :class:`repro.train.state.TrainState` pytree, so checkpointing
and exact resume are properties of the state, not of this loop.

The same ``make_train_step`` is what the multi-pod dry-run lowers — there is
no separate "dry-run model"; the production step function is the artifact
being compiled and analyzed.
"""
from __future__ import annotations

import time
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig, TrainConfig, with_dispatcher
from repro.models.model import loss_fn
from repro.optim.adamw import AdamWState, adamw_update
from repro.optim.schedule import cosine_schedule
from repro.resilience import faults
from repro.resilience.recovery import HangError
from repro.sharding.rules import FoldingPlan
from repro.train.callbacks import Callback, CheckpointCallback, LoggingCallback
from repro.train.state import TrainState, create_train_state


def make_train_step(
    cfg: ModelConfig,
    tcfg: TrainConfig,
    plan: Optional[FoldingPlan],
    use_kernel: bool = False,
    microbatches: Optional[int] = None,
):
    """Returns step(params, opt_state, batch, rng, guard=None)
    -> (params, opt_state, metrics).

    With ``microbatches=m > 1`` the global batch is split into m sequential
    microbatches (lax.scan) whose fp32-accumulated grads feed ONE optimizer
    update — Megatron-style gradient accumulation, bounding per-microbatch
    activation memory to 1/m (§Perf M4).

    ``guard`` (optional dict of f32 scalars, traced — changing values never
    retraces) arms the in-jit anomaly guard: grads are scaled by
    ``grad_scale`` and the observed loss shifted by ``loss_shift`` (both
    identity by default; the fault harness uses them to inject NaN grads /
    loss spikes *inside* the jit), then the step is SKIPPED — params and
    optimizer state (including ``opt.step``) selected back to their inputs
    via ``jnp.where`` — when the observed loss or grad norm is non-finite
    or the loss exceeds ``loss_ceiling``. A skipped step is bitwise clean:
    no partially-applied update can leak. ``metrics["skipped"]`` reports
    the verdict; ``metrics["loss"]`` reports the *observed* (shifted) loss
    so the supervisor sees what tripped the guard."""
    m = microbatches if microbatches is not None else cfg.train_microbatches

    def grad_of(params, batch, rng):
        return jax.value_and_grad(
            lambda p: loss_fn(cfg, plan, p, batch, rng, use_kernel), has_aux=True
        )(params)

    def step(params, opt_state: AdamWState, batch, rng, guard=None):
        B = jax.tree.leaves(batch)[0].shape[0]
        # clamp to a divisor of the actual batch (smoke tests use tiny B)
        m_eff = max(1, min(m, B))
        while B % m_eff:
            m_eff -= 1
        if m_eff > 1:
            mb_batch = jax.tree.map(
                lambda x: x.reshape((m_eff, x.shape[0] // m_eff) + x.shape[1:]), batch
            )
            keys = jax.random.split(rng, m_eff)

            def body(acc, xs):
                g_acc, met_acc = acc
                mb, key = xs
                (_, met), g = grad_of(params, mb, key)
                g_acc = jax.tree.map(
                    lambda a, gi: a + gi.astype(jnp.float32), g_acc, g
                )
                met_acc = jax.tree.map(lambda a, v: a + v, met_acc, met)
                return (g_acc, met_acc), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            met0 = {
                k: jnp.zeros((), jnp.float32)
                for k in ("loss", "ce", "load_balance_loss", "z_loss")
            }
            (g_acc, met_acc), _ = jax.lax.scan(body, (g0, met0), (mb_batch, keys))
            grads = jax.tree.map(lambda g: g / m_eff, g_acc)
            metrics = jax.tree.map(lambda v: v / m_eff, met_acc)
        else:
            (_, metrics), grads = grad_of(params, batch, rng)
        if guard is not None:
            with jax.named_scope("anomaly_guard"):
                grads = jax.tree.map(
                    lambda g: g * guard["grad_scale"].astype(g.dtype), grads
                )
        lr = cosine_schedule(
            opt_state.step, tcfg.lr, tcfg.lr_min, tcfg.warmup_steps, tcfg.total_steps
        )
        with jax.named_scope("adamw"):
            new_params, new_opt = adamw_update(tcfg, grads, opt_state, lr)
        # adamw_update types new params from the grads; microbatch-accumulated
        # grads are fp32, so pin the compute dtype back to the params' (no-op
        # when m_eff == 1) — otherwise step 2 retraces with fp32 params
        new_params = jax.tree.map(
            lambda n, p: n.astype(p.dtype), new_params, params
        )
        with jax.named_scope("grad_norm"):
            gnorm = jnp.sqrt(
                sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
            )
        metrics = {**metrics, "lr": lr, "grad_norm": gnorm}
        if guard is not None:
            with jax.named_scope("anomaly_guard"):
                loss_obs = metrics["loss"] + guard["loss_shift"]
                bad = (
                    ~jnp.isfinite(loss_obs)
                    | ~jnp.isfinite(gnorm)
                    | (loss_obs > guard["loss_ceiling"])
                )
                new_params = jax.tree.map(
                    lambda old, new: jnp.where(bad, old, new), params, new_params
                )
                new_opt = jax.tree.map(
                    lambda old, new: jnp.where(bad, old, new), opt_state, new_opt
                )
            metrics = {
                **metrics, "loss": loss_obs, "skipped": bad.astype(jnp.float32)
            }
        return new_params, new_opt, metrics

    return step


def make_state_step(
    cfg: ModelConfig,
    tcfg: TrainConfig,
    plan: Optional[FoldingPlan],
    use_kernel: bool = False,
    microbatches: Optional[int] = None,
):
    """TrainState-level step: ``step(state, batch, guard=None) -> (state, metrics)``.

    The per-step PRNG split happens INSIDE the jit from ``state.rng``, so
    the key sequence is a pure function of the checkpointed state — exact
    resume needs no host-side RNG bookkeeping. ``state.step`` counts batches
    consumed and always advances (as does the RNG); a guard-skipped step
    leaves only the *optimizer* clock (``opt_state.step``) untouched."""
    inner = make_train_step(cfg, tcfg, plan, use_kernel, microbatches)

    def step(state: TrainState, batch, guard=None):
        rng, sk = jax.random.split(state.rng)
        params, opt_state, metrics = inner(
            state.params, state.opt_state, batch, sk, guard
        )
        return TrainState(state.step + 1, params, opt_state, rng), metrics

    return step


class Trainer:
    """Recipe runtime: owns a TrainState + the jitted state step, and runs
    the loop under composable callbacks. Construct fresh (``params=None`` or
    a params pytree) or from a restored ``state=``
    (:func:`repro.train.state.restore_train_state`)."""

    def __init__(
        self,
        cfg: ModelConfig,
        tcfg: TrainConfig,
        plan: Optional[FoldingPlan] = None,
        params: Optional[Any] = None,
        data_iter: Optional[Iterator[Dict[str, np.ndarray]]] = None,
        use_kernel: bool = False,
        dispatcher: Optional[str] = None,
        state: Optional[TrainState] = None,
        callbacks: Optional[Sequence[Callback]] = None,
        step_timeout_s: Optional[float] = None,
    ):
        cfg = with_dispatcher(cfg, dispatcher)
        self.cfg, self.tcfg, self.plan = cfg, tcfg, plan
        if state is None:
            state = create_train_state(cfg, tcfg, plan, params=params)
        self.state = state
        self.step_fn = jax.jit(
            make_state_step(cfg, tcfg, plan, use_kernel), donate_argnums=(0,)
        )
        self.data_iter = data_iter
        self.callbacks = list(callbacks) if callbacks is not None else None
        self.history: list = []
        # anomaly-guard knobs: the loop always passes a guard dict (scalar
        # values — no retrace when the supervisor tightens the ceiling) and
        # an optional hung-step watchdog (None = disabled)
        self.loss_ceiling = float("inf")
        self.step_timeout_s = step_timeout_s
        # loop iterations run so far: the step number of the profiler's
        # ``repro.train.step`` spans (the global step is on the device)
        self._iterations = 0

    # seed-era attribute access (tests, examples, benchmarks read these)
    @property
    def params(self):
        return self.state.params

    @params.setter
    def params(self, value):
        import dataclasses

        self.state = dataclasses.replace(self.state, params=value)

    @property
    def opt_state(self) -> AdamWState:
        return self.state.opt_state

    @property
    def rng(self):
        return self.state.rng

    def default_callbacks(self, log=print) -> List[Callback]:
        cbs: List[Callback] = [LoggingCallback(log=log, log_every=self.tcfg.log_every)]
        if self.tcfg.ckpt_every:
            cbs.append(
                CheckpointCallback(self.tcfg.ckpt_dir, every=self.tcfg.ckpt_every)
            )
        return cbs

    def run(
        self,
        steps: int,
        log=print,
        callbacks: Optional[Sequence[Callback]] = None,
    ) -> Dict[str, list]:
        """Run ``steps`` more steps. Global step numbering is read back from
        ``state.step`` each step (resume-aware, and a supervisor rollback
        rewinds it naturally); metrics/timing/checkpoints are the callbacks'
        business. Each step runs under the in-jit anomaly guard (see
        :func:`make_train_step`): the ``train.step`` fault site can inject
        NaN grads / loss spikes / a hang, and ``step_timeout_s`` (if set)
        raises :class:`HangError` when one step exceeds its wall budget."""
        assert self.data_iter is not None
        cbs = list(callbacks) if callbacks is not None else self.callbacks
        if cbs is None:
            cbs = self.default_callbacks(log)
        for cb in cbs:
            cb.on_run_begin(self)
        for _ in range(steps):
            self._iterations += 1
            with jax.profiler.StepTraceAnnotation(
                "repro.train.step", step_num=self._iterations
            ):
                self._step(cbs)
        for cb in cbs:
            cb.on_run_end(self)
        return {"history": self.history}

    def _step(self, cbs: Sequence[Callback]) -> None:
        """One loop iteration. Each part runs under a ``repro.train.*``
        profiler span (about a microsecond each when no profiler runs), so a
        trace puts the device's idle time down to the host work that held
        it: the batch upload, the launch, the wait on the device, the step
        read-back and the callbacks."""
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("repro.train.input"):
            batch = {k: jnp.asarray(v) for k, v in next(self.data_iter).items()}
        with jax.profiler.TraceAnnotation("repro.train.launch"):
            guard = {
                "loss_ceiling": jnp.float32(self.loss_ceiling),
                "grad_scale": jnp.float32(1.0),
                "loss_shift": jnp.float32(0.0),
            }
            for spec in faults.fire("train.step"):
                if spec.kind == "nan_grads":
                    guard["grad_scale"] = jnp.float32(float("nan"))
                elif spec.kind == "loss_spike":
                    guard["loss_shift"] = jnp.float32(spec.args.get("shift", 1e4))
                elif spec.kind == "hang":
                    time.sleep(
                        spec.args.get(
                            "seconds", 2.0 * (self.step_timeout_s or 0.05)
                        )
                    )
            self.state, metrics = self.step_fn(self.state, batch, guard)
        # sync on the (tiny) metrics so per-step wall times are honest;
        # the big state buffers stay on device and the checkpoint
        # writer thread still overlaps subsequent steps
        with jax.profiler.TraceAnnotation("repro.train.wait"):
            jax.block_until_ready(metrics["loss"])
        dt = time.perf_counter() - t0
        if self.step_timeout_s is not None and dt > self.step_timeout_s:
            raise HangError(
                f"train step exceeded its {self.step_timeout_s:.3f}s wall "
                f"budget ({dt:.3f}s) — hung collective or wedged host"
            )
        with jax.profiler.TraceAnnotation("repro.train.readback"):
            step_no = int(jax.device_get(self.state.step))
        with jax.profiler.TraceAnnotation("repro.train.callbacks"):
            for cb in cbs:
                cb.on_step_end(self, step_no, metrics, dt)

    def eval_loss(self, batches: int = 8, seed: int = 999, data_seed: Optional[int] = None) -> float:
        """Held-out loss: SAME blend/language (data_seed, default the train
        seed) but a fresh sampling stream (seed)."""
        from repro.data.pipeline import make_train_iter

        extra = None
        if self.cfg.family == "vlm":
            extra = {
                "embeds": (self.tcfg.global_batch, self.cfg.num_prefix_embeds, self.cfg.d_model)
            }
        if self.cfg.family == "encdec":
            extra = {"frames": (self.tcfg.global_batch, self.tcfg.seq_len, self.cfg.d_model)}
        it = make_train_iter(
            self.cfg.vocab_size, self.tcfg.seq_len, self.tcfg.global_batch,
            self.tcfg.blend_ratio,
            data_seed if data_seed is not None else self.tcfg.seed,
            extra, sample_seed=seed,
        )
        fn = jax.jit(lambda p, b: loss_fn(self.cfg, self.plan, p, b)[1]["ce"])
        losses = []
        for _ in range(batches):
            b = {k: jnp.asarray(v) for k, v in next(it).items()}
            losses.append(float(fn(self.params, b)))
        return float(np.mean(losses))
