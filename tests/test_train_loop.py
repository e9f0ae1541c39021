"""End-to-end training behaviour: loss decreases; upcycled-from-trained-dense
starts at the dense loss (the paper's warm-start effect, Fig. 3); the
layer scopes the compiled step carries for a profiler trace."""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_dense
from repro.config import MoEConfig, TrainConfig
from repro.core.upcycle import upcycle_config, upcycle_params
from repro.data.pipeline import make_train_iter
from repro.train.trainer import Trainer

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _tcfg(steps=30, B=8, S=32):
    return TrainConfig(global_batch=B, seq_len=S, lr=3e-3, lr_min=3e-4,
                       warmup_steps=5, total_steps=steps, log_every=10, seed=3)


def test_loss_decreases_dense():
    cfg = tiny_dense(num_layers=2, vocab_size=256)
    tcfg = _tcfg()
    it = make_train_iter(cfg.vocab_size, tcfg.seq_len, tcfg.global_batch, seed=3)
    tr = Trainer(cfg, tcfg, data_iter=it)
    tr.run(30, log=lambda *_: None)
    first, last = tr.history[0]["ce"], tr.history[-1]["ce"]
    assert last < first - 0.3, (first, last)


def test_loss_decreases_moe():
    cfg = tiny_dense(num_layers=2, vocab_size=256).replace(
        family="moe",
        moe=MoEConfig(num_experts=4, top_k=2, capacity_factor=2.0),
    )
    tcfg = _tcfg()
    it = make_train_iter(cfg.vocab_size, tcfg.seq_len, tcfg.global_batch, seed=3)
    tr = Trainer(cfg, tcfg, data_iter=it)
    tr.run(30, log=lambda *_: None)
    assert tr.history[-1]["ce"] < tr.history[0]["ce"] - 0.3
    assert tr.history[-1]["load_balance_loss"] > 0


def test_loss_decreases_moe_sorted_dispatcher():
    """End-to-end training through the sorted dropless dispatcher."""
    cfg = tiny_dense(num_layers=2, vocab_size=256).replace(
        family="moe",
        moe=MoEConfig(num_experts=4, top_k=2, capacity_factor=None,
                      dispatcher="sorted"),
    )
    tcfg = _tcfg()
    it = make_train_iter(cfg.vocab_size, tcfg.seq_len, tcfg.global_batch, seed=3)
    tr = Trainer(cfg, tcfg, data_iter=it)
    tr.run(30, log=lambda *_: None)
    assert tr.history[-1]["ce"] < tr.history[0]["ce"] - 0.3
    assert tr.history[-1]["load_balance_loss"] > 0


def test_trainer_dispatcher_override_matches_explicit_config():
    """Trainer(dispatcher=...) rewrites the MoE config; same seed + data =>
    identical first-step loss to the explicitly-configured run."""
    base = tiny_dense(num_layers=1, vocab_size=256).replace(
        family="moe",
        moe=MoEConfig(num_experts=4, top_k=2, capacity_factor=None),
    )
    tcfg = _tcfg(steps=2)
    runs = []
    for cfg, disp in [
        (base, "sorted"),
        (base.replace(moe=MoEConfig(num_experts=4, top_k=2, capacity_factor=None,
                                    dispatcher="sorted")), None),
    ]:
        it = make_train_iter(cfg.vocab_size, tcfg.seq_len, tcfg.global_batch, seed=3)
        tr = Trainer(cfg, tcfg, data_iter=it, dispatcher=disp)
        assert tr.cfg.moe.dispatcher == "sorted"
        tr.run(2, log=lambda *_: None)
        runs.append(tr.history[0]["loss"])
    assert runs[0] == runs[1], runs


def test_train_step_use_kernel_sorted():
    """Full train steps on the Pallas hot path (interpret mode): the sorted
    dropless dispatcher's grouped GEMM AND flash attention both run under
    jax.grad via their custom_vjp backward kernels — finite loss/grad-norm,
    loss moves."""
    cfg = tiny_dense(num_layers=2, vocab_size=256).replace(
        family="moe",
        moe=MoEConfig(num_experts=4, top_k=2, capacity_factor=None,
                      dispatcher="sorted"),
    )
    tcfg = TrainConfig(global_batch=4, seq_len=32, lr=3e-3, lr_min=3e-4,
                       warmup_steps=2, total_steps=3, log_every=1, seed=3)
    it = make_train_iter(cfg.vocab_size, tcfg.seq_len, tcfg.global_batch, seed=3)
    tr = Trainer(cfg, tcfg, data_iter=it, use_kernel=True)
    tr.run(3, log=lambda *_: None)
    for rec in tr.history:
        assert np.isfinite(rec["loss"]), rec
        assert np.isfinite(rec["grad_norm"]) and rec["grad_norm"] > 0, rec
    assert tr.history[-1]["loss"] < tr.history[0]["loss"] + 0.1


def test_kernel_step_matches_xla_step():
    """One optimizer step with use_kernel=True vs False from identical
    init/data: the kernel path is a numerical drop-in for training (same
    loss to fp tolerance)."""
    cfg = tiny_dense(num_layers=1, vocab_size=256).replace(
        family="moe",
        moe=MoEConfig(num_experts=4, top_k=2, capacity_factor=None,
                      dispatcher="sorted"),
    )
    tcfg = TrainConfig(global_batch=4, seq_len=32, lr=3e-3, lr_min=3e-4,
                       warmup_steps=2, total_steps=2, log_every=1, seed=3)
    losses = {}
    for uk in (False, True):
        it = make_train_iter(cfg.vocab_size, tcfg.seq_len, tcfg.global_batch, seed=3)
        tr = Trainer(cfg, tcfg, data_iter=it, use_kernel=uk)
        tr.run(2, log=lambda *_: None)
        losses[uk] = [r["loss"] for r in tr.history]
    np.testing.assert_allclose(losses[False], losses[True], atol=5e-2)


def test_upcycled_starts_at_dense_loss():
    """Train dense briefly, upcycle, and check the MoE's first-step CE
    matches the dense model's CE (Mixtral router) — the warm-start claim."""
    cfg = tiny_dense(num_layers=2, vocab_size=256)
    tcfg = _tcfg(steps=40)
    it = make_train_iter(cfg.vocab_size, tcfg.seq_len, tcfg.global_batch, seed=3)
    tr = Trainer(cfg, tcfg, data_iter=it)
    tr.run(40, log=lambda *_: None)
    dense_eval = tr.eval_loss(batches=4)

    moe_cfg = upcycle_config(cfg, MoEConfig(num_experts=4, top_k=2, capacity_factor=None))
    moe_params = upcycle_params(cfg, moe_cfg, tr.params, jax.random.PRNGKey(9))
    tr_moe = Trainer(moe_cfg, tcfg, params=moe_params, data_iter=it)
    moe_eval = tr_moe.eval_loss(batches=4)
    assert abs(moe_eval - dense_eval) < 0.05, (dense_eval, moe_eval)


# -- layer scopes: the op-name paths a profiler trace reads ------------------

# scopes inside the scanned, rematted layer body: forward, recompute, backward
BLOCK_SCOPES = ("norm", "attention", "moe_router", "moe_dispatch", "moe_experts",
                "moe_combine")
# scopes of the differentiated model outside the layer body: no recompute
MODEL_SCOPES = ("embed", "layer_scan", "head_loss")
# scopes of the update, outside the gradient
UPDATE_SCOPES = ("adamw", "grad_norm", "anomaly_guard")
ALL_SCOPES = BLOCK_SCOPES + MODEL_SCOPES + UPDATE_SCOPES + ("model",)


def _step_op_names(dispatcher):
    """op_name of every instruction of the compiled state step (tiny E8T2,
    remat "full", XLA paths) that lies under the step's jit."""
    cfg = tiny_dense(num_layers=1, vocab_size=256, remat="full").replace(
        family="moe",
        moe=MoEConfig(num_experts=8, top_k=2, dispatcher=dispatcher,
                      capacity_factor=None if dispatcher == "sorted" else 2.0),
    )
    tcfg = TrainConfig(global_batch=2, seq_len=16, seed=3)
    tr = Trainer(cfg, tcfg, data_iter=iter(()), use_kernel=False)
    batch = {k: jnp.zeros((2, 16), jnp.int32) for k in ("tokens", "labels")}
    guard = {k: jnp.float32(0) for k in ("loss_ceiling", "grad_scale", "loss_shift")}
    text = tr.step_fn.lower(tr.state, batch, guard).compile().as_text()
    return [p for p in re.findall(r'op_name="([^"]*)"', text) if p.startswith("jit(step)/")]


def _segments(path):
    return [re.sub(r"^(?:transpose\()?(?:jvp\()?|\)+$", "", s) for s in path.split("/")]


@pytest.fixture(scope="module", params=["sorted", "allgather"])
def step_paths(request):
    """(dispatcher, op-name paths with the program's scopes, and without:
    ``jax.named_scope`` made a no-op while the same step is compiled)."""
    import contextlib

    scoped = _step_op_names(request.param)
    mp = pytest.MonkeyPatch()
    mp.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    try:
        plain = _step_op_names(request.param)
    finally:
        mp.undo()
    return request.param, scoped, plain


def _phases_with(paths, scope):
    from benchmarks.chip import trace

    return {trace.phase(trace.Op("", "", 0, 0, p)) for p in paths if scope in _segments(p)}


def test_layer_scopes_reach_forward_recompute_and_backward(step_paths):
    _, paths, _ = step_paths
    for scope in BLOCK_SCOPES:
        assert {"forward", "remat_recompute", "backward"} <= _phases_with(paths, scope), scope
    for scope in MODEL_SCOPES:
        assert {"forward", "backward"} <= _phases_with(paths, scope), scope


def test_update_scopes_lie_outside_the_gradient(step_paths):
    _, paths, _ = step_paths
    for scope in UPDATE_SCOPES:
        assert _phases_with(paths, scope) == {"optimizer_update"}, scope


def test_no_scope_is_folded_into_a_transform_segment(step_paths):
    """``jvp(model)`` is the one outer scope; no layer's name may land
    inside a ``jvp(``/``transpose(`` segment, where its transposed ops
    would lose it."""
    _, paths, _ = step_paths
    folded = {s for p in paths for s in p.split("/")
              if "(" in s and re.sub(r"^(?:transpose\()?jvp\(|\)+$", "", s) in ALL_SCOPES}
    assert folded <= {"jvp(model)", "transpose(jvp(model))"}, folded
    assert "jvp(model)" in folded


def test_scopes_leave_the_phases_as_they_were(step_paths):
    """Scopes are metadata: the same step compiled without them has as
    many instructions in each train-step phase."""
    from collections import Counter

    from benchmarks.chip import trace

    _, scoped, plain = step_paths
    count = lambda paths: Counter(trace.phase(trace.Op("", "", 0, 0, p)) for p in paths)  # noqa: E731
    assert count(scoped) == count(plain)
    assert not any(s in _segments(p) for p in plain for s in ALL_SCOPES)
