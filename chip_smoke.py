#!/usr/bin/env python3
"""Bring-up check on a TPU: the main paths at published widths, once.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the expert-parallel paths

One chip, three phases in this one process:

* serve — ``repro.launch.serve.main``: llama3-e8t2 (D 4096, F 14336, 8
  experts top-2, 32/8 heads of 128) cut to 2 layers, paged KV cache,
  sorted dispatcher, Pallas kernels; 4 requests of 128 prompt tokens, 16
  new tokens each.
* serving parity — on one set of seeded params, a 128-token prefill and
  the first decode step through the model's paged path, once with the
  kernels and once on XLA. The decode program must hold the paged-attention
  and grouped-GEMM kernels (the XLA one neither). Logits must agree within
  ``SERVE_TOL`` (relative L2): random-init logits are near-uniform, so
  comparing greedy tokens would test nothing.
* train — a seeded dense stablelm-1.6b cut to 1 layer is written under
  ``experiments/``; ``repro.launch.train.main`` upcycles it online to 8
  experts top-2 (dropless, sorted) and takes 3 kernel steps at sequence
  2048, then 1 XLA step on the same batch. Losses must be finite, the
  step-1 losses must agree within ``TRAIN_TOL``, and the compiled kernel
  step must hold the flash-attention and grouped-GEMM kernels.

``--chips 4`` runs only what exists across chips, each against one chip in
the same process: llama3-e8t2 (2 layers) decode with the experts spread
over ``make_serving_mesh(dp=1, ep=4)`` and the overlapped all-to-all, and
one upcycled-stablelm train step on a (data 1, expert 4, model 1) study
mesh with the all-to-all dispatcher.

Every phase that fails makes the script exit non-zero. Only when all pass
is the last line of stdout ``{"ok": true, "device": {...}}``. With no TPU,
or without the ``repro`` package beside this file, it exits non-zero and
prints no result. Compiled programs are cached as the launchers cache them
(``repro.launch.compile_cache``).
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import math
import os
import re
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Logit agreement at bf16, as the relative L2 error ||a - b|| / ||b|| over
# all (batch, vocab) logits. The paths round bf16 activations at different
# points (the kernels keep SwiGLU inputs and attention sums in fp32 VMEM,
# XLA rounds ragged_dot outputs to bf16 first); on CPU at widths 256-1024
# the kernel-vs-XLA error measured 2.6e-3 to 2.1e-2, and 1.8e-6 to 4.9e-6
# with fp32 weights.
SERVE_TOL = 5e-2
# Step-1 loss (mean next-token CE, nats) kernel vs XLA, and 4 chips vs 1.
TRAIN_TOL = 2e-2

E8T2 = ["--arch", "llama3-e8t2", "--layers", "2"]
PAGE = 16


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def pallas_kernels(hlo: str) -> set:
    """Names of the Pallas (Mosaic) kernels in a compiled TPU program. XLA
    lowers ``ragged_dot`` to a ``tpu_custom_call`` of its own; those are
    not ours."""
    names = re.findall(
        r"%([A-Za-z_][\w-]*?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
        hlo,
    )
    return {n for n in names if not n.startswith("ragged-dot")}


@contextlib.contextmanager
def every_step_logged():
    """The training launcher records loss history every 10 steps; this
    smoke takes 3 and checks each."""
    from repro.train import callbacks

    base = callbacks.LoggingCallback

    class EveryStep(base):
        def __init__(self, log=print, log_every=10):
            super().__init__(log=log, log_every=1)

    callbacks.LoggingCallback = EveryStep
    try:
        yield
    finally:
        callbacks.LoggingCallback = base


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def e8t2_config(dispatcher: str):
    from repro.config import get_config, with_dispatcher

    cfg = get_config("llama3-e8t2").replace(num_layers=2)
    # dropless, so the expert-parallel capacity cannot drop what the
    # one-chip sorted path keeps
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=None))
    return with_dispatcher(cfg, dispatcher)


def prompt_batch(cfg, B=4, S=128, seed=0):
    import numpy as np

    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
    nxt = rng.integers(0, cfg.vocab_size, (B,)).astype(np.int32)
    return prompts, nxt


def first_decode(cfg, plan, params, prompts, nxt, use_kernel):
    """Prefill ``prompts`` into a fresh page pool, then one decode step with
    the fixed next tokens ``nxt``. Returns (prefill logits, decode logits,
    compiled decode program text)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.model import decode_step_paged, paged_forward
    from repro.serving.kv_cache import init_paged_pool

    B, S = prompts.shape
    maxP = math.ceil((S + 1) / PAGE)
    pool = init_paged_pool(cfg, B * maxP, PAGE, plan=plan)
    bt = jnp.arange(B * maxP, dtype=jnp.int32).reshape(B, maxP)
    V = cfg.vocab_size
    prefill = jax.jit(
        lambda p, pool, t: paged_forward(
            cfg, plan, p, pool, t, jnp.zeros((B,), jnp.int32), bt,
            jnp.full((B,), S, jnp.int32), use_kernel=use_kernel,
        ),
        donate_argnums=(1,),
    )
    logits0, pool = prefill(params, pool, jnp.asarray(prompts))
    decode = jax.jit(
        lambda p, pool, t: decode_step_paged(
            cfg, plan, p, pool, t, jnp.full((B,), S, jnp.int32), bt,
            jnp.ones((B,), jnp.int32), use_kernel=use_kernel,
        ),
        donate_argnums=(1,),
    )
    compiled = decode.lower(params, pool, jnp.asarray(nxt)).compile()
    logits1, pool = compiled(params, pool, jnp.asarray(nxt))
    out = (np.asarray(logits0[:, :V], np.float32),
           np.asarray(logits1[:, :V], np.float32), compiled.as_text())
    del pool
    return out


def rel_err(a, b) -> float:
    """Relative L2 error of logits ``a`` against ``b``."""
    import numpy as np

    return float(np.linalg.norm(a - b) / max(float(np.linalg.norm(b)), 1e-30))


def phase_serve():
    from repro.launch import serve

    out = serve.main(E8T2 + [
        "--cache-mode", "paged", "--page-size", str(PAGE), "--dispatcher",
        "sorted", "--use-kernel", "--requests", "4", "--prompt-len", "128",
        "--max-new", "16", "--max-batch", "4",
    ])
    check(len(out) == 4 and all(len(v) == 16 for v in out.values()),
          f"expected 4 requests x 16 tokens, got {[len(v) for v in out.values()]}")
    vocab = e8t2_config("sorted").vocab_size
    check(all(0 <= t < vocab for v in out.values() for t in v),
          "a served token is outside the vocabulary")
    gc.collect()


def phase_serving_parity():
    import jax

    from repro.models.model import model_decl
    from repro.sharding.rules import init_from_decls

    cfg = e8t2_config("sorted")
    params = init_from_decls(model_decl(cfg), jax.random.PRNGKey(0))
    prompts, nxt = prompt_batch(cfg)
    pk, dk, hk = first_decode(cfg, None, params, prompts, nxt, use_kernel=True)
    px, dx, hx = first_decode(cfg, None, params, prompts, nxt, use_kernel=False)
    del params
    gc.collect()
    kern, xla = pallas_kernels(hk), pallas_kernels(hx)
    print(f"  decode kernels: kernel path {sorted(kern)} (tpu_custom_call), "
          f"XLA path {sorted(xla)}")
    check({"paged_attention", "grouped_gemm"} <= kern,
          f"kernel decode program lacks its Pallas kernels: {sorted(kern)}")
    check(not xla, f"XLA decode program holds Pallas kernels: {sorted(xla)}")
    import numpy as np

    check(np.isfinite(dk).all() and np.isfinite(dx).all(), "non-finite logits")
    ep, ed = rel_err(pk, px), rel_err(dk, dx)
    print(f"  kernel vs XLA logits: prefill rel L2 err {ep:.3e}, first decode "
          f"rel L2 err {ed:.3e} (tol {SERVE_TOL:g}); decode max |diff| "
          f"{float(np.max(np.abs(dk - dx))):.4e}, max |logit| "
          f"{float(np.max(np.abs(dx))):.4f}")
    check(ep <= SERVE_TOL and ed <= SERVE_TOL, "kernel and XLA logits disagree")


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

TRAIN = ["--arch", "stablelm-1.6b", "--layers", "1", "--upcycle", "8",
         "--top-k", "2", "--cf", "-1", "--dispatcher", "sorted",
         "--batch", "1", "--seq", "2048"]


def dense_checkpoint() -> str:
    import jax

    from repro.checkpoint.ckpt import save_checkpoint
    from repro.config import get_config
    from repro.models.model import model_decl
    from repro.sharding.rules import init_from_decls

    path = ROOT / "experiments" / "chip_smoke" / "stablelm-1.6b-l1-dense"
    cfg = get_config("stablelm-1.6b").replace(num_layers=1)
    params = init_from_decls(model_decl(cfg), jax.random.PRNGKey(0))
    save_checkpoint(str(path), params)
    del params
    gc.collect()
    return str(path)


def step_inputs(tr):
    """(batch, guard) for ``tr.step_fn``: the next batch of its data
    iterator and the anomaly guard disarmed, as ``Trainer.run`` passes."""
    import jax.numpy as jnp

    batch = {k: jnp.asarray(v) for k, v in next(tr.data_iter).items()}
    guard = {"loss_ceiling": jnp.float32(float("inf")),
             "grad_scale": jnp.float32(1.0), "loss_shift": jnp.float32(0.0)}
    return batch, guard


def phase_train():
    import numpy as np

    from repro.launch import train

    ckpt = dense_checkpoint()
    with every_step_logged():
        tr = train.main(TRAIN + ["--from-ckpt", ckpt, "--use-kernel", "--steps", "3"])
    losses = [r["loss"] for r in tr.history if "loss" in r]
    # the trainer's own step program, compiled again (a cache hit)
    hlo = tr.step_fn.lower(tr.state, *step_inputs(tr)).compile().as_text()
    del tr
    gc.collect()
    kern = pallas_kernels(hlo)
    print(f"  train step kernels: {sorted(kern)} (tpu_custom_call)")
    check({"flash_attention", "grouped_gemm"} <= kern,
          f"kernel train step lacks its Pallas kernels: {sorted(kern)}")
    print(f"  kernel losses: {losses}")
    check(len(losses) == 3 and np.isfinite(losses).all(),
          f"expected 3 finite losses, got {losses}")
    with every_step_logged():
        tr = train.main(TRAIN + ["--from-ckpt", ckpt, "--steps", "1"])
    xla = tr.history[0]["loss"]
    del tr
    gc.collect()
    d = abs(losses[0] - xla)
    print(f"  step-1 loss kernel {losses[0]:.6f} vs XLA {xla:.6f}: "
          f"|diff| {d:.3e} (tol {TRAIN_TOL:g})")
    check(d <= TRAIN_TOL, "kernel and XLA step-1 losses disagree")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def expert_devices(params):
    w = params["stack"]["slot0"]["ffn"]["experts"]["w_gate"]  # (P, E, D, F)
    return {s.device.id: s.data.shape[1] for s in w.addressable_shards}


def phase_ep_serving():
    import jax

    from repro.launch.mesh import make_serving_mesh
    from repro.models.model import model_decl
    from repro.sharding.rules import FoldingPlan, init_from_decls, shardings_from_decls

    cfg = e8t2_config("a2a_overlap")
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, strict_dispatch=True))
    plan = FoldingPlan.make(cfg, make_serving_mesh(dp=1, ep=4))
    check(plan.moe_mode == "ep" and plan.ep_size == 4, f"no EP4 plan: {plan}")
    decls = model_decl(cfg)
    # the same seeded values as the one-chip init below, born sharded
    params = jax.jit(lambda k: init_from_decls(decls, k),
                     out_shardings=shardings_from_decls(decls, plan))(
        jax.random.PRNGKey(0))
    spread = expert_devices(params)
    print(f"  experts per device: {spread}")
    check(len(spread) == 4 and set(spread.values()) == {cfg.moe.num_experts // 4},
          "experts are not spread evenly over 4 chips")
    prompts, nxt = prompt_batch(cfg)
    _, d_ep, hlo = first_decode(cfg, plan, params, prompts, nxt, use_kernel=True)
    del params
    gc.collect()
    colls = {op: hlo.count(op + "(") + hlo.count(op + "-start(")
             for op in ("all-to-all", "collective-permute")}
    print(f"  EP decode collectives: {colls}; kernels "
          f"{sorted(pallas_kernels(hlo))}")
    check(sum(colls.values()) > 0, "EP decode step holds no exchange")

    one = e8t2_config("sorted")
    params = init_from_decls(model_decl(one), jax.random.PRNGKey(0))
    _, d_one, _ = first_decode(one, None, params, prompts, nxt, use_kernel=True)
    del params
    gc.collect()
    e = rel_err(d_ep, d_one)
    print(f"  EP4 vs one-chip first-decode logits: rel L2 err {e:.3e} "
          f"(tol {SERVE_TOL:g})")
    check(e <= SERVE_TOL, "EP4 and one-chip logits disagree")


def phase_sharded_train():
    import jax
    import numpy as np

    from repro.config import MoEConfig, TrainConfig, get_config
    from repro.core.upcycle import upcycle_config, upcycle_params
    from repro.data.pipeline import make_train_iter
    from repro.launch.mesh import make_study_mesh
    from repro.models.model import model_decl
    from repro.sharding.rules import FoldingPlan, init_from_decls, shardings_from_decls
    from repro.train.trainer import Trainer

    dense = get_config("stablelm-1.6b").replace(num_layers=1)
    moe = MoEConfig(num_experts=8, top_k=2, capacity_factor=None,
                    router_type="mixtral", dispatcher="sorted")
    cfg = upcycle_config(dense, moe)
    dense_params = init_from_decls(model_decl(dense), jax.random.PRNGKey(0))
    host = jax.device_get(upcycle_params(dense, cfg, dense_params,
                                         jax.random.PRNGKey(1)))
    del dense_params
    gc.collect()
    tcfg = TrainConfig(global_batch=1, seq_len=2048, lr=3e-4, lr_min=3e-6,
                       warmup_steps=1, total_steps=1, seed=1234)
    batch = next(make_train_iter(cfg.vocab_size, 2048, 1, tcfg.blend_ratio, 1234))

    def step_loss(cfg, plan):
        params = host if plan is None else jax.device_put(
            host, shardings_from_decls(model_decl(cfg), plan))
        tr = Trainer(cfg, tcfg, plan=plan, params=params,
                     data_iter=iter([batch]))
        del params
        inputs = step_inputs(tr)
        compiled = tr.step_fn.lower(tr.state, *inputs).compile()
        spread = expert_devices(tr.state.params)
        _, metrics = compiled(tr.state, *inputs)
        loss = float(metrics["loss"])
        hlo = compiled.as_text()
        del tr, compiled, metrics, inputs
        gc.collect()
        return loss, spread, hlo

    ep_cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, dispatcher="alltoall",
                                                 strict_dispatch=True))
    plan = FoldingPlan.make(ep_cfg, make_study_mesh(1, 4, 1))
    check(plan.ep_axis == "expert" and plan.ep_size == 4, f"no EP4 plan: {plan}")
    l4, spread, hlo = step_loss(ep_cfg, plan)
    print(f"  experts per device: {spread}; all-to-all ops "
          f"{hlo.count('all-to-all(') + hlo.count('all-to-all-start(')}")
    check(len(spread) == 4 and set(spread.values()) == {cfg.moe.num_experts // 4},
          "experts are not spread evenly over 4 chips")
    l1, _, _ = step_loss(cfg, None)
    d = abs(l4 - l1)
    print(f"  step-1 loss EP4 {l4:.6f} vs one chip {l1:.6f}: |diff| {d:.3e} "
          f"(tol {TRAIN_TOL:g})")
    check(np.isfinite([l4, l1]).all() and d <= TRAIN_TOL,
          "EP4 and one-chip losses disagree")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    # no alltoall -> allgather fallback can hide the device path
    os.environ["REPRO_STRICT_DISPATCH"] = "1"
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({ROOT / 'src'}): {e}", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {devices[0].platform!r});"
              " nothing runs in interpret mode here", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    print(f"device: {devices[0].device_kind} x{len(devices)}; compile cache {cache}")

    phases = (
        [("serve", phase_serve), ("serving parity", phase_serving_parity),
         ("train", phase_train)]
        if args.chips == 1 else
        [("EP serving", phase_ep_serving), ("sharded training", phase_sharded_train)]
    )
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        print(f"== {name}", flush=True)
        try:
            fn()
            status = "ok"
        except Exception as e:  # report every phase, then fail the run
            status = f"FAILED: {type(e).__name__}: {e}"
            failed.append(name)
            import traceback

            traceback.print_exc()
        print(f"== {name}: {status} ({time.perf_counter() - t0:.1f} s)", flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
