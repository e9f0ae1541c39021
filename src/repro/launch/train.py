"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b --smoke \\
      --steps 100 --batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --smoke \\
      --upcycle 4 --top-k 2 --cf 4 --from-ckpt /tmp/dense_ckpt --steps 200
  # preempt it, then pick up exactly where it stopped:
  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --smoke \\
      --upcycle 4 --top-k 2 --cf 4 --from-ckpt /tmp/dense_ckpt --steps 200 \\
      --resume

``--smoke`` selects the reduced config (CPU-runnable); without it the full
assigned config is used (cluster scale). ``--layers N`` keeps every
published width and cuts only the depth — how one TPU v5e trains an
upcycled model at real width:

  PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b --layers 1 \\
      --upcycle 8 --top-k 2 --cf -1 --dispatcher sorted --use-kernel \\
      --from-ckpt experiments/dense_ckpt --steps 3 --batch 1 --seq 2048

``--upcycle N`` converts the dense config to an N-expert MoE, optionally
initializing from ``--from-ckpt`` via online upcycling.

Resume semantics: ``--ckpt-every`` writes FULL TrainState checkpoints
(params + AdamW state + RNG + data-stream snapshot) into step-numbered
subdirectories of the checkpoint dir via the async manager; ``--resume``
restores the latest one and continues to ``--steps`` total steps. A run
that started via upcycling restarts from its latest MoE state — the dense
source is only touched when no full-state checkpoint exists yet (the
provenance block in the manifest records the recipe).
"""
from __future__ import annotations

import argparse
import os

import jax

from repro.config import MoEConfig, TrainConfig, get_config, smoke_config
from repro.data.pipeline import make_train_iter
from repro.launch.compile_cache import enable_compile_cache
from repro.train.trainer import Trainer


def build_argparser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="override num_layers (every width stays as "
                         "published): the one-chip depth cut")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=1234)
    ap.add_argument("--upcycle", type=int, default=0, help="num experts")
    ap.add_argument("--top-k", type=int, default=2)
    ap.add_argument("--cf", type=float, default=4.0, help="<=0 => dropless")
    ap.add_argument("--router", default="mixtral", choices=["mixtral", "st"])
    ap.add_argument(
        "--dispatcher", default=None,
        choices=["allgather", "alltoall", "sorted"],
        help="MoE token dispatcher; default keeps the config's choice "
             "(sorted = dropless, recommended with --cf <= 0)",
    )
    ap.add_argument("--from-ckpt", default=None)
    ap.add_argument("--save-ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="full-state checkpoint period (0 = off)")
    ap.add_argument("--ckpt-keep", type=int, default=3,
                    help="keep-last-k retention for full-state checkpoints")
    ap.add_argument("--blocking-ckpt", action="store_true",
                    help="disable the async double-buffered save path")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest full-state checkpoint from the "
                         "checkpoint dir and continue to --steps total")
    ap.add_argument("--use-kernel", action="store_true")
    ap.add_argument("--fused-dispatch", action="store_true",
                    help="fold the sorted dispatcher's token gather and "
                         "gate-weighted combine into the grouped-GEMM "
                         "kernel (no (N_pad, D) dispatch buffer in HBM); "
                         "requires --dispatcher sorted and --use-kernel")
    ap.add_argument("--autotune", action="store_true",
                    help="enable the roofline-driven Pallas tile autotuner "
                         "(sets REPRO_AUTOTUNE=1; winners persist in "
                         "~/.cache/repro_autotune.json)")
    ap.add_argument("--supervise", action="store_true",
                    help="arm the anomaly supervisor: skip NaN/spike steps, "
                         "roll back to the last good checkpoint after "
                         "--rollback-after consecutive bad steps")
    ap.add_argument("--rollback-after", type=int, default=3,
                    help="consecutive anomalous steps before rollback")
    ap.add_argument("--step-timeout", type=float, default=0.0,
                    help="per-step wall-clock watchdog in seconds "
                         "(0 = disabled); a hung step raises HangError")
    ap.add_argument("--quant-weights", default="none",
                    choices=["none", "int8"],
                    help="after training, re-run the held-out eval with "
                         "int8-quantized expert-FFN weights (the serving "
                         "path's quantization) and report the CE delta; "
                         "training itself stays bf16")
    return ap


def main(argv=None):
    args = build_argparser().parse_args(argv)
    if args.autotune:
        os.environ["REPRO_AUTOTUNE"] = "1"  # before any kernel wrapper runs
    if args.fused_dispatch:
        if not args.use_kernel:
            raise SystemExit("--fused-dispatch requires --use-kernel "
                             "(the fusion lives in the Pallas grouped GEMM)")
        if args.dispatcher not in (None, "sorted"):
            raise SystemExit("--fused-dispatch requires --dispatcher sorted")
        args.dispatcher = "sorted"
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    if args.layers:
        cfg = cfg.replace(num_layers=args.layers)
    if (args.layers and not args.upcycle and cfg.moe is not None
            and (args.dispatcher or cfg.moe.dispatcher)
            in ("alltoall", "a2a_overlap")):
        raise SystemExit(f"{args.arch} defaults to the {cfg.moe.dispatcher!r} "
                         "EP dispatcher, which needs an EP mesh; the "
                         "launcher trains on one device: name --dispatcher "
                         "sorted")
    ckpt_dir = args.save_ckpt or "/tmp/repro_ckpt"

    provenance = {}
    dense_cfg = None
    if args.upcycle:
        from repro.core.upcycle import upcycle_config

        cf = args.cf if args.cf > 0 else None
        # dropless default: the sorted dispatcher computes every assignment
        # without the padded layout's C = T blow-up
        dispatcher = args.dispatcher or ("sorted" if cf is None else "allgather")
        moe = MoEConfig(
            num_experts=args.upcycle, top_k=args.top_k, capacity_factor=cf,
            router_type=args.router, dispatcher=dispatcher,
            fused_dispatch=args.fused_dispatch,
        )
        dense_cfg = cfg
        cfg = upcycle_config(dense_cfg, moe)

    tcfg = TrainConfig(
        global_batch=args.batch, seq_len=args.seq, lr=args.lr, lr_min=args.lr / 100,
        warmup_steps=min(100, args.steps // 10 + 1), total_steps=args.steps,
        seed=args.seed, ckpt_every=args.ckpt_every, ckpt_dir=ckpt_dir,
    )

    # -- resume first: a run that started via upcycling restarts from its
    # latest MoE TrainState, NOT by re-upcycling the dense source ----------
    state = None
    data_state = None
    if args.resume:
        from repro.checkpoint.manager import latest_step
        from repro.train.state import restore_train_state

        latest = latest_step(ckpt_dir)
        if latest is not None:
            state, manifest = restore_train_state(ckpt_dir, cfg, plan=None,
                                                  zero1=tcfg.zero1)
            data_state = manifest["meta"].get("data_state")
            provenance = manifest["meta"].get("provenance", {})
            print(f"resumed step {latest} from {ckpt_dir}"
                  + (" (upcycled run)" if provenance.get("upcycled") else ""))
        else:
            print(f"--resume: no full-state checkpoint under {ckpt_dir}; "
                  "starting fresh")

    params = None
    if state is None and args.upcycle and args.from_ckpt:
        from repro.checkpoint.ckpt import load_checkpoint
        from repro.core.upcycle import upcycle_params, upcycle_provenance

        dense_params = load_checkpoint(args.from_ckpt)
        params = upcycle_params(dense_cfg, cfg, dense_params,
                                jax.random.PRNGKey(args.seed))
        del dense_params  # device memory: the MoE state is what trains
        provenance = upcycle_provenance(dense_cfg, cfg, args.from_ckpt)
        print(f"upcycled {dense_cfg.name} -> {cfg.name} from {args.from_ckpt}")

    extra = None
    if cfg.family == "vlm":
        extra = {"embeds": (args.batch, cfg.num_prefix_embeds, cfg.d_model)}
    if cfg.family == "encdec":
        extra = {"frames": (args.batch, args.seq, cfg.d_model)}
    it = make_train_iter(cfg.vocab_size, args.seq, args.batch,
                         tcfg.blend_ratio, args.seed, extra)
    if data_state is not None:
        it.restore(data_state)
    t, a = cfg.param_counts()
    print(f"training {cfg.name}: {t/1e6:.1f}M total / {a/1e6:.1f}M active params")
    # archs that are already MoE take the --dispatcher override here
    tr = Trainer(cfg, tcfg, params=params, state=state, data_iter=it,
                 use_kernel=args.use_kernel, dispatcher=args.dispatcher,
                 step_timeout_s=args.step_timeout or None)
    del params  # the TrainState holds its own copy

    from repro.train.callbacks import (
        AnomalySupervisor,
        CheckpointCallback,
        LoggingCallback,
    )

    callbacks = [LoggingCallback(log_every=tcfg.log_every)]
    ckpt_cb = None
    if args.ckpt_every:
        ckpt_cb = CheckpointCallback(
            ckpt_dir, every=args.ckpt_every, keep_last=args.ckpt_keep,
            async_save=not args.blocking_ckpt,
            extra_meta={"arch": args.arch, "seed": args.seed,
                        **({"provenance": provenance} if provenance else {})},
        )
        callbacks.append(ckpt_cb)
    if args.supervise:
        # AFTER the checkpoint callback: a rollback joins the in-flight
        # write before restoring
        callbacks.append(AnomalySupervisor(
            ckpt=ckpt_cb, rollback_after=args.rollback_after,
        ))

    done = int(jax.device_get(tr.state.step))
    remaining = max(0, args.steps - done)
    if remaining:
        tr.run(remaining, callbacks=callbacks)
    else:
        print(f"checkpoint already at step {done} >= --steps {args.steps}; "
              "nothing to run")
    if args.save_ckpt:
        from repro.checkpoint.ckpt import save_checkpoint

        save_checkpoint(args.save_ckpt, tr.params, step=args.steps)
        print(f"saved checkpoint to {args.save_ckpt}")
    ce = tr.eval_loss(batches=4)
    print(f"final held-out CE: {ce:.4f}")
    if args.quant_weights == "int8":
        if cfg.moe is None:
            print("--quant-weights int8: dense config has no expert FFNs; "
                  "nothing to quantize")
        else:
            from repro.core.quant import quantize_params

            # serving-style inference check: quantize a copy of the expert
            # weights, eval, restore — the TrainState keeps its bf16 params
            dense_params = tr.params
            tr.params = quantize_params(dense_params)
            qce = tr.eval_loss(batches=4)
            tr.params = dense_params
            print(f"int8-expert held-out CE: {qce:.4f} "
                  f"(delta {qce - ce:+.4f} vs bf16)")
    return tr


if __name__ == "__main__":
    main()
