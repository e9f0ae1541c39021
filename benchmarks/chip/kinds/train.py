"""Traffic kind ``train``: continued training of the upcycled model.

The mix file gives ``batch`` rows of ``seq`` tokens per step and the
Zipf exponent ``zipf_a`` of the ids (skewed ids make routing uneven);
every row is new.

Set-up builds one ``Trainer`` (the program's jitted state step with its
state) on weights made from the seed and upcycled by the program, and
drives it through its first three steps by the window's own call
(``Trainer.run``) on three different batches. The window then runs the
same object step after step for ``seconds``. Once the window has closed
and the state is freed, the plain reference follows the same three steps
and the run compares: the largest loss gap; the worst leaf's gap between
its norm and the reference's for the first gradient as AdamW took it
(``m / (1 - b1)`` after step 1) and for the change of the float32 master
weights after step 3, each against the larger of the leaf's and the
median leaf's reference norm.
"""
from __future__ import annotations

import gc
import sys
import time
from typing import Dict, Iterator, List

import numpy as np

from benchmarks.chip import bench, gen, harness, reference, trace


def batches(t: Dict, vocab: int, seed: int) -> Iterator[Dict[str, np.ndarray]]:
    rng = gen.host_rng(seed, "train")
    perm = gen.host_rng(seed, "perm").permutation(vocab)
    probs = gen.zipf_probs(vocab, t["zipf_a"])
    while True:
        ids = gen.zipf_tokens(rng, probs, perm, (t["batch"], t["seq"] + 1))
        yield {"tokens": ids[:, :-1], "labels": ids[:, 1:]}


def canonical(path) -> str:
    """The program's leaf path -> the name gen.py and the reference use."""
    keys = [getattr(p, "key", str(p)) for p in path]
    if keys[0] == "embed":
        return keys[1]
    if keys[0] == "final_norm":
        return "final_norm." + keys[1]
    rest = keys[2:]  # stack/slot0/...
    if rest[0] in ("norm1", "norm2"):
        return f"{rest[0]}.{rest[1]}"
    if rest[0] == "mixer":
        return rest[1]
    if rest[:2] == ["ffn", "router"]:
        return "router"
    if rest[:2] == ["ffn", "experts"]:
        return "experts." + rest[2]
    raise KeyError(f"no canonical name for {'/'.join(keys)}")


def leaf_norms(tree, scale=1.0):
    import jax
    import jax.numpy as jnp

    return {canonical(path): jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) * scale
            for path, v in jax.tree_util.tree_leaves_with_path(tree)}


def rehearse(cell: bench.Cell, sharding):
    """The timed program, the state step, compiled for ``sharding`` from
    shapes alone (see ``rehearse.py``)."""
    import jax
    import jax.numpy as jnp

    from repro.config import TrainConfig
    from repro.models.model import model_decl
    from repro.optim.adamw import opt_state_abstract
    from repro.sharding.rules import abstract_from_decls
    from repro.train.state import TrainState
    from repro.train.trainer import make_state_step

    def abstract(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
                            tree)

    _, cfg = bench.model_config(cell.config)
    t = cell.traffic
    tcfg = TrainConfig(global_batch=t["batch"], seq_len=t["seq"], zero1=False)
    params = abstract_from_decls(model_decl(cfg))
    state = abstract(TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32), params=params,
        opt_state=opt_state_abstract(params), rng=jax.ShapeDtypeStruct((2,), jnp.uint32)))
    batch = abstract({"tokens": jnp.zeros((t["batch"], t["seq"]), jnp.int32),
                      "labels": jnp.zeros((t["batch"], t["seq"]), jnp.int32)})
    guard = abstract({k: jnp.float32(0) for k in ("loss_ceiling", "grad_scale", "loss_shift")})
    step = jax.jit(make_state_step(cfg, tcfg, None, cell.config["run"]["use_kernel"]),
                   donate_argnums=(0,))
    yield "train step", step.lower(state, batch, guard).compile()


def run(cell: bench.Cell, seed: int, seconds: float, trace_on: bool,
        t_start: float, devs) -> harness.Run:
    import jax
    import jax.numpy as jnp

    from repro.config import TrainConfig
    from repro.train.callbacks import Callback
    from repro.train.trainer import Trainer

    from benchmarks.chip import weights

    t = cell.traffic
    tc = cell.config["train"]
    dense_cfg, moe_cfg = bench.model_config(cell.config)
    d = bench.dims(cell.config, moe_cfg.padded_vocab)
    key = gen.seed_key(seed)

    build = weights.make_init(dense_cfg, moe_cfg, d)
    params = build(key)
    weights.check_tree(params, moe_cfg)
    tcfg = TrainConfig(
        global_batch=t["batch"], seq_len=t["seq"], lr=tc["lr"], lr_min=tc["lr_min"],
        warmup_steps=tc["warmup_steps"], total_steps=tc["total_steps"],
        weight_decay=tc["weight_decay"], adam_b1=tc["adam_b1"],
        adam_b2=tc["adam_b2"], adam_eps=tc["adam_eps"], grad_clip=tc["grad_clip"],
        seed=seed % (1 << 31), zero1=False, log_every=1 << 30,
    )
    feed = batches(t, d.vocab, seed)
    checked = [next(feed) for _ in range(3)]

    def stream():
        yield from checked
        yield from feed

    tr = Trainer(moe_cfg, tcfg, params=params, data_iter=stream(),
                 use_kernel=cell.config["run"]["use_kernel"])
    harness.free(params)
    del params
    losses: List[float] = []

    class Losses(Callback):
        def on_step_end(self, trainer, step, metrics, dt):
            losses.append(float(metrics["loss"]))

    # steps 1-3 through the window's own call, on the first three batches
    tr.run(1, callbacks=[Losses()])
    g1 = {k: float(v) for k, v in jax.device_get(jax.jit(leaf_norms)(
        tr.state.opt_state.m, 1.0 / (1.0 - tc["adam_b1"]))).items()}
    tr.run(2, callbacks=[Losses()])

    def delta(master, key):
        init = build(key)
        return leaf_norms(jax.tree.map(lambda a, b: a - b.astype(jnp.float32), master, init))

    d3 = {k: float(v) for k, v in jax.device_get(
        jax.jit(delta)(tr.state.opt_state.master, key)).items()}
    setup_s = time.time() - t_start

    steps = 0
    with harness.traced(trace_on) as tr_file:
        with harness.span("bench.window"):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                with harness.span("bench.step_fn"):
                    tr.run(1, callbacks=[])
                steps += 1
            elapsed = time.perf_counter() - t0
    mem = harness.peak_memory(devs)
    harness.free(tr.state)
    del tr
    gc.collect()

    ref = reference.train_steps(seed, d, checked, tc)
    keep = harness.kept_leaves(ref["grad_norms"])
    loss_gap = max(abs(a - b) for a, b in zip(losses, ref["losses"]))
    grad_gap, g_at = harness.worst_leaf_gap(g1, ref["grad_norms"], keep)
    upd_gap, u_at = harness.worst_leaf_gap(d3, ref["delta_norms"], keep)
    lim = cell.limits
    print(f"train: losses {losses} reference {ref['losses']}; worst grad leaf "
          f"{g_at}, worst update leaf {u_at}; left out "
          f"{sorted(set(ref['grad_norms']) - set(keep))}", file=sys.stderr)
    tokens = steps * t["batch"] * t["seq"]
    return harness.Run(
        e2e={"train_tokens_per_s": tokens / elapsed, "setup_s": setup_s},
        checks={"loss_gap": (loss_gap, lim["loss_gap"]),
                "grad_gap": (grad_gap, lim["grad_gap"]),
                "update_gap": (upd_gap, lim["update_gap"])},
        attempted=steps, failed=0,
        ctx={"kind": "train", "steps": steps, "tokens": tokens, "dims": d,
             "traffic": t, "remat": moe_cfg.remat, "trace_path": tr_file.path,
             "trace_dir": tr_file.dir, "memory_peak_bytes": mem},
    )


def read_trace(ctx: Dict, device: Dict) -> Dict:
    """The traced window's device time by train-step phase (``trace.phase``),
    read once for the phase readers: ``phase_ms``, milliseconds per step
    for each phase, ``other`` and ``idle``, which make up the window over
    the steps. Printed to standard error with each phase's five largest op
    families. Raises where the step is out of the reader's sight (its jit
    name or its op-name paths have changed): a forward, backward or update
    phase with no op, or no recompute while the configuration remats."""
    fams = trace.phase_family_ns(ctx["trace"], ctx["lo"], ctx["hi"])
    per = 1e-6 / ctx["steps"]
    ms = {p: sum(f.values()) * per for p, f in fams.items()}
    need = [p for p in trace.PHASES if p != "remat_recompute" or ctx["remat"] != "none"]
    empty = [p for p in need if not ms[p]]
    if empty:
        raise RuntimeError(
            f"trace: no device op of the window under {trace.STEP_MODULE!r} is in "
            f"phase {', '.join(empty)} (remat {ctx['remat']!r}); trace.phase no "
            "longer sees the train step")
    ms["idle"] = (device["window_s"] - device["busy_s"]) * 1e3 / ctx["steps"]
    print("trace: device ms per step: " + ", ".join(f"{k} {v!r}" for k, v in ms.items())
          + f"; window / steps {device['window_s'] * 1e3 / ctx['steps']!r} over "
          f"{ctx['steps']} steps", file=sys.stderr)
    for p, f in fams.items():
        top = sorted(f.items(), key=lambda kv: -kv[1])[:5]
        print(f"trace: {p} top ms per step: "
              + ", ".join(f"{name} {ns * per!r}" for name, ns in top), file=sys.stderr)
    return {**ctx, "phase_ms": ms}
