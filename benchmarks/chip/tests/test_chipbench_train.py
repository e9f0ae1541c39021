"""A whole training run of the harness at a tiny size on the CPU, with the
look for a chip skipped: a sound run is correct, and each fault that a
one-chip training cell can have, planted under the timed path, makes
``correct`` come out false under the cell's own limits."""
import copy
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

from benchmarks.chip import bench, harness  # noqa: E402

CELL = "train.stablelm2-e8t2.s4096"
TINY = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 4, "head_dim": 16, "num_hidden_layers": 2,
        "vocab_size": 200}


def tiny_cell(monkeypatch, seq=64, **config):
    """The cell as BENCHMARK.json defines it, with the widths, depth and
    sequence cut to a CPU size and ``config`` over its configuration; the
    program's base architecture is cut the same way, so the width check
    still holds."""
    import repro.config

    cell = bench.load_cell(CELL)
    cfg = {**copy.deepcopy(cell.config), **TINY, **config}
    real = repro.config.get_config

    def small(arch):
        base = real(arch)
        if arch != cfg["base_arch"]:
            return base
        return base.replace(d_model=64, d_ff=128, num_heads=4,
                            num_kv_heads=cfg["num_key_value_heads"], head_dim=16,
                            vocab_divisor=64)

    monkeypatch.setattr(repro.config, "get_config", small)
    return bench.Cell(**{**cell.__dict__, "config": cfg,
                         "traffic": dict(cell.traffic, seq=seq)})


def run(cell):
    return harness.execute(cell, seed=2**31 + 12345, seconds=0.5, trace_on=False,
                           t_start=0.0, devs=jax.devices())


def test_sound_run_is_correct(monkeypatch):
    out = run(tiny_cell(monkeypatch))
    assert out["correct"], out["checks"]
    assert out["metrics"]["train_tokens_per_s"]["value"] > 0
    assert list(out)[-1] == "checks"


def test_state_left_unchanged_is_caught(monkeypatch):
    import repro.train.trainer as trainer

    real = trainer.make_state_step

    def frozen(*a, **k):
        step = real(*a, **k)

        def same(state, batch, guard=None):
            return state, step(state, batch, guard)[1]

        return same

    monkeypatch.setattr(trainer, "make_state_step", frozen)
    out = run(tiny_cell(monkeypatch))
    assert not out["correct"], out["checks"]


def test_half_batch_is_caught(monkeypatch):
    import repro.train.trainer as trainer
    from repro.models import model
    from repro.models.layers import cross_entropy

    def half(cfg, plan, params, batch, rng=None, use_kernel=False):
        logits, aux = model.forward(cfg, plan, params, batch, rng, True, use_kernel)
        s = logits.shape[1] // 2
        ce = cross_entropy(logits[:, :s], batch["labels"][:, :s], cfg.vocab_size)
        loss = ce + sum(aux.values())
        return loss, {"loss": loss, "ce": ce, **aux}

    monkeypatch.setattr(trainer, "loss_fn", half)
    out = run(tiny_cell(monkeypatch))
    assert not out["correct"], out["checks"]
