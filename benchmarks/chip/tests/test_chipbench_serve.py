"""A whole serving run of the harness at a tiny size on the CPU, with the
look for a chip skipped: the open loop counts what it attempted and what
failed, a sound run is correct, and each fault that a one-chip serving
cell can have, planted under the timed path, makes ``correct`` come out
false under limits set for that size (``TINY_LIMITS``)."""
import copy
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

from benchmarks.chip import bench, harness, serve_gen  # noqa: E402

CELL = "serve.qwen25-e8t2.chat"
TINY = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
        "vocab_size": 200}
TINY_RUN = {"page_size": 4, "prefill_chunk": 16, "max_batch": 4, "num_pages": 96}
TINY_MIX = {"rate_per_s": 12.0, "preroll_s": 0.3, "residence_s": 0.25,
            "prompt": {"median": 20, "sigma": 0.7, "min": 4, "max": 48},
            "output": {"median": 5, "sigma": 0.7, "min": 2, "max": 10},
            "checked": {"count": 4, "prompt_min": 14, "prompt_max": 23, "decode_steps": 5},
            "served_sample": 3, "drain_limit_s": 60}
SEED = 2**31 + 77
# The chip's limits are set at the published widths, where the program
# reads 0.015-0.038 and 0.004-0.021; at this width it reads 0.005-0.006
# and 0-0.0006 (CPU), and the faults below 0.046-1.17 and 0.0006-0.43:
# limits for this size, set the same way between the two.
TINY_LIMITS = {"checked_logit_gap_mean": 0.02, "served_gap_mean": 0.005}


def tiny_cell(monkeypatch, use_kernel=True, **mix):
    """The cell as BENCHMARK.json defines it, with the widths, depth, pool
    and traffic cut to a CPU size; the program's base architecture is cut
    the same way, so the width check still holds."""
    import repro.config

    cell = bench.load_cell(CELL)
    cfg = {**copy.deepcopy(cell.config), **TINY}
    cfg["run"] = {**cfg["run"], **TINY_RUN, "use_kernel": use_kernel}
    real = repro.config.get_config

    def small(arch):
        base = real(arch)
        if arch != cfg["base_arch"]:
            return base
        return base.replace(d_model=64, d_ff=128, num_heads=4, num_kv_heads=2, head_dim=16,
                            vocab_divisor=64)

    monkeypatch.setattr(repro.config, "get_config", small)
    return bench.Cell(**{**cell.__dict__, "config": cfg, "limits": TINY_LIMITS,
                         "traffic": {**cell.traffic, **TINY_MIX, **mix}})


def run(cell, seconds=1.0):
    return harness.execute(cell, seed=SEED, seconds=seconds, trace_on=False, t_start=0.0,
                           devs=jax.devices())


def test_sound_run_is_correct_and_counted(monkeypatch):
    out = run(tiny_cell(monkeypatch))
    assert out["correct"], out["checks"]
    assert out["attempted"] == 12 and out["failed"] == 0
    m = out["metrics"]
    assert set(m) == {"ttft_mean_ms", "itl_p95_ms", "setup_s"}
    assert m["ttft_mean_ms"]["value"] > 0 and m["itl_p95_ms"]["value"] > 0
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(bench.load_cell(CELL).limits) == set(TINY_LIMITS)


def test_every_seed_serves_the_same_work():
    """Every seed's window holds the same prompt lengths, answer lengths and
    gaps in the mix's order, and as large a population; only the ids
    differ; every request has an id of its own."""
    t = bench.load_cell(CELL).traffic
    runs = []
    for seed in (2**40 + 1, 7):
        sched = serve_gen.schedule(t, seed, 10.0, 1000)
        pop = serve_gen.population(t, seed, 1000)
        ids = [p.idx for p in sched + pop]
        assert len(set(ids)) == len(ids)
        due = [p.due_s for p in sched]
        assert due == sorted(due) and 0.0 in due and t["preroll_s"] in due and max(due) < 10 + t["preroll_s"]
        win = [p for p in sched if p.due_s >= t["preroll_s"]]
        assert len(win) == round(t["rate_per_s"] * 10)
        assert len(pop) == round(t["rate_per_s"] * t["residence_s"])
        runs.append(([len(p.prompt) for p in win], [p.max_new for p in win],
                     [p.due_s for p in win],
                     [(len(p.prompt), p.max_new) for p in pop], [p.prompt[:8].tolist() for p in win]))
    assert runs[0][:4] == runs[1][:4] and runs[0][4] != runs[1][4]


def plant(monkeypatch, fault):
    """Wrap the engine's decode program (``decode_step_paged``, traced by
    the engine's jitted lambda) with ``fault``."""
    import repro.serving.engine as engine

    real = engine.decode_step_paged

    def broken(cfg, plan, params, pool, tokens, pos, page_table, active, **kw):
        return fault(real, cfg, plan, params, pool, tokens, pos, page_table, active, **kw)

    monkeypatch.setattr(engine, "decode_step_paged", broken)


def kv_left_unchanged(real, cfg, plan, params, pool, *a, **kw):
    logits, _ = real(cfg, plan, params, pool, *a, **kw)
    return logits, pool


def token_altered(real, *a, **kw):
    logits, pool = real(*a, **kw)
    return jnp.roll(logits, 1, axis=-1), pool


def block_table_shifted(real, cfg, plan, params, pool, tokens, pos, page_table, active, **kw):
    pad = jnp.full_like(page_table[:, :1], -1)
    return real(cfg, plan, params, pool, tokens, pos,
                jnp.concatenate([page_table[:, 1:], pad], axis=1), active, **kw)


def half_batch_left_out(real, cfg, plan, params, pool, tokens, pos, page_table, active, **kw):
    """The upper half of the decode rows is computed as idle: no key of its
    own, its cache position never written."""
    half = (jnp.arange(active.shape[0]) < active.shape[0] // 2).astype(active.dtype)
    return real(cfg, plan, params, pool, tokens, pos, page_table, active * half, **kw)


@pytest.mark.parametrize("fault", [kv_left_unchanged, token_altered, block_table_shifted,
                                   half_batch_left_out], ids=lambda f: f.__name__)
def test_fault_is_caught(monkeypatch, fault):
    plant(monkeypatch, fault)
    out = run(tiny_cell(monkeypatch, use_kernel=False))
    assert not out["correct"], out["checks"]


def test_population_is_weighted_by_length():
    """The answers in flight at one moment are the law's weighted by
    length: their mean is E[L^2] / E[L] of the law, not E[L]."""
    law = bench.load_cell(CELL).traffic["output"]
    x = serve_gen.lognormal_lengths(law, 4096).astype(float)
    y = serve_gen.length_biased(law, 400)
    assert list(y) == sorted(y) and law["min"] <= y.min() and y.max() <= law["max"]
    assert y.mean() == pytest.approx((x ** 2).mean() / x.mean(), rel=0.01)
    assert y.mean() > 1.4 * x.mean()


@pytest.mark.parametrize("planted", ["fp8_control", "shifted_block_table"])
def test_control_script_plants_fail_the_run(monkeypatch, planted):
    """What ``control_serve.py`` puts in the program's place, through the
    whole run and its limits: the float8 reference as what the program
    produced, and the decode step reading its block table shifted."""
    from benchmarks.chip import control_serve

    cell = tiny_cell(monkeypatch, use_kernel=False)
    with getattr(control_serve, planted)():
        out = run(cell)
    assert not out["correct"], out["checks"]
