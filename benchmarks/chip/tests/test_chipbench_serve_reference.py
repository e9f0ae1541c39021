"""The serving reference against the program's paged engine on seeded
weights at D 256: the logits that the engine's prefill-chunk and decode
programs return for prompts of several chunks, decoded across page
boundaries, agree with the plain float32 reference within a
limit set for this width; the float8 control, the reference one
precision below the served bfloat16, does not."""
import copy
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmarks.chip import bench, gen, serve_gen, serve_loop  # noqa: E402

CELL = "serve.qwen25-e8t2.chat"
SEED = 2**40 + 16
WIDTHS = {"hidden_size": 256, "intermediate_size": 512, "num_attention_heads": 4,
          "num_key_value_heads": 2, "head_dim": 64, "num_hidden_layers": 2,
          "vocab_size": 512}
PROMPTS = (45, 75)  # chunks of 32: two and three of them; pages of 16 crossed in decode
DECODE = 8
# At D 256 the program reads 0.007-0.008 and the float8 control 0.11
# (CPU); the chip's limit (0.12) is set at the published widths, where
# they read 0.015-0.038 and 0.25-0.31. The limit at this width, between:
LIMIT_D256 = 0.03


@pytest.fixture(scope="module")
def readings():
    import repro.config

    mp = pytest.MonkeyPatch()
    cell = bench.load_cell(CELL)
    cfg = {**copy.deepcopy(cell.config), **WIDTHS}
    cfg["run"] = {**cfg["run"], "prefill_chunk": 32, "max_batch": 4, "num_pages": 64}
    real = repro.config.get_config
    mp.setattr(repro.config, "get_config", lambda arch: real(arch).replace(
        d_model=256, d_ff=512, num_heads=4, num_kv_heads=2, head_dim=64, vocab_divisor=128)
        if arch == cfg["base_arch"] else real(arch))
    traffic = {**cell.traffic, "prompt": {**cell.traffic["prompt"], "max": 96},
               "output": {**cell.traffic["output"], "max": 32}}
    cell = bench.Cell(**{**cell.__dict__, "config": cfg, "traffic": traffic})
    engine, d = serve_loop.build(cell, SEED)
    rng = gen.host_rng(SEED, "test")
    prompts = [rng.integers(0, d.vocab, n).astype(np.int32) for n in PROMPTS]
    checked = serve_loop.run_checked(engine, serve_loop.Calls(engine), prompts, DECODE)
    std = serve_gen.delta_std(cfg)
    out = {r: serve_loop.compare(SEED, d, std, checked, {}, rounding=r) for r in ("f32", "fp8")}
    mp.undo()
    return cell, checked, out


def test_engine_logits_match_the_reference(readings):
    cell, checked, out = readings
    assert [len(c["logits"]) for c in checked.values()] == [DECODE + 1] * len(PROMPTS)
    assert out["f32"]["checked_logit_gap_mean"] < LIMIT_D256, out["f32"]


def test_float8_control_is_not_correct(readings):
    cell, _, out = readings
    assert out["fp8"]["checked_logit_gap_mean"] > LIMIT_D256, out["fp8"]
    assert out["fp8"]["checked_logit_gap_mean"] > 3 * out["f32"]["checked_logit_gap_mean"], out
