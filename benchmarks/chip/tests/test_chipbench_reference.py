"""The float32 reference against the program's own model (XLA path, every
weight and activation in float32) at a tiny size, for two block types:
LayerNorm multi-head attention with QKV biases (StableLM-2) and RMSNorm
grouped-query attention with QKV biases (Qwen2.5). Same weights from the
seed on both sides; the loss and every leaf's gradient must agree to
float32 round-off. The float8 control must not."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmarks.chip import bench, gen, harness, reference, weights  # noqa: E402
from benchmarks.chip.kinds import train  # noqa: E402
from test_chipbench_train import tiny_cell  # noqa: E402

BLOCKS = {
    "layernorm-mha": {},
    "rmsnorm-gqa-bias": {"base_arch": "qwen2.5-14b", "norm": "rmsnorm",
                         "num_key_value_heads": 2, "rope_theta": 1e6},
}
SEED = 2**33 + 5


def setup(monkeypatch, block):
    cell = tiny_cell(monkeypatch, **BLOCKS[block])
    dense_cfg, moe_cfg = bench.model_config(cell.config)
    cfg = moe_cfg.replace(dtype="float32")
    d = bench.dims(cell.config, moe_cfg.padded_vocab)
    params = weights.make_init(dense_cfg, moe_cfg, d)(gen.seed_key(SEED))
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    return cfg, d, params


@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_loss_and_gradients_match_the_program(monkeypatch, block):
    from repro.models.model import loss_fn

    cfg, d, params = setup(monkeypatch, block)
    assert d.qkv_bias and cfg.qkv_bias
    b = next(train.batches({"batch": 2, "seq": 48, "zipf_a": 1.1}, d.vocab, SEED))
    batch = {k: jnp.asarray(v) for k, v in b.items()}
    (lp, _), gp = jax.value_and_grad(lambda p: loss_fn(cfg, None, p, batch), has_aux=True)(params)
    w = reference.init_weights(gen.seed_key(SEED), d)
    (lr, _), gr = jax.value_and_grad(
        lambda w: reference.loss(w, batch["tokens"], batch["labels"], d), has_aux=True)(w)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    prog = {k: float(v) for k, v in train.leaf_norms(gp).items()}
    ref = {k: float(np.sqrt(v)) for k, v in reference.leaf_sq_norms(gr).items()}
    assert sorted(prog) == sorted(ref)
    for k in ref:
        assert prog[k] == pytest.approx(ref[k], rel=2e-3, abs=1e-7), k


def test_float8_control_is_not_correct(monkeypatch):
    """At a test's size the control already departs from float32 by more
    than the training cell's limits allow."""
    cell = tiny_cell(monkeypatch)
    _, moe_cfg = bench.model_config(cell.config)
    d = bench.dims(cell.config, moe_cfg.padded_vocab)
    feed = train.batches(cell.traffic, d.vocab, SEED)
    batches = [next(feed) for _ in range(3)]
    tc = cell.config["train"]
    ref = reference.train_steps(SEED, d, batches, tc)
    ctl = reference.train_steps(SEED, d, batches, tc, rounding="fp8")
    keep = harness.kept_leaves(ref["grad_norms"])
    numbers = {
        "loss_gap": max(abs(a - b) for a, b in zip(ctl["losses"], ref["losses"])),
        "grad_gap": harness.worst_leaf_gap(ctl["grad_norms"], ref["grad_norms"], keep)[0],
        "update_gap": harness.worst_leaf_gap(ctl["delta_norms"], ref["delta_norms"], keep)[0],
    }
    assert any(numbers[k] > cell.limits[k] for k in numbers), numbers
