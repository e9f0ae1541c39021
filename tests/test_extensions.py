"""Beyond-paper extensions: Expert-Choice routing (Zhou et al., cited by
the paper's §2), expert-noise upcycling (He et al. [10]), the serving
engine, and the roofline analyzer on a known program."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import init_model, tiny_dense
from repro.config import ModelConfig, MoEConfig
from repro.core.moe import capacity, expert_choice_tables, moe_apply, moe_decl
from repro.sharding.rules import init_from_decls


def _ec_cfg(E=4, C_factor=2.0):
    moe = MoEConfig(num_experts=E, top_k=2, capacity_factor=C_factor,
                    router_type="expert_choice")
    cfg = ModelConfig(name="t", family="moe", num_layers=1, d_model=32,
                      num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=128,
                      vocab_divisor=64, dtype="float32", moe=moe)
    return cfg, moe


def test_expert_choice_perfect_balance():
    """Every expert processes exactly C tokens — balanced by construction."""
    probs = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(0), (64, 4)), -1)
    sel, gate = expert_choice_tables(probs, E=4, C=16)
    assert sel.shape == (4, 16) and gate.shape == (4, 16)
    assert bool(jnp.all(gate > 0))  # every slot filled
    # selected gates are each expert's top scores
    for e in range(4):
        thresh = float(jnp.min(gate[e]))
        assert int(jnp.sum(probs[:, e] > thresh)) <= 16


def test_expert_choice_moe_runs_and_trains():
    cfg, moe = _ec_cfg()
    params = init_from_decls(moe_decl(cfg, moe), jax.random.PRNGKey(0))
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32)) * 0.3

    def loss(p):
        y, aux = moe_apply(cfg, moe, None, p, x)
        return jnp.sum(jnp.square(y)) + sum(aux.values())

    l, g = jax.value_and_grad(loss)(params)
    assert np.isfinite(float(l))
    gn = sum(float(jnp.sum(jnp.abs(v))) for v in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0
    # router receives gradient (EC is differentiable through the gates)
    assert float(jnp.sum(jnp.abs(g["router"]["w_g"]))) > 0


def test_expert_noise_breaks_symmetry_but_stays_close():
    from repro.core.upcycle import upcycle_config, upcycle_params
    from repro.models.model import forward

    cfg = tiny_dense(num_layers=2, dtype="float32")
    dp = init_model(cfg, fp32=True)
    moe_c = upcycle_config(cfg, MoEConfig(num_experts=4, top_k=2, capacity_factor=None))
    mp = upcycle_params(cfg, moe_c, dp, jax.random.PRNGKey(1), expert_noise=0.01)
    wg = np.asarray(mp["stack"]["slot0"]["ffn"]["experts"]["w_gate"], np.float32)
    # experts now differ...
    assert not np.array_equal(wg[:, 0], wg[:, 1])
    # ...but the function stays near the dense one (small perturbation)
    batch = {"tokens": jnp.arange(32, dtype=jnp.int32).reshape(2, 16)}
    ld, _ = forward(cfg, None, dp, batch)
    lm, _ = forward(moe_c, None, mp, batch)
    rel = float(jnp.max(jnp.abs(ld - lm)) / (jnp.max(jnp.abs(ld)) + 1e-9))
    assert 0 < rel < 0.05, rel


def test_serving_engine_end_to_end():
    from repro.serving.engine import Request, ServingEngine

    cfg = tiny_dense(num_layers=2, dtype="float32")
    params = init_model(cfg, fp32=True)
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=48)
    rng = np.random.default_rng(0)
    reqs = [
        Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 6).astype(np.int32),
                max_new_tokens=5 + i)
        for i in range(5)  # 5 requests through 2 slots -> refill path
    ]
    out = eng.run(reqs)
    assert set(out) == {0, 1, 2, 3, 4}
    for i, toks in out.items():
        assert len(toks) == 5 + i
        assert all(0 <= t < cfg.vocab_size for t in toks)
    # greedy + deterministic: resubmitting the same prompt reproduces output
    eng2 = ServingEngine(cfg, params, max_batch=2, max_seq=48)
    out2 = eng2.run([Request(rid=0, prompt=reqs[0].prompt, max_new_tokens=5)])
    assert out2[0] == out[0][:5]


def test_roofline_analyzer_known_program():
    """The trip-count-aware analyzer gets scan FLOPs exactly right where
    XLA's builtin is wrong by the trip count."""
    from repro.roofline.hlo_analysis import analyze

    def scanned(x, ws):
        def body(c, w):
            return c @ w, None

        y, _ = jax.lax.scan(body, x, ws)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((6, 64, 64), jnp.float32)
    compiled = jax.jit(scanned).lower(x, ws).compile()
    got = analyze(compiled.as_text()).flops
    assert got == 6 * 2 * 64**3, got
    ca = compiled.cost_analysis()
    builtin = float(ca.get("flops", 0))
    assert builtin < got  # documents the builtin undercount
