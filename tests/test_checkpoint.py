"""Checkpoint subsystem: atomic commit + retention, crash-mid-save safety,
exact (bitwise) resume parity, elastic mesh-reshape restore, format-1
backward compat, and async-save donation safety."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_dense
from repro.checkpoint import (
    CheckpointManager,
    latest_step,
    list_steps,
    load_checkpoint,
    restore_tree,
    save_checkpoint,
)
from repro.config import MoEConfig, TrainConfig
from repro.data.pipeline import make_train_iter
from repro.train.callbacks import CheckpointCallback, LoggingCallback
from repro.train.state import restore_train_state, state_to_tree
from repro.train.trainer import Trainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tcfg(steps=30, B=4, S=16, **kw):
    return TrainConfig(global_batch=B, seq_len=S, lr=3e-3, lr_min=3e-4,
                       warmup_steps=5, total_steps=steps, log_every=1, seed=3,
                       **kw)


def _tree(seed=0):
    r = np.random.default_rng(seed)
    return {
        "a": jnp.asarray(r.standard_normal((6, 4)), jnp.float32),
        "b": {"c": jnp.asarray(r.standard_normal(8), jnp.float32).astype(jnp.bfloat16),
              "step": jnp.int32(5)},
    }


def _leaves_equal(t1, t2) -> bool:
    l1, l2 = jax.tree.leaves(t1), jax.tree.leaves(t2)
    return len(l1) == len(l2) and all(
        np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(l1, l2)
    )


# -- manager: atomicity, retention, crash safety ---------------------------


def test_manager_commit_and_retention(tmp_path):
    d = str(tmp_path / "ck")
    m = CheckpointManager(d, keep_last=2, async_save=False)
    for s in (1, 2, 3, 4):
        m.save(_tree(s), s)
    assert list_steps(d) == [3, 4]
    assert latest_step(d) == 4
    tree, manifest = restore_tree(d)
    assert manifest["step"] == 4 and _leaves_equal(tree, _tree(4))
    # no stale tmp dirs after commits
    assert not [n for n in os.listdir(d) if n.startswith(".tmp-")]


def test_crash_mid_save_keeps_last_good(tmp_path):
    """Kill the process while step-2's shard files are being written: the
    tmp dir must never be promoted, step 1 stays the restorable latest, and
    the next manager instance sweeps the debris."""
    d = str(tmp_path / "ck")
    code = f"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np, jax.numpy as jnp
from repro.checkpoint import CheckpointManager
from repro.checkpoint import sharded

tree = {{"a": jnp.arange(24, dtype=jnp.float32).reshape(6, 4),
         "b": {{"c": jnp.ones(8, jnp.float32), "d": jnp.zeros(8, jnp.float32)}}}}
m = CheckpointManager({d!r}, keep_last=5, async_save=False)
m.save(tree, 1)

calls = [0]
real = np.save
def dying_save(*a, **kw):
    calls[0] += 1
    if calls[0] > 1:  # die after the first shard file of step 2
        os._exit(9)
    return real(*a, **kw)
np.save = dying_save
sharded.np.save = dying_save
m.save(tree, 2)
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode == 9, f"expected the injected kill: {r.stderr[-2000:]}"
    # last-good checkpoint survives; the torn write is invisible
    assert latest_step(d) == 1
    tree, _ = restore_tree(d)
    assert float(np.asarray(tree["a"]).ravel()[-1]) == 23.0
    tmp = [n for n in os.listdir(d) if n.startswith(".tmp-")]
    assert tmp, "the killed writer should have left a tmp dir behind"
    CheckpointManager(d)  # init sweeps stale tmp dirs
    assert not [n for n in os.listdir(d) if n.startswith(".tmp-")]


def test_async_save_matches_blocking_and_is_donation_safe(tmp_path):
    """An async save snapshots the state at save time: training on (which
    donates and overwrites the device buffers) must not corrupt the bytes
    that land on disk."""
    cfg = tiny_dense(num_layers=1, vocab_size=256)
    tcfg = _tcfg()
    it = make_train_iter(cfg.vocab_size, tcfg.seq_len, tcfg.global_batch, seed=3)
    tr = Trainer(cfg, tcfg, data_iter=it)
    cb = CheckpointCallback(str(tmp_path / "async"), every=2, async_save=True)
    tr.run(2, log=lambda *_: None, callbacks=[cb])
    snap_at_2 = jax.device_get(state_to_tree(tr.state))  # values at step 2
    tr.run(2, log=lambda *_: None, callbacks=[cb])  # donates/overwrites buffers
    cb.manager.wait()
    tree2, _ = restore_tree(str(tmp_path / "async"), step=2)
    assert _leaves_equal(tree2, snap_at_2)
    tree4, _ = restore_tree(str(tmp_path / "async"), step=4)
    assert _leaves_equal(tree4, jax.device_get(state_to_tree(tr.state)))
    assert not _leaves_equal(tree2["params"], tree4["params"])


# -- flat checkpoints: format compat ---------------------------------------


def test_load_checkpoint_v1_compat(tmp_path):
    """Seed-era format-1 manifests (one whole-array .npy per leaf, bf16 as
    uint16 view) stay loadable."""
    d = tmp_path / "v1"
    d.mkdir()
    w = np.arange(12, dtype=np.float32).reshape(3, 4)
    e = (np.ones(8, np.float32) * 1.5).astype(jnp.bfloat16)
    np.save(d / "layer__w.npy", w)
    np.save(d / "layer__e.npy", e.view(np.uint16))
    manifest = {"step": 7, "meta": {}, "leaves": {
        "layer::w": {"file": "layer__w.npy", "dtype": "float32"},
        "layer::e": {"file": "layer__e.npy", "dtype": "bfloat16"},
    }}
    (d / "manifest.json").write_text(json.dumps(manifest))
    loaded = load_checkpoint(str(d))
    assert np.array_equal(np.asarray(loaded["layer"]["w"]), w)
    assert loaded["layer"]["e"].dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(loaded["layer"]["e"]), np.asarray(e))


def test_flat_roundtrip_v2(tmp_path):
    t = _tree(1)
    save_checkpoint(str(tmp_path / "flat"), t, step=9)
    assert _leaves_equal(load_checkpoint(str(tmp_path / "flat")), t)
    man = json.load(open(tmp_path / "flat" / "manifest.json"))
    assert man["format"] == 2 and man["step"] == 9
    # every leaf records its spec slot and shard indices
    assert all("shards" in e for e in man["leaves"].values())


# -- data pipeline state ----------------------------------------------------


def test_data_iterator_state_restore():
    it = make_train_iter(256, 16, 4, seed=11)
    for _ in range(3):
        next(it)
    snap = it.state()
    want = [next(it) for _ in range(2)]
    it2 = make_train_iter(256, 16, 4, seed=11).restore(snap)
    got = [next(it2) for _ in range(2)]
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w["tokens"], g["tokens"])
        np.testing.assert_array_equal(w["labels"], g["labels"])
    # snapshot must survive a JSON round trip (it rides the manifest meta)
    snap_json = json.loads(json.dumps(snap))
    it3 = make_train_iter(256, 16, 4, seed=11).restore(snap_json)
    np.testing.assert_array_equal(next(it3)["tokens"], want[0]["tokens"])


# -- exact resume parity ----------------------------------------------------


def _run_straight(cfg, tcfg, steps, **trainer_kw):
    it = make_train_iter(cfg.vocab_size, tcfg.seq_len, tcfg.global_batch,
                         tcfg.blend_ratio, tcfg.seed)
    tr = Trainer(cfg, tcfg, data_iter=it, **trainer_kw)
    tr.run(steps, log=lambda *_: None)
    return tr


def test_resume_bitwise_parity(tmp_path):
    """k steps + save + restore-in-a-fresh-Trainer + n steps == k+n straight
    steps, bitwise: params, fp32 master/moments, and logged metrics."""
    cfg = tiny_dense(num_layers=1, vocab_size=256)
    tcfg = _tcfg()
    straight = _run_straight(cfg, tcfg, 6)

    d = str(tmp_path / "ck")
    it = make_train_iter(cfg.vocab_size, tcfg.seq_len, tcfg.global_batch,
                         tcfg.blend_ratio, tcfg.seed)
    tr1 = Trainer(cfg, tcfg, data_iter=it)
    cb = CheckpointCallback(d, every=3, async_save=True)
    tr1.run(3, log=lambda *_: None, callbacks=[LoggingCallback(log=lambda *_: None, log_every=1), cb])

    state, manifest = restore_train_state(d, cfg)
    assert manifest["step"] == 3
    it2 = make_train_iter(cfg.vocab_size, tcfg.seq_len, tcfg.global_batch,
                          tcfg.blend_ratio, tcfg.seed)
    it2.restore(manifest["meta"]["data_state"])
    tr2 = Trainer(cfg, tcfg, state=state, data_iter=it2)
    tr2.run(3, log=lambda *_: None)

    assert int(jax.device_get(tr2.state.step)) == 6
    assert _leaves_equal(tr2.params, straight.params)
    assert _leaves_equal(tr2.opt_state.master, straight.opt_state.master)
    assert _leaves_equal(tr2.opt_state.m, straight.opt_state.m)
    assert _leaves_equal(tr2.opt_state.v, straight.opt_state.v)
    assert np.array_equal(np.asarray(tr2.rng), np.asarray(straight.rng))
    # logged metrics of the resumed tail are bitwise those of the straight run
    tail = {r["step"]: r for r in tr2.history}
    ref = {r["step"]: r for r in straight.history}
    for s in (4, 5, 6):
        for k in ("loss", "ce", "lr", "grad_norm"):
            assert tail[s][k] == ref[s][k], (s, k, tail[s][k], ref[s][k])


def test_resume_composes_with_upcycle(tmp_path):
    """A run started via upcycling restarts from its latest MoE state, not
    by re-upcycling — and matches the uninterrupted upcycled run bitwise."""
    from repro.core.upcycle import upcycle_config, upcycle_params

    dense_cfg = tiny_dense(num_layers=1, vocab_size=256)
    tcfg = _tcfg()
    dense = _run_straight(dense_cfg, tcfg, 3)
    moe_cfg = upcycle_config(
        dense_cfg, MoEConfig(num_experts=4, top_k=2, capacity_factor=None,
                             dispatcher="sorted"))
    moe_params = upcycle_params(dense_cfg, moe_cfg, dense.params,
                                jax.random.PRNGKey(9))

    straight = _run_straight(moe_cfg, tcfg, 4, params=moe_params)

    d = str(tmp_path / "ck")
    it = make_train_iter(moe_cfg.vocab_size, tcfg.seq_len, tcfg.global_batch,
                         tcfg.blend_ratio, tcfg.seed)
    tr1 = Trainer(moe_cfg, tcfg, params=moe_params, data_iter=it)
    cb = CheckpointCallback(d, every=2, async_save=True,
                            extra_meta={"provenance": {"upcycled": True}})
    tr1.run(2, log=lambda *_: None, callbacks=[cb])

    state, manifest = restore_train_state(d, moe_cfg)
    assert manifest["meta"]["provenance"]["upcycled"] is True
    it2 = make_train_iter(moe_cfg.vocab_size, tcfg.seq_len, tcfg.global_batch,
                          tcfg.blend_ratio, tcfg.seed)
    it2.restore(manifest["meta"]["data_state"])
    tr2 = Trainer(moe_cfg, tcfg, state=state, data_iter=it2)
    tr2.run(2, log=lambda *_: None)
    assert _leaves_equal(tr2.params, straight.params)
    assert _leaves_equal(tr2.opt_state.master, straight.opt_state.master)


def test_restore_rejects_wrong_config(tmp_path):
    cfg = tiny_dense(num_layers=1, vocab_size=256)
    tcfg = _tcfg()
    d = str(tmp_path / "ck")
    it = make_train_iter(cfg.vocab_size, tcfg.seq_len, tcfg.global_batch, seed=3)
    tr = Trainer(cfg, tcfg, data_iter=it)
    tr.run(1, log=lambda *_: None,
           callbacks=[CheckpointCallback(d, every=1, async_save=False)])
    other = tiny_dense(num_layers=2, vocab_size=256)
    with pytest.raises(AssertionError, match="do(es)? not match"):
        restore_train_state(d, other)


# -- satellite: steady-state timing accounting ------------------------------


def test_history_timing_excludes_warmup():
    cfg = tiny_dense(num_layers=1, vocab_size=256)
    tcfg = _tcfg()
    it = make_train_iter(cfg.vocab_size, tcfg.seq_len, tcfg.global_batch, seed=3)
    tr = Trainer(cfg, tcfg, data_iter=it)
    tr.run(4, log=lambda *_: None)
    last = tr.history[-1]
    for key in ("ms_per_step_steady", "wall_total_s", "sec_per_step"):
        assert key in last and last[key] > 0, key
    # step 1 pays jit compilation; the steady figure must exclude it
    step1_s = tr.history[0]["wall_total_s"]
    assert last["sec_per_step"] <= step1_s, (last["sec_per_step"], step1_s)
    assert last["wall_total_s"] >= step1_s
    assert last["sec_per_step"] == pytest.approx(last["ms_per_step_steady"] / 1e3)


# -- elastic mesh reshaping -------------------------------------------------


def test_mesh_reshape_restore_parity():
    """Save the full TrainState under EP on the 3-D study mesh; restore it
    (a) onto the 2-D production-style mesh (EP folds onto 'model') and
    (b) onto the host (no plan) — bitwise both times, with the optimizer
    state re-sharded per the target plan's ZeRO-1 rules."""
    code = """
import json, numpy as np, jax, jax.numpy as jnp
from repro.config import ModelConfig, MoEConfig, TrainConfig
from repro.launch.mesh import make_study_mesh
from repro.sharding.rules import FoldingPlan
from repro.checkpoint import CheckpointManager, restore_tree
from repro.train.state import (create_train_state, restore_train_state,
                               state_to_tree)

moe = MoEConfig(num_experts=4, top_k=2, capacity_factor=None, dispatcher="sorted")
cfg = ModelConfig(name="t", family="moe", num_layers=1, d_model=64, num_heads=4,
                  num_kv_heads=4, d_ff=128, vocab_size=256, vocab_divisor=64,
                  dtype="float32", moe=moe)
tcfg = TrainConfig(global_batch=4, seq_len=16, seed=0)

study = make_study_mesh(1, 4, 2)
plan_s = FoldingPlan.make(cfg, study)
assert plan_s.moe_mode == "ep" and plan_s.ep_axis == "expert"
state = create_train_state(cfg, tcfg, plan_s)
ref = jax.device_get(state_to_tree(state))
m = CheckpointManager("/tmp/ck_reshape", keep_last=1, async_save=False)
m.save(state_to_tree(state), 1)

prod = jax.make_mesh((2, 4), ("data", "model"))
plan_p = FoldingPlan.make(cfg, prod)
assert plan_p.moe_mode == "ep" and plan_p.ep_axis == "model"
got_p, _ = restore_train_state("/tmp/ck_reshape", cfg, plan_p, zero1=tcfg.zero1)
got_h, _ = restore_train_state("/tmp/ck_reshape", cfg, plan=None)

def eq(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))

wg = got_p.params["stack"]["slot0"]["ffn"]["experts"]["w_gate"]
out = {
  "prod_equal": eq(jax.device_get(state_to_tree(got_p)), ref),
  "host_equal": eq(jax.device_get(state_to_tree(got_h)), ref),
  "wg_spec": str(wg.sharding.spec),
  "master_data_sharded": any(
      "data" in str(l.sharding.spec) for l in jax.tree.leaves(got_p.opt_state.master)
      if hasattr(l, "sharding")),
}
print(json.dumps(out))
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    # fake-device meshes live on the host (CPU) platform; pin it so the
    # child never probes a real accelerator plugin (libtpu init can hang
    # when the machine has the plugin but no device)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["prod_equal"], out
    assert out["host_equal"], out
    assert "model" in out["wg_spec"], out  # experts now shard the model axis
    assert out["master_data_sharded"], out
