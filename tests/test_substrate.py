"""Substrate tests: data pipeline, checkpointing, optimizers, partitioner."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.data import SyntheticTokens, batch_for_step, chunk_batch
from repro.checkpoint import (CheckpointManager, load_checkpoint,
                              save_checkpoint)
from repro.distributed.partitioner import AxisRules, make_rules
from repro.models.config import ModelConfig
from repro.optim import adafactor, adamw, apply_updates, clip_by_global_norm


# -------------------------------------------------------------------- data
def test_data_deterministic():
    cfg = ModelConfig(vocab_size=1000)
    a = batch_for_step(cfg, 5, 8, 32, seed=1)
    b = batch_for_step(cfg, 5, 8, 32, seed=1)
    assert np.array_equal(a["tokens"], b["tokens"])
    c = batch_for_step(cfg, 6, 8, 32, seed=1)
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_data_chunk_equals_slice():
    """A task's chunk == the same rows of the full batch (the property
    that makes rDLB re-execution interchangeable)."""
    cfg = ModelConfig(vocab_size=1000)
    full = batch_for_step(cfg, 3, 16, 32)
    part = chunk_batch(full, 4, 4)
    assert np.array_equal(part["tokens"], full["tokens"][4:8])
    # row content independent of which worker materializes it:
    direct = batch_for_step(cfg, 3, 4, 32, row_offset=4)
    assert np.array_equal(part["tokens"], direct["tokens"])


def test_data_labels_shifted():
    cfg = ModelConfig(vocab_size=97)
    b = batch_for_step(cfg, 0, 4, 16)
    assert np.array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])
    assert b["tokens"].max() < 97


@given(step=st.integers(0, 1000), vocab=st.integers(2, 100000))
@settings(max_examples=30, deadline=None)
def test_data_in_vocab_range(step, vocab):
    gen = SyntheticTokens(vocab, 16, seed=0)
    rows = gen.rows(step, np.arange(4))
    assert rows.min() >= 0 and rows.max() < vocab


# -------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16),
                  "d": jnp.int32(7)}}
    save_checkpoint(tmp_path / "ck", tree, step=42)
    restored, step = load_checkpoint(tmp_path / "ck", tree)
    assert step == 42
    for x, y in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(restored)):
        assert np.array_equal(np.asarray(x, np.float32),
                              np.asarray(y, np.float32))


def test_checkpoint_manager_retention(tmp_path):
    mgr = CheckpointManager(tmp_path, interval=1, keep=2, async_save=False)
    tree = {"x": jnp.zeros(3)}
    for s in range(1, 5):
        mgr.maybe_save(s, tree)
    mgr.wait()
    dirs = sorted(p.name for p in tmp_path.glob("step_*"))
    assert dirs == ["step_00000003", "step_00000004"]
    restored = mgr.restore_latest(tree)
    assert restored is not None and restored[1] == 4


def test_checkpoint_async_overlap(tmp_path):
    mgr = CheckpointManager(tmp_path, interval=1, keep=1, async_save=True)
    tree = {"x": jnp.arange(10)}
    assert mgr.maybe_save(1, tree)
    mgr.wait()
    assert mgr.latest() is not None


def test_restart_training_equivalence(tmp_path):
    """checkpoint -> restart reproduces the same parameters as an
    uninterrupted run (the checkpoint/restart baseline of §3.1)."""
    from repro import api
    from repro.models import build_model
    from repro.runtime import RDLBTrainExecutor
    cfg = ModelConfig(family="dense", n_layers=1, d_model=32, n_heads=2,
                      n_kv_heads=2, d_ff=64, vocab_size=128)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ex = RDLBTrainExecutor(model, spec=api.train_spec(n_workers=2, n_tasks=4),
                           exact_accumulation=True)
    opt = ex.opt.init(params)

    # uninterrupted: 4 steps
    p, o = params, opt
    for s in range(4):
        r = ex.train_step(p, o, batch_for_step(cfg, s, 8, 16))
        p, o = r.params, r.opt_state

    # interrupted at step 2 + restart from checkpoint
    p2, o2 = params, opt
    for s in range(2):
        r = ex.train_step(p2, o2, batch_for_step(cfg, s, 8, 16))
        p2, o2 = r.params, r.opt_state
    save_checkpoint(tmp_path / "ck", {"p": p2, "o": o2}, step=2)
    (state, step) = load_checkpoint(tmp_path / "ck", {"p": p2, "o": o2})
    p2, o2 = state["p"], state["o"]
    for s in range(step, 4):
        r = ex.train_step(p2, o2, batch_for_step(cfg, s, 8, 16))
        p2, o2 = r.params, r.opt_state
    for a, b in zip(jax.tree_util.tree_leaves(p),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-6)


# --------------------------------------------------------------- optimizers
def test_adamw_decreases_quadratic_loss():
    opt = adamw(lr=0.1, weight_decay=0.0)
    params = {"w": jnp.array([3.0, -2.0])}
    state = opt.init(params)
    for _ in range(100):
        grads = {"w": 2 * params["w"]}
        updates, state = opt.update(grads, state, params)
        params = apply_updates(params, updates)
    assert float(jnp.abs(params["w"]).max()) < 0.1


def test_adafactor_factored_state_small():
    opt = adafactor(lr=0.05)
    params = {"w": jnp.zeros((64, 32)), "b": jnp.zeros((32,))}
    state = opt.init(params)
    assert state["v"]["w"]["vr"].shape == (64,)
    assert state["v"]["w"]["vc"].shape == (32,)
    assert state["v"]["b"]["v"].shape == (32,)
    grads = {"w": jnp.ones((64, 32)), "b": jnp.ones((32,))}
    updates, state = opt.update(grads, state, params)
    assert updates["w"].shape == (64, 32)
    assert float(updates["w"][0, 0]) < 0


def test_grad_clip():
    tree = {"a": jnp.full((4,), 10.0)}
    clipped, norm = clip_by_global_norm(tree, 1.0)
    assert norm == pytest.approx(20.0)
    assert float(jnp.linalg.norm(clipped["a"])) == pytest.approx(1.0, rel=1e-5)


# -------------------------------------------------------------- partitioner
def test_rules_resolution():
    rules = AxisRules(make_rules())
    spec = rules.spec(("batch", "seq", "heads"))
    assert spec == jax.sharding.PartitionSpec(("pod", "data"), None, "model")


def test_rules_divisibility_fallback():
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rules = AxisRules(make_rules())
    # 7 not divisible by model size 1? size-1 axes always divide: kept
    spec = rules.spec(("heads",), (7,), mesh)
    assert spec == jax.sharding.PartitionSpec("model")


def test_rules_no_double_axis_use():
    rules = AxisRules(make_rules(fsdp=True))
    # embed->data and batch->(pod,data): batch first, embed falls back
    # (trailing None is stripped -> 1-entry spec)
    spec = rules.spec(("batch", "embed"))
    assert tuple(spec) == (("pod", "data"),)


def test_fsdp_rules_shard_embed():
    rules = AxisRules(make_rules(fsdp=True))
    spec = rules.spec(("embed", "mlp"))
    assert spec == jax.sharding.PartitionSpec("data", "model")
