"""Fault-tolerance, checkpointing, data, compression, sharding tests."""

import dataclasses
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import checkpoint as ckpt
from repro.configs import get_config, smoke_config
from repro.data.pipeline import DataConfig, synthetic_batch
from repro.parallel import compression as comp
from repro.parallel import sharding as shardlib
from repro.runtime.trainer import Trainer, TrainerConfig
from repro.runtime.watchdog import StepWatchdog, WatchdogConfig

CKPT_DIR = "/tmp/repro_pytest_ckpt"


@pytest.fixture(autouse=True)
def _clean():
    shutil.rmtree(CKPT_DIR, ignore_errors=True)
    yield
    shutil.rmtree(CKPT_DIR, ignore_errors=True)


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------


def _tree():
    return {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "nested": {"b": jnp.ones((4,), jnp.int32)},
            "tuple": (jnp.zeros((2,)), jnp.full((3,), 7.0))}


def test_checkpoint_roundtrip():
    tree = _tree()
    ckpt.save(CKPT_DIR, 5, tree, metadata={"k": "v"})
    restored, manifest = ckpt.restore(CKPT_DIR, tree)
    assert manifest["step"] == 5 and manifest["metadata"]["k"] == "v"
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_latest_and_prune():
    tree = _tree()
    for s in (1, 2, 3, 4):
        ckpt.save(CKPT_DIR, s, tree)
    assert ckpt.latest_step(CKPT_DIR) == 4
    ckpt.prune(CKPT_DIR, keep=2)
    assert ckpt.latest_step(CKPT_DIR) == 4
    assert not os.path.exists(os.path.join(CKPT_DIR, "step_00000001"))


def test_checkpoint_atomic_no_partial(tmp_path):
    """A .tmp directory left behind never counts as a checkpoint."""
    tree = _tree()
    ckpt.save(CKPT_DIR, 1, tree)
    os.makedirs(os.path.join(CKPT_DIR, "step_00000009.tmp"))
    assert ckpt.latest_step(CKPT_DIR) == 1


# ---------------------------------------------------------------------------
# trainer recovery + determinism
# ---------------------------------------------------------------------------


def _trainer(steps=8):
    cfg = smoke_config(get_config("smollm-135m"))
    tcfg = TrainerConfig(steps=steps, ckpt_every=4, ckpt_dir=CKPT_DIR,
                         log_every=1000)
    dcfg = DataConfig(batch_size=2, seq_len=16, seed=3)
    return Trainer(cfg, tcfg, dcfg)


@pytest.mark.slow
def test_resume_is_bit_deterministic():
    t1 = _trainer()
    hist = t1.run()
    losses = {h["step"]: h["loss"] for h in hist}
    # Fresh trainer resumes from the step-4 checkpoint and replays 4..7.
    t2 = _trainer()
    assert t2.try_resume()
    assert t2.step == 8
    # restore the *intermediate* checkpoint explicitly
    tree, manifest = ckpt.restore(CKPT_DIR, t2._state_tree(), step=4)
    t2.params, t2.opt_state = tree["params"], tree["opt"]
    t2.step = manifest["metadata"]["data_step"]
    t2.history = []
    t2.run()
    for h in t2.history:
        assert abs(losses[h["step"]] - h["loss"]) < 1e-6, h["step"]


def test_data_pipeline_deterministic():
    cfg = smoke_config(get_config("qwen3-8b"))
    dcfg = DataConfig(batch_size=2, seq_len=32, seed=11)
    b1 = synthetic_batch(cfg, dcfg, 7)
    b2 = synthetic_batch(cfg, dcfg, 7)
    b3 = synthetic_batch(cfg, dcfg, 8)
    assert jnp.array_equal(b1["tokens"], b2["tokens"])
    assert not jnp.array_equal(b1["tokens"], b3["tokens"])


def test_watchdog_timeout_and_refractory():
    fired = []
    cfg = WatchdogConfig(deadline_factor=1.0, min_deadline_s=0.05,
                         ema_alpha=1.0, refractory_s=10.0)
    wd = StepWatchdog(cfg, on_timeout=lambda: fired.append(time.monotonic()))
    with wd:
        time.sleep(0.15)          # exceeds deadline → fires once
    assert len(fired) == 1
    with wd:
        time.sleep(0.12)          # within refractory → suppressed
    assert len(fired) == 1
    assert wd.timeouts == 1


# ---------------------------------------------------------------------------
# gradient compression (sparse events + error feedback)
# ---------------------------------------------------------------------------


def test_sparsify_densify_roundtrip_topk():
    g = jnp.array([[0.1, -5.0, 0.01], [3.0, 0.0, -0.2]])
    frame, residual = comp.sparsify(g, capacity=2)
    dense = comp.densify(frame)
    # the two largest-magnitude entries survive
    assert float(dense[0, 1]) == -5.0 and float(dense[1, 0]) == 3.0
    np.testing.assert_allclose(np.asarray(dense + residual), np.asarray(g),
                               atol=1e-7)


def test_error_feedback_accumulates():
    state = comp.init_feedback(jnp.zeros((10,)))
    g = jnp.ones((10,)) * 0.1
    g = g.at[0].set(5.0)
    frame, state = comp.compress_with_feedback(g, state, frac=0.1)  # k=1
    assert frame.indices[0] == 0
    # the small entries live on in the residual and eventually get sent
    total = comp.densify(frame)
    for _ in range(12):
        frame, state = comp.compress_with_feedback(jnp.zeros((10,)), state,
                                                   frac=0.1)
        total = total + comp.densify(frame)
    # After enough rounds every entry has been transmitted exactly once.
    np.testing.assert_allclose(np.asarray(total), np.asarray(g), atol=1e-6)


def test_int8_quantization_error_bounded():
    key = jax.random.key(0)
    x = jax.random.normal(key, (1000,))
    q, scale = comp.quantize_int8(x)
    back = comp.dequantize_int8(q, scale)
    assert float(jnp.max(jnp.abs(back - x))) <= float(scale) * 1.01


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------


def test_resolve_spec_divisibility_fallback():
    import os
    mesh = shardlib.auto_mesh((1,), ("model",))
    # dim divisible by 1 → sharded on model
    spec = shardlib.resolve_spec(("vocab", "embed"), (100, 64), mesh)
    assert spec[0] == "model"


def test_resolve_spec_conflict_first_wins():
    mesh = shardlib.auto_mesh((1,), ("model",))
    # experts and ff both want 'model'; experts (first) wins
    spec = shardlib.resolve_spec(("experts", "embed", "ff"), (8, 64, 128),
                                 mesh)
    assert spec[0] == "model" and spec[2] is None


def test_param_shardings_cover_tree():
    cfg = smoke_config(get_config("qwen3-8b"))
    from repro.models import model as M
    params = jax.eval_shape(lambda: M.init_params(jax.random.key(0), cfg))
    mesh = shardlib.auto_mesh((1,), ("model",))
    shardings = shardlib.param_shardings(params, mesh)
    n_params = len(jax.tree.leaves(params))
    n_shards = len(jax.tree.leaves(
        shardings, is_leaf=lambda x: isinstance(x, jax.sharding.NamedSharding)))
    assert n_params == n_shards
