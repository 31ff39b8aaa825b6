"""Training substrate: optimizer, microbatching, checkpoint/restart
(incl. crash injection), elastic re-meshing, straggler detection."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

import repro.models as M
from repro.configs import get_config
from repro.models.config import reduced
from repro.train import (
    AdamWConfig,
    StragglerDetector,
    TrainConfig,
    latest_step,
    make_train_step,
    restore_checkpoint,
    save_checkpoint,
)
from repro.train.optimizer import adamw_init

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _setup(arch="tinyllama_1_1b", **kw):
    cfg = reduced(get_config(arch), **kw)
    params = M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    batch = dict(
        tokens=jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab, (4, 32)),
            jnp.int32),
        labels=jnp.asarray(
            np.random.default_rng(1).integers(0, cfg.vocab, (4, 32)),
            jnp.int32),
    )
    return cfg, params, batch


def test_train_step_reduces_loss():
    cfg, params, batch = _setup()
    opt = adamw_init(params)
    step = jax.jit(make_train_step(
        cfg, TrainConfig(opt=AdamWConfig(lr=5e-3, total_steps=50))))
    losses = []
    for _ in range(12):
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.9, losses
    assert int(opt.step) == 12


def test_microbatching_matches_full_batch():
    cfg, params, batch = _setup()
    opt = adamw_init(params)
    s1 = make_train_step(cfg, TrainConfig(microbatches=1))
    s2 = make_train_step(cfg, TrainConfig(microbatches=2))
    p1, _, m1 = jax.jit(s1)(params, opt, batch)
    p2, _, m2 = jax.jit(s2)(params, opt, batch)
    np.testing.assert_allclose(
        float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    a = jax.tree.leaves(p1)[0]
    b = jax.tree.leaves(p2)[0]
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=1e-5, rtol=1e-4)


def test_grad_compression_still_trains():
    cfg, params, batch = _setup()
    opt = adamw_init(params)
    step = jax.jit(make_train_step(
        cfg, TrainConfig(compress_grads=True,
                         opt=AdamWConfig(lr=5e-3, total_steps=50))))
    losses = []
    for _ in range(10):
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses


def test_checkpoint_roundtrip(tmp_path):
    cfg, params, batch = _setup()
    opt = adamw_init(params)
    path = str(tmp_path / "ckpt")
    save_checkpoint(path, 7, params, opt, extra=dict(arch=cfg.name))
    assert latest_step(path) == 7
    p2, o2, man = restore_checkpoint(path, 7, params, opt)
    assert man["extra"]["arch"] == cfg.name
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(o2.step) == int(opt.step)


def test_incomplete_checkpoint_invisible(tmp_path):
    cfg, params, batch = _setup()
    opt = adamw_init(params)
    path = str(tmp_path / "ckpt")
    save_checkpoint(path, 3, params, opt)
    # simulate a crash mid-save at step 9: directory without manifest
    os.makedirs(os.path.join(path, "step_00000009"))
    assert latest_step(path) == 3


def test_crash_and_resume(tmp_path):
    """Kill training mid-run; resume must continue from the checkpoint
    and finish with the same data order (bit-reproducible pipeline)."""
    ckpt = str(tmp_path / "ckpt")
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    args = [sys.executable, "-m", "repro.launch.train",
            "--arch", "tinyllama_1_1b", "--reduced",
            "--steps", "30", "--batch", "4", "--seq", "32",
            "--ckpt-dir", ckpt, "--ckpt-every", "10",
            "--log-every", "5"]
    out1 = subprocess.run(args + ["--crash-at", "15"],
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert out1.returncode == 42, out1.stderr[-1500:]
    assert latest_step(ckpt) == 10
    out2 = subprocess.run(args, env=env, capture_output=True, text=True,
                          timeout=900)
    assert out2.returncode == 0, out2.stderr[-1500:]
    assert "resumed from step 10" in out2.stdout
    assert latest_step(ckpt) == 30


def test_elastic_remesh_subprocess():
    """Restore state onto a different device count (pod loss): 8 -> 4."""
    import textwrap
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    src = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        assert hasattr(AxisType, "Auto")
        import repro.models as M
        from repro.configs import get_config
        from repro.models.config import reduced
        from repro.train.optimizer import adamw_init
        from repro.train.elastic import remesh
        cfg = reduced(get_config("tinyllama_1_1b"))
        params = M.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        opt = adamw_init(params)
        axes = M.logical_axes(cfg)
        devs = np.array(jax.devices())
        m8 = jax.sharding.Mesh(devs.reshape(2, 4), ("data", "model"))
        p8, o8 = remesh(params, opt, axes, m8)
        # lose half the devices
        m4 = jax.sharding.Mesh(devs[:4].reshape(2, 2), ("data", "model"))
        p4, o4 = remesh(p8, o8, axes, m4)
        a = np.asarray(jax.tree.leaves(params)[0])
        b = np.asarray(jax.tree.leaves(p4)[0])
        assert np.array_equal(a, b)
        print("ELASTIC_OK")
    """)
    out = subprocess.run([sys.executable, "-c", src], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "ELASTIC_OK" in out.stdout


def test_straggler_detector():
    det = StragglerDetector(alpha=0.5, threshold_sigma=1.0)
    import time
    for _ in range(5):
        det.start()
        time.sleep(0.01)
        det.stop()
    det.start()
    time.sleep(0.08)
    assert det.stop() is True


def test_data_pipeline_determinism():
    from repro.data import DataConfig, synthetic_batches
    cfg = DataConfig(batch=4, seq=16, vocab=100, seed=3)
    a = next(synthetic_batches(cfg, start_step=5))
    b = next(synthetic_batches(cfg, start_step=5))
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = next(synthetic_batches(cfg, start_step=6))
    assert not np.array_equal(a["tokens"], c["tokens"])
