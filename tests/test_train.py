"""Training substrate: optimizer, loss descent, checkpoint/restart,
elastic remesh, gradient compression, data determinism."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.launch.train import train
from repro.models import model as M
from repro.train import checkpoint as ckpt
from repro.train.compression import (compress_grads, decompress_grads,
                                     init_residuals, int8_compress,
                                     int8_decompress)
from repro.train.data import SyntheticStream
from repro.train.optimizer import OptConfig, adamw_update, init_opt_state, lr_schedule


def test_loss_descends_smollm(tmp_path):
    out = train(get_smoke_config("smollm-135m"), steps=30, global_batch=4,
                seq_len=64, lr=2e-3, log_every=100)
    losses = out["losses"]
    assert losses[-1] < losses[0] - 0.3, (losses[0], losses[-1])


def test_checkpoint_restart_bit_identical(tmp_path):
    """Kill-and-resume produces the same final state as an unbroken run."""
    cfg = get_smoke_config("smollm-135m")
    d1 = str(tmp_path / "a")
    # unbroken 20 steps
    r_full = train(cfg, steps=20, global_batch=2, seq_len=32, ckpt_dir=None,
                   lr=1e-3, log_every=100)
    # broken run: killed after 10 steps (checkpoint), then resume to 20.
    # stop_after keeps the LR schedule identical to the unbroken run.
    train(cfg, steps=20, stop_after=10, global_batch=2, seq_len=32, ckpt_dir=d1,
          ckpt_every=10, lr=1e-3, log_every=100)
    r_resumed = train(cfg, steps=20, global_batch=2, seq_len=32, ckpt_dir=d1,
                      ckpt_every=10, lr=1e-3, log_every=100)
    np.testing.assert_allclose(
        r_full["losses"][-5:], r_resumed["losses"][-5:], rtol=1e-4, atol=1e-5
    )


def test_checkpoint_atomicity(tmp_path):
    d = str(tmp_path / "ck")
    tree = {"a": np.arange(10), "b": {"c": np.ones((2, 2))}}
    ckpt.save(d, 5, tree, extra={"step": 5})
    # a torn write (no manifest) must be ignored
    os.makedirs(os.path.join(d, "step_00000009"), exist_ok=True)
    assert ckpt.latest_step(d) == 5
    restored, extra = ckpt.restore(d, tree)
    np.testing.assert_array_equal(restored["a"], tree["a"])
    assert extra["step"] == 5


def test_elastic_remesh_restore(tmp_path):
    """Save under one sharding, restore under another mesh shape."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_host_mesh
    d = str(tmp_path / "el")
    tree = {"w": np.arange(16, dtype=np.float32).reshape(4, 4)}
    ckpt.save(d, 1, tree, extra={})
    mesh = make_host_mesh((1,), ("data",))
    sh = {"w": NamedSharding(mesh, P("data", None))}
    restored, _ = ckpt.restore(d, tree, shardings=sh)
    np.testing.assert_array_equal(np.asarray(restored["w"]), tree["w"])
    assert restored["w"].sharding == sh["w"]


def test_adamw_descends_quadratic():
    cfg = OptConfig(lr=0.3, warmup_steps=1, total_steps=10000, weight_decay=0.0)
    params = {"w": jnp.array([5.0, -3.0])}
    opt = init_opt_state(params, cfg)
    for _ in range(100):
        grads = {"w": 2 * params["w"]}
        params, opt, _ = adamw_update(params, grads, opt, cfg)
    assert float(jnp.abs(params["w"]).max()) < 0.5


def test_lr_schedule_shape():
    cfg = OptConfig(lr=1.0, warmup_steps=10, total_steps=100)
    lrs = [float(lr_schedule(cfg, jnp.int32(s))) for s in [0, 5, 10, 50, 100]]
    assert lrs[1] < lrs[2]  # warmup ascending
    assert lrs[2] >= lrs[3] >= lrs[4]  # cosine descending
    assert lrs[4] >= 0.09  # floor


def test_int8_compression_error_feedback():
    rng = np.random.RandomState(0)
    g = jnp.asarray(rng.randn(1000).astype(np.float32))
    c, resid = int8_compress(g)
    deq = int8_decompress(c, g.shape, g.dtype)
    rel = float(jnp.linalg.norm(deq - g) / jnp.linalg.norm(g))
    assert rel < 0.02, rel  # blockwise int8 ≈ 1% error
    np.testing.assert_allclose(np.asarray(deq + resid), np.asarray(g), rtol=1e-5, atol=1e-6)
    # 4x payload reduction
    assert c.q.nbytes <= g.nbytes // 4 + 64


def test_grad_compression_roundtrip_pytree():
    rng = np.random.RandomState(1)
    grads = {"a": jnp.asarray(rng.randn(37, 5).astype(np.float32)),
             "b": {"c": jnp.asarray(rng.randn(8).astype(np.float32))}}
    for mode in ("none", "bf16", "int8"):
        resid = init_residuals(grads, mode)
        comp, resid = compress_grads(grads, resid, mode)
        out = decompress_grads(comp, grads, mode)
        tol = {"none": 0, "bf16": 1e-2, "int8": 3e-2}[mode]
        for k in ("a",):
            np.testing.assert_allclose(np.asarray(out[k]), np.asarray(grads[k]),
                                       rtol=tol, atol=tol)


def test_data_stream_deterministic_and_resumable():
    cfg = get_smoke_config("smollm-135m")
    s1 = SyntheticStream(cfg, 4, 32, seed=7)
    b1 = [s1.next_batch()["tokens"] for _ in range(3)]
    s2 = SyntheticStream(cfg, 4, 32, seed=7)
    s2.next_batch()
    snap = s2.snapshot()
    s3 = SyntheticStream(cfg, 4, 32, seed=0)
    s3.restore(snap)
    np.testing.assert_array_equal(s3.next_batch()["tokens"], b1[1])
    np.testing.assert_array_equal(s3.next_batch()["tokens"], b1[2])


def test_data_stream_host_sharding():
    cfg = get_smoke_config("smollm-135m")
    full = SyntheticStream(cfg, 8, 16, seed=1, host_id=0, num_hosts=1)
    h0 = SyntheticStream(cfg, 8, 16, seed=1, host_id=0, num_hosts=2)
    h1 = SyntheticStream(cfg, 8, 16, seed=1, host_id=1, num_hosts=2)
    b0, b1 = h0.next_batch()["tokens"], h1.next_batch()["tokens"]
    assert b0.shape == (4, 16) and b1.shape == (4, 16)
    assert not np.array_equal(b0, b1)  # hosts draw different shards
