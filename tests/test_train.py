"""Training substrate: optimizer, microbatching, checkpointing, FT, compression."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs import smoke_config
from repro.models.transformer import init_lm
from repro.train import checkpoint as ckpt
from repro.train import compression as comp
from repro.train import optimizer as opt
from repro.train.data import DataConfig, synth_batch
from repro.train.fault_tolerance import (HeartbeatMonitor, StragglerPolicy,
                                         recovery_plan)
from repro.train.train_loop import make_train_step, softmax_xent


def test_adamw_reduces_quadratic():
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3)}
    ocfg = opt.AdamWConfig(lr=0.1, warmup_steps=1, total_steps=200,
                           weight_decay=0.0)
    state = opt.init(params)

    def loss(p):
        return jnp.sum((p["w"] - target) ** 2)

    for _ in range(150):
        g = jax.grad(loss)(params)
        params, state, _ = opt.update(ocfg, params, g, state)
    assert float(loss(params)) < 1e-2


def test_train_loss_decreases_end_to_end():
    cfg = smoke_config("yi-6b")
    dc = DataConfig(seq_len=32, global_batch=4, seed=0)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    state = opt.init(params)
    ocfg = opt.AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=30)
    step = jax.jit(make_train_step(cfg, ocfg))
    losses = []
    for s in range(12):
        b = {k: jnp.asarray(v) for k, v in synth_batch(cfg, dc, 0).items()}
        params, state, m = step(params, state, b)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.3, losses


def test_microbatching_matches_full_batch():
    cfg = smoke_config("yi-6b").replace(param_dtype="float32")
    dc = DataConfig(seq_len=16, global_batch=4, seed=1)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    ocfg = opt.AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    b = {k: jnp.asarray(v) for k, v in synth_batch(cfg, dc, 0).items()}

    s1 = make_train_step(cfg, ocfg, n_microbatches=1)
    s2 = make_train_step(cfg, ocfg, n_microbatches=2)
    p1, _, m1 = s1(params, opt.init(params), b)
    p2, _, m2 = s2(params, opt.init(params), b)
    for a, c in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(c, np.float32),
                                   rtol=2e-4, atol=2e-5)


def test_masked_loss_ignores_minus_one():
    logits = jnp.zeros((1, 4, 8))
    labels = jnp.asarray([[1, 2, -1, -1]])
    loss, denom = softmax_xent(logits, labels, z_loss=0.0)
    assert float(denom) == 2.0
    np.testing.assert_allclose(float(loss), np.log(8.0), rtol=1e-5)


def test_checkpoint_roundtrip_and_corruption(tmp_path):
    # leaf large enough that a mid-file byte-flip lands in array data
    tree = {"a": jnp.arange(65536, dtype=jnp.float32),
            "b": {"c": jnp.ones((2, 3), jnp.bfloat16)}}
    d = str(tmp_path / "ck")
    ckpt.save(d, 3, tree)
    assert ckpt.latest_step(d) == 3
    back = ckpt.restore(d, 3, tree)
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))
    # corrupt a byte -> restore must fail loudly
    shard = os.path.join(d, "step_00000003", "shard_0.npz")
    data = bytearray(open(shard, "rb").read())
    data[len(data) // 2] ^= 0xFF
    open(shard, "wb").write(bytes(data))
    with pytest.raises(Exception):
        ckpt.restore(d, 3, tree)


def test_checkpoint_resume_determinism(tmp_path):
    """Train 4 steps == train 2, checkpoint, restore, train 2."""
    from repro.launch.train import train
    d = str(tmp_path / "run")
    r1 = train(arch="internvl2-1b", steps=4, seq_len=16, batch=2,
               ckpt_dir=None)
    r2a = train(arch="internvl2-1b", steps=2, seq_len=16, batch=2,
                ckpt_dir=d, ckpt_every=2)
    r2b = train(arch="internvl2-1b", steps=4, seq_len=16, batch=2,
                ckpt_dir=d, ckpt_every=2)
    assert abs(r1["final_loss"] - r2b["final_loss"]) < 5e-2


@given(st.integers(0, 2**31 - 1), st.integers(1, 4))
@settings(max_examples=20, deadline=None)
def test_compression_roundtrip_bounded_error(seed, scale):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(515).astype(np.float32) * scale)
    d, r = comp.compress_roundtrip(x)
    np.testing.assert_allclose(np.asarray(d + r), np.asarray(x), rtol=1e-6,
                               atol=1e-6)
    # max error bounded by scale/127 per block
    amax = float(jnp.abs(x).max())
    assert float(jnp.abs(r).max()) <= amax / 127.0 + 1e-6


def test_compressed_psum_single_device():
    # axis of size 1: compressed psum == identity up to quantization error
    mesh = jax.make_mesh((1,), ("pod",))
    from jax.sharding import PartitionSpec as P
    x = jnp.linspace(-1, 1, 256)
    fn = jax.shard_map(lambda t: comp.compressed_psum(t, "pod"), mesh=mesh,
                       in_specs=(P(),), out_specs=P(), check_vma=False)
    y = fn(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=1e-2)


def test_heartbeat_and_recovery_plan():
    hb = HeartbeatMonitor(n_hosts=4, timeout_s=10)
    for h in range(4):
        hb.beat(h, t=100.0)
    assert hb.alive(now=105.0) == [0, 1, 2, 3]
    assert hb.dead(now=111.0) == [0, 1, 2, 3]
    hb.beat(2, t=110.0)
    assert hb.alive(now=111.0) == [2]

    plan = recovery_plan(n_alive_chips=384, model_parallel=16,
                         chips_per_pod=256)
    pods, data, model = plan["mesh_shape"]
    assert model == 16
    assert pods * data * model <= 384
    assert plan["chips_used"] % (model) == 0


def test_straggler_policy():
    sp = StragglerPolicy(threshold=2.0, evict_after=2)
    for step in range(3):
        for h in range(4):
            sp.record(h, 1.0 if h != 3 else 5.0)
        skip, evict = sp.classify()
        assert 3 in skip
    assert 3 in evict
    assert sp.gradient_scale(4, len(skip)) == pytest.approx(4 / 3)
