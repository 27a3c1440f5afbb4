"""Runtime substrate: data pipeline, checkpointing, fault tolerance,
gradient compression, straggler policies, sharding rules, optimizer."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.checkpoint import AsyncCheckpointer, latest_step, restore, save
from repro.data import DataConfig, DataIterator, global_batch_at, shard_batch_at
from repro.optim import adamw
from repro.runtime import sharding as shd
from repro.runtime.compression import (compressed_psum, dequantize_int8,
                                       quantize_int8)
from repro.runtime.straggler import StepWatchdog, StragglerSim, WatchdogConfig


# ------------------------------------------------------------------ #
# data pipeline                                                        #
# ------------------------------------------------------------------ #
def test_data_deterministic_and_resumable():
    cfg = DataConfig(vocab_size=997, seq_len=16, global_batch=8, seed=3)
    a = [next(DataIterator(cfg, start_step=k))["tokens"] for k in range(5)]
    it = DataIterator(cfg)
    b = [next(it)["tokens"] for _ in range(5)]
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    # resume from checkpointed cursor
    st = it.state_dict()
    it2 = DataIterator.from_state(cfg, st, shard=0, n_shards=1)
    np.testing.assert_array_equal(next(it2)["tokens"], next(it)["tokens"])


def test_data_shard_layout_invariance():
    """Global stream content is invariant to the DP shard layout."""
    cfg = DataConfig(vocab_size=50_000, seq_len=8, global_batch=16)
    g = global_batch_at(cfg, step=7)
    for n_shards in (1, 2, 4, 8):
        parts = [shard_batch_at(cfg, 7, s, n_shards)["tokens"]
                 for s in range(n_shards)]
        np.testing.assert_array_equal(np.concatenate(parts), g["tokens"])


def test_data_targets_shifted():
    cfg = DataConfig(vocab_size=101, seq_len=12, global_batch=4)
    b = global_batch_at(cfg, 0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["targets"][:, :-1])


# ------------------------------------------------------------------ #
# checkpointing                                                        #
# ------------------------------------------------------------------ #
def _tree(seed):
    k = jax.random.PRNGKey(seed)
    return {"a": jax.random.normal(k, (4, 8)),
            "b": {"c": jnp.arange(5, dtype=jnp.int32)}}


def test_checkpoint_roundtrip_and_retention(tmp_path):
    d = str(tmp_path)
    for s in (1, 2, 3, 4, 5):
        save(d, s, _tree(s), keep=2)
    assert latest_step(d) == 5
    steps = sorted(int(x.split("_")[1]) for x in os.listdir(d))
    assert steps == [4, 5]  # retention
    got, meta = restore(d, _tree(0))
    for l1, l2 in zip(jax.tree.leaves(got), jax.tree.leaves(_tree(5))):
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


def test_checkpoint_integrity_detects_corruption(tmp_path):
    d = str(tmp_path)
    save(d, 1, _tree(1))
    path = os.path.join(d, "step_00000001", "arrays.npz")
    with open(path, "r+b") as f:
        f.seek(200)
        f.write(b"\xde\xad\xbe\xef")
    with pytest.raises(Exception):
        restore(d, _tree(0))


def test_async_checkpointer(tmp_path):
    d = str(tmp_path)
    ck = AsyncCheckpointer(d, keep=2)
    for s in (10, 20):
        ck.save(s, _tree(s), extra={"step": s})
    ck.wait()
    assert latest_step(d) == 20
    _, meta = restore(d, _tree(0))
    assert meta["extra"]["step"] == 20


def test_ft_resume_bitwise_identical(tmp_path):
    """Kill-and-resume reproduces the uninterrupted run exactly."""
    from repro.configs import ARCHS, reduced
    from repro.launch.train import train
    cfg = reduced(ARCHS["qwen1.5-0.5b"]).replace(
        dtype="float32", num_layers=2)
    kw = dict(steps=6, global_batch=2, seq_len=16, ckpt_every=2,
              log_fn=lambda *_: None)
    ref = train(cfg, **kw)                        # uninterrupted
    d = str(tmp_path / "ck")
    train(cfg, ckpt_dir=d, run_steps=3, **kw)     # preempted after 3
    out = train(cfg, ckpt_dir=d, **kw)            # resume to 6
    for l1, l2 in zip(jax.tree.leaves(ref["params"]),
                      jax.tree.leaves(out["params"])):
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    assert out["losses"][-1] == ref["losses"][-1]


def test_elastic_reshard_subprocess(tmp_path):
    """Save on a (2,4) mesh, restore on (4,2) — different layout."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.checkpoint import save, restore
        d = sys.argv[1]
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
        xs = jax.device_put(x, NamedSharding(mesh, P("data", "model")))
        save(d, 1, {"x": xs})
        mesh2 = jax.make_mesh((4, 2), ("data", "model"))
        sh2 = {"x": NamedSharding(mesh2, P("data", "model"))}
        got, _ = restore(d, {"x": x}, shardings=sh2)
        np.testing.assert_array_equal(np.asarray(got["x"]), np.asarray(x))
        assert got["x"].sharding.mesh.shape["data"] == 4
        print("ELASTIC_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script, str(tmp_path)],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert "ELASTIC_OK" in r.stdout, r.stderr[-2000:]


# ------------------------------------------------------------------ #
# gradient compression                                                 #
# ------------------------------------------------------------------ #
def test_quantize_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(513,)).astype(np.float32)) * 3.0
    q, scale, err = quantize_int8(x)
    deq = dequantize_int8(q, scale, x.shape, x.dtype)
    # error bounded by half an lsb per element
    assert float(jnp.max(jnp.abs(x - deq))) <= float(scale.max()) * 0.51
    np.testing.assert_allclose(np.asarray(deq + err), np.asarray(x),
                               rtol=1e-6, atol=1e-6)


def test_error_feedback_reduces_bias():
    """With error feedback, the *running mean* of compressed grads
    converges to the true gradient (unbiased in the long run)."""
    rng = np.random.default_rng(1)
    g_true = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
    err = jnp.zeros_like(g_true)
    acc = jnp.zeros_like(g_true)
    n = 50
    for _ in range(n):
        q, scale, err = quantize_int8(g_true + err)
        deq = dequantize_int8(q, scale, g_true.shape, g_true.dtype)
        acc = acc + deq
    np.testing.assert_allclose(np.asarray(acc / n), np.asarray(g_true),
                               atol=2e-3)


def test_compressed_psum_single_axis():
    mesh = jax.make_mesh((1,), ("pod",))
    x = jnp.arange(32, dtype=jnp.float32) / 7.0
    err0 = jnp.zeros_like(x)
    f = jax.shard_map(lambda a, e: compressed_psum(a, "pod", e),
                  mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()))
    out, err = f(x, err0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), atol=2e-2)


# ------------------------------------------------------------------ #
# stragglers                                                           #
# ------------------------------------------------------------------ #
def test_watchdog_flags_outliers():
    wd = StepWatchdog(WatchdogConfig(window=20, slow_factor=2.0,
                                     min_samples=5))
    for _ in range(10):
        wd.observe(0.1)
    assert wd.observe(0.5) is True
    assert wd.observe(0.11) is False


def test_straggler_policies_improve_tail():
    sim = StragglerSim(n_workers=128, tail_prob=0.02, tail_factor=10)
    sync = sim.run(400, policy="sync")
    drop = sim.run(400, policy="drop", drop_frac=0.05)
    backup = sim.run(400, policy="backup", backup_frac=0.05)
    assert drop["p99_ms"] < sync["p99_ms"]
    assert backup["mean_ms"] <= sync["mean_ms"]
    assert drop["throughput_rel"] > sync["throughput_rel"]


# ------------------------------------------------------------------ #
# sharding rules                                                       #
# ------------------------------------------------------------------ #
def test_fit_spec_divisibility_fallback():
    mesh = jax.make_mesh((1,), ("model",))  # size-1 axis -> replicate
    spec = shd.fit_spec((10, 64), ("model", "model"), mesh)
    assert spec == P(None, None)


def test_param_specs_cover_model():
    from repro.configs import ARCHS, reduced
    from repro.models import init_params
    cfg = reduced(ARCHS["mixtral-8x22b"]).replace(dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    specs = shd.param_specs(params, None,
                            stacked_prefixes=("decoder", "encoder"))
    n_leaves = len(jax.tree.leaves(params))
    n_specs = len(jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)))
    assert n_leaves == n_specs


def test_adamw_matches_reference_step():
    cfg = adamw.AdamWConfig(lr=0.1, b1=0.9, b2=0.99, weight_decay=0.0,
                            clip_norm=1e9, warmup_steps=1, total_steps=2,
                            min_lr_frac=1.0, clip_latent=False)
    p = {"w": jnp.asarray([1.0, -2.0])}
    g = {"w": jnp.asarray([0.5, 0.5])}
    st = adamw.init(p)
    newp, st2, _ = adamw.apply_updates(p, st, g, cfg)
    m = 0.1 * 0.5 / (1 - 0.9)
    v = 0.01 * 0.25 / (1 - 0.99)
    expect = np.asarray([1.0, -2.0]) - 0.1 * (m / (np.sqrt(v) + 1e-8))
    np.testing.assert_allclose(np.asarray(newp["w"]), expect, rtol=1e-5)
