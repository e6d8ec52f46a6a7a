"""Training substrate tests: optimizer, checkpointing (fault tolerance,
elastic restore), compression, trainer loop, sampler, serving."""
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist import compat
from repro.train import optimizer as opt
from repro.runtime.checkpoint import CheckpointManager
from repro.train.compression import init_residuals, psum_compressed
from repro.train.trainer import TrainLoopConfig, run_training


def _quad_problem():
    target = jnp.asarray(np.random.default_rng(0).normal(size=(8,)))

    def loss_fn(p, batch):
        return jnp.sum((p["w"] - target) ** 2) + 0.0 * batch.sum()

    return target, loss_fn


def test_adamw_converges():
    target, loss_fn = _quad_problem()
    params = {"w": jnp.zeros((8,))}
    cfg = opt.OptConfig(lr=0.05, weight_decay=0.0, warmup_steps=5,
                        total_steps=300)
    state = opt.init(params, cfg)
    for _ in range(300):
        g = jax.grad(loss_fn)(params, jnp.zeros(()))
        params, state, _ = opt.update(g, state, params, cfg)
    assert float(jnp.abs(params["w"] - target).max()) < 0.05


def test_clip_by_global_norm():
    g = {"a": jnp.full((4,), 10.0)}
    clipped, gn = opt.clip_by_global_norm(g, 1.0)
    assert abs(float(opt.global_norm(clipped)) - 1.0) < 1e-5
    assert float(gn) == 20.0


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": [jnp.ones((2,), jnp.int32), jnp.zeros(())]}
    mgr.save(10, tree)
    mgr.save(20, jax.tree.map(lambda x: x + 1, tree))
    restored, step = mgr.restore(tree)
    assert step == 20
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(tree["a"]) + 1)
    # keep=2 retention
    mgr.save(30, tree)
    assert mgr.steps() == [20, 30]


def test_checkpoint_corruption_fallback(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=5)
    tree = {"a": jnp.arange(4.0)}
    mgr.save(1, tree)
    mgr.save(2, jax.tree.map(lambda x: x * 2, tree))
    # corrupt the newest data file
    (mgr._step_dir(2) / "data.bin").write_bytes(b"garbage garbage!")
    restored, step = mgr.restore(tree)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.arange(4.0))


def test_checkpoint_kill_mid_save(tmp_path):
    """SIGKILL the process in the middle of ``save``: the store must keep
    the previous step fully restorable, never surface the torn one, and a
    subsequent save of the same step must succeed (stale tmp cleanup)."""
    import os
    import signal
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent(f"""
        import os, signal
        import numpy as np
        from repro.runtime import checkpoint as cp

        mgr = cp.CheckpointManager({str(tmp_path)!r}, keep=5)
        mgr.save(1, {{"w": np.arange(64.0)}})
        orig = cp.CheckpointManager._write_data
        def dying_write(self, tmp, flat, manifest):
            orig(self, tmp, flat, manifest)
            os.kill(os.getpid(), signal.SIGKILL)   # die before publish
        cp.CheckpointManager._write_data = dying_write
        mgr.save(2, {{"w": np.arange(64.0) * 2}})
    """)
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == -signal.SIGKILL, proc.stderr[-2000:]
    # the kill left a staging dir behind, but it is invisible to steps()
    assert any(p.name.startswith(".tmp_step_")
               for p in tmp_path.iterdir()), "expected a torn staging dir"
    mgr = CheckpointManager(tmp_path, keep=5)
    assert mgr.steps() == [1]
    restored, step = mgr.restore({"w": jnp.zeros((64,))})
    assert step == 1
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.arange(64.0))
    # retrying the interrupted step reclaims the stale tmp dir
    mgr.save(2, {"w": jnp.arange(64.0) * 2})
    assert mgr.steps() == [1, 2]
    restored, step = mgr.restore({"w": jnp.zeros((64,))})
    assert step == 2


def test_checkpoint_overwrite_same_step(tmp_path):
    """Re-saving an existing step swaps it atomically — the new data wins
    and no staging/trash dirs are left behind."""
    mgr = CheckpointManager(tmp_path, keep=5)
    mgr.save(7, {"w": jnp.zeros((4,))})
    mgr.save(7, {"w": jnp.ones((4,))})
    restored, step = mgr.restore({"w": jnp.zeros((4,))})
    assert step == 7
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.ones(4))
    assert mgr.steps() == [7]
    assert not [p for p in tmp_path.iterdir() if p.name.startswith(".")]


def test_checkpoint_trash_orphan_reclaimed(tmp_path):
    """A .trash_step dir orphaned by a kill between the two swap renames
    is reclaimed by the next save, whatever step it saves."""
    mgr = CheckpointManager(tmp_path, keep=5)
    mgr.save(1, {"w": jnp.zeros((4,))})
    (tmp_path / ".trash_step_0000000001").mkdir()
    (tmp_path / ".trash_step_0000000001" / "data.bin").write_bytes(b"old")
    mgr.save(2, {"w": jnp.ones((4,))})
    assert not [p for p in tmp_path.iterdir()
                if p.name.startswith(".trash_")]
    assert mgr.steps() == [1, 2]


def test_checkpoint_template_mismatch_raises(tmp_path):
    """An intact checkpoint missing a template field is a structural
    mismatch and must raise, not fall back to 'no restorable checkpoint'."""
    mgr = CheckpointManager(tmp_path, keep=5)
    mgr.save(1, {"w": jnp.zeros((4,))})
    with pytest.raises(KeyError, match="does not match"):
        mgr.restore({"w": jnp.zeros((4,)), "extra": jnp.zeros(())})


def test_checkpoint_truncated_data_falls_back(tmp_path):
    """A torn data.bin (short write) must fall back to the previous step
    instead of crashing the resume."""
    mgr = CheckpointManager(tmp_path, keep=5)
    tree = {"a": jnp.arange(8.0)}
    mgr.save(1, tree)
    mgr.save(2, jax.tree.map(lambda x: x + 1, tree))
    data = mgr._step_dir(2) / "data.bin"
    data.write_bytes(data.read_bytes()[:5])     # not even one element
    restored, step = mgr.restore(tree)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(restored["a"]), np.arange(8.0))


def test_checkpoint_elastic_reshard(tmp_path):
    """Restore onto explicit shardings (mesh-size change simulation)."""
    mgr = CheckpointManager(tmp_path)
    tree = {"w": jnp.arange(16, dtype=jnp.float32)}
    mgr.save(5, tree)
    mesh = compat.make_mesh((1,), ("d",))
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = {"w": NamedSharding(mesh, P("d"))}
    restored, _ = mgr.restore(tree, shardings=sh)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.arange(16.0))
    assert restored["w"].sharding == sh["w"]


def test_trainer_resume(tmp_path):
    target, loss_fn = _quad_problem()
    cfg = opt.OptConfig(lr=0.05, weight_decay=0.0, warmup_steps=0,
                        total_steps=100)

    def step_fn(params, state, batch):
        loss, g = jax.value_and_grad(loss_fn)(params, batch)
        params, state, stats = opt.update(g, state, params, cfg)
        return params, state, loss, stats["grad_norm"]

    def batches():
        while True:
            yield jnp.zeros(())

    params = {"w": jnp.zeros((8,))}
    state = opt.init(params, cfg)
    tcfg = TrainLoopConfig(total_steps=40, ckpt_every=10,
                           ckpt_dir=str(tmp_path), log_every=100)
    p1, s1, _ = run_training(step_fn, params, state, batches(), tcfg,
                             log=lambda *_: None)
    # "crash" and resume: the loop must pick up from step 40 and finish 60
    tcfg2 = TrainLoopConfig(total_steps=60, ckpt_every=10,
                            ckpt_dir=str(tmp_path), log_every=100)
    p2, s2, hist = run_training(step_fn, params, state, batches(), tcfg2,
                                log=lambda *_: None)
    assert int(s2["step"]) == 60
    assert hist[0]["step"] >= 40      # resumed, not restarted


def test_compression_error_feedback():
    """int8 EF-compression: single-worker psum == identity + residual→0."""
    mesh = compat.make_mesh((1,), ("d",))
    from jax.sharding import PartitionSpec as P

    g = {"w": jnp.asarray(np.random.default_rng(1).normal(size=(64,))
                          .astype(np.float32))}
    r = init_residuals(g)

    def body(g, r):
        return psum_compressed(g, r, "d")

    out, new_r = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P())))(g, r)
    # quantization error bounded by scale/2 and captured in the residual
    scale = float(jnp.abs(g["w"]).max()) / 127.0
    assert float(jnp.abs(out["w"] - g["w"]).max()) <= scale
    np.testing.assert_allclose(np.asarray(out["w"] + new_r["w"]),
                               np.asarray(g["w"]), rtol=1e-5, atol=1e-6)


def test_neighbor_sampler():
    from repro.graphs.rmat import rmat
    from repro.graphs.sampler import NeighborSampler

    g = rmat(9, 8, seed=1)
    s = NeighborSampler(g, fanout=(5, 3), seed=0)
    batch = s.sample(np.array([3, 7, 11]))
    assert batch["nodes"].shape == (3, 1 + 5 + 15)
    assert batch["edge_index"].shape == (3, 2, 2 * 20)
    # edges reference sampled-local node slots only
    assert (batch["edge_index"] < s.nodes_cap).all()
    # masked edges consistent with counts
    assert (batch["edge_mask"].sum(1) <= 2 * 20).all()


def test_serving_loop():
    from repro.models.lm.transformer import LMConfig, init_params
    from repro.models.lm.serve import ServeConfig, serve_batch

    cfg = LMConfig(name="t", n_layers=2, d_model=32, n_heads=4,
                   n_kv_heads=2, d_ff=64, vocab=64, dtype=jnp.float32,
                   remat="none")
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = np.random.default_rng(0).integers(0, 64, (2, 5)).astype(
        np.int32)
    out = serve_batch(params, prompts, cfg,
                      ServeConfig(max_new_tokens=4, cache_len=16))
    assert out.shape == (2, 9)
    assert (out[:, :5] == prompts).all()
    assert (out >= 0).all() and (out < 64).all()
