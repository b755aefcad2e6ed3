"""Integration: end-to-end training with Chipmink checkpointing, frozen
params → ASCC/AVF savings, fault-tolerant restart, elastic re-shard,
straggler detection, gradient compression, async vs sync equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import Chipmink, LGA, MemoryStore
from repro.core.ascc import readonly_state_leaves
from repro.launch.train import snapshot_of, train
from repro.models.model import api, init_model_params, model_logical_axes
from repro.runtime.fault_tolerance import (StragglerMonitor,
                                           TrainingSupervisor,
                                           elastic_restore)
from repro.train.data import TokenPipeline
from repro.train.grad_compress import (compressed_psum, quantize,
                                       quantize_dequantize)
from repro.train.optimizer import OptConfig, opt_init
from repro.train.train_step import init_train_state, make_train_step


@pytest.mark.slow
def test_loss_decreases():
    out = train("qwen1.5-0.5b", steps=30, save_every=10, global_batch=4,
                seq_len=64, log=False)
    losses = out["losses"]
    assert np.mean(losses[-5:]) < np.mean(losses[:5])


@pytest.mark.slow
def test_checkpoint_resume_bit_exact():
    """Stop at step 20, resume from the Chipmink checkpoint, and verify
    the resumed run reproduces the uninterrupted run's loss curve (data
    cursor rides in the checkpoint)."""
    out = train("qwen1.5-0.5b", steps=30, save_every=10, global_batch=4,
                seq_len=64, log=False, async_save=False)
    ref_losses = out["losses"]

    out2 = train("qwen1.5-0.5b", steps=20, save_every=10, global_batch=4,
                 seq_len=64, log=False, async_save=False)
    ck: Chipmink = out2["chipmink"]
    loaded = ck.load()
    cfg = get_config("qwen1.5-0.5b").reduced()
    opt_cfg = OptConfig(lr=1e-3)
    state = {"params": jax.tree.map(jnp.asarray, loaded["params"]),
             "opt": jax.tree.map(jnp.asarray, loaded["opt"]),
             "step": jnp.asarray(loaded["step"], jnp.int32)}
    pipe = TokenPipeline(cfg.vocab, 4, 64)
    pipe.restore(loaded["data"])
    step_fn = jax.jit(make_train_step(cfg, opt_cfg, remat=False))
    resumed = []
    for i in range(10):
        batch = {k: jnp.asarray(v) for k, v in pipe.next_batch().items()}
        state, metrics = step_fn(state, batch)
        resumed.append(float(metrics["nll"]))
    np.testing.assert_allclose(resumed, ref_losses[20:], rtol=1e-4, atol=1e-4)


def test_frozen_params_identity_and_savings():
    """Frozen subtrees: (1) step returns them bit-identical, (2) ASCC
    proves it, (3) Chipmink writes ~nothing for them after save 1."""
    cfg = get_config("qwen1.5-0.5b").reduced()
    frozen = ("params/layers/0", "params/embed")
    opt_cfg = OptConfig(lr=1e-3)
    params = init_model_params(cfg, jax.random.key(0))
    state = init_train_state(cfg, params, opt_cfg)
    step_fn = jax.jit(make_train_step(cfg, opt_cfg, frozen=frozen,
                                      remat=False))
    pipe = TokenPipeline(cfg.vocab, 4, 64)
    batch = {k: jnp.asarray(v) for k, v in pipe.next_batch().items()}

    ro = readonly_state_leaves(step_fn, state, batch)
    assert any(p.startswith("params/layers/0") for p in ro)
    assert any(p.startswith("params/embed") for p in ro)

    new_state, _ = step_fn(state, batch)
    assert np.array_equal(np.asarray(new_state["params"]["embed"]),
                          np.asarray(state["params"]["embed"]))

    ck = Chipmink(MemoryStore(), LGA(), chunk_bytes=1 << 16)
    pipe2 = TokenPipeline(cfg.vocab, 4, 64)
    ck.save(snapshot_of(state, pipe2))
    state2, _ = step_fn(state, batch)
    ck.save(snapshot_of(state2, pipe2), readonly_paths=ro)
    s = ck.save_stats[-1]
    # frozen embedding (the biggest tensor) was neither hashed nor written
    assert s["n_active_leaves"] < s["n_leaves"]
    full_bytes = ck.save_stats[0]["bytes_written"]
    assert s["bytes_written"] < full_bytes


def test_supervisor_restart_with_injected_failures():
    cfg = get_config("qwen1.5-0.5b").reduced()
    opt_cfg = OptConfig(lr=1e-3)
    params = init_model_params(cfg, jax.random.key(0))
    state = init_train_state(cfg, params, opt_cfg)
    pipe = TokenPipeline(cfg.vocab, 4, 64)
    step_fn = jax.jit(make_train_step(cfg, opt_cfg, remat=False))

    def do_step(st, i):
        batch = {k: jnp.asarray(v) for k, v in pipe.next_batch().items()}
        new, _ = step_fn(st, batch)
        return new

    def make_snapshot(st):
        return snapshot_of(st, pipe)

    def restore(loaded):
        pipe.restore(loaded["data"])
        return {"params": jax.tree.map(jnp.asarray, loaded["params"]),
                "opt": jax.tree.map(jnp.asarray, loaded["opt"]),
                "step": jnp.asarray(loaded["step"], jnp.int32)}

    ck = Chipmink(MemoryStore(), LGA(), chunk_bytes=1 << 16)
    sup = TrainingSupervisor(ck, save_every=5)
    final, stats = sup.run(state, 20, do_step, make_snapshot=make_snapshot,
                           restore=restore, fail_at={7, 13})
    assert stats["failures"] == 2
    assert int(np.asarray(final["step"])) == 20


def test_elastic_restore_single_device():
    """A checkpoint written by any mesh restores onto the local mesh."""
    from repro.launch.mesh import make_local_mesh
    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_model_params(cfg, jax.random.key(0))
    ck = Chipmink(MemoryStore(), chunk_bytes=1 << 16)
    t = ck.save({"params": params})
    loaded = ck.load(time_id=t)
    mesh = make_local_mesh()
    axes = model_logical_axes(cfg)
    restored = elastic_restore(loaded["params"], mesh, axes)
    ref, got = jax.tree.leaves(params), jax.tree.leaves(restored)
    for a, b in zip(ref, got):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))


def test_straggler_monitor():
    mon = StragglerMonitor(window=8, threshold=1.5, min_samples=4)
    rng = np.random.default_rng(0)
    for step in range(10):
        for host in range(8):
            base = 1.0 + 0.05 * rng.standard_normal()
            if host == 3:
                base *= 2.5  # slow host
            mon.record(host, base)
    rep = mon.report()
    assert rep.stragglers == [3]
    assert mon.healthy_hosts(range(8)) == [0, 1, 2, 4, 5, 6, 7]


def test_grad_quantization_error_feedback():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal(1000), jnp.float32)
    ghat, ef = quantize_dequantize(g, None)
    rel = float(jnp.linalg.norm(ghat - g) / jnp.linalg.norm(g))
    assert rel < 0.02
    # residual is exactly what was lost
    np.testing.assert_allclose(np.asarray(ef, np.float32),
                               np.asarray(g - ghat), atol=1e-2)


def test_compressed_psum_shardmap():
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("d",), devices=jax.devices()[:1])
    from jax.sharding import PartitionSpec as P
    x = jnp.asarray(np.random.default_rng(0).standard_normal(256),
                    jnp.float32)
    f = jax.shard_map(lambda v: compressed_psum(v, "d"), mesh=mesh,
                  in_specs=P(), out_specs=P())
    y = f(x)
    rel = float(jnp.linalg.norm(y - x) / jnp.linalg.norm(x))
    assert rel < 0.02


@pytest.mark.slow
def test_grad_compress_training_converges():
    out = train("qwen1.5-0.5b", steps=20, save_every=20, global_batch=4,
                seq_len=64, log=False, grad_compress=True)
    assert np.isfinite(out["losses"]).all()
    assert np.mean(out["losses"][-5:]) < np.mean(out["losses"][:5]) * 1.2


def test_serve_session_snapshots():
    from repro.launch.serve import serve
    out = serve("qwen1.5-0.5b", n_requests=2, gen_tokens=8, cache_len=32,
                save_every=4, log=False)
    stats = out["snap_stats"]
    assert len(stats) >= 2
    # later session snapshots are deltas: much smaller than the first
    assert stats[-1]["bytes_written"] < stats[0]["bytes_written"]
