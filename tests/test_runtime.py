"""Runtime substrate tests: sharding rules, checkpoint/eleastic, data
pipeline, optimizer, compression."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (AsyncCheckpointer, latest_step,
                              restore_checkpoint, save_checkpoint,
                              checkpoint_bytes)
from repro.data import SyntheticTokens
from repro.optim import adamw, clip_by_global_norm
from repro.optim.compression import int8_roundtrip, topk_error_feedback
from repro.runtime import elastic, sharding


class FakeMesh:
    """Duck-typed mesh: .shape mapping only (what the resolver reads)."""
    def __init__(self, **axes):
        self.shape = dict(axes)
        self.axis_names = tuple(axes)


# -- sharding rules ---------------------------------------------------------

def test_spec_divisibility_fallback():
    mesh = FakeMesh(data=16, model=16)
    # heads=12 not divisible by 16 → None; mlp=8960 divisible → model
    spec = sharding.spec_for(("embed", "heads", "head_dim"),
                             (1536, 12, 128), mesh)
    assert spec == jax.sharding.PartitionSpec("data", None, None)
    spec = sharding.spec_for(("embed", "mlp"), (1536, 8960), mesh)
    assert spec == jax.sharding.PartitionSpec("data", "model")


def test_spec_no_axis_reuse():
    mesh = FakeMesh(data=16, model=16)
    # experts takes model; mlp then must NOT reuse model
    spec = sharding.spec_for(("experts", "embed", "mlp"),
                             (16, 6144, 10752), mesh)
    assert spec == jax.sharding.PartitionSpec("model", "data", None)


def test_cache_batch_vs_seq_context_dependence():
    """Batched decode shards the cache on batch; long-context (batch=1)
    automatically falls through to sequence sharding (SP)."""
    mesh = FakeMesh(data=16, model=16)
    batched = sharding.spec_for(
        ("cache_batch", "cache_seq", "kv_heads", "head_dim"),
        (128, 32768, 8, 128), mesh)
    assert batched[0] == "data" and batched[1] is None
    longctx = sharding.spec_for(
        ("cache_batch", "cache_seq", "kv_heads", "head_dim"),
        (1, 524288, 4, 256), mesh)
    assert longctx[0] is None and longctx[1] == "data"


def test_multi_axis_batch():
    mesh = FakeMesh(pod=2, data=16, model=16)
    spec = sharding.spec_for(("act_batch", "act_seq"), (256, 4096), mesh)
    assert spec[0] == ("pod", "data")


# -- checkpoint + elastic ---------------------------------------------------

def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return dict(w=jax.random.normal(k, (8, 4)),
                step=jnp.zeros((), jnp.int32))


def test_checkpoint_roundtrip(tmp_path):
    state = _state()
    save_checkpoint(str(tmp_path), 7, state)
    assert latest_step(str(tmp_path)) == 7
    restored = restore_checkpoint(str(tmp_path), 7, state)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(state["w"]))


def test_checkpoint_atomic_no_tmp_left(tmp_path):
    save_checkpoint(str(tmp_path), 1, _state())
    assert not [d for d in os.listdir(tmp_path) if d.startswith(".tmp")]


def test_checkpoint_bytes_matches_manifest(tmp_path):
    state = _state()
    b = checkpoint_bytes(state)
    assert b == 8 * 4 * 4 + 4


def test_async_checkpointer(tmp_path):
    ck = AsyncCheckpointer(str(tmp_path), every=2)
    st = _state()
    assert not ck.maybe_save(1, st)
    assert ck.maybe_save(2, st)
    ck.wait()
    assert latest_step(str(tmp_path)) == 2


def test_elastic_restart_exactly_recovers(tmp_path):
    """Training with injected failures ends in EXACTLY the same state as an
    uninterrupted run (checkpoint/restart is bitwise at step granularity)."""
    def step_fn(state, batch, step):
        return dict(w=state["w"] + batch,
                    step=state["step"] + 1)

    def batch_fn(step):
        return jnp.float32(step + 1)

    clean = elastic.run_elastic(
        _state(), step_fn, batch_fn, num_steps=12,
        ckpt_dir=str(tmp_path / "a"), ckpt_every=3)
    faulty = elastic.run_elastic(
        _state(), step_fn, batch_fn, num_steps=12,
        ckpt_dir=str(tmp_path / "b"), ckpt_every=3,
        injector=elastic.FailureInjector(fail_after_steps=(5, 9)))
    assert faulty["restarts"] == 2
    np.testing.assert_array_equal(np.asarray(clean["state"]["w"]),
                                  np.asarray(faulty["state"]["w"]))


def test_watchdog_flags_stragglers():
    wd = elastic.StepWatchdog(deadline_s=0.1)
    assert not wd.observe(0.05)
    assert wd.observe(0.5)


# -- persistent compile cache -----------------------------------------------

@pytest.mark.parametrize("outside", [False, True])
def test_compile_cache_goes_where_the_environment_says(monkeypatch, tmp_path,
                                                       outside):
    """``JAX_COMPILATION_CACHE_DIR`` wins and no directory is set in code;
    without it the cache sits at the fixed in-checkout path. Every program
    is cached either way. The config is restored before anything compiles,
    so the suite itself never writes a cache."""
    from repro.runtime import platform

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    if outside:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        used = platform.use_compile_cache()
        now = {k: getattr(jax.config, k) for k in keys}
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    expect = str(tmp_path) if outside else os.path.join(root, ".jax_cache")
    assert used == expect
    assert now["jax_compilation_cache_dir"] == \
        (saved["jax_compilation_cache_dir"] if outside else expect)
    assert now["jax_persistent_cache_min_compile_time_secs"] == 0.0


# -- data pipeline ----------------------------------------------------------

def test_data_deterministic_and_resumable():
    src = SyntheticTokens(vocab=128, seq_len=16, global_batch=4, seed=0)
    b5a = src.batch(5)
    b5b = src.batch(5)
    np.testing.assert_array_equal(np.asarray(b5a["tokens"]),
                                  np.asarray(b5b["tokens"]))
    # labels are next-token shifted
    assert b5a["tokens"].shape == (4, 16)
    b6 = src.batch(6)
    assert not np.array_equal(np.asarray(b5a["tokens"]),
                              np.asarray(b6["tokens"]))


# -- optimizer + compression -------------------------------------------------

def test_adamw_minimizes_quadratic():
    opt = adamw(lr=lambda s: 0.1, weight_decay=0.0)
    params = dict(w=jnp.array([3.0, -2.0]))
    state = opt.init(params)
    for _ in range(60):
        grads = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        params, state, _ = opt.update(grads, state, params)
    assert float(jnp.abs(params["w"]).max()) < 0.15


def test_clip_by_global_norm():
    g = dict(a=jnp.ones((10,)) * 10.0)
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(np.sqrt(1000.0))
    n2 = float(jnp.linalg.norm(clipped["a"]))
    assert n2 == pytest.approx(1.0, rel=1e-5)


def test_int8_roundtrip_error_bound():
    g = dict(w=jax.random.normal(jax.random.PRNGKey(0), (256,)))
    out = int8_roundtrip(g, jax.random.PRNGKey(1))
    scale = float(jnp.abs(g["w"]).max()) / 127.0
    assert float(jnp.abs(out["w"] - g["w"]).max()) <= scale * 1.01


def test_topk_error_feedback_conserves_mass():
    g = dict(w=jax.random.normal(jax.random.PRNGKey(0), (100,)))
    sent, res = topk_error_feedback(g, None, frac=0.1)
    np.testing.assert_allclose(np.asarray(sent["w"] + res["w"]),
                               np.asarray(g["w"]), atol=1e-6)
    assert int((np.asarray(sent["w"]) != 0).sum()) <= 11
