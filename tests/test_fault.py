"""Runtime tests: preemption, stragglers, heartbeat, checkpoint
retention/commit protocol, elastic data replay, compile-cache location."""
import os
import signal
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import store
from repro.data.synthetic import TokenStream
from repro.runtime import compile_cache as cc
from repro.runtime import fault


def test_preemption_guard_triggers_save(tmp_path):
    saves = []

    def step_fn(state, batch):
        if state == 3:  # simulate SIGTERM mid-run
            os.kill(os.getpid(), signal.SIGTERM)
        return state + 1, {}

    state, last, reason = fault.train_loop(
        step_fn, 0, lambda i: i,
        start_step=0, num_steps=100, save_every=50,
        save_fn=lambda s, st: saves.append(s),
    )
    assert reason == "preempted"
    assert last == 4           # stopped right after the signalled step
    assert saves == [4]        # checkpointed immediately, lost nothing


def test_train_loop_completes_and_saves(tmp_path):
    saves = []
    state, last, reason = fault.train_loop(
        lambda s, b: (s + 1, {}), 0, lambda i: i,
        start_step=0, num_steps=7, save_every=3,
        save_fn=lambda s, st: saves.append(s),
    )
    assert reason == "done" and last == 7
    assert saves == [3, 6, 7]  # periodic + final partial


def test_straggler_monitor():
    mon = fault.StragglerMonitor(window=8, threshold=1.5)
    for step in range(8):
        for host in range(4):
            mon.record(host, 1.0 if host != 2 else 2.5)
    assert mon.stragglers() == [2]
    assert mon.mitigation(2) != "none"
    assert mon.mitigation(0) == "none"


def test_heartbeat(tmp_path):
    path = str(tmp_path / "hb.json")
    hb = fault.Heartbeat(path, interval_s=0.05).start()
    time.sleep(0.12)
    hb.stop()
    assert fault.Heartbeat.age(path) < 5.0
    assert fault.Heartbeat.age(str(tmp_path / "missing.json")) == float("inf")


def test_commit_marker_protocol(tmp_path):
    """Uncommitted (crashed mid-write) checkpoints are invisible."""
    root = str(tmp_path / "ck")
    state = {"w": jnp.arange(8.0)}
    store.save(root, 1, state)
    store.save(root, 2, state)
    # simulate a crash: step_3 dir exists but no commit marker
    os.makedirs(os.path.join(root, "step_000000003"))
    assert store.committed_steps(root) == [1, 2]
    assert store.latest_step(root) == 2


def test_retention(tmp_path):
    root = str(tmp_path / "ck")
    state = {"w": jnp.arange(4.0)}
    for s in range(1, 6):
        store.save(root, s, state, keep=2)
    assert store.committed_steps(root) == [4, 5]


def test_async_save(tmp_path):
    root = str(tmp_path / "ck")
    state = {"w": jnp.arange(16.0)}
    t = store.save_async(root, 7, state)
    store.wait_pending()
    restored, at = store.restore(root, state)
    assert at == 7
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.arange(16.0))


def test_elastic_data_replay():
    """Changing dp size across a restart must not duplicate/skip tokens
    within a step: the union of shards equals the global batch either way."""
    ts = TokenStream(vocab_size=1000, seq_len=16, global_batch=8)
    full = ts.batch(5, shard=0, num_shards=1)["tokens"]
    for dp in (2, 4):
        parts = [ts.batch(5, shard=i, num_shards=dp)["tokens"] for i in range(dp)]
        merged = np.concatenate(parts, axis=0)
        assert merged.shape == full.shape
        # deterministic per (step, shard, num_shards); shards are disjoint rows
        assert len({p.tobytes() for p in parts}) == dp
    plan = fault.ElasticPlan(resume_step=5, old_dp=2, new_dp=4)
    assert plan.shard_for(6) == (2, 4)


# ---------------------------------------------------------------------------
# Persistent compile cache location
# ---------------------------------------------------------------------------

@pytest.fixture
def cache_dir_option():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_uses_env_dir_and_sets_nothing(monkeypatch,
                                                     cache_dir_option):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(cc.ENV, "/cache/from/env")
    assert cc.enable_compile_cache() == "/cache/from/env"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_ignored_dir(monkeypatch,
                                                     cache_dir_option):
    monkeypatch.delenv(cc.ENV, raising=False)
    got = cc.enable_compile_cache()
    root = Path(__file__).resolve().parents[1]
    assert got == str(root / ".jax_cache") == cc.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == got
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
