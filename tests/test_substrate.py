"""Substrate tests: optimizer, data pipeline, checkpointing, fault tolerance."""
import json
import os
import pathlib
import signal
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro  # noqa: F401
from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import DataConfig, IteratorState, PrefetchIterator, SyntheticLMData
from repro.distributed.monitor import StepMonitor
from repro.launch.mesh import make_mesh
from repro.optim import adamw


class TestAdamW:
    def test_quadratic_convergence(self):
        params = {"w": jnp.asarray([5.0, -3.0])}
        state = adamw.init_state(params)
        cfg = adamw.AdamWConfig(lr=0.2, weight_decay=0.0, grad_clip=100.0,
                                warmup_steps=0, total_steps=200, schedule="constant")
        for _ in range(150):
            grads = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(state["params"])
            state, _ = adamw.apply_updates(state, grads, cfg)
        assert float(jnp.max(jnp.abs(state["params"]["w"]))) < 1e-2

    def test_grad_clip(self):
        params = {"w": jnp.ones(3)}
        state = adamw.init_state(params)
        cfg = adamw.AdamWConfig(grad_clip=1.0, warmup_steps=0, schedule="constant")
        _, metrics = adamw.apply_updates(state, {"w": jnp.full(3, 1e6)}, cfg)
        assert float(metrics["grad_norm"]) > 1e5  # reported pre-clip

    def test_schedule_warmup_cosine(self):
        cfg = adamw.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100)
        s = adamw.make_schedule(cfg)
        assert float(s(jnp.int32(5))) == pytest.approx(0.5, rel=1e-3)
        assert float(s(jnp.int32(10))) == pytest.approx(1.0, rel=1e-3)
        assert float(s(jnp.int32(100))) == pytest.approx(0.0, abs=1e-6)


class TestData:
    def test_determinism_and_resume(self):
        cfg = DataConfig(vocab_size=1000, seq_len=32, global_batch=4)
        d = SyntheticLMData(cfg)
        b7a = d.batch_at(7)
        b7b = d.batch_at(7)
        np.testing.assert_array_equal(b7a["tokens"], b7b["tokens"])

        it = PrefetchIterator(d)
        first = [next(it) for _ in range(3)]
        state = it.state
        it.close()
        it2 = PrefetchIterator(d, state=state)
        b3 = next(it2)
        it2.close()
        np.testing.assert_array_equal(b3["tokens"], d.batch_at(3)["tokens"])

    def test_per_host_sharding(self):
        cfg = DataConfig(vocab_size=100, seq_len=8, global_batch=8)
        h0 = SyntheticLMData(cfg, process_index=0, process_count=2)
        h1 = SyntheticLMData(cfg, process_index=1, process_count=2)
        assert h0.local_batch == 4
        assert not np.array_equal(h0.batch_at(0)["tokens"], h1.batch_at(0)["tokens"])

    def test_targets_shifted(self):
        cfg = DataConfig(vocab_size=100, seq_len=16, global_batch=2)
        b = SyntheticLMData(cfg).batch_at(0)
        assert b["tokens"].shape == (2, 16)
        assert b["targets"].shape == (2, 16)


class TestCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path):
        mgr = CheckpointManager(tmp_path)
        state = {"params": {"w": jnp.arange(6.0).reshape(2, 3)}, "step": jnp.int32(3)}
        mgr.save(3, state, extra={"step": 3})
        restored, extra = mgr.restore(like=state)
        np.testing.assert_array_equal(restored["params"]["w"], state["params"]["w"])
        assert extra["step"] == 3

    def test_latest_and_gc(self, tmp_path):
        mgr = CheckpointManager(tmp_path, keep_last=2)
        state = {"w": jnp.zeros(2)}
        for s in [1, 2, 3, 4]:
            mgr.save(s, state)
        assert mgr.latest_step() == 4
        assert mgr.all_steps() == [3, 4]  # gc'd to keep_last

    def test_atomic_no_partial(self, tmp_path):
        """A .tmp dir (simulated crash mid-save) must be invisible to restore."""
        mgr = CheckpointManager(tmp_path)
        mgr.save(1, {"w": jnp.ones(2)})
        crash = tmp_path / "step_00000002.tmp"
        crash.mkdir()
        (crash / "leaf_00000.npy").write_bytes(b"garbage")
        assert mgr.latest_step() == 1

    def test_elastic_reshard_on_restore(self, tmp_path):
        """Restore onto explicit shardings (different 'mesh')."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        mgr = CheckpointManager(tmp_path)
        state = {"w": jnp.arange(8.0)}
        mgr.save(1, state)
        mesh = make_mesh((1,), ("data",))
        sh = {"w": NamedSharding(mesh, P("data"))}
        restored, _ = mgr.restore(like=state, shardings=sh)
        assert restored["w"].sharding == sh["w"]


class TestMonitor:
    def test_straggler_detection(self):
        m = StepMonitor(window=50, threshold=2.0, patience=2)
        import time as _t

        for i in range(12):
            m.start_step()
            m.end_step(i)
        # inject two slow steps by faking the clock
        for i in range(12, 14):
            m.start_step()
            m._t0 -= 10.0  # pretend the step took 10s
            ev = m.end_step(i)
            assert ev is not None
        assert m.should_evict

    def test_caller_timed_steps(self):
        # the launcher times each step once, as a span, and hands the time in
        m = StepMonitor(window=50, threshold=2.0, patience=2)
        for i in range(12):
            assert m.end_step(i, 1.0) is None
        assert m.end_step(12, 2.5).step_time == 2.5
        assert m.end_step(13, 3.0) is not None
        assert m.should_evict

    def test_heartbeat(self, tmp_path):
        hb = tmp_path / "hb.json"
        m = StepMonitor(heartbeat_path=str(hb))
        m.start_step()
        m.end_step(0)
        assert json.loads(hb.read_text())["step"] == 0


class TestTrainResume:
    def test_checkpoint_restart_continuity(self, tmp_path):
        """Train 6 steps; restart from step-4 checkpoint; loss stream matches
        an uninterrupted run (fault-tolerance requirement)."""
        from repro.launch.train import train

        args = [
            "--arch", "repro-100m", "--reduced", "--batch", "2", "--seq", "64",
            "--ckpt-every", "4", "--log-every", "100",
        ]
        rc = train(args + ["--steps", "6", "--ckpt-dir", str(tmp_path / "a")])
        assert rc in (0, 2)
        # interrupted run: first 4 steps only (ckpt at 4), then resume to 6
        rc = train(args + ["--steps", "5", "--ckpt-dir", str(tmp_path / "b")])
        rc = train(args + ["--steps", "6", "--ckpt-dir", str(tmp_path / "b")])
        assert rc in (0, 2)
        mgr_a = CheckpointManager(tmp_path / "a")
        mgr_b = CheckpointManager(tmp_path / "b")
        from repro.models import Model
        from repro.configs import get_reduced_config

        model = Model(get_reduced_config("repro-100m"))
        proto = adamw.init_state(model.init(jax.random.PRNGKey(0)))
        sa, _ = mgr_a.restore(step=6, like=proto)
        sb, _ = mgr_b.restore(step=6, like=proto)
        for la, lb in zip(jax.tree_util.tree_leaves(sa), jax.tree_util.tree_leaves(sb)):
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb), rtol=1e-5, atol=1e-6)
