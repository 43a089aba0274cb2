"""Distributed-runtime tests: optimizer, data determinism, checkpointing,
fault tolerance, straggler detection, gradient compression, sharding rules."""

import dataclasses
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint.checkpointer import Checkpointer
from repro.data.pipeline import DataConfig, Pipeline, batch_at
from repro.optim.adamw import AdamW
from repro.optim.grad_compress import compress_grads, init_state
from repro.optim.schedule import warmup_cosine
from repro.parallel.sharding import ShardingRules, partition_spec
from repro.runtime.fault_tolerance import FaultTolerantCluster, plan_restart
from repro.runtime.straggler import StragglerDetector


class TestAdamW:
    def test_descends_quadratic(self):
        opt = AdamW(lr=0.1, weight_decay=0.0)
        params = {"w": jnp.array([5.0, -3.0])}
        state = opt.init(params)
        for _ in range(200):
            grads = {"w": 2 * params["w"]}
            params, state = opt.update(grads, state, params)
        assert float(jnp.abs(params["w"]).max()) < 0.1

    def test_moment_dtype(self):
        opt = AdamW(moment_dtype=jnp.bfloat16)
        params = {"w": jnp.ones((4,), jnp.bfloat16)}
        state = opt.init(params)
        assert state.mu["w"].dtype == jnp.bfloat16
        params2, state2 = opt.update({"w": jnp.ones(4)}, state, params)
        assert state2.mu["w"].dtype == jnp.bfloat16
        assert params2["w"].dtype == jnp.bfloat16

    def test_grad_clip(self):
        opt = AdamW(lr=1e-3, grad_clip=1.0)
        params = {"w": jnp.zeros((2,))}
        state = opt.init(params)
        p1, _ = opt.update({"w": jnp.array([1e6, 0.0])}, state, params)
        assert np.isfinite(np.asarray(p1["w"])).all()


class TestSchedule:
    def test_warmup_then_decay(self):
        lrs = [float(warmup_cosine(jnp.int32(s), warmup=10, total=100))
               for s in range(100)]
        assert lrs[0] < lrs[9] <= 1.0  # warmup ascends
        assert lrs[99] < lrs[50] < lrs[11]  # cosine descends
        assert lrs[99] >= 0.1 - 1e-6  # floor


class TestData:
    def test_deterministic_across_restarts(self):
        cfg = DataConfig(vocab=1000, seq_len=16, global_batch=4)
        a = batch_at(cfg, 7)["tokens"]
        b = batch_at(cfg, 7)["tokens"]
        np.testing.assert_array_equal(a, b)
        assert not np.array_equal(a, batch_at(cfg, 8)["tokens"])

    def test_host_sharding_disjoint_streams(self):
        c0 = DataConfig(vocab=1000, seq_len=16, global_batch=8, n_hosts=2, host_id=0)
        c1 = dataclasses.replace(c0, host_id=1)
        assert c0.host_batch == 4
        assert not np.array_equal(batch_at(c0, 0)["tokens"], batch_at(c1, 0)["tokens"])

    def test_pipeline_prefetch_order(self):
        cfg = DataConfig(vocab=100, seq_len=8, global_batch=2)
        pipe = Pipeline(cfg, start_step=0)
        b0 = next(pipe)
        b1 = next(pipe)
        pipe.close()
        np.testing.assert_array_equal(b0["tokens"], batch_at(cfg, 0)["tokens"])
        np.testing.assert_array_equal(b1["tokens"], batch_at(cfg, 1)["tokens"])

    def test_tokens_in_vocab(self):
        cfg = DataConfig(vocab=311, seq_len=32, global_batch=4)
        t = batch_at(cfg, 3)["tokens"]
        assert t.min() >= 0 and t.max() < 311

    def test_bigram_structure_present(self):
        """The synthetic stream embeds a learnable bigram rule: about half
        the transitions follow it."""
        cfg = DataConfig(vocab=1000, seq_len=512, global_batch=4)
        t = batch_at(cfg, 0)["tokens"]
        pred = (t[:, :-1] * 31 + 7) % cfg.vocab
        frac = (t[:, 1:] == pred).mean()
        assert 0.35 < frac < 0.65


class TestCheckpointer:
    def test_save_restore_roundtrip(self):
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d)
            tree = {"a": jnp.arange(8.0), "b": {"c": jnp.ones((2, 3), jnp.bfloat16)}}
            ck.save(5, tree, blocking=True)
            assert ck.latest_complete() == 5
            out = ck.restore(5, tree)
            np.testing.assert_array_equal(np.asarray(out["a"]), np.arange(8.0))
            assert out["b"]["c"].dtype == jnp.bfloat16

    def test_corruption_detected(self):
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d)
            tree = {"a": jnp.arange(4.0)}
            ck.save(1, tree, blocking=True)
            # corrupt the shard
            import pathlib

            f = next(pathlib.Path(d).glob("step_*/*a*.npy"))
            f.write_bytes(b"garbage" * 10)
            with pytest.raises(IOError):
                ck.restore(1, tree)

    def test_gc_keeps_latest(self):
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d, keep=2)
            tree = {"a": jnp.zeros(2)}
            for s in (1, 2, 3, 4):
                ck.save(s, tree, blocking=True)
            assert ck.latest_complete() == 4
            import pathlib

            dirs = sorted(pathlib.Path(d).glob("step_*"))
            assert len(dirs) == 2

    def test_async_save(self):
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d)
            ck.save(9, {"a": jnp.ones(16)})
            ck.wait()
            assert ck.latest_complete() == 9


class TestFaultTolerance:
    def test_heartbeat_timeout(self):
        t = [0.0]
        cluster = FaultTolerantCluster(n_hosts=4, timeout_s=10, clock=lambda: t[0])
        t[0] = 8.0
        for h in (0, 1, 2):
            cluster.heartbeat(h)
        t[0] = 16.0  # host 3's last beat (t=0) is now 16s stale; 0-2 are 8s
        dead = cluster.check()
        assert dead == [3]
        assert cluster.alive_count == 3

    def test_restart_same_size_with_spares(self):
        plan = plan_restart(
            alive_hosts=63, hosts_per_replica=8, base_mesh=(16, 16),
            spare_hosts=2, latest_checkpoint=1000,
        )
        assert plan.kind == "same_size"
        assert plan.replay_from == 1001

    def test_elastic_downsize_without_spares(self):
        plan = plan_restart(
            alive_hosts=20, hosts_per_replica=8, base_mesh=(16, 16),
            spare_hosts=0, latest_checkpoint=500,
        )
        assert plan.kind == "elastic_downsize"
        data_ax, model_ax = plan.mesh_shape
        assert model_ax == 16  # model axis preserved (sharding stays valid)
        assert data_ax * model_ax <= 20 * 8
        assert data_ax & (data_ax - 1) == 0  # power of two

    def test_halt_when_no_model_replica_fits(self):
        """Survivors can't hold even one model replica: the plan must be an
        explicit halt, not a bogus (1, model_ax) mesh the cluster cannot
        place (capacity 8 chips < model axis 16)."""
        plan = plan_restart(
            alive_hosts=1, hosts_per_replica=8, base_mesh=(16, 16),
            spare_hosts=0, latest_checkpoint=700,
        )
        assert plan.kind == "halt"
        assert plan.mesh_shape == (0, 16)
        assert plan.restore_step == 700  # checkpoint kept for backfill
        assert plan.replay_from is None  # nothing will consume data

    def test_elastic_boundary_exactly_one_replica(self):
        """capacity == model_ax is the smallest feasible elastic mesh:
        exactly one data replica, not a halt."""
        plan = plan_restart(
            alive_hosts=2, hosts_per_replica=8, base_mesh=(16, 16),
            spare_hosts=0, latest_checkpoint=None,
        )
        assert plan.kind == "elastic_downsize"
        assert plan.mesh_shape == (1, 16)
        assert plan.replay_from is None

    def test_heartbeat_revives_marked_host(self):
        t = [0.0]
        cluster = FaultTolerantCluster(n_hosts=2, timeout_s=5,
                                       clock=lambda: t[0])
        t[0] = 10.0
        cluster.heartbeat(0)
        assert cluster.check() == [1]
        cluster.heartbeat(1)  # late beat: the host is back
        assert cluster.check() == []
        assert cluster.alive_count == 2

    def test_elastic_restore_resharding(self):
        """A checkpoint saved under one mesh restores onto a smaller one."""
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d)
            tree = {"w": jnp.arange(16.0).reshape(4, 4)}
            ck.save(3, tree, blocking=True)
            mesh = jax.make_mesh((1, 1), ("data", "model"))
            from jax.sharding import NamedSharding, PartitionSpec

            sh = {"w": NamedSharding(mesh, PartitionSpec(None, None))}
            out = ck.restore(3, tree, shardings=sh)
            np.testing.assert_array_equal(
                np.asarray(out["w"]), np.arange(16.0).reshape(4, 4)
            )


class TestStraggler:
    def test_flags_persistent_straggler(self):
        det = StragglerDetector(n_hosts=4, patience=3)
        decisions = {}
        for step in range(20):
            times = [1.0, 1.0, 1.0, 1.0]
            if step >= 8:
                times[2] = 3.5  # host 2 degrades
            decisions.update(det.observe(times))
        assert 2 in decisions
        assert decisions[2] in ("exclude_next_rescale", "immediate_restart")

    def test_no_false_positives_on_noise(self):
        rng = np.random.default_rng(0)
        det = StragglerDetector(n_hosts=8, patience=5)
        bad = {}
        for _ in range(50):
            times = list(1.0 + 0.02 * rng.standard_normal(8))
            bad.update(det.observe(times))
        assert not bad

    def test_mitigations_escalate_in_order(self):
        """A persistent slow host walks the ladder: rebalance first, then
        exclude-at-next-rescale once patience runs out."""
        det = StragglerDetector(n_hosts=4, patience=4)
        seen = []
        for step in range(12):
            times = [1.0, 1.0, 1.0, 1.0]
            if step >= 2:
                times[1] = 1.8  # slow but under hard_ratio * fleet mean
            for host, action in det.observe(times).items():
                assert host == 1
                seen.append(action)
        assert "rebalance_input" in seen
        assert "exclude_next_rescale" in seen
        assert "immediate_restart" not in seen
        assert seen.index("rebalance_input") < seen.index(
            "exclude_next_rescale"
        )

    def test_hard_straggler_restarts(self):
        """A 4x slowdown (past hard_ratio of the fleet mean) escalates to
        immediate restart once patience is exhausted."""
        det = StragglerDetector(n_hosts=4, patience=3)
        decisions = {}
        for step in range(10):
            times = [1.0, 1.0, 1.0, 1.0]
            if step >= 2:
                times[3] = 4.0
            decisions.update(det.observe(times))
        assert decisions.get(3) == "immediate_restart"


class TestGradCompression:
    def test_error_feedback_preserves_sum(self):
        """With error feedback, quantization error does not accumulate:
        the running sum of dequantized grads tracks the true sum."""
        rng = np.random.default_rng(0)
        params = {"w": jnp.zeros((512,))}
        state = init_state(params)
        true_sum = np.zeros(512)
        deq_sum = np.zeros(512)
        for _ in range(30):
            g = {"w": jnp.asarray(rng.normal(0, 1, 512), jnp.float32)}
            true_sum += np.asarray(g["w"])
            deq, state = compress_grads(g, state)
            deq_sum += np.asarray(deq["w"])
        err = np.abs(true_sum - deq_sum).max()
        scale = np.abs(true_sum).max()
        assert err < 0.05 * scale + 0.1

    def test_quantization_bounded_error_per_step(self):
        g = {"w": jnp.asarray(np.linspace(-3, 3, 1024), jnp.float32)}
        deq, _ = compress_grads(g, init_state(g))
        err = np.abs(np.asarray(deq["w"]) - np.asarray(g["w"])).max()
        assert err <= 3.0 / 127 + 1e-5


class TestShardingRules:
    def test_divisibility_fallback(self):
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        # single-device mesh: everything replicates (axis size 1)
        spec = partition_spec((8, 64), ("batch", "mlp"), mesh, ShardingRules())
        assert spec == jax.sharding.PartitionSpec()

    @given(st.integers(1, 128), st.integers(1, 128))
    @settings(max_examples=30, deadline=None)
    def test_spec_never_overshards(self, d0, d1):
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        spec = partition_spec((d0, d1), ("embed", "mlp"), mesh, ShardingRules())
        # on a 1x1 mesh nothing may be sharded
        assert all(e is None for e in spec)
