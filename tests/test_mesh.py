"""GSPMD mesh substrate tests (apex_tpu/mesh, docs/mesh.md).

The conftest forces 8 simulated CPU devices, so every test here runs
on a real (8-way) mesh. The heavier end-to-end guarantees — dp=8 loss
parity vs 1 device and model-sharded decode token identity — are ALSO
proven by tools/check_mesh.sh in fresh processes; the in-suite copies
here are the tier-1 regression net.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu import mesh as gmesh
from apex_tpu.mesh import annotate
from apex_tpu.models.gpt import GPTConfig, GPTModel, gpt_loss_fn


@pytest.fixture(autouse=True)
def clean_mesh():
    gmesh.destroy_mesh()
    yield
    gmesh.destroy_mesh()


def tiny_cfg(**kw):
    kw.setdefault("vocab_size", 128)
    kw.setdefault("max_seq_len", 32)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 4)
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("param_dtype", jnp.float32)
    return GPTConfig(**kw)


class TestMeshLifecycle:
    def test_default_is_degenerate(self):
        assert not gmesh.mesh_initialized()
        assert gmesh.mesh_size() == 1
        assert gmesh.axis_sizes() == {"batch": 1, "pipe": 1, "model": 1}
        with pytest.raises(RuntimeError):
            gmesh.current_mesh()

    def test_initialize_defaults_batch(self):
        mesh = gmesh.initialize_mesh(model=2)
        n = len(jax.devices())
        assert mesh.axis_names == ("batch", "pipe", "model")
        assert gmesh.axis_sizes() == {"batch": n // 2, "pipe": 1,
                                      "model": 2}
        assert gmesh.mesh_size() == n

    def test_one_device_mesh_is_legal(self):
        gmesh.initialize_mesh(batch=1, model=1, pipe=1,
                              devices=jax.devices()[:1])
        assert gmesh.mesh_initialized()
        assert gmesh.mesh_size() == 1

    def test_explicit_devices_keep_the_callers_order(self):
        # only the default list is laid out by topology; a caller's own
        # list (a subset, a chosen order) is reshaped as it was given
        devs = jax.devices()[4:0:-1]
        mesh = gmesh.initialize_mesh(batch=2, model=2, devices=devs)
        assert list(mesh.devices.flatten()) == devs

    def test_default_layout_error_is_not_swallowed(self, monkeypatch):
        from jax.experimental import mesh_utils

        def refuse(*a, **kw):
            raise NotImplementedError("no such topology")

        monkeypatch.setattr(mesh_utils, "create_device_mesh", refuse)
        with pytest.raises(NotImplementedError, match="no such topology"):
            gmesh.initialize_mesh(model=2)
        assert not gmesh.mesh_initialized()

    def test_bad_factorization_raises(self):
        with pytest.raises(ValueError):
            gmesh.initialize_mesh(model=3)
        with pytest.raises(ValueError):
            gmesh.initialize_mesh(batch=2, model=2, pipe=3)

    def test_destroy(self):
        gmesh.initialize_mesh()
        gmesh.destroy_mesh()
        assert not gmesh.mesh_initialized()
        assert gmesh.mesh_size() == 1


class TestShardingPlan:
    def test_identity_on_one_device(self):
        """Every shard_* entry point returns THE SAME OBJECT on a
        1-device mesh — the byte-identity guarantee existing
        single-chip paths rely on."""
        gmesh.initialize_mesh(batch=1, devices=jax.devices()[:1])
        cfg = tiny_cfg()
        model = GPTModel(cfg)
        toks = jnp.zeros((2, 8), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), toks)
        plan = gmesh.plan_gpt(params)
        assert plan.is_identity()
        assert plan.shard_params(params) is params
        assert plan.shard_batch(toks) is toks
        state = {"anything": jnp.ones((3,))}
        assert plan.shard_state(state) is state

    def test_gpt_plan_shards_tensor_dims_on_model_axis(self):
        gmesh.initialize_mesh(model=2)
        cfg = tiny_cfg()
        model = GPTModel(cfg)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((2, 8), jnp.int32))
        plan = gmesh.plan_gpt(params)
        specs = plan.param_specs
        leaves = jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        axes = {a for s in leaves for a in s if a is not None}
        assert axes == {"model"}         # only the model axis appears
        assert any(any(a == "model" for a in s) for s in leaves)

    def test_shard_params_and_batch_commit(self):
        gmesh.initialize_mesh(model=2)
        cfg = tiny_cfg()
        model = GPTModel(cfg)
        toks = jnp.zeros((8, 8), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), toks)
        plan = gmesh.plan_gpt(params)
        sharded = plan.shard_params(params)
        chex_leaf = jax.tree.leaves(sharded)[0]
        assert len(chex_leaf.sharding.device_set) == 8
        batch = plan.shard_batch(toks)
        assert str(tuple(batch.sharding.spec)) == "('batch',)"
        d = plan.detail()
        assert d["n_devices"] == 8
        assert d["param_leaves_sharded"] > 0


class TestAnnotate:
    def test_constrain_identity_without_mesh(self):
        x = jnp.ones((4, 4))
        assert annotate.constrain(x, None, "model") is x
        assert not annotate.mesh_active()

    def test_constrain_identity_on_one_device_mesh(self):
        gmesh.initialize_mesh(batch=1, devices=jax.devices()[:1])
        x = jnp.ones((4, 4))
        assert annotate.constrain_hidden(x) is x

    def test_constrain_applies_on_real_mesh(self):
        gmesh.initialize_mesh(model=2)
        assert annotate.mesh_active()

        @jax.jit
        def f(x):
            return annotate.constrain(x, "batch", None) * 2.0

        y = f(jnp.ones((8, 4)))
        np.testing.assert_allclose(np.asarray(y), 2.0)

    def test_shard_kv_pool_identity_without_mesh(self):
        state = {"k": jnp.zeros((2, 3, 4, 2, 8))}
        assert annotate.shard_kv_pool(state) is state


class TestMeshTrainStep:
    def _data(self, cfg, batch=8, seq=16):
        rng = np.random.RandomState(0)
        toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                           jnp.int32)
        labels = jnp.asarray(
            rng.randint(0, cfg.vocab_size, (batch, seq)), jnp.int32)
        return toks, labels

    def _run(self, n_steps=3):
        from apex_tpu.optimizers import FusedAdam

        cfg = tiny_cfg()
        model = GPTModel(cfg)
        toks, labels = self._data(cfg)
        params = model.init(jax.random.PRNGKey(0), toks)
        plan = gmesh.plan_gpt(params) if gmesh.mesh_initialized() else \
            gmesh.plan_gpt(params, mesh=_single_mesh())
        step = gmesh.make_mesh_train_step(
            model, FusedAdam(lr=1e-3, impl="xla"), plan)
        state = step.init(params)
        losses = []
        for _ in range(n_steps):
            state, loss = step(state, toks, labels)
            losses.append(float(loss))
        return losses

    def test_dp8_matches_single_device(self):
        """The acceptance guarantee: the SAME model code, 1-device vs
        dp=8 GSPMD, loss-identical to fp32 tolerance."""
        ref = self._run()                  # no mesh -> identity plan
        gmesh.initialize_mesh()            # pure dp over all devices
        assert gmesh.axis_sizes()["batch"] == len(jax.devices())
        dp = self._run()
        np.testing.assert_allclose(dp, ref, rtol=2e-5, atol=2e-5)

    def test_tp2_matches_single_device(self):
        ref = self._run()
        gmesh.initialize_mesh(model=2)
        tp = self._run()
        np.testing.assert_allclose(tp, ref, rtol=2e-5, atol=2e-5)

    def test_observes_compile_and_publishes_shardings(self):
        from apex_tpu import telemetry
        from apex_tpu.optimizers import FusedAdam
        from apex_tpu.telemetry import compiled as tcompiled
        from apex_tpu.telemetry import metrics as tmetrics

        telemetry.reset()
        try:
            gmesh.initialize_mesh()
            cfg = tiny_cfg()
            model = GPTModel(cfg)
            toks, labels = self._data(cfg)
            params = model.init(jax.random.PRNGKey(0), toks)
            step = gmesh.make_mesh_train_step(
                model, FusedAdam(lr=1e-3, impl="xla"),
                gmesh.plan_gpt(params))
            tracker = tcompiled.enable()
            state = step.init(params)
            state, _ = step(state, toks, labels)   # compile
            state, _ = step(state, toks, labels)   # hot
            state, _ = step(state, toks, labels)   # hot
            s = tracker.summary()
            # one observed signature, zero hot-loop recompiles
            assert s["signatures"].get("mesh_train_step") == 1
            assert s["recompiles"] == 0
            g = tmetrics.registry().snapshot()["gauges"]
            assert g.get('sharding_devices{fn="mesh_train_step"}') == \
                len(jax.devices())
            detail = telemetry.snapshot_detail()
            assert "mesh_train_step" in (detail["sharding"] or {})
        finally:
            telemetry.reset()


def _single_mesh():
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1, 1),
                gmesh.MESH_AXES)


class TestServingSharded:
    def test_model_sharded_decode_token_identical(self):
        """A model-sharded checkpoint + kv_heads-sharded paged pool
        through the REAL serving DecodeStep produces the same greedy
        stream as the unsharded engine."""
        from apex_tpu.serving import KVCache, make_decode_step

        cfg = tiny_cfg(num_heads=4, num_kv_heads=2)
        model = GPTModel(cfg)
        prompt = jnp.asarray(
            np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 8)),
            jnp.int32)
        params = model.init(jax.random.PRNGKey(0), prompt)

        def stream(params, cache_state_sharder):
            cache = KVCache.for_config(cfg, num_blocks=16, block_size=8)
            state = cache_state_sharder(cache.init_state())
            step = make_decode_step(model, cache)
            for i in range(2):
                cache.allocate(i, 8 + 4)
            tables = cache.table_array([0, 1], width=4)
            lengths = np.asarray([8, 8], np.int32)
            out = step.prefill(params, state, prompt, lengths, tables)
            state, tok = out.cache, out.next_token
            toks = [np.asarray(tok)]
            pos = lengths.copy()
            for _ in range(3):
                out = step.decode(params, state, np.asarray(tok), pos,
                                  tables)
                state, tok = out.cache, out.next_token
                pos = pos + 1
                toks.append(np.asarray(tok))
            return np.stack(toks)

        ref = stream(params, lambda s: s)
        gmesh.initialize_mesh(model=2)
        sharded = stream(annotate.shard_params_for_serving(params),
                         annotate.shard_kv_pool)
        np.testing.assert_array_equal(sharded, ref)
