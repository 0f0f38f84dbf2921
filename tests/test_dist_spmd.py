"""The SPMD subsystem: profiles, planner, mesh-aware dispatch, replicas.

Runs on a forced multi-device host platform (conftest sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` unless the
environment already pins XLA_FLAGS); tests that need >1 device skip
below that.

Covers the contracts the issue names:

* sharded-vs-unsharded numerical parity across >=2 buckets for the
  ``dp`` / ``fsdp`` / ``tp`` profiles, on both pipelines;
* mesh-divisible bucket constraint enforcement: a ``Dim`` whose contract
  cannot be tightened (``bucket="exact"``, non-divisible ``max``) raises
  at ``lower()`` time, and tightened policies produce only mesh-divisible
  buckets;
* compile-count parity under a mesh (sharding never adds compiles);
* replica routing order + replicated-vs-single generation parity.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import disc
from repro.core.constraints import ConstraintViolation
from repro.dist import (DP_AXES, ShardingProfile, fit_spec, get_profile,
                        maybe_shard, use_mesh)
from repro.launch.mesh import make_mesh

N_DEV = len(jax.devices())

needs2 = pytest.mark.skipif(N_DEV < 2, reason="needs >=2 devices")
needs4 = pytest.mark.skipif(N_DEV < 4, reason="needs >=4 devices")


def _mesh_2d():
    """A (data, model) mesh using as many devices as the platform has."""
    if N_DEV >= 8:
        shape = (4, 2)
    elif N_DEV >= 4:
        shape = (2, 2)
    elif N_DEV >= 2:
        shape = (2, 1)
    else:
        shape = (1, 1)
    return make_mesh(shape, ("data", "model"))


def _fn(w1, w2, x):
    return jax.nn.relu(x @ w1) @ w2


def _specs(**dim_kw):
    return [(16, 32), (32, 8),
            (disc.Dim("B", max=64, **dim_kw), 16)]


def _weights(seed=0):
    rng = np.random.RandomState(seed)
    return (rng.randn(16, 32).astype(np.float32),
            rng.randn(32, 8).astype(np.float32))


GRANULE1 = disc.BucketPolicy(kind="pow2", granule=1)


# --------------------------------------------------------------- factory --

class TestMakeMesh:
    def test_general_factory(self):
        mesh = make_mesh((N_DEV,), ("data",))
        assert dict(mesh.shape) == {"data": N_DEV}

    @needs4
    def test_2d_shape(self):
        mesh = make_mesh((2, 2), ("data", "model"))
        assert dict(mesh.shape) == {"data": 2, "model": 2}

    def test_shape_axes_mismatch(self):
        with pytest.raises(ValueError, match="axis names"):
            make_mesh((2, 2), ("data",))

    def test_too_few_devices(self):
        with pytest.raises(RuntimeError, match="force"):
            make_mesh((N_DEV + 1,), ("data",))

    def test_production_preset_uses_factory(self):
        # 256-device floor still enforced by the preset, not the factory
        if N_DEV >= 256:
            pytest.skip("platform actually has a production mesh")
        with pytest.raises(RuntimeError):
            from repro.launch.mesh import make_production_mesh
            make_production_mesh()


# ----------------------------------------------------------- maybe_shard --

class TestMaybeShardRank:
    @needs2
    def test_overlong_spec_truncates_with_warning(self):
        # regression: a spec longer than the array rank used to fall into
        # the blanket except and silently skip sharding; now it truncates
        mesh = make_mesh((N_DEV,), ("data",))
        x = jnp.ones((N_DEV, 4))
        with use_mesh(mesh):
            with pytest.warns(UserWarning, match="truncating"):
                y = maybe_shard(x, P("data", None, "model"))
        assert np.allclose(np.asarray(y), np.asarray(x))
        assert "data" in str(y.sharding)

    def test_no_warning_on_matching_rank(self):
        mesh = make_mesh((1,), ("data",))
        x = jnp.ones((4, 4))
        with use_mesh(mesh):
            with warnings.catch_warnings():
                warnings.simplefilter("error")
                maybe_shard(x, P("data", None))


# -------------------------------------------------------------- profiles --

class TestProfiles:
    def test_builtins_resolve(self):
        for name in ("dp", "fsdp", "tp"):
            assert get_profile(name).name == name
        prof = get_profile("dp")
        assert get_profile(prof) is prof

    def test_unknown_profile(self):
        with pytest.raises(ValueError, match="unknown sharding profile"):
            get_profile("zz")
        with pytest.raises(ValueError, match="unknown sharding profile"):
            disc.CompileOptions(mesh=make_mesh((1,), ("data",)),
                                sharding_profile="zz")

    def test_profile_without_mesh_rejected(self):
        with pytest.raises(ValueError, match="needs a mesh"):
            disc.CompileOptions(sharding_profile="dp")

    def test_dim_axes(self):
        assert get_profile("dp").axes_for_dim("B") == DP_AXES
        assert get_profile("dp").axes_for_dim("S") is None
        custom = get_profile("dp").replace(
            name="sp", dim_axes=(("S", ("model",)),))
        assert custom.axes_for_dim("S") == ("model",)

    def test_param_layouts(self):
        shape = (16, 32)
        assert get_profile("dp").leaf_spec(shape) == P(None, None)
        assert get_profile("fsdp").leaf_spec(shape) == \
            P(None, ("pod", "data", "model"))  # folds onto the larger dim
        assert get_profile("tp").leaf_spec(shape) == P(None, "model")


# ------------------------------------------------------ sharded dispatch --

class TestShardedDispatchParity:
    @pytest.mark.parametrize("profile", ["dp", "fsdp", "tp"])
    def test_dhlo_parity_two_buckets(self, profile):
        mesh = _mesh_2d()
        w1, w2 = _weights()
        base = disc.compile(_fn, specs=_specs(),
                            options=disc.CompileOptions(policy=GRANULE1))
        sh = disc.compile(_fn, specs=_specs(),
                          options=disc.CompileOptions(
                              policy=GRANULE1, mesh=mesh,
                              sharding_profile=profile))
        for b in (5, 33):  # two distinct buckets
            x = np.random.randn(b, 16).astype(np.float32)
            np.testing.assert_allclose(
                np.asarray(base(w1, w2, x)), np.asarray(sh(w1, w2, x)),
                atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("profile", ["dp", "fsdp", "tp"])
    def test_jit_parity_two_buckets(self, profile):
        mesh = _mesh_2d()
        w1, w2 = _weights()
        opts = dict(pipeline="jit", policy=GRANULE1)
        base = disc.compile(_fn, specs=[None, None,
                                        (disc.Dim("B", max=64), 16)],
                            options=disc.CompileOptions(**opts))
        sh = disc.compile(_fn, specs=[None, None,
                                      (disc.Dim("B", max=64), 16)],
                          options=disc.CompileOptions(
                              mesh=mesh, sharding_profile=profile, **opts))
        for b in (5, 33):
            x = np.random.randn(b, 16).astype(np.float32)
            # jit-pipeline outputs stay padded (lens-aware contract) and
            # bucket sizes may differ under the tightened policy: compare
            # the true rows
            np.testing.assert_allclose(
                np.asarray(base(jnp.asarray(w1), jnp.asarray(w2), x))[:b],
                np.asarray(sh(jnp.asarray(w1), jnp.asarray(w2), x))[:b],
                atol=1e-5, rtol=1e-5)

    @needs2
    def test_padded_buckets_actually_sharded(self):
        """The generated dispatch device_puts the padded bucket onto the
        mesh: the emitted source contains the put, the plan's sharding is
        the data-parallel one, and the result is correct."""
        mesh = make_mesh((N_DEV,), ("data",))
        fn = disc.compile(lambda x: x * 2.0,
                          specs=[(disc.Dim("B", max=64), 4)],
                          options=disc.CompileOptions(
                              pipeline="jit", policy=GRANULE1, mesh=mesh,
                              sharding_profile="dp"))
        out = fn(np.ones((3, 4), np.float32))
        np.testing.assert_allclose(np.asarray(out)[:3], 2.0)
        assert "_put0(" in fn.dispatch_source
        assert fn.lower().sharding_plan.arg_sharding(0).spec == \
            P("data", None)

    def test_report_shows_shardings_and_constraints(self):
        mesh = _mesh_2d()
        sh = disc.compile(_fn, specs=_specs(),
                          options=disc.CompileOptions(
                              policy=GRANULE1, mesh=mesh,
                              sharding_profile="dp"))
        rep = sh.report()
        assert rep["sharding"]["profile"] == "dp"
        assert rep["sharding"]["per_arg"][2] == "PartitionSpec('data', None)"
        dp = int(mesh.shape["data"])
        if dp > 1:
            [c] = rep["sharding"]["constraints"]
            assert c == {"dim": "B", "axes": ["data"], "multiple_of": dp}
            # surfaced in the dhlo constraint store too
            assert rep["constraints"]["mesh_constraints"] == 1
        assert rep["placement"]["device_target"].startswith("mesh(")

    def test_compile_count_parity_under_mesh(self):
        # with the default granule-16 policy (mesh axes divide 16) the
        # tightening is a no-op, so sharding adds ZERO compiles
        mesh = _mesh_2d()
        w1, w2 = _weights()
        calls = [3, 5, 17, 33, 40, 33]

        def run(options):
            fn = disc.compile(_fn, specs=_specs(), options=options)
            for b in calls:
                fn(w1, w2, np.random.randn(b, 16).astype(np.float32))
            return fn.compile_counts()

        base = run(disc.CompileOptions())
        shard = run(disc.CompileOptions(mesh=mesh, sharding_profile="dp"))
        assert shard == base
        assert shard["bucket"] == 3  # 16, 32, 64

    def test_tightened_granule_merges_never_splits(self):
        mesh = _mesh_2d()
        w1, w2 = _weights()
        calls = [3, 5, 9, 33, 40, 33]

        def run(options):
            fn = disc.compile(_fn, specs=_specs(), options=options)
            for b in calls:
                fn(w1, w2, np.random.randn(b, 16).astype(np.float32))
            return fn.compile_counts()

        base = run(disc.CompileOptions(policy=GRANULE1))
        shard = run(disc.CompileOptions(policy=GRANULE1, mesh=mesh,
                                        sharding_profile="dp"))
        assert shard["total"] <= base["total"]
        assert shard["bucket"] >= 1

    def test_legacy_backend_rejected_under_mesh(self):
        # a backend whose build_bucket predates the SPMD contract fails
        # loudly at bucket-compile time, not with a far-away sharding
        # mismatch at the AOT call
        from repro.api.backends import Backend, register_backend
        legacy = Backend(
            name="legacy",
            build_bucket=lambda graph, plan, syms, padded, donate: None,
            build_exact=lambda graph, plan: None)
        register_backend("legacy-spmd-test", legacy, overwrite=True)
        fn = disc.compile(_fn, specs=_specs(),
                          options=disc.CompileOptions(
                              mesh=_mesh_2d(), sharding_profile="dp",
                              backend="legacy-spmd-test"))
        w1, w2 = _weights()
        with pytest.raises(ValueError, match="arg_shardings"):
            fn(w1, w2, np.random.randn(5, 16).astype(np.float32))

    def test_mesh_artifacts_never_share_cache_entries(self):
        # same fn + same specs + one shared CompileCache, meshless vs
        # meshed: the fingerprints must differ or the shared cache would
        # serve wrongly-sharded executables
        mesh = _mesh_2d()
        base = disc.compile(_fn, specs=_specs())
        sh = disc.compile(_fn, specs=_specs(),
                          options=disc.CompileOptions(
                              mesh=mesh, sharding_profile="fsdp"))
        assert base.lower().fingerprint() != sh.lower().fingerprint()

    @needs2
    def test_same_shape_different_devices_distinct_fingerprints(self):
        # two same-SHAPE meshes over disjoint device sets compile
        # incompatible executables: device identity is in the token
        devs = jax.devices()
        mesh_a = make_mesh((1,), ("data",), devices=devs[:1])
        mesh_b = make_mesh((1,), ("data",), devices=devs[1:2])
        fps = [disc.compile(_fn, specs=_specs(),
                            options=disc.CompileOptions(
                                mesh=m, sharding_profile="dp")
                            ).lower().fingerprint()
               for m in (mesh_a, mesh_b)]
        assert fps[0] != fps[1]

    @needs2
    def test_escalation_under_mesh(self):
        mesh = make_mesh((N_DEV,), ("data",))
        w1, w2 = _weights()
        fn = disc.compile(_fn, specs=_specs(),
                          options=disc.CompileOptions(
                              policy=GRANULE1, mesh=mesh,
                              sharding_profile="dp",
                              escalation_threshold=2))
        x = np.random.randn(7, 16).astype(np.float32)  # 7 % N_DEV != 0
        ref = None
        for _ in range(3):
            out = np.asarray(fn(w1, w2, x))
            if ref is None:
                ref = out
            # the third call runs the escalated exact-shape program, which
            # sums in a different order than the padded-bucket program
            np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-6)
        assert fn.compile_counts()["exact"] == 1
        assert fn.cache_stats()["escalations"] == 1


# ---------------------------------------------------- bucket constraints --

class TestMeshDivisibleBuckets:
    @needs2
    def test_policy_tightened_to_axis_multiple(self):
        mesh = make_mesh((N_DEV,), ("data",))
        fn = disc.compile(_fn, specs=_specs(),
                          options=disc.CompileOptions(
                              policy=GRANULE1, mesh=mesh,
                              sharding_profile="dp"))
        low = fn.lower()
        for v in (1, 3, 5, 17, 33):
            assert low.policy.bucket("B", v) % N_DEV == 0

    @needs2
    def test_exact_bucket_raises_at_lower(self):
        mesh = make_mesh((N_DEV,), ("data",))
        with pytest.raises(ConstraintViolation, match="exact"):
            disc.compile(_fn, specs=_specs(bucket="exact"),
                         options=disc.CompileOptions(
                             mesh=mesh, sharding_profile="dp"))

    @needs2
    def test_non_divisible_max_raises_at_lower(self):
        mesh = make_mesh((N_DEV,), ("data",))
        with pytest.raises(ConstraintViolation, match="max"):
            disc.compile(
                _fn, specs=[(16, 32), (32, 8),
                            (disc.Dim("B", max=N_DEV + 1), 16)],
                options=disc.CompileOptions(mesh=mesh,
                                            sharding_profile="dp"))

    @needs2
    def test_unsharded_dim_unconstrained(self):
        # "S" is not in the dp profile's dim_axes: exact bucketing stays
        # legal and no constraint is recorded for it
        mesh = make_mesh((N_DEV,), ("data",))
        fn = disc.compile(
            lambda x: x * 2.0,
            specs=[(disc.Dim("B", max=64),
                    disc.Dim("S", bucket="exact", max=16))],
            options=disc.CompileOptions(pipeline="jit", mesh=mesh,
                                        sharding_profile="dp"))
        dims = {c["dim"] for c in
                fn.lower().sharding_plan.report()["constraints"]}
        assert dims == {"B"}

    @needs4
    def test_fit_spec_drops_non_dividing_axes(self):
        mesh = make_mesh((2, 2), ("data", "model"))
        assert fit_spec((6, 7), P("data", "model"), mesh) == \
            P("data", None)
        assert fit_spec((5,), P(("pod", "data")), mesh) == P(None)
        assert fit_spec((6,), P(("pod", "data")), mesh) == P("data")


# ---------------------------------------------------------------- serve --

def _tiny_model():
    import dataclasses as dc
    from repro.configs import get_config
    from repro.models.registry import get_model
    cfg = dc.replace(get_config("tinyllama_11b").reduced(),
                     n_layers=2, vocab=128)
    model = get_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _requests(vocab, plens, max_new=3):
    from repro.data.pipeline import Request
    rng = np.random.RandomState(7)
    return [Request(rid=i, tokens=rng.randint(
        0, vocab, size=pl).astype(np.int32), max_new_tokens=max_new)
        for i, pl in enumerate(plens)]


class TestReplicatedServe:
    def test_routing_order_least_loaded(self):
        from disc import ServeConfig, ServeEngine
        cfg, model, params = _tiny_model()
        eng = ServeEngine(model, params,
                          ServeConfig(max_batch=2, max_seq=64, replicas=2))
        eng.submit(_requests(cfg.vocab, [8, 8, 8, 8]))
        eng._admit()
        # FIFO order, least-loaded routing: r0 gets rid 0, r1 gets rid 1
        # (now equal load -> lowest index), r0 gets 2, r1 gets 3
        placed = {i: s.rid for i, s in enumerate(eng.slots)
                  if s is not None}
        assert placed == {0: 0, 1: 2, 2: 1, 3: 3}
        eng._refresh_stats()
        per = eng.stats["per_replica"]
        assert [p["admitted"] for p in per] == [2, 2]
        assert [p["occupied_slots"] for p in per] == [2, 2]

    def test_generation_parity_with_single(self):
        from disc import ServeConfig, ServeEngine
        cfg, model, params = _tiny_model()
        reqs = lambda: _requests(cfg.vocab, [9, 5, 12, 7, 6, 10])
        e1 = ServeEngine(model, params,
                         ServeConfig(max_batch=2, max_seq=64))
        e1.submit(reqs())
        e2 = ServeEngine(model, params,
                         ServeConfig(max_batch=2, max_seq=64, replicas=3))
        e2.submit(reqs())
        assert e1.run_until_done() == e2.run_until_done()
        per = e2.stats["per_replica"]
        assert sum(p["requests_completed"] for p in per) == 6
        assert sum(p["tokens_generated"] for p in per) == \
            e2.stats["tokens_generated"]

    @needs2
    def test_mesh_serve_parity(self):
        from disc import ServeConfig, ServeEngine
        cfg, model, params = _tiny_model()
        # one data shard per replica: 2 replicas x max_batch 2 = 4 slots
        # over a 2-way data axis
        mesh = make_mesh((2,), ("data",))
        reqs = lambda: _requests(cfg.vocab, [9, 5, 12, 7])
        e1 = ServeEngine(model, params,
                         ServeConfig(max_batch=2, max_seq=64, replicas=2))
        e1.submit(reqs())
        e2 = ServeEngine(model, params,
                         ServeConfig(max_batch=2, max_seq=64, replicas=2,
                                     mesh=mesh, sharding_profile="dp"))
        e2.submit(reqs())
        assert e1.run_until_done() == e2.run_until_done()
        rep = e2._prefill_fn.report()
        assert rep["sharding"]["profile"] == "dp"
        assert any(c["dim"] == "B"
                   for c in rep["sharding"]["constraints"])
        # the sharded KV cache stays partitioned along data
        leaf = jax.tree.leaves(e2.cache)[0]
        assert "data" in str(leaf.sharding.spec)

    @needs2
    def test_tp_profile_honors_model_cache_layout(self):
        # param_mode "tp": the KV cache follows model.cache_specs()
        # (heads/sequence on "model"), not the batch-only heuristic
        from disc import ServeConfig, ServeEngine
        cfg, model, params = _tiny_model()
        # a real (size>1) model axis: a trivial axis would be
        # canonicalized out of the shardings
        mesh = (make_mesh((2, 2), ("data", "model")) if N_DEV >= 4
                else make_mesh((1, 2), ("data", "model")))
        reqs = lambda: _requests(cfg.vocab, [9, 5, 12])
        e1 = ServeEngine(model, params,
                         ServeConfig(max_batch=2, max_seq=64, replicas=2))
        e1.submit(reqs())
        e2 = ServeEngine(model, params,
                         ServeConfig(max_batch=2, max_seq=64, replicas=2,
                                     mesh=mesh, sharding_profile="tp"))
        leaf_specs = [str(c.sharding.spec)
                      for c in jax.tree.leaves(e2.cache)]
        assert any("model" in s for s in leaf_specs), leaf_specs
        if N_DEV >= 4:
            assert any("data" in s for s in leaf_specs), leaf_specs
        e2.submit(reqs())
        assert e1.run_until_done() == e2.run_until_done()

    @needs2
    def test_mesh_slot_divisibility_checked(self):
        from disc import ServeConfig, ServeEngine
        cfg, model, params = _tiny_model()
        mesh = make_mesh((N_DEV,), ("data",))
        with pytest.raises(ValueError, match="divide"):
            ServeEngine(model, params,
                        ServeConfig(max_batch=1, max_seq=64,
                                    replicas=N_DEV + 1, mesh=mesh,
                                    sharding_profile="dp"))

    def test_replicas_validated(self):
        from disc import ServeConfig, ServeEngine
        cfg, model, params = _tiny_model()
        with pytest.raises(ValueError, match="replica"):
            ServeEngine(model, params, ServeConfig(replicas=0))

    def test_profile_without_mesh_rejected(self):
        # mirror CompileOptions: no silent single-device fallback
        from disc import ServeConfig, ServeEngine
        cfg, model, params = _tiny_model()
        with pytest.raises(ValueError, match="needs a mesh"):
            ServeEngine(model, params,
                        ServeConfig(sharding_profile="fsdp"))

    @needs2
    def test_custom_profile_batch_axes_drive_engine_layout(self):
        # the engine's cache layout / divisibility guard follow the
        # PROFILE's batch axes, not a hardcoded DP set
        from disc import ServeConfig, ServeEngine, get_profile
        cfg, model, params = _tiny_model()
        mesh = make_mesh((2,), ("model",))  # no data axis at all
        prof = get_profile("dp").replace(name="mp",
                                         dim_axes=(("B", ("model",)),))
        eng = ServeEngine(model, params,
                          ServeConfig(max_batch=2, max_seq=64, replicas=2,
                                      mesh=mesh, sharding_profile=prof))
        assert eng._dp_axes == ("model",)
        leaf = jax.tree.leaves(eng.cache)[0]
        assert "model" in str(leaf.sharding.spec)
        with pytest.raises(ValueError, match="divide"):
            ServeEngine(model, params,
                        ServeConfig(max_batch=1, max_seq=64, replicas=3,
                                    mesh=mesh, sharding_profile=prof))
