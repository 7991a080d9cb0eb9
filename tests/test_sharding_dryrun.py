"""Sharding-system property tests + a reduced multi-device dry run.

``resolve_pspec`` properties are checked with hypothesis. The actual
multi-device lower+compile is exercised in a SUBPROCESS with
``xla_force_host_platform_device_count=8`` (device count locks at first
jax init, so it can never run in the main pytest process).
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest
pytest.importorskip("hypothesis")  # optional dep: pip install -e .[test]
from hypothesis import given, settings, strategies as st

import jax
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, get_config, smoke_config
from repro.models.layers import axis_rules, resolve_pspec

REPO = os.path.join(os.path.dirname(__file__), "..")


class TestResolvePspec:
    @pytest.fixture(scope="class")
    def mesh(self):
        # a fake mesh object exposing axis_names + shape, no devices needed
        class FakeMesh:
            axis_names = ("data", "model")
            shape = {"data": 4, "model": 2}

        return FakeMesh()

    @given(st.lists(st.sampled_from(
        ["batch", "heads", "ff", "vocab", "embed", None, "kv_heads"]),
        min_size=1, max_size=4),
        st.lists(st.integers(1, 64), min_size=1, max_size=4))
    @settings(max_examples=50, deadline=None)
    def test_divisibility_and_axis_uniqueness(self, logical, dims):
        class FakeMesh:
            axis_names = ("data", "model")
            shape = {"data": 4, "model": 2}

        mesh = FakeMesh()
        n = min(len(logical), len(dims))
        logical, dims = logical[:n], dims[:n]
        cfg = get_config("yi-6b")
        spec = resolve_pspec(logical, dims, mesh, axis_rules(cfg))
        used = []
        for entry, dim in zip(list(spec), dims):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            total = 1
            for a in axes:
                assert a not in used, "mesh axis used twice"
                used.append(a)
                total *= mesh.shape[a]
            assert dim % total == 0, "sharded dim must divide axis size"

    def test_indivisible_falls_back_to_replicated(self):
        class FakeMesh:
            axis_names = ("data", "model")
            shape = {"data": 4, "model": 16}

        cfg = get_config("gemma-2b")   # 8 heads < 16-way model axis
        spec = resolve_pspec(("batch", "seq", "heads", "head_dim"),
                             (32, 128, 8, 256), FakeMesh(), axis_rules(cfg))
        # trailing Nones are stripped by PartitionSpec; only batch shards
        assert list(spec) == ["data"]

    def test_fsdp_rules_shard_weights_over_data(self):
        class FakeMesh:
            axis_names = ("data", "model")
            shape = {"data": 16, "model": 16}

        cfg = get_config("llama3-405b")
        assert cfg.sharding == "fsdp_tp"
        spec = resolve_pspec(("layers", "embed_w", "heads", "head_dim"),
                             (126, 16384, 128, 128), FakeMesh(), axis_rules(cfg))
        assert list(spec) == [None, "data", "model"]


@pytest.mark.slow
class TestSmallMeshDryRun:
    """Real lower+compile on an 8-device CPU mesh, one subprocess per family."""

    @pytest.mark.parametrize("arch", ["yi-6b", "qwen3-moe-30b-a3b", "rwkv6-1.6b",
                                      "recurrentgemma-2b", "whisper-large-v3",
                                      "internvl2-1b"])
    def test_reduced_dryrun_compiles(self, arch):
        code = textwrap.dedent(f"""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import jax, json
            import jax.numpy as jnp
            from jax.sharding import NamedSharding
            from repro.configs import smoke_config
            from repro.configs.base import ShapeConfig
            from repro.models import mesh_context
            from repro.models.model_api import build_model
            from repro.train.optimizer import OptimizerConfig, init_opt_state
            from repro.train.train_step import make_train_step
            from repro.launch.dryrun import _sds, _opt_pspecs

            mesh = jax.make_mesh((4, 2), ("data", "model"),
                                 axis_types=(jax.sharding.AxisType.Auto,) * 2)
            cfg = smoke_config({arch!r}).with_(d_model=64, n_heads=4, head_dim=16,
                                               d_ff=128, grad_accum=2)
            model = build_model(cfg)
            oc = OptimizerConfig()
            shape = ShapeConfig("t", 32, 8, "train")
            with mesh_context(mesh, cfg):
                p_specs = model.pspecs(mesh)
                p_sds = _sds(model.shapes(), p_specs, mesh)
                opt_shapes = jax.eval_shape(lambda p: init_opt_state(p, oc), p_sds)
                o_sds = _sds(opt_shapes, _opt_pspecs(p_specs, opt_shapes, oc), mesh)
                batch_sds = model.input_specs(shape, mesh)
                step = make_train_step(model, oc, mesh)
                compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
                    p_sds, o_sds, batch_sds).compile()
            cost = compiled.cost_analysis()
            if isinstance(cost, list): cost = cost[0]
            print(json.dumps({{"flops": float(cost.get("flops", 0))}}))
        """)
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
        proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                              text=True, env=env, timeout=420)
        assert proc.returncode == 0, proc.stderr[-3000:]
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        assert out["flops"] > 0

    def test_decode_writes_a_sequence_sharded_cache_in_place(self):
        """The decode step's in-place cache write partitions without
        gathering the cache: with 2 kv heads on a 4-way model axis the
        cache shards its sequence, and only the new token, its indices
        and the query cross devices."""
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import jax, json, re
            from repro.configs import smoke_config
            from repro.configs.base import ShapeConfig
            from repro.models import mesh_context
            from repro.models.model_api import build_model
            from repro.serve.decode import make_dryrun_serve_step
            from repro.launch.dryrun import _sds

            mesh = jax.make_mesh((2, 4), ("data", "model"),
                                 axis_types=(jax.sharding.AxisType.Auto,) * 2)
            cfg = smoke_config("yi-6b").with_(d_model=64, n_heads=4, n_kv_heads=2,
                                              head_dim=16, d_ff=128)
            model = build_model(cfg)
            B, S = 4, 64
            with mesh_context(mesh, cfg):
                c_specs = model.cache_pspecs(mesh, B, S)
                p_sds = _sds(model.shapes(), model.pspecs(mesh), mesh)
                c_sds = _sds(model.cache_shapes(B, S), c_specs, mesh)
                io = model.input_specs(ShapeConfig("d", S, B, "decode"), mesh)
                compiled = jax.jit(make_dryrun_serve_step(model), donate_argnums=(1,)).lower(
                    p_sds, c_sds, io["tokens"], io["lengths"]).compile()
            moved = []
            for m in re.finditer(r"= \\w+\\[([\\d,]*)\\]\\S* (all-gather|all-to-all|collective-permute)\\(",
                                 compiled.as_text()):
                n = 1
                for d in filter(None, m.group(1).split(",")):
                    n *= int(d)
                moved.append(n)
            print(json.dumps({"spec": [str(a) for a in c_specs["layers"]["k"]],
                              "moved": moved}))
        """)
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
        proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                              text=True, env=env, timeout=420)
        assert proc.returncode == 0, proc.stderr[-3000:]
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        assert out["spec"][3] == "model"                     # the sequence is sharded
        per_device_layer = (4 // 2) * 2 * (64 // 4) * 16     # B/data x KV x S/model x hd
        assert max(out["moved"], default=0) < per_device_layer
