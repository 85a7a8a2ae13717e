"""Checkpointing (atomic/async/elastic) and the distributed stack
(sharding rules, DDP, pipeline, multi-device train step) — the
device-count-dependent parts run in subprocesses with
``--xla_force_host_platform_device_count``."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# meshes in the snippets below take Auto axes: the sharding rules
# annotate and let GSPMD propagate (jax.make_mesh defaults to Explicit)
PREAMBLE = "from jax.sharding import AxisType\nAUTO = AxisType.Auto\n"


def run_subprocess(code: str, n_devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{n_devices}")
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c",
                          PREAMBLE + textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=540)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        state = {"params": {"w": jnp.arange(6.0).reshape(2, 3)},
                 "step": jnp.int32(7)}
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(state, 7)
        restored = mgr.restore_latest(state)
        np.testing.assert_allclose(np.asarray(restored["params"]["w"]),
                                   np.asarray(state["params"]["w"]))
        assert int(restored["step"]) == 7

    def test_atomicity_no_tmp_left(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save({"x": jnp.ones(3)}, 1)
        names = os.listdir(tmp_path)
        assert "step_1" in names
        assert not any(n.endswith(".tmp") for n in names)
        assert os.path.exists(tmp_path / "step_1" / "manifest.json")

    def test_keep_n_retention(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep_n=2)
        for s in (1, 2, 3, 4):
            mgr.save({"x": jnp.ones(2) * s}, s)
        assert mgr.all_steps() == [3, 4]

    def test_async_save(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save_async({"x": jnp.ones(4)}, 5)
        mgr.wait()
        assert mgr.all_steps() == [5]

    def test_restore_latest_none_when_empty(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        assert mgr.restore_latest({"x": jnp.ones(1)}) is None

    def test_elastic_restore_between_meshes(self, tmp_path):
        """Save under a 4-way mesh, restore under an 8-way mesh."""
        out = run_subprocess(f"""
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.checkpoint import CheckpointManager
            mesh4 = jax.make_mesh((4,), ("data",),
                devices=jax.devices()[:4], axis_types=(AUTO,))
            w = jax.device_put(jnp.arange(32.0).reshape(8, 4),
                               NamedSharding(mesh4, P("data", None)))
            mgr = CheckpointManager(r"{tmp_path}")
            mgr.save({{"w": w}}, 1)

            mesh8 = jax.make_mesh((8,), ("data",), axis_types=(AUTO,))
            like = jax.device_put(jnp.zeros((8, 4)),
                                  NamedSharding(mesh8, P("data", None)))
            restored = mgr.restore(1, {{"w": like}}, mesh8)
            np.testing.assert_allclose(np.asarray(restored["w"]),
                                       np.arange(32.0).reshape(8, 4))
            assert restored["w"].sharding.mesh.shape["data"] == 8
            print("ELASTIC_OK")
        """)
        assert "ELASTIC_OK" in out


class TestShardingRules:
    def test_param_specs_divisibility(self):
        """Property: every sharded dim must be divisible by the mesh axis
        it is sharded over — checked for all archs × both meshes."""
        out = run_subprocess("""
            import jax
            from repro.configs import ARCHS, get_config
            from repro.models.lm import abstract_params
            from repro.distributed.sharding import param_specs
            mesh = jax.make_mesh((2, 4), ("data", "model"),
                                 axis_types=(AUTO,) * 2)
            for arch in ARCHS:
                cfg = get_config(arch)
                ap = abstract_params(cfg)
                specs = param_specs(cfg, ap, mesh)
                flat_p = jax.tree_util.tree_leaves(ap)
                flat_s = jax.tree_util.tree_leaves(
                    specs, is_leaf=lambda x: isinstance(
                        x, jax.sharding.PartitionSpec))
                assert len(flat_p) == len(flat_s)
                for leaf, spec in zip(flat_p, flat_s):
                    for dim, entry in zip(leaf.shape, tuple(spec)):
                        if entry is None:
                            continue
                        axes = entry if isinstance(entry, tuple) \\
                            else (entry,)
                        k = 1
                        for a in axes:
                            k *= mesh.shape[a]
                        assert dim % k == 0, (arch, leaf.shape, spec)
            print("SPECS_OK")
        """)
        assert "SPECS_OK" in out

    def test_train_step_runs_and_learns_on_mesh(self):
        out = run_subprocess("""
            import jax, jax.numpy as jnp
            from repro.configs import get_smoke_config
            from repro.launch.train import make_train_step
            from repro.models.lm import init_params
            from repro.optim.functional import make_optimizer
            mesh = jax.make_mesh((4, 2), ("data", "model"),
                                 axis_types=(AUTO,) * 2)
            cfg = get_smoke_config("gemma-2b")
            batch_abs = {
                "tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32),
                "labels": jax.ShapeDtypeStruct((8, 16), jnp.int32),
            }
            step, shardings, state_abs, _ = make_train_step(
                cfg, mesh, optimizer="adamw", lr=1e-2,
                batch_abs=batch_abs)
            with mesh:
                params = jax.jit(
                    lambda k: init_params(cfg, k),
                    out_shardings=shardings["params"])(jax.random.key(0))
                init_opt, _ = make_optimizer("adamw", lr=1e-2)
                opt = jax.jit(init_opt,
                              out_shardings=shardings["opt"])(params)
                state = {"params": params, "opt": opt,
                         "step": jnp.zeros((), jnp.int32)}
                tok = jax.random.randint(jax.random.key(1), (8, 16), 0,
                                         cfg.vocab_size)
                batch = {"tokens": tok, "labels": tok}
                losses = []
                for _ in range(12):
                    state, metrics = step(state, batch)
                    losses.append(float(metrics["loss"]))
            assert losses[-1] < losses[0] * 0.9, losses
            assert int(state["step"]) == 12
            print("TRAIN_MESH_OK", round(losses[0], 3),
                  "->", round(losses[-1], 3))
        """)
        assert "TRAIN_MESH_OK" in out

    def test_grad_accumulation_matches_full_batch(self):
        out = run_subprocess("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs import get_smoke_config
            from repro.launch.train import make_train_step
            from repro.models.lm import init_params
            from repro.optim.functional import make_optimizer
            mesh = jax.make_mesh((2, 1), ("data", "model"),
                                 axis_types=(AUTO,) * 2)
            cfg = get_smoke_config("yi-34b")
            batch_abs = {
                "tokens": jax.ShapeDtypeStruct((8, 8), jnp.int32),
                "labels": jax.ShapeDtypeStruct((8, 8), jnp.int32),
            }
            def build(accum):
                return make_train_step(cfg, mesh, optimizer="sgd",
                                       lr=0.1, batch_abs=batch_abs,
                                       accum_steps=accum, donate=False)
            step1, sh, _, _ = build(1)
            step4, _, _, _ = build(4)
            with mesh:
                params = jax.jit(lambda k: init_params(cfg, k),
                                 out_shardings=sh["params"])(
                    jax.random.key(0))
                init_opt, _ = make_optimizer("sgd", lr=0.1)
                opt = init_opt(params)
                tok = jax.random.randint(jax.random.key(1), (8, 8), 0,
                                         cfg.vocab_size)
                batch = {"tokens": tok, "labels": tok}
                s0 = {"params": params, "opt": opt,
                      "step": jnp.zeros((), jnp.int32)}
                o1, m1 = step1(s0, batch)
                s0b = {"params": params, "opt": opt,
                       "step": jnp.zeros((), jnp.int32)}
                o4, m4 = step4(s0b, batch)
            np.testing.assert_allclose(float(m1["loss"]),
                                       float(m4["loss"]), rtol=1e-4)
            for a, b in zip(jax.tree_util.tree_leaves(o1["params"]),
                            jax.tree_util.tree_leaves(o4["params"])):
                np.testing.assert_allclose(
                    np.asarray(a, np.float32), np.asarray(b, np.float32),
                    rtol=2e-3, atol=2e-5)
            print("ACCUM_OK")
        """)
        assert "ACCUM_OK" in out


class TestDDPAndPipeline:
    def test_ddp_and_pipeline(self):
        out = run_subprocess("""
            import jax, jax.numpy as jnp, numpy as np
            import repro, repro.nn as nn
            import repro.nn.functional as F
            from repro.distributed.ddp import DistributedDataParallel
            from repro.distributed.pipeline import pipeline_apply
            mesh = jax.make_mesh((8,), ("data",), axis_types=(AUTO,))
            m = nn.Sequential(nn.Linear(16, 32), nn.ReLU(),
                              nn.Linear(32, 4))
            ddp = DistributedDataParallel(m, mesh=mesh, bucket_mb=1e-4)
            x = repro.randn(8, 16); y = repro.randint(0, 4, (8,))
            F.cross_entropy(ddp(x), y).backward()
            before = {id(p): np.asarray(p.grad.data).copy()
                      for p in m.parameters()}
            ddp.sync_gradients()
            for p in m.parameters():
                np.testing.assert_allclose(np.asarray(p.grad.data),
                                           before[id(p)], rtol=1e-5)
            assert ddp.stats["num_allreduce"] >= 2
            print("DDP_OK")

            mesh_p = jax.make_mesh((8,), ("pod",), axis_types=(AUTO,))
            ws = jax.random.normal(jax.random.key(0), (8, 16, 16)) * 0.1
            out = pipeline_apply(
                lambda w, x: jnp.tanh(x @ w["w"]), {"w": ws},
                jax.random.normal(jax.random.key(1), (32, 16)),
                mesh=mesh_p, n_microbatches=4)
            ref = jax.random.normal(jax.random.key(1), (32, 16))
            for i in range(8):
                ref = jnp.tanh(ref @ ws[i])
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-4, atol=2e-5)
            print("PIPELINE_OK")
        """)
        assert "DDP_OK" in out and "PIPELINE_OK" in out


class TestFaultTolerance:
    def test_train_restart_resumes(self, tmp_path):
        """Kill training mid-run; restart must resume from checkpoint."""
        code = f"""
            import jax.numpy as jnp
            from repro.configs import get_smoke_config
            from repro.launch.train import train_loop
            cfg = get_smoke_config("gemma3-1b")
            res = train_loop(cfg, steps={{steps}}, batch_size=4,
                             seq_len=16, optimizer="adamw", lr=1e-3,
                             checkpoint_dir=r"{tmp_path}",
                             checkpoint_every=3, log_every=100)
            print("STEPS_RUN", res["steps"])
        """
        out1 = run_subprocess(code.replace("{steps}", "7"), n_devices=1)
        assert "STEPS_RUN 7" in out1
        out2 = run_subprocess(code.replace("{steps}", "10"), n_devices=1)
        # resumed from step 7 checkpoint → only 3 more steps
        assert "STEPS_RUN 3" in out2
