"""Distribution layer: sharding rules, legalization, multi-device subprocess
tests (compressed psum, sharded train step)."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.registry import get_config
from repro.distributed import roofline as rf
from repro.distributed import sharding as sh
from repro.launch import specs as specs_lib
from repro.train import optimizer as opt_lib

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _subproc(body: str, devices: int = 8) -> str:
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import sys; sys.path.insert(0, {repr(SRC)})
        import jax, jax.numpy as jnp, numpy as np
    """) + textwrap.dedent(body)
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_param_rules_cover_all_archs():
    """Every parameter leaf of every arch matches a rule (or is replicated
    deliberately); matrices bigger than 1M params must not silently
    replicate."""
    rules = sh.ShardingRules(tp_axis="model", fsdp_axis=None, dp_axes=("data",))
    for arch in ("smollm-360m", "olmoe-1b-7b", "zamba2-7b",
                 "seamless-m4t-medium", "mamba2-130m", "llava-next-34b"):
        cfg = get_config(arch)
        params = specs_lib.param_shape_specs(cfg)
        specs = sh.param_specs(params, rules)
        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        sflat = jax.tree_util.tree_structure(params).flatten_up_to(specs)
        for (path, leaf), spec in zip(flat, sflat):
            n = int(np.prod(leaf.shape))
            if n > 4_000_000:
                assert any(e is not None for e in spec), \
                    f"{arch}: {sh._path_str(path)} ({n} params) replicated"


def test_legalize_drops_indivisible():
    mesh = jax.make_mesh((1,), ("model",))  # 1 device: everything divisible
    # synthetic: mesh with model=16 can't shard dim of 15
    import unittest.mock as mock
    fake_mesh = mock.Mock()
    fake_mesh.axis_names = ("model",)
    fake_mesh.devices = np.empty((16,))
    spec_tree = {"w": P(None, "model")}
    shapes = {"w": jax.ShapeDtypeStruct((4, 15), jnp.float32)}
    legal, dropped = sh.legalize(spec_tree, shapes, fake_mesh)
    assert legal["w"] == P(None, None)
    assert len(dropped) == 1


def test_compressed_psum_matches_exact():
    out = _subproc("""
        from repro.distributed.compress import compressed_psum
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 16))
        exact = x * 4  # psum over data of replicated x = 4x
        got = compressed_psum(x, mesh, "data")
        rel = float(jnp.abs(got - exact).max() / jnp.abs(exact).max())
        assert rel < 0.02, rel
        print("PSUM_OK", rel)
    """)
    assert "PSUM_OK" in out


@pytest.mark.slow
def test_sharded_train_step_multidevice():
    """Small sharded train step on a 4x2 mesh runs and is finite."""
    out = _subproc("""
        from repro.configs.registry import get_config
        from repro.distributed import sharding as sh
        from repro.train import optimizer as opt_lib, train_step as ts_lib
        from jax.sharding import PartitionSpec as P
        cfg = get_config("smollm-360m").reduced(
            n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
            d_ff=128, vocab_size=256, vocab_pad_multiple=32,
            dtype="float32", remat="none")
        opt_cfg = opt_lib.OptimizerConfig(warmup_steps=0, total_steps=5)
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        rules = sh.ShardingRules(tp_axis="model", fsdp_axis=None,
                                 dp_axes=("data",))
        state = ts_lib.init_train_state(jax.random.PRNGKey(0), cfg, opt_cfg)
        pspecs, _ = sh.legalize(sh.param_specs(state["params"], rules),
                                state["params"], mesh)
        sspecs = {"params": pspecs,
                  "opt": sh.opt_state_specs(pspecs, state["opt"]),
                  "step": P()}
        batch = {
            "tokens": jnp.zeros((8, 16), jnp.int32),
            "labels": jnp.zeros((8, 16), jnp.int32),
            "loss_mask": jnp.ones((8, 16), jnp.float32),
        }
        bspecs, _ = sh.legalize(sh.batch_specs(batch, rules), batch, mesh)
        step = jax.jit(ts_lib.make_train_step(cfg, opt_cfg),
                       in_shardings=(sh.named(mesh, sspecs),
                                     sh.named(mesh, bspecs)),
                       donate_argnums=(0,))
        with mesh:
            state = jax.device_put(state, sh.named(mesh, sspecs))
            batch = jax.device_put(batch, sh.named(mesh, bspecs))
            state, metrics = step(state, batch)
        loss = float(metrics["loss"])
        assert np.isfinite(loss)
        print("SHARDED_OK", loss)
    """)
    assert "SHARDED_OK" in out


def test_roofline_analyzer_counts_loops():
    """The loop-aware analyzer must multiply while bodies by trip count."""
    def scanned(x, ws):
        def body(c, w):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, ws)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((12, 64, 64), jnp.float32)
    compiled = jax.jit(scanned).lower(x, ws).compile()
    res = rf.analyze(compiled.as_text())
    want = 2 * 64 * 64 * 64 * 12
    assert abs(res["dot_flops"] - want) / want < 0.05, res["dot_flops"]
    # and the body-once xla number really is ~12x smaller
    xla = rf.xla_cost_analysis(compiled)["flops"]
    assert res["dot_flops"] > 8 * xla


def test_roofline_terms_and_dominance():
    a = {"dot_flops": 197e12, "hbm_bytes": 819e9 / 2,
         "collective_bytes": {}, "collective_bytes_total": 50e9 * 2}
    t = rf.roofline_terms(a)
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["memory_s"] == pytest.approx(0.5)
    assert t["collective_s"] == pytest.approx(2.0)
    assert t["dominant"] == "collective"
