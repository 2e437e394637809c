"""Roofline plumbing tests: HLO collective parsing + the 3-term model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro  # noqa: F401
from repro.models import SHAPE_CELLS
from repro.roofline import hlo_parse
from repro.roofline.model import (
    PEAK_FLOPS,
    RooflineReport,
    active_params,
    analytic_memory_traffic,
    analytic_peak_memory,
    model_flops_train,
)


class TestHLOParse:
    def test_collective_bytes_synthetic(self):
        hlo = """
        ENTRY main {
          %x = f32[1024,256]{1,0} parameter(0)
          %ag = f32[1024,4096]{1,0} all-gather(%x), dimensions={1}
          %ar = bf16[512]{0} all-reduce(%y), to_apply=%add
          %rs = f32[128]{0} reduce-scatter(%z), dimensions={0}
          %cp = f32[64,64]{1,0} collective-permute(%w)
          %dot = f32[8,8]{1,0} dot(%a, %b)
        }
        """
        out = hlo_parse.collective_bytes(hlo)
        assert out["all-gather"] == 1024 * 4096 * 4
        assert out["all-reduce"] == 512 * 2
        assert out["reduce-scatter"] == 128 * 4
        assert out["collective-permute"] == 64 * 64 * 4
        assert out["count"] == 4

    def test_real_compiled_psum(self):
        """Parse a real 4-device compiled module and find its all-reduce."""
        import subprocess, sys, textwrap, os, pathlib

        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["PYTHONPATH"] = str(pathlib.Path(__file__).parent.parent / "src")
        r = subprocess.run(
            [sys.executable, "-c", textwrap.dedent("""
                import jax, jax.numpy as jnp, functools
                from jax.sharding import PartitionSpec as P
                from repro.distributed.shard_fused import shard_map
                import repro
                from repro.launch.mesh import make_mesh
                from repro.roofline import hlo_parse

                mesh = make_mesh((4,), ("d",))
                @functools.partial(shard_map, mesh=mesh, in_specs=P("d"), out_specs=P())
                def f(x):
                    return jax.lax.psum(x.sum(0), "d")
                c = jax.jit(f).lower(jax.ShapeDtypeStruct((8, 128), jnp.float32)).compile()
                out = hlo_parse.collective_bytes(c.as_text())
                assert out["all-reduce"] >= 128 * 4, out
                print("OK", out["all-reduce"])
            """)],
            capture_output=True, text=True, timeout=300, env=env,
        )
        assert r.returncode == 0, r.stderr[-1500:]

    def test_op_histogram(self):
        hlo = "%d = f32[8,8]{1,0} dot(%a, %b)\n%e = f32[8,8]{1,0} dot(%c, %d)"
        assert hlo_parse.op_histogram(hlo)["dot"] == 2


class TestRooflineModel:
    def test_report_terms_and_bottleneck(self):
        r = RooflineReport(
            name="t", chips=256,
            hlo_flops=1.97e14,      # exactly 1 second of compute
            hlo_bytes=819e9 / 2,    # 0.5 s of HBM
            coll_bytes=50e9 / 4,    # 0.25 s of ICI
            model_flops=1.97e14 * 256 * 0.5,
            peak_mem_bytes=8 * 2**30,
        )
        assert r.t_compute == pytest.approx(1.0)
        assert r.t_memory == pytest.approx(0.5)
        assert r.t_collective == pytest.approx(0.25)
        assert r.bottleneck == "compute"
        assert r.mfu == pytest.approx(0.5)

    def test_active_params_dense_vs_moe(self):
        from repro.configs import get_config

        dense = get_config("qwen2.5-32b")
        n = active_params(dense)
        assert 30e9 < n < 36e9, n  # ~32.8B params (embeddings included)
        moe = get_config("olmoe-1b-7b")
        n_act = active_params(moe)
        assert 0.9e9 < n_act < 1.6e9, n_act  # ~1.3B active

    def test_model_flops_train_scaling(self):
        from repro.configs import get_config

        cfg = get_config("olmo-1b")
        assert model_flops_train(cfg, 1000) == pytest.approx(
            6 * active_params(cfg) * 1000
        )

    def test_analytic_memory_positive_all_cells(self):
        from repro.configs import ARCH_IDS, get_config

        mesh_shape = {"data": 16, "model": 16}
        for arch in ARCH_IDS:
            cfg = get_config(arch)
            for cell in SHAPE_CELLS.values():
                assert analytic_memory_traffic(cfg, cell, mesh_shape) > 0
                assert analytic_peak_memory(cfg, cell, mesh_shape, 4) > 0
