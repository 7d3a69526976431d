"""Dry-run cell machinery on a small subprocess mesh: build_cell +
lower + compile + loop-aware analysis for one cell of each kind."""
import os

from test_multidevice import run_with_devices


def test_cells_lower_on_small_mesh():
    run_with_devices("""
        import jax
        from repro.configs.base import get_smoke_config
        from repro.launch.cells import build_cell
        from repro.launch.mesh import make_mesh
        from repro.launch.hlo_analysis import analyze_hlo
        from repro.sharding import rules

        mesh = make_mesh((4, 2), ("data", "model"))
        import repro.configs.base as B
        # shrink the shape cells for the smoke configs
        B.SHAPE_CELLS = {
            "train_4k": B.ShapeCell("train_4k", 32, 8, "train"),
            "prefill_32k": B.ShapeCell("prefill_32k", 64, 4, "prefill"),
            "decode_32k": B.ShapeCell("decode_32k", 64, 8, "decode"),
        }
        for arch, cell in [("tinyllama_1_1b", "train_4k"),
                           ("qwen3_moe_30b_a3b", "train_4k"),
                           ("gemma3_12b", "prefill_32k"),
                           ("rwkv6_1_6b", "decode_32k"),
                           ("hymba_1_5b", "decode_32k")]:
            cfg = get_smoke_config(arch)
            spec = build_cell(arch, cell, mesh, cfg=cfg, ce_chunk=16)
            with rules.activate(mesh):
                compiled = jax.jit(spec.fn, in_shardings=spec.in_shardings,
                                   out_shardings=spec.out_shardings,
                                   donate_argnums=spec.donate
                                   ).lower(*spec.args).compile()
            cost = analyze_hlo(compiled.as_text())
            assert cost.dot_flops > 0, (arch, cell)
            print(arch, cell, "OK", int(cost.dot_flops))
    """, n=8, timeout=600)
