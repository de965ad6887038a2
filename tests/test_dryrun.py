"""Dry-run machinery: HLO collective parser + small-mesh lower/compile for
one arch per family (subprocess: needs its own fake device count)."""
import subprocess
import sys
import textwrap

import pytest

from repro.roofline.analysis import collective_bytes, extrapolate


def test_collective_parser_on_synthetic_hlo():
    hlo = """
  %all-reduce.1 = f32[16,4096]{1,0} all-reduce(%x), replica_groups=[16,16]<=[256]
  %all-gather.2 = bf16[32,1024]{1,0} all-gather(%y), replica_groups=[4,8]<=[32], dimensions={0}
  %reduce-scatter.3 = f32[8,128]{1,0} reduce-scatter(%z), replica_groups=[2,4]<=[8]
  %all-to-all.4 = s32[64,64]{1,0} all-to-all(%w), replica_groups=[1,64]<=[64]
  %cp = f32[100]{0} collective-permute(%v), source_target_pairs={{0,1}}
  %all-gather-done.9 = bf16[32,1024]{1,0} all-gather-done(%ag)
"""
    out = collective_bytes(hlo)
    assert out["all-reduce"] == 16 * 4096 * 4
    assert out["all-gather"] == 32 * 1024 * 2 // 8      # operand = result/k
    assert out["reduce-scatter"] == 8 * 128 * 4 * 4     # operand = result*k
    assert out["all-to-all"] == 64 * 64 * 4
    assert out["collective-permute"] == 100 * 4
    assert out["_counts"]["all-gather"] == 1            # -done not counted


def test_extrapolate_linear():
    c1 = {"flops": 10.0, "bytes": 100.0, "nested": {"x": 1.0}}
    c2 = {"flops": 16.0, "bytes": 130.0, "nested": {"x": 3.0}}
    c8 = extrapolate(c1, c2, 8)
    assert c8["flops"] == 10 + 7 * 6
    assert c8["bytes"] == 100 + 7 * 30
    assert c8["nested"]["x"] == 1 + 7 * 2


SMALL_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, jax
    from repro import configs
    from repro.launch.dryrun import lower_cell
    from repro.roofline.analysis import cost_dict
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg0 = configs.get_config("{arch}")
    pattern = len(cfg0.superblock())
    cfg = dataclasses.replace(cfg0, num_layers=pattern,
                              enc_layers=min(cfg0.enc_layers, 1))
    comp, low, secs = lower_cell(cfg, "{kind}", {seq}, {batch}, mesh, 4)
    assert cost_dict(comp.cost_analysis()).get("flops", 0) > 0
    txt = comp.as_text()
    print("OK", comp.memory_analysis().argument_size_in_bytes)
""")


@pytest.mark.slow
@pytest.mark.parametrize("arch,kind,seq,batch", [
    ("yi-6b", "train", 256, 8),
    ("mixtral-8x22b", "train", 256, 8),
    ("jamba-v0.1-52b", "decode", 1024, 8),
    ("mamba2-370m", "train", 256, 8),
    ("seamless-m4t-medium", "prefill", 256, 8),
    ("llama-3.2-vision-11b", "train", 256, 8),
])
def test_small_mesh_lower_compile(arch, kind, seq, batch):
    """Every family lowers + compiles on a 3-axis (pod, data, model) mesh —
    the small-scale replica of the production multi-pod dry-run."""
    code = SMALL_MESH_SCRIPT.format(arch=arch, kind=kind, seq=seq,
                                    batch=batch)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root", "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
