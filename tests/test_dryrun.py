"""Dry-run machinery on a small 8-device mesh (subprocess so the forced
device count doesn't leak into other tests) + roofline parser units.
"""
import subprocess
import sys
import textwrap

import pytest
from _subproc import ENV

from repro.launch.roofline import Roofline, _shape_bytes, collective_bytes


def test_shape_bytes():
    assert _shape_bytes("bf16[16,512]") == 16 * 512 * 2
    assert _shape_bytes("f32[2,3,4]{2,1,0}") == 96
    assert _shape_bytes("(f32[2], u32[4])") == 8 + 16
    assert _shape_bytes("pred[]") == 1
    assert _shape_bytes("token[]") == 0


def test_collective_bytes_parse():
    hlo = textwrap.dedent("""
      %ag = bf16[512,128]{1,0} all-gather(%x), dimensions={0}
      ROOT %ar = f32[64]{0} all-reduce(%y), to_apply=%add
      %cp = f32[8]{0} collective-permute(%z), source_target_pairs={{0,1}}
      %a2a.1 = (f32[4]{0}, f32[4]{0}) all-to-all(%p, %q)
      %rs-start = bf16[32]{0} reduce-scatter-start(%w)
      %not = f32[9]{0} add(%a, %b)
    """)
    out = collective_bytes(hlo)
    assert out["all-gather"] == 512 * 128 * 2
    assert out["all-reduce"] == 256
    assert out["collective-permute"] == 32
    assert out["all-to-all"] == 32
    assert out["reduce-scatter"] == 64


def test_roofline_terms_and_dominant():
    r = Roofline(flops=197e12, hbm_bytes=819e9 * 2, coll_bytes=50e9 * 0.5,
                 coll_detail={})
    assert abs(r.compute_s - 1.0) < 1e-9
    assert abs(r.memory_s - 2.0) < 1e-9
    assert abs(r.collective_s - 0.5) < 1e-9
    assert r.dominant == "memory"
    d = r.as_dict()
    assert d["dominant"] == "memory"


_SMALL_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, jax
    import repro.launch.mesh as lm
    # shrink the production mesh for an 8-device smoke of the dry-run path
    lm.SINGLE_POD = (2, 4); lm.MULTI_POD = (2, 2, 2)
    import repro.launch.dryrun as dr
    import repro.configs.base as cb
    # mutate IN PLACE: inputs.py/dryrun.py/etc. hold references to this dict
    cb.INPUT_SHAPES.clear()
    cb.INPUT_SHAPES.update({
        "train_4k": dict(seq_len=64, global_batch=8, kind="train"),
        "prefill_32k": dict(seq_len=128, global_batch=4, kind="prefill"),
        "decode_32k": dict(seq_len=128, global_batch=8, kind="decode"),
        "long_500k": dict(seq_len=256, global_batch=2, kind="decode"),
    })
    dr.TRAIN_ACCUM.clear()
    real_get = dr.get_config
    dr.arch_config.__globals__["get_config"] = (
        lambda a, **kw: real_get(a, smoke=True))
    dr.LONG_OK["qwen2.5-3b"] = 64
    ok = err = 0
    for mesh_kw in ({}, {"multi_pod": True}):
        mesh = lm.make_production_mesh(**mesh_kw)
        for arch in ["qwen2.5-3b", "deepseek-v2-236b", "rwkv6-3b",
                     "zamba2-1.2b", "seamless-m4t-large-v2", "internvl2-2b"]:
            for shape in ["train_4k", "prefill_32k", "decode_32k"]:
                with mesh:
                    r = dr.lower_one(arch, shape, mesh, verbose=False)
                assert r["roofline"]["flops_per_device"] > 0
                ok += 1
    # fedsikd distillation step lowers too (the paper's technique)
    mesh = lm.make_production_mesh()
    with mesh:
        r = dr.lower_one("qwen2.5-3b", "train_4k", mesh, step_kind="fedsikd",
                         verbose=False)
    assert r["step"] == "fedsikd"
    print(f"DRYRUN-OK {ok}")
""")


@pytest.mark.slow
def test_small_mesh_dryrun_all_families():
    r = subprocess.run([sys.executable, "-c", _SMALL_MESH_SCRIPT],
                       capture_output=True, text=True, timeout=1200, env=ENV)
    assert "DRYRUN-OK" in r.stdout, r.stdout[-2000:] + r.stderr[-4000:]
