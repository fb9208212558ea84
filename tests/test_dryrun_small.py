"""Dry-run machinery tests at CPU scale (no 512-device requirement).

The full production-mesh pass lives in ``launch/dryrun.py`` (results under
``results/``); these tests exercise the same code path on a 1×1 mesh so the
shape/sharding plumbing is covered by pytest.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import hlo as hlolib
from repro.configs import ARCH_NAMES, get_config, smoke_config
from repro.launch.shapes import SHAPES, cell_supported, input_specs
from repro.parallel.sharding import auto_mesh


def test_cell_support_matrix():
    expected_skips = {
        ("llava-next-mistral-7b", "long_500k"),
        ("smollm-135m", "long_500k"),
        ("phi3-medium-14b", "long_500k"),
        ("gemma-7b", "long_500k"),
        ("qwen3-8b", "long_500k"),
        ("deepseek-v2-236b", "long_500k"),
        ("grok-1-314b", "long_500k"),
        ("whisper-medium", "long_500k"),
    }
    skips = set()
    for arch in ARCH_NAMES:
        for shape in SHAPES:
            ok, reason = cell_supported(get_config(arch), shape)
            if not ok:
                skips.add((arch, shape))
                assert reason
    assert skips == expected_skips


@pytest.mark.parametrize("arch", ARCH_NAMES)
@pytest.mark.parametrize("shape", ["train_4k", "decode_32k"])
def test_input_specs_are_abstract(arch, shape):
    cfg = get_config(arch)
    ok, _ = cell_supported(cfg, shape)
    if not ok:
        pytest.skip("unsupported cell")
    spec = input_specs(cfg, shape)
    for leaf in jax.tree.leaves(spec, is_leaf=lambda x: isinstance(
            x, jax.ShapeDtypeStruct)):
        if isinstance(leaf, jax.ShapeDtypeStruct):
            assert leaf.shape is not None     # never a concrete array
    if shape == "train_4k":
        b = SHAPES[shape]["global_batch"]
        leaves = jax.tree.leaves(spec["batch"])
        assert all(l.shape[0] == b for l in leaves)
    else:
        assert spec["tokens"].shape == (SHAPES[shape]["global_batch"],)
        assert spec["caches"] is not None


def test_smoke_cell_lowers_and_compiles():
    """The dry-run path end-to-end on a 1-device mesh with a smoke config."""
    from repro.launch import dryrun

    cfg = smoke_config(get_config("qwen3-8b"))
    mesh = auto_mesh((1, 1), ("data", "model"))
    # Reuse build_cell with a smoke config by monkey-building inputs.
    import jax.numpy as jnp
    from repro.models import model as M
    from repro.optim import adamw
    from repro.parallel import sharding as shardlib

    params = jax.eval_shape(lambda: M.init_params(jax.random.key(0), cfg))
    pshard = shardlib.param_shardings(params, mesh)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 17), jnp.int32)}

    def step(p, b):
        loss, m = M.train_loss(p, b, cfg)
        return loss

    with mesh, shardlib.activation_shardings(mesh):
        compiled = jax.jit(step, in_shardings=(
            pshard, {"tokens": shardlib.data_sharding_if_divisible(
                mesh, (2, 17))})).lower(params, batch).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0
    cost = compiled.cost_analysis()
    assert cost.get("flops", 0) > 0


def test_hlo_collective_parser():
    txt = """
  %ag = bf16[8,512] all-gather(%p0), replica_groups={}
  %ar.1 = f32[128] all-reduce(%x), to_apply=%sum
  %tup = (f32[64], f32[32]) all-to-all(%a, %b)
  %cp = u32[16] collective-permute(%c)
"""
    per = hlolib.collective_bytes(txt)
    assert per["all-gather"] == 8 * 512 * 2
    assert per["all-reduce"] == 128 * 4
    assert per["all-to-all"] == 64 * 4 + 32 * 4
    assert per["collective-permute"] == 16 * 4
    assert hlolib.total_collective_bytes(txt) == (
        8 * 512 * 2 + 128 * 4 + 96 * 4 + 64)
