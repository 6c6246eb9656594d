"""The §Perf sharding variants must (a) lower through the dry-run glue and
(b) compute the same mathematics as the baseline rules (the mesh is 1x1
here, so every layout is numerically identical by construction — what this
pins is that the variant *specs* are legal for every param/cache shape)."""
import jax
import pytest

from repro.configs import get_config
from repro.distributed import use_sharding
from repro.launch.mesh import make_mesh
from repro.launch.specs import build_step_spec, shape_rules
import repro.launch.specs as specs_mod

TINY_SHAPES = {
    "train_4k": dict(seq=32, batch=4, kind="train"),
    "decode_32k": dict(seq=32, batch=2, kind="decode"),
}


@pytest.fixture
def tiny_shapes():
    saved = dict(specs_mod.SHAPES)
    specs_mod.SHAPES = dict(TINY_SHAPES)
    yield
    specs_mod.SHAPES = saved


def _mesh11():
    return make_mesh((1, 1), ("data", "model"))


@pytest.mark.parametrize("moe_shard", ["fsdp", "2d", "ep"])
def test_moe_variants_lower_and_agree(tiny_shapes, moe_shard):
    cfg = get_config("grok-1-314b").reduced()
    mesh = _mesh11()
    rules = shape_rules(cfg, "train_4k", mesh, fsdp=True,
                        moe_shard=moe_shard)
    spec = build_step_spec(cfg, "train_4k")
    with use_sharding(mesh, rules):
        jitted = jax.jit(spec.fn,
                         in_shardings=spec.in_shardings(mesh, rules),
                         out_shardings=spec.out_shardings(mesh, rules),
                         donate_argnums=spec.donate_argnums)
        compiled = jitted.lower(*spec.args).compile()
    assert compiled.cost_analysis() is not None


@pytest.mark.parametrize("layout", ["dp", "2dtp"])
def test_decode_layouts_lower(tiny_shapes, layout):
    cfg = get_config("jamba-1.5-large-398b").reduced()
    mesh = _mesh11()
    rules = shape_rules(cfg, "decode_32k", mesh, fsdp=True, layout=layout,
                        moe_shard="2d" if layout == "2dtp" else "fsdp")
    spec = build_step_spec(cfg, "decode_32k")
    with use_sharding(mesh, rules):
        jitted = jax.jit(spec.fn,
                         in_shardings=spec.in_shardings(mesh, rules),
                         out_shardings=spec.out_shardings(mesh, rules),
                         donate_argnums=spec.donate_argnums)
        compiled = jitted.lower(*spec.args).compile()
    assert compiled.cost_analysis() is not None


def test_microbatched_spec_lowers(tiny_shapes):
    cfg = get_config("qwen2-0.5b").reduced()
    mesh = _mesh11()
    rules = shape_rules(cfg, "train_4k", mesh, fsdp=False)
    spec = build_step_spec(cfg, "train_4k", microbatches=2,
                           microbatch_unroll=True)
    with use_sharding(mesh, rules):
        compiled = jax.jit(
            spec.fn, in_shardings=spec.in_shardings(mesh, rules),
            out_shardings=spec.out_shardings(mesh, rules),
            donate_argnums=spec.donate_argnums).lower(*spec.args).compile()
    assert compiled.cost_analysis() is not None
