"""Sharding rules: spec assignment, divisibility sanitisation, and a real
jit lowering through the specs machinery on a 1x1 mesh (the full 16x16 /
2x16x16 meshes are exercised by launch/dryrun.py, which owns the 512-device
flag)."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.distributed import default_rules, param_shardings, use_sharding
from repro.distributed.sharding import sanitize_spec
from repro.launch.mesh import make_mesh
from repro.launch.specs import build_step_spec, shape_rules
from repro.models import build_model


def _mesh11():
    return make_mesh((1, 1), ("data", "model"))


def test_param_specs_assigned_by_name():
    cfg = get_config("qwen2-0.5b").reduced()
    model = build_model(cfg)
    shapes = model.init_shapes()
    mesh = _mesh11()
    rules = default_rules(mesh, fsdp=True)
    sh = param_shardings(shapes, mesh, rules)
    # attention projection: fsdp x tp (leading None = stacked layer dim)
    blk = sh["block"]["pos0"]["attn"]
    assert blk["wq"].spec == P(None, "data", "model")
    assert blk["wo"].spec == P(None, "model", "data")
    # norms replicated (P(None) == unsharded dim)
    assert sh["final_ln"].spec in (P(), P(None))


def test_stacked_leading_dims_get_none():
    cfg = get_config("grok-1-314b").reduced()
    shapes = build_model(cfg).init_shapes()
    mesh = _mesh11()
    sh = param_shardings(shapes, mesh, default_rules(mesh, fsdp=True))
    we = sh["block"]["pos0"]["moe"]["we_gate"]      # [R, E, D, F]
    assert we.spec == P(None, None, "data", "model")


def test_sanitize_spec_drops_nondivisible():
    mesh = make_mesh((1, 1), ("data", "model"))
    # 1x1 mesh divides everything — use shape logic directly via a fake
    spec = sanitize_spec(P("data", "model"), (10, 16), mesh)
    assert spec == P("data", "model")               # 1 divides all

    class FakeMesh:
        shape = {"data": 16, "model": 16}
    spec = sanitize_spec(P("data", "model"), (50280, 32), FakeMesh())
    assert spec == P(None, "model")                 # 50280 % 16 != 0


def test_constrain_is_noop_without_mesh():
    from repro.distributed import constrain
    x = jnp.ones((4, 4))
    y = constrain(x, "batch", "tp")
    np.testing.assert_array_equal(x, y)


def test_step_specs_lower_on_host_mesh():
    """End-to-end: every step kind lowers+compiles through the dry-run glue
    (reduced config, 1x1 mesh, tiny shapes injected)."""
    cfg = get_config("qwen2-0.5b").reduced()
    mesh = _mesh11()
    import repro.launch.specs as specs_mod
    saved = dict(specs_mod.SHAPES)
    specs_mod.SHAPES = {
        "train_4k": dict(seq=32, batch=2, kind="train"),
        "prefill_32k": dict(seq=32, batch=2, kind="prefill"),
        "decode_32k": dict(seq=32, batch=2, kind="decode"),
        "long_500k": dict(seq=64, batch=1, kind="decode"),
    }
    try:
        for shape in specs_mod.SHAPES:
            rules = shape_rules(cfg, shape, mesh, fsdp=False)
            spec = build_step_spec(cfg, shape)
            with use_sharding(mesh, rules):
                jitted = jax.jit(
                    spec.fn, in_shardings=spec.in_shardings(mesh, rules),
                    out_shardings=spec.out_shardings(mesh, rules),
                    donate_argnums=spec.donate_argnums)
                compiled = jitted.lower(*spec.args).compile()
            assert compiled.cost_analysis() is not None
    finally:
        specs_mod.SHAPES = saved


def test_shape_rules_long_context():
    cfg = get_config("yi-34b")
    mesh = _mesh11()
    rules = shape_rules(cfg, "long_500k", mesh)
    assert rules["batch"] is None                   # batch=1: no data shard
    assert "model" in rules["kv_seq"]
    assert rules["fsdp"] == "data"                  # 34B > threshold
