"""Distribution-layer tests that run on 1 CPU device.

Static sharding validity is checked against the production mesh *shape*
(16x16 and 2x16x16) without devices: every named axis in every param spec
must divide the corresponding dim for all 10 archs.  Functional execution
uses a degenerate (1,1) mesh.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.launch import steps as steps_mod
from repro.launch.hlo_analysis import analyze
from repro.launch.mesh import make_smoke_mesh
from repro.launch.sharding import make_param_pspecs

MESH_SHAPES = {"single": {"data": 16, "model": 16},
               "multi": {"pod": 2, "data": 16, "model": 16}}


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("mesh_name", ["single", "multi"])
def test_param_specs_divide_dims(arch, mesh_name):
    """Every sharded axis divides its dim on the production mesh (full cfg)."""
    cfg = get_config(arch)
    structs = steps_mod.param_specs(cfg)
    specs = make_param_pspecs(structs)
    sizes = MESH_SHAPES[mesh_name]

    def check(path, leaf, spec):
        for dim, ax in enumerate(tuple(spec) + (None,) * (leaf.ndim -
                                                          len(tuple(spec)))):
            if ax is None:
                continue
            axs = ax if isinstance(ax, tuple) else (ax,)
            n = 1
            for a in axs:
                n *= sizes.get(a, 1)
            assert leaf.shape[dim] % n == 0, \
                f"{arch}: {jax.tree_util.keystr(path)} dim{dim} " \
                f"{leaf.shape} not divisible by {ax}={n}"

    flat_s, _ = jax.tree_util.tree_flatten_with_path(structs)
    flat_p = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    for (path, leaf), spec in zip(flat_s, flat_p):
        check(path, leaf, spec)


def test_train_step_runs_on_smoke_mesh():
    cfg = get_config("smollm-135m", smoke=True)
    mesh = make_smoke_mesh()
    with jax.set_mesh(mesh):
        mk = steps_mod.make_train_step(cfg, mesh, optimizer_name="adamw",
                                       lr=1e-3)
        state = mk["make_init"](jax.random.PRNGKey(0))()
        batch = {"tokens": jnp.zeros((2, 32), jnp.int32),
                 "labels": jnp.zeros((2, 32), jnp.int32)}
        jitted = mk["jit"]({k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                            for k, v in batch.items()})
        state2, metrics = jitted(state, batch)
        assert bool(jnp.isfinite(metrics["loss"]))
        assert int(metrics["step"]) == 1


def test_decode_step_runs_on_smoke_mesh():
    cfg = get_config("granite-3-2b", smoke=True)
    mesh = make_smoke_mesh()
    with jax.set_mesh(mesh):
        mk = steps_mod.make_decode_step(cfg, mesh, max_seq=64, batch_size=2)
        params = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), steps_mod.param_specs(cfg))
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                             mk["cache_struct"])
        batch = {"token": jnp.zeros((2, 1), jnp.int32),
                 "pos": jnp.zeros((2,), jnp.int32)}
        jitted = mk["jit"]({k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                            for k, v in batch.items()})
        logits, new_cache = jitted(params, cache, batch)
        assert logits.shape == (2, 1, cfg.vocab)


def test_hlo_analyzer_exact_dot_flops():
    def f(x, w):
        return x @ w

    x = jnp.zeros((64, 128))
    w = jnp.zeros((128, 32))
    comp = jax.jit(f).lower(x, w).compile()
    cost = analyze(comp.as_text())
    assert cost.flops == pytest.approx(2 * 64 * 128 * 32, rel=0.05)


def test_hlo_analyzer_scales_while_trip_count():
    def f(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        out, _ = jax.lax.scan(body, x, w)
        return out

    x = jnp.zeros((32, 64))
    w = jnp.zeros((12, 64, 64))
    comp = jax.jit(f).lower(x, w).compile()
    cost = analyze(comp.as_text())
    dot_flops = 2 * 32 * 64 * 64 * 12
    assert cost.flops == pytest.approx(dot_flops, rel=0.10)
