"""chip_smoke.py rehearsed on the CPU, and where the compilation cache goes.

The three phases run at width 1/16 with the kernels in interpret mode: the
same jitted forwards, reference comparison and checks as on the chip, minus
the Mosaic kernel count (nothing is lowered to Mosaic off a TPU), which the
jaxpr's ``pallas_call`` count stands in for.
"""
import functools
import importlib.util
import os

import jax
import pytest

from repro.models.cnn import resnet50_apply
from repro.runtime.compile_cache import use_compilation_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def phases(chip_smoke):
    return chip_smoke.make_phases(0, width=1 / 16)


def _pallas_calls(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
            continue
        for p in eqn.params.values():
            sub = getattr(p, "jaxpr", p)
            if hasattr(sub, "eqns"):
                n += _pallas_calls(sub)
    return n


@pytest.mark.parametrize("i", [0, 1, 2], ids=["dense_b1", "dense_b8",
                                               "pruned_b1"])
def test_phase_passes_at_width_1_16_in_interpret_mode(chip_smoke, phases, i):
    name, params, x = phases[i]
    r = chip_smoke.run_phase(name, params, x, timed_calls=1)
    assert chip_smoke.phase_failures(r) == []
    assert r["kernel_calls"] == 0               # interpreted off a TPU
    jaxpr = jax.make_jaxpr(functools.partial(resnet50_apply,
                                             impl="pallas"))(params, x)
    assert _pallas_calls(jaxpr.jaxpr) == r["conv_dispatches"] == 53


def test_main_without_tpu_exits_nonzero_and_prints_no_ok(chip_smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


@pytest.fixture
def keep_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compilation_cache_dir_from_environment_is_left_alone(
        monkeypatch, keep_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/given/from/outside")
    jax.config.update("jax_compilation_cache_dir", "/given/from/outside")
    assert use_compilation_cache() == "/given/from/outside"


def test_compilation_cache_dir_defaults_to_the_checkout(
        monkeypatch, keep_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert use_compilation_cache() == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == os.path.join(REPO,
                                                                ".jax_cache")
