"""The compile counter in ``runtime/compile_cache.py``, on the CPU."""
import jax
import jax.numpy as jnp

from repro.runtime.compile_cache import compile_stats, count_compiles


def test_a_new_shape_counts_one_compile_and_a_repeat_none():
    count_compiles()
    count_compiles()          # a second start adds no second listener
    f = jax.jit(lambda x: jnp.sin(x) * 3 + 1)
    x, y = jnp.ones((7, 13)), jnp.ones((7, 14))
    before = compile_stats()
    f(x).block_until_ready()
    first = compile_stats()
    f(x).block_until_ready()
    second = compile_stats()
    assert first["compiles"] - before["compiles"] == 1
    assert first["compile_s"] > before["compile_s"]
    assert second == first
    f(y).block_until_ready()          # another shape compiles
    assert compile_stats()["compiles"] - second["compiles"] == 1
