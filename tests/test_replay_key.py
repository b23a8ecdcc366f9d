"""The replay-cache key: ``buffers_signature`` of a whole buffer dict."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import buffers_signature


def _arr(*shape, dtype=jnp.float32):
    return jnp.zeros(shape, dtype)


def _spec(*shape, dtype=jnp.float32, weak_type=False):
    return jax.ShapeDtypeStruct(shape, dtype, weak_type=weak_type)


# name -> (buffers a, buffers b, whether their signatures are equal)
CASES = {
    "arrays_equal_specs": (
        lambda: {"a": _arr(2, 3), "b": (_arr(4), {"c": _arr(dtype=jnp.int32)})},
        lambda: {"a": _spec(2, 3),
                 "b": (_spec(4), {"c": _spec(dtype=jnp.int32)})},
        True),
    "insertion_order": (
        lambda: {"a": _arr(2), "b": _arr(3), "c": _arr(4)},
        lambda: {"c": _arr(4), "a": _arr(2), "b": _arr(3)},
        True),
    "mapping_equals_dict": (
        lambda: types.MappingProxyType({"a": _arr(2), "b": _arr(3)}),
        lambda: {"a": _spec(2), "b": _spec(3)},
        True),
    "shape": (
        lambda: {"a": _arr(2, 3)},
        lambda: {"a": _arr(3, 2)},
        False),
    "dtype": (
        lambda: {"a": _arr(2, 3)},
        lambda: {"a": _arr(2, 3, dtype=jnp.bfloat16)},
        False),
    "slot_name": (
        lambda: {"a": _arr(2), "b": _arr(2)},
        lambda: {"a": _arr(2), "c": _arr(2)},
        False),
    "slot_set": (
        lambda: {"a": _arr(2), "b": _arr(2)},
        lambda: {"a": _arr(2)},
        False),
    "nesting_container": (
        lambda: {"a": (_arr(2), _arr(2))},
        lambda: {"a": [_arr(2), _arr(2)]},
        False),
    "nesting_depth": (
        lambda: {"a": {"x": _arr(2)}},
        lambda: {"a": _arr(2)},
        False),
    "leaf_moved_between_slots": (
        lambda: {"a": (_arr(2), _arr(3)), "b": _arr(4)},
        lambda: {"a": _arr(2), "b": (_arr(3), _arr(4))},
        False),
    "python_scalar_same_type": (
        lambda: {"a": 1.0, "b": _arr(2)},
        lambda: {"a": 2.5, "b": _spec(2)},
        True),
    "python_scalar_other_type": (
        lambda: {"a": 1},
        lambda: {"a": 1.0},
        False),
    "python_scalar_vs_array": (
        lambda: {"a": 1.0},
        lambda: {"a": np.float64(1.0)},
        False),
    "weak_scalar_array": (
        lambda: {"a": jnp.asarray(1.0)},
        lambda: {"a": jnp.asarray(1.0, jnp.float32)},
        True),
    "weak_spec": (
        lambda: {"a": _spec(weak_type=True)},
        lambda: {"a": _arr()},
        True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_buffers_signature(case):
    make_a, make_b, equal = CASES[case]
    sig_a, sig_b = buffers_signature(make_a()), buffers_signature(make_b())
    assert (sig_a == sig_b) is equal
    assert (sig_a in {sig_b: None}) is equal
    if equal:
        assert hash(sig_a) == hash(sig_b)
    # The same buffers always give the same key.
    assert buffers_signature(make_a()) == sig_a
