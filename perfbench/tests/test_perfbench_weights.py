"""Weights from the seed: a stacked leaf made whole equals its layers made
one at a time, so the reference can make the model again layer by layer."""
import jax
import jax.numpy as jnp
import numpy as np

import pytest

import tiny  # noqa: F401
from harness import weights as W

ABSTRACT = {"embed": jax.ShapeDtypeStruct((64, 8), jnp.bfloat16),
            "final_norm": jax.ShapeDtypeStruct((8,), jnp.float32),
            "layers": {"w": jax.ShapeDtypeStruct((3, 8, 16), jnp.bfloat16),
                       "norm": jax.ShapeDtypeStruct((3, 8), jnp.bfloat16)}}
RULES = {"embed": ["normal", 0.02], "final_norm": ["ones"],
         "layers/w": ["normal", 0.35], "layers/norm": ["ones"]}


def test_layers_made_alone_equal_the_stack():
    seed = 2**33 + 5
    whole = W.make_params(seed, ABSTRACT, RULES)
    for i in range(3):
        layer = W.make_layer(seed, ABSTRACT, RULES, i)
        np.testing.assert_array_equal(np.asarray(layer["w"]),
                                      np.asarray(whole["layers"]["w"][i].astype(jnp.float32)))
        np.testing.assert_array_equal(np.asarray(layer["norm"]), np.ones(8, np.float32))
    g = W.make_globals(seed, ABSTRACT, RULES)
    np.testing.assert_array_equal(np.asarray(g["embed"]),
                                  np.asarray(whole["embed"].astype(jnp.float32)))
    assert whole["layers"]["w"].dtype == jnp.bfloat16


def test_seeds_give_different_weights_and_the_same_seed_the_same():
    a = W.make_params(1, ABSTRACT, RULES)["layers"]["w"]
    b = W.make_params(1, ABSTRACT, RULES)["layers"]["w"]
    c = W.make_params(1 + 2**32, ABSTRACT, RULES)["layers"]["w"]
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    assert abs(float(jnp.std(a.astype(jnp.float32))) - 0.35) < 0.05


def test_a_leaf_without_a_rule_is_an_error():
    with pytest.raises(KeyError):
        W.make_params(1, ABSTRACT, {k: v for k, v in RULES.items() if k != "embed"})


@pytest.mark.parametrize("rule,lo,hi", [
    (["uniform", -0.5, 0.5], -0.5, 0.5),
    (["log_of_uniform", 1.0, 16.0], 0.0, float(np.log(16.0))),
    # the time-step bias: softplus of it lies in [1e-3, 1e-1]
    (["softplus_inverse_of_log_uniform", 0.001, 0.1, 0.0001], float(np.log(np.expm1(1e-3))),
     float(np.log(np.expm1(0.1)))),
])
def test_each_random_rule_keeps_its_range_and_its_layers(rule, lo, hi):
    abstract = {"layers": {"w": jax.ShapeDtypeStruct((3, 64, 32), jnp.float32)}}
    rules = {"layers/w": rule}
    whole = W.make_params(7, abstract, rules)["layers"]["w"]
    x = np.asarray(whole)
    assert lo - 1e-5 <= x.min() and x.max() <= hi + 1e-5
    assert x.max() - x.min() > 0.8 * (hi - lo)
    for i in range(3):
        np.testing.assert_array_equal(np.asarray(W.make_layer(7, abstract, rules, i)["w"]), x[i])
