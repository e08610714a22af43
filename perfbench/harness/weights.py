"""Weights made from the seed, on the device, by rules in the config file.

The program receives them as inputs; the plain reference makes the same
values again, one layer at a time, without anything the program made.
Each leaf's values depend only on (seed, leaf path, layer), so a stacked
``layers/...`` leaf made whole equals the stack of its layers made one by
one.  ``init`` in the config file maps each leaf path to a rule:

* ``["ones"]``, ``["zeros"]``;
* ``["normal", std]``; ``["uniform", lo, hi]``;
* ``["log_of_uniform", lo, hi]``: ``log(u)``, ``u`` uniform in [lo, hi];
* ``["softplus_inverse_of_log_uniform", lo, hi, floor]``: the bias whose
  softplus is ``max(exp(u), floor)``, ``u`` uniform in [log lo, log hi]
  (Mamba2's time-step bias).
"""
from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int) -> np.ndarray:
    """A seed of up to 64 bits as two uint32 words (a traced argument, so
    one compiled generator serves every seed)."""
    seed = int(seed)
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return np.asarray([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def _path_word(path: str) -> int:
    return int.from_bytes(hashlib.blake2b(path.encode(), digest_size=4).digest(),
                          "little") & 0x7FFFFFFF


def leaf_key(words, path: str):
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, words[0])
    key = jax.random.fold_in(key, words[1])
    return jax.random.fold_in(key, _path_word(path))


def rule_for(rules: dict, path: str):
    if path not in rules:
        raise KeyError(f"config file has no init rule for leaf {path!r}")
    return rules[path]


def _random(key, shape, rule, dtype):
    kind, args = rule[0], [float(a) for a in rule[1:]]
    if kind == "normal":
        x = jax.random.normal(key, shape, jnp.float32) * args[0]
    elif kind == "uniform":
        x = jax.random.uniform(key, shape, jnp.float32, args[0], args[1])
    elif kind == "log_of_uniform":
        x = jnp.log(jax.random.uniform(key, shape, jnp.float32, args[0], args[1]))
    elif kind == "softplus_inverse_of_log_uniform":
        u = jax.random.uniform(key, shape, jnp.float32, np.log(args[0]), np.log(args[1]))
        dt = jnp.maximum(jnp.exp(u), args[2])
        x = dt + jnp.log(-jnp.expm1(-dt))
    else:
        raise ValueError(f"unknown init rule {rule!r}")
    return x.astype(dtype)


def make_leaf(words, path: str, shape, dtype, rule, stacked: bool):
    kind = rule[0]
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    key = leaf_key(words, path)
    if stacked:
        keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
            jnp.arange(shape[0], dtype=jnp.uint32))
        return jax.vmap(lambda k: _random(k, shape[1:], rule, dtype))(keys)
    return _random(key, shape, rule, dtype)


def make_layer_leaf(words, path: str, shape, dtype, rule, layer):
    """Layer ``layer`` of a stacked leaf; equals ``make_leaf(...)[layer]``."""
    if rule[0] in ("ones", "zeros"):
        return make_leaf(words, path, shape[1:], dtype, rule, False)
    key = jax.random.fold_in(leaf_key(words, path), jnp.uint32(layer))
    return _random(key, shape[1:], rule, dtype)


def _flat(tree, prefix=""):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _flat(tree[k], f"{prefix}{k}/")
    else:
        yield prefix[:-1], tree


def paths(abstract) -> dict:
    """{"layers/wq": leaf, ...} of a parameter tree (abstract or not)."""
    return dict(_flat(abstract))


def _unflat(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return out


def make_params(seed: int, abstract, rules: dict):
    """Every leaf of ``abstract`` in its own dtype, in one jitted call."""
    flat = paths(abstract)

    @jax.jit
    def build(words):
        return _unflat({p: make_leaf(words, p, s.shape, s.dtype, rule_for(rules, p),
                                     p.startswith("layers/"))
                        for p, s in flat.items()})

    return build(seed_words(seed))


def make_layer(seed: int, abstract, rules: dict, layer: int, as_dtype=True):
    """Layer ``layer`` of every ``layers/...`` leaf, in float32, holding the
    values of the dtype the program holds them in (``as_dtype``)."""
    flat = {p: s for p, s in paths(abstract).items() if p.startswith("layers/")}
    return _layer_fn(tuple((p, s.shape, jnp.dtype(s.dtype).name) for p, s in flat.items()),
                     _freeze(rules), as_dtype)(seed_words(seed), jnp.uint32(layer))


def make_globals(seed: int, abstract, rules: dict, as_dtype=True):
    """Every leaf outside ``layers/`` in float32 (values of its own dtype)."""
    flat = {p: s for p, s in paths(abstract).items() if not p.startswith("layers/")}
    return _globals_fn(tuple((p, s.shape, jnp.dtype(s.dtype).name) for p, s in flat.items()),
                       _freeze(rules), as_dtype)(seed_words(seed))


def _freeze(rules: dict) -> tuple:
    return tuple(sorted((k, tuple(v)) for k, v in rules.items()))


_CACHE: dict = {}


def _layer_fn(leaves: tuple, rules: tuple, as_dtype: bool):
    key = ("layer", leaves, rules, as_dtype)
    if key not in _CACHE:
        r = dict(rules)

        @jax.jit
        def fn(words, layer):
            out = {}
            for p, shape, dt in leaves:
                x = make_layer_leaf(words, p, shape, jnp.dtype(dt) if as_dtype
                                    else jnp.float32, r[p], layer)
                out[p.split("/", 1)[1]] = x.astype(jnp.float32)
            return out
        _CACHE[key] = fn
    return _CACHE[key]


def _globals_fn(leaves: tuple, rules: tuple, as_dtype: bool):
    key = ("globals", leaves, rules, as_dtype)
    if key not in _CACHE:
        r = dict(rules)

        @jax.jit
        def fn(words):
            return {p: make_leaf(words, p, shape, jnp.dtype(dt) if as_dtype
                                 else jnp.float32, r[p], False).astype(jnp.float32)
                    for p, shape, dt in leaves}
        _CACHE[key] = fn
    return _CACHE[key]
