"""Activation registry.

The reference hand-writes forward/backward slicewise op pairs for Mish, SiLU,
LeCunTanh and Softsign purely to avoid storing activations in Mesh-TF
(/root/reference/src/model/activation.py:13-145).  On TPU/XLA that machinery is
counter-productive: elementwise chains fuse into the surrounding matmuls and
`jax.checkpoint` governs what is stored, so these are plain jnp functions.
LeCunTanh keeps the reference's (nonstandard) ``tanh(x) + 0.1 x`` definition
(activation.py:96).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..nd import NT


def _wrap(fn):
    def inner(t: NT) -> NT:
        return NT(fn(t.x), t.names)

    return inner


def mish(x):
    return x * jnp.tanh(jax.nn.softplus(x))


def lecun_tanh(x):
    return jnp.tanh(x) + x * 0.1


def softsign(x):
    return x / (1 + jnp.abs(x))


#: name -> function over plain arrays
PLAIN = {
    "relu": jax.nn.relu,
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "gelu": jax.nn.gelu,
    "lecun_tanh": lecun_tanh,
    "silu": jax.nn.silu,
    "mish": mish,
    "mtf_mish": mish,
    "softsign": softsign,
    "exp": jnp.exp,
}
ACTIVATIONS = {name: _wrap(fn) for name, fn in PLAIN.items()}


def activate(args) -> NT:
    """Dispatch on the first known activation name in the DSL extras
    (reference activation.py:201-211); identity fallback."""
    for fn_name in args:
        if fn_name in ACTIVATIONS:
            return ACTIVATIONS[fn_name](args.tensor)
    return args.tensor
