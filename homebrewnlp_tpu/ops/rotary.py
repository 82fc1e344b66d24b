"""Rotary positions: the table of a layer type and the rotation itself.

A head of width ``d`` is ``d / 2`` pairs ``(x_i, x_{i + d/2})`` (rotate-half
over the whole head), or, interleaved, ``(x_2i, x_2i+1)`` (``rotate``'s
``interleaved``: ``mla-rope`` under ``rope_interleave``); pair ``i`` of the
token at position ``p`` is turned by the angle ``p * inv_freq_i``:

    rot(x, p) = x * cos(p f) + [-x_hi, x_lo] * sin(p f)

``inv_freq`` comes from the layer type's entry of upstream's
``rope_parameters``:

- ``default``: ``inv_freq_i = theta^(-2i / d)``;
- ``yarn`` (Peng et al. 2023, as ``transformers`` computes it): pair ``i``
  is blended between ``theta^(-2i / d)`` (short wavelengths, left as they
  are) and ``theta^(-2i / d) / factor`` (long ones, stretched) by a linear
  ramp over the pairs between the one that turns ``beta_fast`` times within
  ``original_max_position_embeddings`` positions and the one that turns
  ``beta_slow`` times, and cos and sin are multiplied by
  ``attention_factor`` (``0.1 ln(factor) + 1`` where the entry gives none).

An entry of upstream's ``rope_scaling`` may name its kind ``type`` and carry
``mrope_section``: the pairs split between temporal, height and width
positions (Qwen2-VL's multimodal rotary positions).  For text all three
positions are the token's index, so the table is the one of the kind; the
sections must still cover the head's ``d / 2`` pairs exactly.

The frequencies are worked out on the host in numpy's double precision
while the layer is traced (they depend on the configuration alone) and enter
the trace as ``d / 2`` float32 constants; angles, cos and sin are float32.
"""
from __future__ import annotations

import math
import typing

import jax.numpy as jnp
import numpy as np


def inverse_frequencies(conf: dict, dim: int
                        ) -> typing.Tuple[np.ndarray, float]:
    """``(inv_freq [dim / 2] float32, what cos and sin are multiplied by)``
    of one entry of ``rope_parameters``."""
    kind = conf.get("rope_type", conf.get("type", "default"))
    theta = float(conf["rope_theta"])
    sections = conf.get("mrope_section")
    if sections is not None and sum(sections) != dim // 2:
        raise ValueError(f"mrope_section {sections} covers {sum(sections)} "
                         f"pairs of a head of {dim}: it must cover {dim // 2}")
    plain = theta ** (-np.arange(0, dim, 2) / dim)
    if kind == "default":
        return plain.astype(np.float32), 1.0
    if kind != "yarn":
        raise ValueError(f"no rotary table of rope_type {kind!r}")
    factor = float(conf["factor"])
    reach = conf["original_max_position_embeddings"]

    def pair_turning(times: float) -> float:
        """The (fractional) pair that turns ``times`` times in ``reach``."""
        return dim * math.log(reach / (times * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(pair_turning(conf.get("beta_fast", 32))), 0)
    high = min(math.ceil(pair_turning(conf.get("beta_slow", 1))), dim - 1)
    if low == high:
        high += 0.001
    stretched = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    blended = plain / factor * stretched + plain * (1 - stretched)
    return blended.astype(np.float32), float(conf.get(
        "attention_factor", 0.1 * math.log(factor) + 1.0))


def table(entry: dict, dim: int, length: int):
    """``(cos, sin)``, each ``[length, dim / 2]`` float32, of positions ``0
    .. length - 1``."""
    inv_freq, factor = inverse_frequencies(entry, dim)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq[None]
    return jnp.cos(angle) * factor, jnp.sin(angle) * factor


def rotate(x, cos, sin, interleaved: bool = False):
    """``x [B, S, H, d]`` (any float type) turned by ``cos, sin [S, d / 2]``;
    float32.  ``interleaved``: the pairs are ``(x_2i, x_2i+1)`` (upstream's
    ``rope_interleave``), and the result holds the turned pairs rotate-half
    wise, ``[y_0, y_2, .., y_d-2, y_1, y_3, .., y_d-1]``, as upstream's
    DeepSeek-V3 returns them: a query and a key turned alike keep their dot
    product, which is all attention reads."""
    x = x.astype(jnp.float32)
    if interleaved:
        x = jnp.swapaxes(x.reshape(x.shape[:-1] + (-1, 2)), -1, -2).reshape(
            x.shape)
    low, high = jnp.split(x, 2, -1)
    cos, sin = cos[:, None], sin[:, None]
    return jnp.concatenate([low * cos - high * sin, high * cos + low * sin],
                           -1)
