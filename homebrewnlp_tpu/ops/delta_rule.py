"""The gated delta rule with a decay for every channel, in chunks.

Per head, with a state ``S`` of ``[d_k, d_v]`` that starts at zero::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``g_t <= 0`` is the log of the decay, one number a channel of the key.  The
recurrence is run a chunk of ``C`` tokens at a time.  With ``G`` the running
sum of ``g`` inside a chunk and ``S_0`` the state the chunk starts from, every
``S_t`` of the chunk is ``Diag(exp(G_t)) S_0 + sum_{i<=t} Diag(exp(G_t - G_i))
k_i u_i^T`` for pseudo-values ``u`` that solve one unit lower-triangular
system of ``C`` rows::

    A_ti = beta_t sum_c k_tc k_ic exp(G_tc - G_ic)            (i < t)
    [W | U'] = (I + A)^-1 [beta K exp(G) | beta V],   U = U' - W S_0
    O   = (Q exp(G)) S_0 + P U,   P_ti = sum_c q_tc k_ic exp(G_tc - G_ic)  (i <= t)
    S_C = Diag(exp(G_C)) S_0 + (K exp(G_C - G))^T U

The decay enters only as ``exp`` of a difference of running sums that is
``<= 0``: ``exp(G_t - G_i)`` for ``i <= t``, ``exp(G_t)`` and ``exp(G_C -
G_t)``.  It is never factored into ``exp(G_t) exp(-G_i)``, whose second
factor overflows float32 after a few strongly decayed tokens; the price is
a product over ``[t, i, d_k]`` under ``A`` and ``P`` that no matrix unit
takes.  :func:`_pair_products` pays it only inside sub-blocks of ``sub``
tokens and splits every other pair's decay at a point between ``i`` and
``t``, where both factors are ``<= 1``.
The decay, ``A``, ``P`` and the inverse are float32; the products against the
state take their operands in the type of ``q`` and add up in float32.

``beta`` is an operand and nothing here bounds it.  One place leans on its
size all the same, in float32 only: ``(I + A)^-1`` by repeated squaring
carries the powers of ``A`` up to ``A^(C/2)``, whose entries grow like
``binom(C, C/2) (beta k_t.k_i)^(C/2)`` before they cancel in the product.
With ``beta <= 1`` and keys a seeded model makes they stay near 1; with
``beta`` up to 2 (``wide_beta``: ``kda_allow_neg_eigval``) and keys that lie
close together they pass 1e4 beside an inverse of 1: at the widths of
``solar_open2_250b.train`` the third and fourth layers stood 1.3e-3 of their
scale from the recurrence, and keys at cosines of 0.9 lose the result
whole (tests/solar_open2_test.py).  There the
squaring runs only inside diagonal blocks of ``sub`` tokens and the blocks
are merged pair by pair (``[[P, 0], [R, Q]]^-1 = [[P^-1, 0], [-Q^-1 R P^-1,
Q^-1]]``): every intermediate is the inverse of a part of the chunk, as
large as the result and no larger, for as many matrix products.

Two stages: the chunks' own work (``A``, ``P``, the inverse) runs ``group``
chunks at a time, each group recomputed in the backward so that the
``[C, C, d_k]`` products never outlive their step; the state then walks the
chunks one by one, keeping only itself a chunk for the backward.  Where the
head widths are whole lane tiles (multiples of 128) the first stage is the
pair of Mosaic kernels in ``ops/pallas_kda.py``; every other shape takes the
scan here, which is also the kernels' oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from ..nd import einsum_f32

_HIGHEST = jax.lax.Precision.HIGHEST


def _pair_products(rows, k, run, sub: int):
    """``sum_c rows_tc k_ic exp(run_tc - run_ic)`` for ``i <= t``, zero above:
    ``rows [..., L, C, d]`` (``L`` sets of rows against the same keys), ``k``
    and ``run [..., C, d]`` -> ``[..., L, C, C]``.

    The chunk is cut into sub-blocks of ``sub`` tokens.  Only the pairs
    inside one sub-block take the ``[sub, sub, d]`` product.  A pair with
    ``t`` in sub-block ``a`` and ``i`` in an earlier one splits its decay at
    ``R_a``, the running sum at ``a``'s first token: ``exp(run_t - R_a)`` on
    the row and ``exp(R_a - run_i)`` on the key are both ``<= 1``, and what is
    left is a matrix product."""
    c, d = k.shape[-2:]
    n = c // sub
    cut = lambda x: x.reshape(x.shape[:-2] + (n, sub, d))
    rows, k, run = cut(rows), cut(k), cut(run)                 # [.., a, s, d]
    first = run[..., :1, :]                                    # R_a
    at = jnp.arange(sub)
    inside = jnp.exp(jnp.where(
        (at[:, None] >= at[None, :])[..., None],
        run[..., :, None, :] - run[..., None, :, :], -jnp.inf))
    diagonal = jnp.sum(rows[..., :, None, :]
                       * (k[..., None, :, :] * inside)[..., None, :, :, :, :],
                       -1)                                     # [.., L, a, t, i]
    block = jnp.arange(n)
    earlier = (block[:, None] > block[None, :])[:, :, None, None]
    keys = k[..., None, :, :, :] * jnp.exp(jnp.where(
        earlier, first[..., :, None, :, :] - run[..., None, :, :, :],
        -jnp.inf))                                             # [.., a, b, i, d]
    below = jnp.einsum("...latd,...abid->...latbi",
                       rows * jnp.exp(run - first)[..., None, :, :, :], keys,
                       precision=_HIGHEST)
    same = jnp.eye(n, dtype=below.dtype)[:, None, :, None]
    out = below + diagonal[..., :, :, None, :] * same
    return out.reshape(out.shape[:-4] + (c, c))


def _unit_lower_inverse(a, inside: int):
    """``(I + a)^-1`` for strictly lower-triangular ``a [..., C, C]``, in
    float32 matrix products.  Inside diagonal blocks of ``inside`` rows:
    ``a`` is nilpotent, so with ``n = -a`` the inverse is ``(I + n)(I +
    n^2)(I + n^4) ...`` up to the power ``inside / 2``.  (The chip's
    triangular solve took a quarter of the mixer's time.)  Blocks smaller
    than the chunk are then merged pair by pair: with ``m`` the inverse of
    the blocks on the diagonal and ``r`` what ``a`` holds between the two
    blocks of a pair, the pairs' inverse is ``m - m r m``."""
    c = a.shape[-1]
    power = -a
    if inside < c:
        at = jnp.arange(c)
        together = lambda size: at[:, None] // size == at[None, :] // size
        power = jnp.where(together(inside), power, 0.0)
    inverse = power + jnp.eye(c, dtype=a.dtype)
    for _ in range(max(0, (min(inside, c) - 1).bit_length() - 1)):
        power = jnp.matmul(power, power, precision=_HIGHEST)
        inverse = inverse + jnp.matmul(inverse, power, precision=_HIGHEST)
    size = inside
    while size < c:
        between = jnp.where(together(2 * size) & ~together(size), a, 0.0)
        inverse = inverse - jnp.matmul(
            jnp.matmul(inverse, between, precision=_HIGHEST), inverse,
            precision=_HIGHEST)
        size *= 2
    return inverse


def _within_chunk(q, k, v, g, beta, sub: int, inside: int):
    """What a chunk needs besides the state it starts from.  All arguments
    ``[..., C, d]`` float32 (``beta`` ``[..., C]``); ``inside`` is
    :func:`_unit_lower_inverse`'s."""
    c = q.shape[-2]
    d_k = k.shape[-1]
    run = jnp.cumsum(g, axis=-2)                                  # G, <= 0
    pairs = _pair_products(jnp.stack([q, k], -3), k, run, sub)
    p = pairs[..., 0, :, :]                                       # i <= t
    at = jnp.arange(c)
    a = jnp.where(at[:, None] > at[None, :],
                  pairs[..., 1, :, :] * beta[..., None], 0.0)     # i < t
    into = jnp.exp(run)
    rhs = jnp.concatenate([k * into, v], -1) * beta[..., None]
    solved = jnp.matmul(_unit_lower_inverse(a, inside), rhs,
                        precision=_HIGHEST)
    out_of = jnp.exp(run[..., -1:, :] - run)                      # G_C - G_t
    return (solved[..., :d_k], solved[..., d_k:], p, q * into, k * out_of,
            into[..., -1, :])


def _walk_state(w, u, p, q_in, k_out, last):
    """The state's walk over the chunks: arguments ``[N, ..., C, d]``
    (``last`` ``[N, ..., d_k]``), chunk axis first.  Returns ``[N, ..., C,
    d_v]`` float32."""
    kind = w.dtype

    def dot(spec, a, b):
        return einsum_f32(spec, a, b, precision=_HIGHEST)

    @jax.checkpoint
    def chunk(state, part):
        w_n, u_n, p_n, q_n, k_n, last_n = part
        held = state.astype(kind)
        u_n = (u_n - dot("...ck,...kv->...cv", w_n, held)).astype(kind)
        out = dot("...ck,...kv->...cv", q_n, held) + dot(
            "...ci,...iv->...cv", p_n, u_n)
        state = state * last_n[..., None] + dot("...ck,...cv->...kv", k_n, u_n)
        return state, out

    zero = jnp.zeros(w.shape[1:-2] + (w.shape[-1], u.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(chunk, zero, (w, u, p, q_in, k_out, last))
    return out


def chunked_kda(q, k, v, g, beta, chunk: int = 32, sub: int = 8,
                group: int = 0, wide_beta: bool = False):
    """``o`` of the recurrence above for ``q, k [B, T, H, d_k]``, ``v [B, T,
    H, d_v]``, ``g [B, T, H, d_k]`` (``<= 0``) and ``beta [B, T, H]``; ``T``
    need be no multiple of the chunk, the chunk is one of ``sub``.
    ``wide_beta`` says that ``beta`` may pass 1 (up to 2): the chunk's
    inverse is then squared inside blocks of ``sub`` tokens only.  The
    result has the type of ``v``."""
    if chunk % sub:
        raise ValueError(f"a chunk of {chunk} is no multiple of {sub}")
    b, t, h, d_v = v.shape
    chunk = min(chunk, -(-t // sub) * sub)
    n = -(-t // chunk)
    group = min(group or max(1, 256 // chunk), n)   # 256 tokens a step
    n_pad = -(-n // group) * group
    inside = sub if wide_beta else chunk

    if not (q.shape[-1] % 128 or d_v % 128 or sub % 8):
        parts = _kernel_parts(q, k, v, g, beta, chunk, sub, group, n_pad,
                              inside)
    else:
        parts = _scan_parts(q, k, v, g, beta, chunk, sub, group, n_pad,
                            inside)
    out = _walk_state(*parts)
    out = jnp.moveaxis(out, 0, 1)                                 # [B,N,H,C,d]
    out = jnp.moveaxis(out, 2, 3).reshape(b, n_pad * chunk, h, d_v)
    return out[:, :t].astype(v.dtype)


def _scan_parts(q, k, v, g, beta, chunk, sub, group, n_pad, inside):
    """``_walk_state``'s arguments by a scan over groups of chunks: every
    shape's path, and the kernel's oracle."""
    kind = q.dtype
    b, t = q.shape[:2]
    pad = n_pad * chunk - t

    def chunks(x):
        """[B, T, H, ...] -> [n_pad / group, group, B, H, C, ...]; the
        padding (zero keys, values, gates, log-decay) changes no state."""
        x = jnp.pad(x.astype(jnp.float32),
                    ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((b, n_pad // group, group, chunk) + x.shape[2:])
        x = jnp.moveaxis(x, 4, 3)                  # heads before the chunk
        return jnp.moveaxis(x, 0, 2)

    @jax.checkpoint
    def within(_, part):
        w, u, p, q_in, k_out, last = _within_chunk(*part, sub=sub,
                                                    inside=inside)
        return None, (w.astype(kind), u.astype(kind), p.astype(kind),
                      q_in.astype(kind), k_out.astype(kind), last)

    _, parts = jax.lax.scan(within, None, tuple(
        chunks(x) for x in (q, k, v, g, beta)))
    return tuple(x.reshape((n_pad,) + x.shape[2:]) for x in parts)


def _kernel_parts(q, k, v, g, beta, chunk, sub, group, n_pad, inside):
    """The same by ``ops/pallas_kda.py``'s kernels, for head widths that are
    whole lane tiles: operands heads-major in float32, as ``_scan_parts``
    casts them, results chunk axis first as the kernel writes them."""
    from . import pallas_interpret
    from .pallas_kda import kda_chunks
    t = beta.shape[1]
    pad = n_pad * chunk - t

    def heads_major(x):
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x, 2, 1)

    f32 = lambda x: heads_major(x.astype(jnp.float32))
    *parts, last = kda_chunks(
        f32(q), f32(k), f32(v), f32(g), f32(beta), jnp.dtype(q.dtype), chunk,
        sub, group, inside, pallas_interpret())
    return (*parts, last[..., 0, :])
