"""Reversible residual streams with O(1) activation memory.

The reference implements reversible (RevNet) and MomentumNet layers by cloning
graph operations and hand-walking them in reverse inside Mesh-TF
(/root/reference/src/model/revnet.py:14-120, momentumnet.py:14-125).  The JAX
equivalent is a ``custom_vjp`` over the whole chain: forward stores only the
two final streams; backward reconstructs each block's inputs from its outputs
and re-plays the block under ``jax.vjp``.  Works unchanged under pjit/shard_map
because reconstruction is ordinary traced computation.

Chain state is a pair of like-shaped pytrees (x1, x2):
  revnet step   : (x1, x2) -> (x2, x1 + f(p, x2))          [final out: x1 + x2]
  momentum step : (x, v)   -> (x + v', v'),  v' = a*v + (1-a)*f(p, x)
The reference's 4-tuple stream (x, x_backwards, v, v_backwards) carries the
reconstruction slots explicitly; here they are implicit in the vjp residuals.
"""
from __future__ import annotations

import typing

import jax

Pytree = typing.Any


def make_reversible_chain(fs: typing.Sequence[typing.Callable],
                          mode: str = "revnet", alpha: float = 0.99,
                          remat_blocks: bool = False):
    """Build a reversible chain over residual-branch functions ``fs``.

    Each ``fs[i](params_i, x) -> y`` must be shape-preserving and
    deterministic (re-executed during backward).  Returns
    ``chain(params_tuple, x1, x2) -> (y1, y2)``.

    ``remat_blocks`` wraps blocks in ``jax.checkpoint`` for the
    backward's ``jax.vjp`` replay: the replay forward then stores no
    internal residuals (norm stats, pre-activations, widened mids) and the
    pullback recomputes them — more FLOPs for fewer HBM bytes, profitable
    exactly when the step sits on the bandwidth roofline while the MXU is
    idle (docs/perf/README.md round 4: the 32mixer_group workload).
    Numerics are unchanged (same math, different schedule).  A bool
    applies to every block; a per-block sequence lets callers skip blocks
    that are already byte-minimal (round 5: a fused-kernel block's
    custom_vjp stores only its inputs, so checkpointing it would re-add
    the exact recompute the kernel already performs).
    """
    fs = tuple(fs)
    if isinstance(remat_blocks, (list, tuple)):
        assert len(remat_blocks) == len(fs), (len(remat_blocks), len(fs))
        remat_flags = tuple(bool(r) for r in remat_blocks)
    else:
        remat_flags = (bool(remat_blocks),) * len(fs)

    tsub = jax.tree_util.tree_map
    if mode == "revnet":
        def step(f, p, x1, x2):
            return x2, tsub(lambda a, b: a + b, x1, f(p, x2))

        def inv_and_grads(f, p, y1, y2, dy1, dy2, remat):
            x2 = y1
            fx, vjp = jax.vjp(jax.checkpoint(f) if remat else f, p, x2)
            x1 = tsub(lambda a, b: a - b, y2, fx)
            dp, dx2_f = vjp(dy2)
            dx1 = dy2
            dx2 = tsub(lambda a, b: a + b, dy1, dx2_f)
            return x1, x2, dx1, dx2, dp
    elif mode == "momentum":
        def step(f, p, x, v):
            fx = f(p, x)
            new_v = tsub(lambda a, b: alpha * a + (1 - alpha) * b, v, fx)
            new_x = tsub(lambda a, b: a + b, x, new_v)
            return new_x, new_v

        def inv_and_grads(f, p, y1, y2, dy1, dy2, remat):
            # y1 = x + v', y2 = v' = a*v + (1-a)*f(p, x)
            x = tsub(lambda a, b: a - b, y1, y2)
            fx, vjp = jax.vjp(jax.checkpoint(f) if remat else f, p, x)
            v = tsub(lambda a, b: (a - (1 - alpha) * b) / alpha, y2, fx)
            d_sum = tsub(lambda a, b: a + b, dy1, dy2)
            dp, dx_f = vjp(tsub(lambda a: (1 - alpha) * a, d_sum))
            dx = tsub(lambda a, b: a + b, dy1, dx_f)
            dv = tsub(lambda a: alpha * a, d_sum)
            return x, v, dx, dv, dp
    else:
        raise ValueError(f"unknown reversible mode {mode}")

    def forward(params, x1, x2):
        for f, p in zip(fs, params):
            x1, x2 = step(f, p, x1, x2)
        return x1, x2

    @jax.custom_vjp
    def chain(params, x1, x2):
        return forward(params, x1, x2)

    def chain_fwd(params, x1, x2):
        y1, y2 = forward(params, x1, x2)
        return (y1, y2), (params, y1, y2)

    def chain_bwd(res, cotangents):
        params, y1, y2 = res
        dy1, dy2 = cotangents
        dparams = [None] * len(fs)
        for i in range(len(fs) - 1, -1, -1):
            y1, y2, dy1, dy2, dparams[i] = inv_and_grads(
                fs[i], params[i], y1, y2, dy1, dy2, remat_flags[i])
        return tuple(dparams), dy1, dy2

    chain.defvjp(chain_fwd, chain_bwd)
    return chain
