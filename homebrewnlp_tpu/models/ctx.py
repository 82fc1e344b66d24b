"""Functional parameter store with deterministic scoped naming.

The reference relies on TF variable scopes plus a global deterministic name
counter (``scoped``/``NAME_INDICES``, /root/reference/src/utils_core.py:16-67)
so that rebuilding the graph yields identical variable names — macro-batching
and the optimizer's name-based heuristics depend on it.  Here the same idea is
a pure-Python scope stack: every layer invocation pushes ``name{counter}`` and
parameters live in a flat ``dict[str, jnp.ndarray]`` pytree keyed by the scope
path.  Because JAX is functional there is no variable cache to invalidate: the
same ``Ctx`` machinery runs once for shape/param discovery (init) and then
inside ``jit`` for apply.

Weight sharing (the ``shared`` DSL flag, reference src/model/backend.py:43-94)
is reproduced by dropping the depth component from the scope path: the
reference's scope-parsing rotation assigns the k-th shared call within a block
in depth i>0 the variable created by the k-th call at depth 0, which is exactly
"same path modulo depth index".
"""
from __future__ import annotations

import hashlib
import typing

import jax
import jax.numpy as jnp

from .. import nd
from ..config import Config
from ..nd import NT

DEPTH_TOKEN = "@d"  # scope component that identifies the depth index


def _name_seed(name: str) -> int:
    return int.from_bytes(hashlib.blake2b(name.encode(), digest_size=4).digest(), "little")


class DecodeState:
    """KV-cache decode mode (infer/kv_cache.py): the model runs on ONE
    sequence position; ``pos`` is the absolute position of that row and
    ``caches`` maps attention-layer ids to (k, v) arrays of shape
    [batch, seq, heads, key].  Attention layers read/update their entry;
    position-dependent embeddings slice their row at ``pos``."""

    def __init__(self, pos, caches: typing.Dict[str, tuple], seq: int):
        self.pos = pos
        self.caches = caches
        self.seq = seq


class Ctx:
    """Carries config + parameters + scope state through model construction."""

    def __init__(self, cfg: Config, params: typing.Optional[dict] = None,
                 seed: int = 0, train: bool = True,
                 rng: typing.Optional[jax.Array] = None, mesh=None,
                 decode: typing.Optional[DecodeState] = None,
                 outer_mesh=None):
        self.cfg = cfg
        self.params = params  # None => init (collect) mode
        self.mesh = mesh  # device mesh for shard_map islands (ring attention)
        # the concrete mesh when building INSIDE a manual shard_map region
        # (pipeline stage bodies): ``mesh`` must stay None there — a
        # with_sharding_constraint over the concrete mesh cannot apply inside
        # the region — but eligibility checks (ring/fused-kernel/blocked-map)
        # and the nested ring-attention path still need the real axis sizes
        self.outer_mesh = outer_mesh
        self.decode = decode  # KV-cache incremental decode state
        self.collected: typing.Dict[str, jnp.ndarray] = {}
        self.axis_names: typing.Dict[str, typing.Tuple[str, ...]] = {}
        self.train = train
        self.seed = seed
        self.rng = rng  # per-step PRNG key for dropout etc.
        self._scope: typing.List[str] = []
        self._counters: typing.Dict[typing.Tuple[str, str], int] = {}
        self._rng_counter = 0
        self.attention_idx = 0
        # stash for contrastive loss (reference dataclass.py:29-31)
        self.text_input_embedding: typing.Optional[NT] = None
        # layer-collected auxiliary loss terms (routed-MoE load balance);
        # only propagated out of non-reversible bodies — see _body
        self.aux_losses: typing.List[jnp.ndarray] = []
        # pairs that fell on each held expert, one vector a routed layer;
        # leaves the body as aux_losses does, for the step's counters
        self.expert_load: typing.List[jnp.ndarray] = []
        # rows each routed layer's grouped products multiply this step
        # (trips of its loop times a chunk's rows), beside expert_load
        self.expert_rows: typing.List[jnp.ndarray] = []
        # a learned sparse attention's kept pairs over its causal pairs, and
        # its indexer's loss, one scalar a layer (models/hybrid.py::gqa)
        self.dsa_kept: typing.List[jnp.ndarray] = []
        self.dsa_kl: typing.List[jnp.ndarray] = []
        self.param_count = 0

    @property
    def effective_mesh(self):
        """The mesh for eligibility decisions (ring/fused-kernel/blocked-map)
        regardless of where the build is running: ``mesh`` at top level,
        ``outer_mesh`` inside a pipeline stage.  Consumers that APPLY
        constraints must keep using ``mesh`` (None inside manual regions)."""
        return self.mesh if self.mesh is not None else self.outer_mesh

    # -- scoping ------------------------------------------------------------
    def scope(self, name: str) -> "_Scope":
        return _Scope(self, name)

    def preset_scope(self, *parts: str) -> "_PresetScope":
        """Seed the scope stack of a per-block sub-Ctx (reversible chain /
        pipeline stage builds) with an already-resolved prefix, mirroring it
        onto the nd diagnostic stack so rank-mismatch errors raised inside
        the block name the FULL parameter path, not just the block suffix."""
        return _PresetScope(self, parts)

    def scoped(self, name: str, fn, *args, **kwargs):
        with self.scope(name):
            return fn(*args, **kwargs)

    def path(self, name: str = "") -> str:
        return "/".join(self._scope + ([name] if name else []))

    # -- parameters ---------------------------------------------------------
    def param(self, name: str, names: typing.Sequence[str],
              sizes: typing.Sequence[int], init_fn,
              shared: bool = False, dtype=None) -> NT:
        """Fetch-or-create a parameter as an :class:`NT`.

        ``init_fn(key, shape) -> f32 array``; storage dtype from config.
        ``shared=True`` removes the depth component from the key so all depth
        iterations address one tensor."""
        full = self.path(name)
        if shared:
            # "@d{i}_{c}" -> "shared_{c}": one tensor per block-config slot,
            # reused across all depth iterations (reference backend.py:43-94).
            parts = []
            for p in full.split("/"):
                if p.startswith(DEPTH_TOKEN):
                    parts.append("shared_" + p.rsplit("_", 1)[1])
                else:
                    parts.append(p)
            full = "/".join(parts)
        # device-resident params live in slice_dtype (MTF's per-device slice
        # copy, reference dataclass.py:253-255); storage_dtype is the
        # checkpoint master copy (train/checkpoint.py casts on save)
        store_dtype = dtype or self.cfg.slice_dtype
        if self.params is not None:
            if full not in self.params:
                raise KeyError(f"missing parameter {full}")
            arr = self.params[full]
            return NT(arr.astype(self.cfg.calculation_dtype), tuple(names))
        if full not in self.collected:
            key = jax.random.key(self.seed)
            key = jax.random.fold_in(key, _name_seed(full))
            arr = init_fn(key, tuple(int(s) for s in sizes)).astype(store_dtype)
            self.collected[full] = arr
            self.axis_names[full] = tuple(names)
            self.param_count += int(arr.size)
        return NT(self.collected[full].astype(self.cfg.calculation_dtype), tuple(names))

    # -- randomness ---------------------------------------------------------
    def next_rng(self) -> jax.Array:
        if self.rng is None:
            # init mode: deterministic placeholder
            self.rng = jax.random.key(self.seed + 1)
        self._rng_counter += 1
        return jax.random.fold_in(self.rng, self._rng_counter)

    def dropout(self, t: NT, rate: float) -> NT:
        if not self.train or rate <= 0.0:
            return t
        keep = 1.0 - rate
        mask = jax.random.bernoulli(self.next_rng(), keep, t.x.shape)
        return NT(jnp.where(mask, t.x / keep, 0).astype(t.dtype), t.names)


class _Scope:
    def __init__(self, ctx: Ctx, name: str):
        self.ctx = ctx
        self.name = name

    def __enter__(self):
        ctx = self.ctx
        key = ("/".join(ctx._scope), self.name)
        idx = ctx._counters.get(key, 0)
        ctx._counters[key] = idx + 1
        resolved = f"{self.name}{idx}" if idx else self.name
        ctx._scope.append(resolved)
        # mirror onto the nd-module diagnostic stack so rank-mismatch errors
        # (and analyzer findings) can name the enclosing parameter path
        nd.push_scope(resolved)
        return ctx

    def __exit__(self, *exc):
        self.ctx._scope.pop()
        nd.pop_scope()
        return False


class _PresetScope:
    def __init__(self, ctx: Ctx, parts: typing.Sequence[str]):
        self.ctx = ctx
        self.parts = tuple(parts)

    def __enter__(self):
        self.ctx._scope = list(self.parts)
        for p in self.parts:
            nd.push_scope(p)
        return self.ctx

    def __exit__(self, *exc):
        for _ in self.parts:
            nd.pop_scope()
        return False


class Args:
    """Layer-call carrier: (ctx, tensor, name_extras, is_last) — the JAX
    analogue of the reference's BlockArgs (dataclass.py:387-419).  Calling it
    rebinds tensor / extras, mirroring the reference API so layer code reads
    the same way."""

    __slots__ = ("ctx", "tensor", "name_extras", "is_last")

    def __init__(self, ctx: Ctx, tensor: typing.Optional[NT],
                 name_extras: typing.List[str], is_last: bool = False):
        self.ctx = ctx
        self.tensor = tensor
        self.name_extras = list(name_extras)
        self.is_last = is_last

    @property
    def cfg(self) -> Config:
        return self.ctx.cfg

    def __call__(self, *args):
        new = Args(self.ctx, self.tensor, self.name_extras[:], self.is_last)
        for a in args:
            if isinstance(a, NT):
                new.tensor = a
            elif isinstance(a, (list, tuple)):
                new.name_extras = list(a)
            elif isinstance(a, str):
                new.name_extras.append(a)
            elif isinstance(a, Ctx):
                new.ctx = a
            else:
                raise ValueError(f"unsupported Args argument {a!r}")
        return new

    def __iter__(self):
        return iter(self.name_extras)

    def __contains__(self, item):
        return item in self.name_extras

    def __len__(self):
        return len(self.name_extras)

    def __getitem__(self, idx):
        return self.name_extras[idx]
