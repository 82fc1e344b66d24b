"""Device-mesh construction from the config's parallelism knobs.

Axis order is (data, sequence, model): model innermost so tensor-parallel
collectives ride the fastest ICI links, data outermost so gradient all-reduce
tolerates DCN hops on multi-host — the same intent as the reference's
``mesh_shape="b:N,h:H"`` ordering (dataclass.py:247-252) where the head axis
maps to the minor mesh dimension.
"""
from __future__ import annotations

import typing

import jax
import numpy as np
from jax.sharding import Mesh

from ..config import Config

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "sequence_parallel"
PIPE_AXIS = "pipeline"

#: every mesh axis a PartitionSpec in this codebase may legally name — the
#: ground truth for graftcheck's sharding-spec validation
MESH_AXES = (DATA_AXIS, SEQ_AXIS, PIPE_AXIS, MODEL_AXIS)


def axis_sizes(cfg: Config, n_devices: int,
               quiet: bool = False) -> typing.Dict[str, int]:
    """Resolve mesh axis sizes for ``n_devices``.  ``heads`` bounds the model
    axis; remaining devices fold into data parallelism (reference behavior:
    b = tpu_size / heads).  The pipeline axis (GPipe stages, ops/pipeline.py)
    is exactly ``cfg.pipeline_parallel``.  ``quiet`` suppresses the shrink
    warning — the elastic degraded-resume path replaces it with the mesh
    searcher's suggestion (reliability/dist.py::suggest_mesh)."""
    model = cfg.mesh_model
    seq = cfg.sequence_parallel
    pipe = cfg.pipeline_parallel
    denom = model * seq * pipe
    if n_devices % denom:
        # shrink the model axis to the largest divisor that fits
        model = 1
        for cand in range(min(cfg.mesh_model, n_devices), 0, -1):
            # the model axis must also divide the head count or head-sharded
            # parameters cannot be placed on the mesh
            if n_devices % (cand * seq * pipe) == 0 and cfg.heads % cand == 0:
                model = cand
                break
        denom = model * seq * pipe
        if n_devices % denom:
            raise ValueError(
                f"cannot factor {n_devices} devices into seq={seq} pipe={pipe}")
        if not quiet:
            print(f"WARNING: model axis shrunk from {cfg.mesh_model} to "
                  f"{model} to factor {n_devices} devices (seq={seq}, "
                  f"pipe={pipe}); `python tools/graftmesh.py --config "
                  f"<config> --world {n_devices}` searches the layout "
                  f"instead of folding")
    return {DATA_AXIS: n_devices // denom, SEQ_AXIS: seq, PIPE_AXIS: pipe,
            MODEL_AXIS: model}


def mesh_factorizations(cfg: Config, n_devices: int,
                        free_axes: typing.Sequence[str] = ()
                        ) -> typing.List[typing.Dict[str, int]]:
    """Every DP/SP/PP/TP axis-size assignment of ``n_devices`` this config
    could actually instantiate, in deterministic order — the enumeration
    space of the mesh searcher (analysis/mesh_search.py).

    Default constraints mirror :func:`axis_sizes`' degrees of freedom: the
    sequence and pipeline axes are STRUCTURAL declarations (they change the
    traced program — ring attention chunks, pipeline stage scans), so they
    stay pinned to the config's values while data x model placement varies.
    Passing axis names in ``free_axes`` (``sequence_parallel`` and/or
    ``pipeline``) unlocks them, subject to the validity rules config.py
    enforces: the model axis must divide ``heads`` (head-sharded params),
    the data axis must divide ``train_batch_size`` (make_mesh would drop
    surplus devices), a free sequence axis must divide ``sequence_length``
    (ring chunking), and a free pipeline axis must divide ``depth`` under a
    compatible memory-reduction strategy."""
    free = set(free_axes)
    unknown = free - {SEQ_AXIS, PIPE_AXIS}
    if unknown:
        raise ValueError(f"free_axes may name {SEQ_AXIS!r} and {PIPE_AXIS!r} "
                         f"only; got {sorted(unknown)}")

    def _divisors(n: int) -> typing.List[int]:
        return [d for d in range(1, n + 1) if n % d == 0]

    if SEQ_AXIS in free:
        seqs = [s for s in _divisors(n_devices)
                if cfg.sequence_length % s == 0]
    else:
        seqs = [cfg.sequence_parallel]
    if PIPE_AXIS in free:
        pipes = [p for p in _divisors(n_devices)
                 if p == 1 or (cfg.depth % p == 0 and not cfg.use_video
                               and cfg.memory_reduction_strategy
                               in ("none", "checkpoint"))]
    else:
        pipes = [cfg.pipeline_parallel]
    out: typing.List[typing.Dict[str, int]] = []
    for seq in seqs:
        for pipe in pipes:
            if seq > 1 and pipe > 1 and cfg.pipeline_schedule != "1f1b":
                continue  # config.py rejects the composition under gpipe
            rest = n_devices // (seq * pipe)
            if seq * pipe * rest != n_devices:
                continue
            for model in _divisors(rest):
                if model > cfg.heads or cfg.heads % model:
                    continue
                data = rest // model
                if cfg.train_batch_size % data:
                    continue
                out.append({DATA_AXIS: data, SEQ_AXIS: seq, PIPE_AXIS: pipe,
                            MODEL_AXIS: model})
    out.sort(key=lambda s: (s[DATA_AXIS], s[SEQ_AXIS], s[PIPE_AXIS],
                            s[MODEL_AXIS]))
    return out


def make_mesh(cfg: Config,
              devices: typing.Optional[typing.Sequence[jax.Device]] = None,
              quiet: bool = False) -> Mesh:
    """Mesh over ``devices`` (default: every device JAX sees).  When the
    batch cannot shard over the data axis the axis drops to the largest
    batch divisor and the surplus devices stay out of the mesh — which on an
    accelerator is an error (a chip that is paid for and idle, and a
    per-chip throughput divided by the wrong count) unless the caller
    passes ``quiet`` and deals with the smaller mesh itself (the elastic
    degraded resume, the static analysis).  The CPU test mesh only warns."""
    devices = list(devices if devices is not None else jax.devices())
    sizes = axis_sizes(cfg, len(devices), quiet=quiet)
    batch = cfg.train_batch_size
    if batch % sizes[DATA_AXIS]:
        data = max(d for d in range(1, sizes[DATA_AXIS] + 1)
                   if batch % d == 0)
        if not quiet:
            unused = ((sizes[DATA_AXIS] - data) * sizes[SEQ_AXIS]
                      * sizes[PIPE_AXIS] * sizes[MODEL_AXIS])
            msg = (f"data axis shrunk from {sizes[DATA_AXIS]} to {data} "
                   f"(train_batch_size={batch}); {unused} device(s) left "
                   f"unused")
            if devices[0].platform != "cpu":
                raise ValueError(
                    f"{msg} — pick a batch the data axis divides (`python "
                    f"tools/graftmesh.py --config <config> --world "
                    f"{len(devices)}` searches layouts)")
            print(f"WARNING: {msg}")
        sizes[DATA_AXIS] = data
    names = (DATA_AXIS, SEQ_AXIS, PIPE_AXIS, MODEL_AXIS)
    n_used = 1
    for n in names:
        n_used *= sizes[n]
    grid = np.asarray(devices[:n_used]).reshape([sizes[n] for n in names])
    return Mesh(grid, names)
