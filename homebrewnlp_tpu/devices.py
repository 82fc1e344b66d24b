"""Per-topology device constants for the static cost model (analysis/).

One small table, deliberately approximate: public per-chip HBM capacity,
HBM bandwidth, and ICI (inter-chip interconnect) bandwidth per mesh
direction, plus a per-collective latency constant for the alpha-beta
estimate.  The numbers exist so "does this config fit / what is it bound
by" can be answered BEFORE a ~2-minute TPU compile; they are calibrated
against measured ``memory_stats()`` peaks and XLA cost analysis by
bench.py's ``resources`` validation hook (``prediction_error`` rides the
BENCH trajectory), and tightened as that data accrues.

This module is a LEAF — no package imports — so ``config.py`` can validate
the ``target_device`` knob and ``analysis/cost_model.py`` can price a graph
without import cycles.  Peak FLOP/s stays in ``train/flops.py::PEAK_BF16``
(the live-MFU source of truth), keyed by the same canonical kinds;
``tests/graftcost_test.py`` pins that every kind here resolves there too.
"""
from __future__ import annotations

import dataclasses
import typing


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    kind: str  # canonical name: the ``target_device`` knob's vocabulary
    hbm_bytes: int  # per-chip HBM capacity
    hbm_bw: float  # per-chip HBM bandwidth, bytes/s
    ici_bw: float  # per-link ICI bandwidth, bytes/s (one mesh direction)
    alpha_s: float = 1e-6  # per-collective launch/hop latency (alpha term)


_GIB = 1024 ** 3

#: Sources: public TPU system specs; ici_bw is the per-direction figure the
#: alpha-beta model charges each mesh axis independently.
DEVICE_TABLE: typing.Tuple[DeviceSpec, ...] = (
    DeviceSpec("v6e", 32 * _GIB, 1640e9, 448e9),
    DeviceSpec("v5p", 95 * _GIB, 2765e9, 600e9),
    DeviceSpec("v5e", 16 * _GIB, 819e9, 200e9),
    DeviceSpec("v4", 32 * _GIB, 1228e9, 300e9),
    DeviceSpec("v3", 32 * _GIB, 900e9, 162e9),
    DeviceSpec("v2", 16 * _GIB, 700e9, 62e9),
)
_BY_KIND = {s.kind: s for s in DEVICE_TABLE}

#: ``jax.devices()[0].device_kind`` EXACTLY as the installed runtime reports
#: it, mapped to the canonical kind.  Only strings printed on a machine
#: this repo ran on belong here (jax 0.9.0 / libtpu 0.0.34 on the TPU v5e:
#: chip run of PR 21); a new machine adds its string, it is never guessed.
RUNTIME_DEVICE_KINDS = {"TPU v5 lite": "v5e"}

#: kinds tools/graftcost.py sweeps by default (one per HBM class)
SWEEP_KINDS = ("v5e", "v4", "v5p")


def canonical_kind(kind: str) -> typing.Optional[str]:
    """Table key for a ``target_device`` name or a runtime ``device_kind``,
    by exact match.  ``"cpu"`` (the test platform) is None: no capacity,
    bandwidth or peak is claimed there.  Anything else unknown raises — a
    substring match once priced every unknown "v5..." as a v5p, and a None
    for an unknown TPU made MFU quietly vanish."""
    kind = RUNTIME_DEVICE_KINDS.get(kind, kind)
    if kind in _BY_KIND:
        return kind
    if kind == "cpu":
        return None
    raise ValueError(
        f"unknown device kind {kind!r}; known kinds: "
        f"{', '.join(known_kinds())}; runtime device_kind strings: "
        f"{sorted(RUNTIME_DEVICE_KINDS)} (add the exact string the runtime "
        f"prints to homebrewnlp_tpu/devices.py::RUNTIME_DEVICE_KINDS)")


def resolve_device(kind: str) -> typing.Optional[DeviceSpec]:
    """Spec for a device kind (:func:`canonical_kind` rules: exact match,
    None on ``"cpu"``, an error on anything unknown)."""
    canon = canonical_kind(kind)
    return _BY_KIND[canon] if canon else None


def known_kinds() -> typing.Tuple[str, ...]:
    return tuple(s.kind for s in DEVICE_TABLE)
