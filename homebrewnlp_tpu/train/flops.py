"""Static utilization accounting: per-step FLOPs from the compiled HLO.

The reference framework never knew what a step *cost* — utilization was a
number someone computed by hand from the model card.  bench.py grew an XLA
cost-analysis path (EXECUTED flops of the exact compiled step) for its
offline BENCH line; this module makes that same accounting available to the
*live* run, so ``train/metrics.py`` rows, the ``/metrics`` exporter, and
``metrics.jsonl`` carry MFU / tokens-per-second / goodput continuously
instead of once per benchmark session.

One source of truth: ``PEAK_BF16`` (per-chip peak dense bf16 FLOP/s by
``device_kind``) and the cost-analysis call both live here and bench.py
imports them, so the two MFU figures cannot drift — they are the same
arithmetic over the same compiled executable (pinned by
tests/telemetry_test.py::test_flops_reconcile_with_bench_cost_analysis).

Everything here is HOST-side and runs once at startup (the cost analysis
rides the step compile the run pays anyway, via
``Trainer.step_cost_analysis``'s kept AOT executable); nothing touches the
per-step hot path.
"""
from __future__ import annotations

import dataclasses
import typing

#: Peak dense bf16 FLOP/s per chip (public specs), keyed by the canonical
#: kinds of ``homebrewnlp_tpu/devices.py``.
PEAK_BF16: typing.Dict[str, float] = {
    "v6e": 918e12, "v5p": 459e12, "v5e": 197e12,
    "v4": 275e12, "v3": 123e12, "v2": 45e12,
}


def peak_flops(device_kind: str) -> typing.Optional[float]:
    """Per-chip peak bf16 FLOP/s for a runtime ``device_kind`` or a
    canonical kind, by exact match (``devices.py::canonical_kind``): None
    on ``"cpu"`` (no MFU claim), an error for any unknown kind."""
    from ..devices import canonical_kind
    kind = canonical_kind(device_kind)
    return PEAK_BF16[kind] if kind else None


def eqn_dot_flops(eqn) -> float:
    """Multiply-add flops of one ``dot_general`` equation from its abstract
    operand shapes (2 * batch * M * N * K), zero for anything else."""
    if eqn.primitive.name != "dot_general":
        return 0.0
    try:
        (contract, batch_dims) = eqn.params["dimension_numbers"]
        (lc, rc), (lb, _rb) = contract, batch_dims
        lhs = eqn.invars[0].aval.shape
        rhs = eqn.invars[1].aval.shape
    except Exception:
        return 0.0
    k = 1
    for d in lc:
        k *= int(lhs[d])
    b = 1
    for d in lb:
        b *= int(lhs[d])
    m = 1
    for i, d in enumerate(lhs):
        if i not in lc and i not in lb:
            m *= int(d)
    n = 1
    for i, d in enumerate(rhs):
        if i not in rc and i not in (_rb or ()):
            n *= int(d)
    return 2.0 * b * m * n * k


def jaxpr_flops(jaxpr) -> float:
    """Static matmul-flop count of a (Closed)Jaxpr — the compile-free twin
    of the XLA cost analysis ``step_flops`` runs on the compiled step, used
    by the analysis cost model's roofline verdict (analysis/cost_model.py).

    ``dot_general`` dominates every workload here; elementwise/conv flops
    are ignored (they are noise next to the matmuls and XLA fuses them into
    the dots' memory traffic anyway).  Sub-jaxprs multiply by their trip
    count: ``scan`` bodies by ``length`` (gradient accumulation, pipeline
    ticks), everything else (pjit/custom_vjp/checkpoint/while/cond) by 1 —
    a ``while`` with an unknowable trip count undercounts, which keeps the
    figure a lower bound like the unfused-twin convention above."""
    inner = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    total = 0.0
    for eqn in inner.eqns:
        total += eqn_dot_flops(eqn)
        mult = 1
        if eqn.primitive.name == "scan":
            mult = int(eqn.params.get("length", 1) or 1)
        for v in eqn.params.values():
            vals = v if isinstance(v, (list, tuple)) else [v]
            for item in vals:
                if hasattr(item, "eqns") or (
                        hasattr(item, "jaxpr")
                        and hasattr(item.jaxpr, "eqns")):
                    total += mult * jaxpr_flops(item)
    return total


def step_flops(trainer, state, batch) -> float:
    """EXECUTED flops of the exact compiled train step (XLA cost analysis,
    same figure bench.py records as ``flops_per_step``).  The AOT executable
    is kept by the trainer, so the analysis costs no extra compile and the
    loop's subsequent steps reuse it."""
    cost = trainer.step_cost_analysis(state, batch)
    return float(cost.get("flops", 0.0))


def kernels_opaque(cfg) -> bool:
    """True when the config routes work through hand-written pallas kernels
    whose in-kernel flops XLA cost analysis cannot see — the executed count
    of the fused step is then incomplete (BENCH_r05's
    ``flops_executed_partial`` / ``mfu: null`` failure mode)."""
    return bool(cfg.fused_mixer_block)


def unfused_twin_flops(trainer, state, batch) -> float:
    """Flops of the SAME step with the fused pallas kernels off — an
    explicit, documented LOWER BOUND on the fused step's executed flops
    (the kernels run the identical math plus in-kernel backward recompute,
    so fusing never removes arithmetic; docs/performance.md "Utilization
    accounting").  Everything else about the config — remat, blocked-map
    depth, quantization — is kept, so the bound tracks the step actually
    being timed.

    Cost: one extra XLA compile of the unfused step (no execution, no
    init: params / optimizer slots are adopted from the measured trainer).
    The cheaper pre-compile ``Lowered.cost_analysis`` was measured and
    rejected: unoptimized-HLO counts run ~7x the compiled figure on the
    tiny test config — an OVER-estimate, which would overstate MFU and
    break the lower-bound contract.  On the live path this only runs for
    fused configs with telemetry enabled, and the compile is served by the
    persistent XLA cache on every restart after the first."""
    import copy

    from ..optim import Optimizer
    from .state import Trainer
    cfg = copy.copy(trainer.cfg)  # knob flip only; derived fields carry over
    cfg.fused_mixer_block = False
    twin = Trainer(cfg, trainer.mesh)
    twin.axes = trainer.axes
    twin.optimizer = Optimizer(cfg, trainer.axes)
    return step_flops(twin, state, batch)


def executed_flops_with_bound(trainer, state, batch
                              ) -> typing.Tuple[float, bool]:
    """(hardware flops per step, is_lower_bound): the cost-analyzed count of
    the exact compiled step, replaced by the unfused twin's count whenever
    opaque kernels make the direct figure incomplete.  The second element
    flags the substitution so consumers label the resulting MFU a lower
    bound instead of an exact figure."""
    flops = step_flops(trainer, state, batch)
    if not kernels_opaque(trainer.cfg):
        return flops, False
    return max(flops, unfused_twin_flops(trainer, state, batch)), True


@dataclasses.dataclass
class Utilization:
    """Static per-step accounting; ``rates(step_seconds)`` turns a measured
    step wall time into the live MFU / throughput figures."""

    flops_per_step: float
    tokens_per_step: int
    n_chips: int
    peak_flops_per_chip: typing.Optional[float]
    device_kind: str = ""
    # True when flops_per_step is the unfused-twin LOWER BOUND (opaque
    # pallas kernels hide their in-kernel flops from cost analysis) — the
    # derived mfu is then a floor, not an exact figure
    flops_lower_bound: bool = False

    def rates(self, step_seconds: float) -> typing.Dict[str, float]:
        if not step_seconds or step_seconds <= 0:
            return {}
        out = {
            "tokens_per_sec": self.tokens_per_step / step_seconds,
            "tokens_per_sec_per_chip": (self.tokens_per_step / step_seconds
                                        / max(1, self.n_chips)),
        }
        if self.peak_flops_per_chip and self.flops_per_step:
            out["mfu"] = (self.flops_per_step / step_seconds
                          / (self.peak_flops_per_chip * max(1, self.n_chips)))
        return out


def utilization_for(trainer, state, batch, tokens_per_step: int
                    ) -> Utilization:
    """Build the static accounting for one run: cost-analyze the compiled
    step and pin the device peak.  Called once at startup when telemetry is
    enabled (main.py)."""
    import jax
    devices = jax.devices()
    kind = devices[0].device_kind
    flops, lower_bound = executed_flops_with_bound(trainer, state, batch)
    return Utilization(
        flops_per_step=flops,
        tokens_per_step=int(tokens_per_step),
        n_chips=max(1, len(devices)),
        peak_flops_per_chip=peak_flops(kind),
        device_kind=kind,
        flops_lower_bound=lower_bound)
