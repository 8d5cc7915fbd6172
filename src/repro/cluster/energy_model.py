"""Roofline-derived task durations and energy for ML jobs as FJSP tasks.

This is the (A)<->(B) bridge of DESIGN.md §2: each assigned architecture's
dry-run roofline (FLOPs/bytes/collective seconds per step) prices a
"train N steps of arch X" or "serve N requests of arch X" task on a menu
of heterogeneous TPU slices — the machine classes the paper's scheduler
(repro.core) then places tasks on.

Machine classes mirror the paper's heterogeneous setup (5 power/speed
tiers) but are grounded in v5e slices: speed scales with chip count times
a utilization factor (small slices run at higher MFU — less collective
overhead — exactly the speed/efficiency tension §3.2 of the paper probes).

Step time comes from the analytic 6·N·D estimate, so results depend only
on committed code.
"""
from __future__ import annotations

import dataclasses

from repro.launch.roofline import PEAK_FLOPS
from repro.models.common import ArchConfig, SHAPES

CHIP_POWER_KW = 0.30          # v5e chip + share of host/interconnect


@dataclasses.dataclass(frozen=True)
class MachineClass:
    name: str
    chips: int
    utilization: float            # achieved fraction of peak (MFU-ish)

    @property
    def power_kw(self) -> float:
        return self.chips * CHIP_POWER_KW

    @property
    def throughput(self) -> float:  # effective FLOP/s
        return self.chips * PEAK_FLOPS * self.utilization


# Five tiers, paper-style: speeds ~ {1/3, 1/2, 1, 4/3, 2} x the 64-chip
# baseline; smaller slices are more efficient per chip.
TPU_V5E_CLASSES: tuple[MachineClass, ...] = (
    MachineClass("v5e-16", 16, 0.55),
    MachineClass("v5e-32", 32, 0.50),
    MachineClass("v5e-64", 64, 0.45),
    MachineClass("v5e-96", 96, 0.42),
    MachineClass("v5e-160", 160, 0.38),
)

def step_flops(cfg: ArchConfig, shape: str) -> float:
    """Total FLOPs of one step of the (arch, shape) cell (analytic)."""
    sc = SHAPES[shape]
    tokens = sc.batch * (sc.seq if sc.kind != "decode" else 1)
    n = cfg.active_param_count()
    mult = 6.0 if sc.kind == "train" else 2.0
    return mult * n * tokens


def task_profile(cfg: ArchConfig, shape: str, n_steps: int,
                 machine: MachineClass, epoch_hours: float = 0.25
                 ) -> tuple[int, float]:
    """(duration_epochs, energy_kwh) of running ``n_steps`` of the cell on
    ``machine`` — the p_{t,m} / E_{t,m} inputs of the paper's Appendix A."""
    work = step_flops(cfg, shape) * n_steps
    seconds = work / machine.throughput
    epochs = max(1, round(seconds / (epoch_hours * 3600)))
    energy = machine.power_kw * epochs * epoch_hours
    return epochs, energy


def baseline_durations(cfg: ArchConfig, shape: str, n_steps: int,
                       classes=TPU_V5E_CLASSES) -> dict[str, int]:
    return {m.name: task_profile(cfg, shape, n_steps, m)[0] for m in classes}
