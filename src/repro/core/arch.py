"""Architecture classes: the tuning key that makes federation fleet-safe.

Federation (PR 4) merged records under the silent assumption that every
producer ran identical hardware — a winner tuned on one device generation
would overwrite (and poison) the winner another generation measured for the
same fingerprint. This module introduces the missing tuning parameter: an
:class:`ArchProfile` — a frozen, hashable description of the machine class a
record was measured on (lane count, VMEM capacity, the compute/bandwidth
roofline ratio, backend tag) — whose canonical string form (:attr:`ArchProfile.cls`)
is stamped onto every :class:`~repro.core.tuner.TuningRecord`.

The contract downstream:

  * records carrying the *same* arch class last-writer-wins merge exactly as
    before (:mod:`repro.core.federate` partitions per class);
  * records from a *different* class never become direct database hits —
    the selector re-ranks their policies under the local (calibrated)
    machine instead (the ``"xarch"`` warm-seed dispatch source), tritonBLAS'
    analytical model as the cross-arch translator;
  * legacy arch-less artifacts parse into the :data:`DEFAULT_ARCH` class
    (``"default"``) and keep dispatching byte-identically.

Profiles are *coarse* on purpose: two hosts of the same device generation
must land in the same class even when their calibrated constants differ by a
few percent, so the ratio term is quantized (:data:`_RATIO_STEP`). Deriving
a profile from a :class:`~repro.core.costmodel.Machine`
(:meth:`ArchProfile.from_machine`) or the live JAX device
(:func:`detect_arch`) yields the same class for the same hardware.
"""

from __future__ import annotations

import json
from dataclasses import asdict, dataclass

from repro.core.costmodel import V5E, Machine

#: arch class every record written before (or without) arch awareness
#: belongs to. Stamping it is encoding-free: journal lines, snapshots, and
#: sieve key bytes of ``"default"``-class artifacts stay byte-identical to
#: the pre-arch formats, which is what keeps single-class fleets (and every
#: existing artifact) on the exact PR-4 merge behavior.
DEFAULT_ARCH = "default"

#: quantization step of the compute/bandwidth ratio term: hosts of one
#: device generation must classify together despite calibration-level
#: drift in their fitted constants, so the ratio rounds to this granularity.
_RATIO_STEP = 25


@dataclass(frozen=True)
class ArchProfile:
    """One machine class: the coordinates tuning records federate within.

    Frozen and hashable — profiles key dictionaries (per-class record
    partitions, per-class calibrations) and participate in journal entries.
    """

    #: execution backend tag ("tpu", "gpu", "cpu", ...)
    backend: str = "tpu"
    #: parallel lanes the scheduler fills (cores / SMs / forced host devices)
    lanes: int = 8
    #: per-lane VMEM / shared-memory capacity in bytes (tile feasibility)
    vmem_bytes: int = V5E.vmem_bytes
    #: quantized peak-FLOP/s : HBM-byte/s roofline ratio — the "clock/byte"
    #: coordinate that separates device generations with the same lane count
    flops_per_byte: int = 250

    @property
    def cls(self) -> str:
        """Canonical class string records are stamped with (stable,
        human-readable: ``"tpu:l8:v16m:r250"``)."""
        return (
            f"{self.backend}:l{self.lanes}"
            f":v{self.vmem_bytes >> 20}m:r{self.flops_per_byte}"
        )

    @classmethod
    def from_machine(cls, mach: Machine, backend: str = "tpu") -> "ArchProfile":
        """Classify a cost-model machine (nominal or calibrated base).

        The roofline ratio quantizes to :data:`_RATIO_STEP` so two hosts of
        one generation with slightly different calibrated constants land in
        the same class."""
        ratio = mach.peak_flops / max(mach.hbm_bw, 1.0)
        return cls(
            backend=backend,
            lanes=mach.lanes,
            vmem_bytes=mach.vmem_bytes,
            flops_per_byte=int(round(ratio / _RATIO_STEP)) * _RATIO_STEP,
        )

    def to_json(self) -> dict:
        """JSON payload (the ``{"arch": ...}`` journal entry body)."""
        d = asdict(self)
        d["cls"] = self.cls
        return d

    @classmethod
    def from_json(cls, d: dict) -> "ArchProfile":
        """Inverse of :meth:`to_json` (the redundant ``cls`` field is
        ignored — the class string is always re-derived, so a hand-edited
        payload cannot desynchronize the two)."""
        return cls(
            backend=str(d.get("backend", "tpu")),
            lanes=int(d.get("lanes", 8)),
            vmem_bytes=int(d.get("vmem_bytes", V5E.vmem_bytes)),
            flops_per_byte=int(d.get("flops_per_byte", 250)),
        )


def detect_arch(mach: Machine = V5E) -> ArchProfile:
    """Profile of the live JAX device (backend tag from the device platform,
    machine coordinates from ``mach`` — the nominal/overridden machine the
    caller scores under). A backend that fails to start raises: it is
    never classified as ``"cpu"``."""
    import jax

    return ArchProfile.from_machine(mach, backend=jax.devices()[0].platform)


def arch_entry(profile: ArchProfile) -> str:
    """One journal line declaring the producer's arch profile — the third
    tagged entry type the tuning journal understands (see the registry in
    :mod:`repro.core.tuner`). Consumers store it in
    ``TuningDatabase.arch_profiles`` keyed by class string, so a merged
    fleet knows the coordinates behind every class it carries."""
    return json.dumps({"arch": profile.to_json()})


def append_arch(path: str, profile: ArchProfile) -> None:
    """Append an arch-profile entry to the JSONL journal."""
    with open(path, "a") as f:
        f.write(arch_entry(profile) + "\n")
