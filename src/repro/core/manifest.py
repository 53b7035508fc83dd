"""Environment manifests — the container image of this framework.

The paper's portability claim rests on an *immutable, version-pinned
software environment* whose only site-specific parts (drivers, NICs, GPUs)
are bound at launch.  Our equivalent:

  portable part   — PortableEnv: model/shape/rule-set configs, code +
                    jax/numpy versions, XLA flags, dtype policy.  Hashable;
                    two runs with equal hashes are the same "image".
  host binding    — HostBinding: device kind/count, mesh shape/axes,
                    per-chip peaks.  Attached late (bind()).
  attestation     — after lowering, the HLO fingerprint + collective
                    summary are recorded; re-running on another host with
                    the same portable hash but a different HLO fingerprint
                    is the "container behaves differently on this site"
                    signal the paper detects with microbenchmarks.

Manifests serialize to JSON; ``diff`` explains any mismatch — the Table-1
"toolchain comparison" of the paper, automated.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import platform
import sys
from dataclasses import dataclass, field
from typing import Any

import jax
import numpy as np

from repro.configs.base import MeshConfig, ModelConfig, ShapeConfig, TrainConfig


def _hash(obj: Any) -> str:
    return hashlib.sha256(
        json.dumps(obj, sort_keys=True, default=str).encode()).hexdigest()[:16]


@dataclass(frozen=True)
class PortableEnv:
    """Everything that must be identical across sites."""

    model: dict
    shape: dict
    train: dict
    rules: str
    jax_version: str = ""
    numpy_version: str = ""
    python_version: str = ""
    xla_flags: str = ""
    dtype_policy: str = "bf16-params/f32-master"

    @classmethod
    def capture(cls, model: ModelConfig, shape: ShapeConfig,
                train: TrainConfig | None = None, rules: str = "auto",
                xla_flags: str = "") -> "PortableEnv":
        import os

        return cls(
            model=dataclasses.asdict(model),
            shape=dataclasses.asdict(shape),
            train=dataclasses.asdict(train or TrainConfig()),
            rules=rules,
            jax_version=jax.__version__,
            numpy_version=np.__version__,
            python_version=sys.version.split()[0],
            xla_flags=xla_flags or os.environ.get("XLA_FLAGS", ""),
        )

    @property
    def image_hash(self) -> str:
        return _hash(dataclasses.asdict(self))


@dataclass(frozen=True)
class HostBinding:
    """Site-specific, non-encapsulatable facts (late-bound)."""

    device_kind: str
    n_devices: int
    mesh_shape: tuple[int, ...]
    mesh_axes: tuple[str, ...]
    platform_: str = ""
    hostname: str = ""

    @property
    def peaks(self):
        """This chip's published peaks (``core.peaks``; raises for a
        device kind without a table entry — never a borrowed default)."""
        from repro.core.peaks import chip_peaks
        return chip_peaks(self.device_kind)

    @classmethod
    def capture(cls, mesh) -> "HostBinding":
        dev = jax.devices()[0]
        return cls(
            device_kind=dev.device_kind,
            n_devices=mesh.devices.size,
            mesh_shape=tuple(mesh.devices.shape),
            mesh_axes=tuple(mesh.axis_names),
            platform_=dev.platform,
            hostname=platform.node(),
        )


@dataclass
class Manifest:
    portable: PortableEnv
    binding: HostBinding | None = None
    attestation: dict = field(default_factory=dict)

    def bind(self, mesh) -> "Manifest":
        self.binding = HostBinding.capture(mesh)
        return self

    def attest(self, *, hlo_text: str | None = None,
               collectives: dict | None = None,
               cost: dict | None = None) -> "Manifest":
        if hlo_text is not None:
            self.attestation["hlo_fingerprint"] = hashlib.sha256(
                hlo_text.encode()).hexdigest()[:16]
            self.attestation["hlo_bytes"] = len(hlo_text)
        if collectives is not None:
            self.attestation["collectives"] = collectives
        if cost is not None:
            self.attestation["cost"] = cost
        return self

    # ---- serialization ----
    def to_json(self) -> str:
        return json.dumps({
            "image_hash": self.portable.image_hash,
            "portable": dataclasses.asdict(self.portable),
            "binding": dataclasses.asdict(self.binding) if self.binding else None,
            "attestation": self.attestation,
        }, indent=1, default=str)

    @classmethod
    def from_json(cls, text: str) -> "Manifest":
        raw = json.loads(text)
        portable = PortableEnv(**raw["portable"])
        m = cls(portable=portable)
        if raw.get("binding"):
            b = {k: v for k, v in raw["binding"].items()
                 if k in {f.name for f in dataclasses.fields(HostBinding)}}
            b["mesh_shape"] = tuple(b["mesh_shape"])
            b["mesh_axes"] = tuple(b["mesh_axes"])
            m.binding = HostBinding(**b)
        m.attestation = raw.get("attestation", {})
        return m


def diff(a: Manifest, b: Manifest) -> list[str]:
    """Explain differences between two manifests (paper Table 1, automated).

    Portable-part differences are *environment divergence* (a reproducibility
    bug); binding differences are expected host variation; attestation
    differences under equal portable hashes indicate the binding changed the
    compiled behavior — the thing the paper's microbenchmarks exist to catch.
    """
    out: list[str] = []
    da, db = dataclasses.asdict(a.portable), dataclasses.asdict(b.portable)
    for k in sorted(set(da) | set(db)):
        if da.get(k) != db.get(k):
            out.append(f"portable.{k}: {da.get(k)!r} != {db.get(k)!r}")
    if a.binding and b.binding:
        ba, bb = dataclasses.asdict(a.binding), dataclasses.asdict(b.binding)
        for k in sorted(set(ba) | set(bb)):
            if ba.get(k) != bb.get(k):
                out.append(f"binding.{k}: {ba.get(k)!r} != {bb.get(k)!r} (host)")
    fa = a.attestation.get("hlo_fingerprint")
    fb = b.attestation.get("hlo_fingerprint")
    if fa and fb and fa != fb:
        tag = ("EXPECTED (binding differs)" if out else
               "UNEXPECTED — same env+binding, different program")
        out.append(f"attestation.hlo_fingerprint: {fa} != {fb} [{tag}]")
    return out
