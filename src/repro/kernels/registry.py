"""Kernel registry: uniform discovery of the Pallas ops for the query engine.

Every ``kernels/<name>/ops.py`` registers a :class:`KernelSpec` describing
its public entry point and which engine query modes it accelerates; the
engine's ``PallasBackend`` routes through :func:`get` instead of importing
kernel modules directly, so adding a kernel is a one-line registration and
backends discover capabilities (e.g. "which ops can serve 'conjunctive'?")
without hard-coding module paths.

Specs are registered at ops-module import; :func:`get` imports the module
lazily on first use so merely constructing an engine never pays kernel
import cost.
"""

from __future__ import annotations

import importlib
from dataclasses import dataclass, field
from typing import Callable

# kernel name -> module that registers it (lazy import target)
_OPS_MODULES = {
    "intersect": "repro.kernels.intersect.ops",
    "topk_score": "repro.kernels.topk_score.ops",
    "dvbyte_decode": "repro.kernels.dvbyte_decode.ops",
    "retrieval_dot": "repro.kernels.retrieval_dot.ops",
    "fused_query": "repro.kernels.fused_query.ops",
}

_REGISTRY: dict[str, "KernelSpec"] = {}


@dataclass(frozen=True)
class KernelSpec:
    """One registered kernel entry point.

    ``modes`` names the engine query modes the op accelerates (empty for ops
    outside the term-query path, e.g. dense two-tower scoring).  Every entry
    point takes ``interpret=None``, which resolves through
    :func:`default_interpret`.
    """

    name: str
    fn: Callable
    modes: tuple[str, ...] = ()
    description: str = ""
    extras: dict = field(default_factory=dict)


def register(spec: KernelSpec) -> KernelSpec:
    _REGISTRY[spec.name] = spec
    return spec


def get(name: str) -> KernelSpec:
    """Spec for ``name``, importing its ops module on first use."""
    if name not in _REGISTRY:
        mod = _OPS_MODULES.get(name)
        if mod is None:
            raise KeyError(f"unknown kernel {name!r}; "
                           f"known: {sorted(_OPS_MODULES)}")
        importlib.import_module(mod)
    return _REGISTRY[name]


def supporting(mode: str) -> list[KernelSpec]:
    """All registered kernels accelerating engine query ``mode``."""
    for name in _OPS_MODULES:
        get(name)
    return [s for s in _REGISTRY.values() if mode in s.modes]


def default_interpret() -> bool:
    """True when Pallas bodies should run in interpret mode (no TPU)."""
    import jax
    return jax.default_backend() not in ("tpu",)
