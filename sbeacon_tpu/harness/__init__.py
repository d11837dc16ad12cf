"""Simulation/chaos harness.

``faults`` (stdlib-only chaos hooks) is imported eagerly — the serving
path calls its ``fault_point`` — but the simulation tooling
is exposed LAZILY (PEP 562): core modules import
``sbeacon_tpu.harness.faults`` at module load, and that must not drag
the synthetic-data writers and genomics fixtures into every production
server process.
"""

from . import faults

_LAZY = {
    "populate": "simulate",
    "random_submission": "simulate",
    "run_latency_suite": "latency",
}

__all__ = ["faults", *_LAZY]


def __getattr__(name: str):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(f".{mod}", __name__), name)
