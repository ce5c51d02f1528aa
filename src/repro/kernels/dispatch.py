"""Backend detection and kernel-implementation dispatch — the one rule.

Every Pallas kernel in this package has up to three execution strategies:

* ``"pallas"``           — compiled ``pl.pallas_call`` (TPU only)
* ``"pallas_interpret"`` — the same kernel through the Pallas interpreter
                           (CPU-correct but slow; debugging / parity only)
* ``"xla"``              — a tiled pure-jnp formulation compiled by XLA
                           (the CPU fast path; memory profile matches the
                           Pallas kernel — no (N, N) float32 in host RAM)

``resolve("auto")`` picks compiled Pallas on a TPU and XLA tiles
elsewhere.  Interpret mode is never selected implicitly — it must be
requested by name (or via the ``REPRO_KERNEL_IMPL`` environment variable).
Asking for compiled ``"pallas"`` off a TPU is an error, not a quiet
downgrade.  ``interpret_mode`` answers the same question for call sites
that only have a Pallas kernel (flash attention, the SSD scan, dense
pairdist).
"""
from __future__ import annotations

import os

import jax

IMPLS = ("pallas", "pallas_interpret", "xla", "ref")

_ENV_VAR = "REPRO_KERNEL_IMPL"


def backend() -> str:
    """The active JAX backend: "cpu" or "tpu"."""
    return jax.default_backend()


def resolve(impl: str = "auto") -> str:
    """Map a requested implementation to a concrete one.

    "auto" honours ``REPRO_KERNEL_IMPL`` if set, then picks compiled
    Pallas on TPU and the XLA tile path elsewhere.  Explicit names pass
    through; "pallas" raises when the backend is not a TPU.
    """
    if impl in ("auto", None):
        impl = os.environ.get(_ENV_VAR, "").strip().lower() or "auto"
    if impl == "auto":
        return "pallas" if backend() == "tpu" else "xla"
    if impl not in IMPLS:
        raise ValueError(f"unknown kernel impl {impl!r}; expected one of "
                         f"{('auto',) + IMPLS}")
    if impl == "pallas" and backend() != "tpu":
        raise RuntimeError(
            f"compiled Pallas needs a TPU, but the JAX backend is "
            f"{backend()!r}; ask for 'pallas_interpret' to run the kernel "
            f"in the interpreter")
    return impl


def interpret_mode(impl: str = "auto") -> bool:
    """Whether a ``pl.pallas_call`` for this request runs in the
    interpreter.  Raises when the request resolves to a non-Pallas
    strategy, which a Pallas-only call site cannot honour."""
    resolved = resolve(impl)
    if resolved not in ("pallas", "pallas_interpret"):
        raise RuntimeError(
            f"kernel impl {impl!r} resolves to {resolved!r} on backend "
            f"{backend()!r}, but this call site only has a Pallas kernel; "
            f"pass interpret=True or set {_ENV_VAR}=pallas_interpret")
    return resolved == "pallas_interpret"
