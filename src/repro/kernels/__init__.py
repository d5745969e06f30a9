"""Pallas TPU kernels of the serving hot path, their jnp oracles
(``ref.py``) and the dispatch the model layer calls (``ops.py``)."""
from __future__ import annotations


def pallas_native() -> bool:
    """Whether the default backend compiles Pallas kernels natively (a
    TPU).  Off one, the kernels run in interpret mode."""
    import jax
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret=None) -> bool:
    """Whether a Pallas kernel runs in interpret mode — the one place that
    decides it.  ``None`` follows the platform (``pallas_native``):
    interpret on a backend that is not a TPU (the CPU test backend),
    compiled on a TPU.  An explicit ``False`` compiles for the TPU (the
    cross-compile tests); interpret mode is refused on a TPU."""
    on_tpu = pallas_native()
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("Pallas interpret mode never runs on a TPU")
    return bool(interpret)
