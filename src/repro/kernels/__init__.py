"""Pallas kernels of the secure stream path (ChaCha20, CW-MAC, enclave map).

Every ``ops.py`` wrapper asks :func:`interpret_mode` whether to run its
kernel in Pallas interpret mode.
"""
import jax


def interpret_mode() -> bool:
    """True only on the CPU backend (tests, rehearsals).

    Any other platform compiles the kernels for real: a TPU backend that
    failed to come up must fail the run, not fall back to the interpreter.
    """
    return jax.default_backend() == "cpu"
