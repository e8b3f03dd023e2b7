"""Pallas TPU kernels (``ops.py`` dispatches, ``ref.py`` holds the jnp
oracles).  Interpret mode is chosen by platform, never by a flag: the
kernels compile for the TPU and run in the Pallas interpreter only on
the CPU backend."""
import jax


def interpret_mode(interpret=None) -> bool:
    """``interpret`` if given, else True exactly on the CPU backend."""
    if interpret is not None:
        return interpret
    return jax.default_backend() == "cpu"
