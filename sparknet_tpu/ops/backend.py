"""What every pallas kernel module asks of the backend, in a module that
imports no kernel and not `jax.experimental.pallas`: a kernel module is
imported in the branch that calls it, and asking this question loads none
of the others."""

import jax


def _should_interpret():
    """Interpret mode is for the CPU tests only. Every other backend
    compiles the kernel, and raises where it cannot: nothing on the chip
    path quietly runs the interpreter instead."""
    return jax.default_backend() == "cpu"
