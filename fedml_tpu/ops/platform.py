"""Where the pallas kernels run: compiled by Mosaic on a TPU, interpreted
on the CPU test mesh, refused anywhere else."""

from __future__ import annotations

import jax


def pallas_interpret() -> bool:
    """The ``interpret=`` argument for every ``pallas_call`` in this
    package, decided at trace time from the default backend: ``False`` on
    ``tpu`` (the kernel compiles or the call fails), ``True`` on ``cpu``
    (the test mesh; tests/conftest.py). Any other backend raises — these
    are TPU kernels, and an interpreted run on an accelerator would pass
    for a kernel run. ``chip_smoke.py`` proves the compiled path by
    finding the Mosaic custom call in the lowered module."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"pallas TPU kernels need backend 'tpu' (or 'cpu' for the "
        f"interpreted test path); the default backend is {backend!r}")
