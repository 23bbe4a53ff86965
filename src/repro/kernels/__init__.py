import os

from repro.kernels import (  # noqa: F401
    bucket_ring, fused_memory, ops, ref, ring_sum, squant, wire_round)


def default_interpret() -> bool:
    """Resolve Pallas interpret mode for kernel call sites that do not pin it.

    ``REPRO_INTERPRET=1/0`` forces interpret on/off (e.g. force-compile
    Mosaic in CI, or interpret-debug on a TPU host); unset/``auto`` selects
    interpret on CPU and compiled Mosaic on accelerator backends.
    """
    env = os.environ.get("REPRO_INTERPRET", "").strip().lower()
    if env in ("1", "true", "yes", "on"):
        return True
    if env in ("0", "false", "no", "off"):
        return False
    import jax
    return jax.default_backend() == "cpu"
