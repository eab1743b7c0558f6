"""The one answer to "are we on the chip" for the Pallas kernels.

Kernels compile for the device on platform ``tpu`` and run their identical
bodies in interpret mode on platform ``cpu`` (the CPU tests' validation
route). No third platform is served: asking on anything else raises instead
of quietly picking one of the two.
"""

from __future__ import annotations

import jax


def on_tpu() -> bool:
    """True on ``tpu``, False on ``cpu``; any other platform raises."""
    platform = jax.default_backend()
    if platform == "tpu":
        return True
    if platform == "cpu":
        return False
    raise RuntimeError(
        f"unsupported jax platform {platform!r}: the Pallas kernels compile "
        "for 'tpu' and run interpreted on 'cpu' only")
