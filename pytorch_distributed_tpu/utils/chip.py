"""The accelerator a measurement ran on — and no measurement without one.

``bench.py`` and ``chip_smoke.py`` call ``require_tpu`` before anything
else touches JAX.  One process owns a chip: call it in the process that
does the work, never in a parent that then starts children.
"""

from __future__ import annotations

from typing import Dict, Union


def require_tpu() -> Dict[str, Union[str, int]]:
    """Pin JAX to the TPU and return the device as JAX reports it:
    ``{"platform", "kind", "count"}``.

    With the platform pinned, a TPU that is absent, held by another
    process or failing to initialise makes ``jax.devices()`` raise
    ``RuntimeError`` instead of silently dropping to the CPU."""
    import jax

    jax.config.update("jax_platforms", "tpu")
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}
