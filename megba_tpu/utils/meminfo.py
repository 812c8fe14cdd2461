"""Live device allocator statistics for telemetry reports."""

from __future__ import annotations

from typing import Optional

import numpy as np


def device_memory_stats(device=None) -> Optional[dict]:
    """Live allocator stats of one device, or None when unavailable.

    TPU/GPU backends expose `Device.memory_stats()` (bytes_in_use,
    peak_bytes_in_use, bytes_limit, ...); XLA:CPU does not — telemetry
    (observability/report.py) records whatever the backend offers and
    omits the section otherwise, so reports stay backend-portable.
    It reads counters, so it is cheap enough for the per-solve report
    path.
    """
    import jax

    if device is None:
        local = jax.local_devices()
        if not local:
            return None
        device = local[0]
    try:
        stats = device.memory_stats()
    except (AttributeError, RuntimeError, NotImplementedError):
        return None
    if not stats:
        return None
    return {k: int(v) for k, v in stats.items()
            if isinstance(v, (int, np.integer))}
