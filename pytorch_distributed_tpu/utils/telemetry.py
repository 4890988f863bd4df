"""Device telemetry sampler — the reference's ``statistics.sh`` equivalent.

The reference samples ``nvidia-smi --query-gpu=timestamp,index,memory.total,
memory.used,utilization.gpu`` every 500 ms into a per-recipe CSV
(reference statistics.sh:1-4).  Here the same file contract is fed from the
TPU runtime's per-device memory statistics (``Device.memory_stats()``), plus
wall-clock; columns: ``timestamp,index,bytes_limit,bytes_in_use,peak_bytes``.

Run standalone (``python tpu_statistics.py``) or in-process via ``TelemetrySampler``.

Where the runtime exposes no ``memory_stats`` (the CPU simulator),
``bytes_in_use``/``peak_bytes`` fall back to a client-side accounting over
``jax.live_arrays()`` — real buffer bytes per device as seen from this
process, not zeros (``bytes_limit`` stays 0: the runtime doesn't report
capacity there).
"""

from __future__ import annotations

import csv
import threading
import time
from typing import Dict, Optional


def _client_side_bytes() -> Dict[int, int]:
    """Live device-buffer bytes per device id, from the client's array
    registry (works on every backend).  Uses per-shard sizes, which are
    exact for replicated layouts too — every replica holds the full bytes."""
    import jax

    per_dev: Dict[int, int] = {}
    try:
        for arr in jax.live_arrays():
            for shard in arr.addressable_shards:
                d = shard.device
                per_dev[d.id] = per_dev.get(d.id, 0) + shard.data.nbytes
    except Exception:
        return {}
    return per_dev


def sample_devices(peaks: Optional[Dict[int, int]] = None):
    """One CSV row per local device.  ``peaks``: caller-owned running-peak
    state for the client-side fallback (each sampler passes its own dict so
    concurrent samplers don't corrupt one another's peak column); None
    reports peak = current in-use."""
    import jax

    rows = []
    now = time.time()
    client = None  # computed lazily, once per sample
    for i, d in enumerate(jax.local_devices()):
        stats = {}
        try:
            stats = d.memory_stats() or {}
        except Exception:  # backends without memory_stats (CPU sim)
            pass
        in_use = stats.get("bytes_in_use")
        peak = stats.get("peak_bytes_in_use")
        if in_use is None:
            if client is None:
                client = _client_side_bytes()
            in_use = client.get(d.id, 0)
            if peaks is not None:
                peaks[d.id] = max(peaks.get(d.id, 0), in_use)
                peak = peaks[d.id]
            else:
                peak = in_use
        rows.append(
            [now, i, stats.get("bytes_limit", 0), in_use, peak or 0]
        )
    return rows


class TelemetrySampler:
    """Background 500 ms sampler appending CSV rows (statistics.sh contract)."""

    def __init__(self, path: str, interval_s: float = 0.5):
        self.path = path
        self.interval_s = interval_s
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "TelemetrySampler":
        # Per-instance peak tracking: concurrent samplers stay independent.
        peaks: Dict[int, int] = {}

        def loop():
            while not self._stop.is_set():
                rows = sample_devices(peaks)
                with open(self.path, "a+", newline="") as f:
                    csv.writer(f).writerows(rows)
                self._stop.wait(self.interval_s)

        self._thread = threading.Thread(target=loop, daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread:
            self._thread.join()
