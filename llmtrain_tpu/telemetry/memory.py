"""Device + host memory accounting for the telemetry subsystem.

HBM is the budget every scaling decision spends against (batch size,
remat, prefetch depth, checkpoint gathers), yet until this module the
framework only read the allocator's peak ONCE, at the end of the run
(utils/hw.peak_memory_bytes). The monitor samples at every log interval:

* ``mem/hbm_used`` / ``mem/hbm_peak`` / ``mem/hbm_limit`` from the PJRT
  ``Device.memory_stats()`` counters. ``mem/hbm_peak`` is the allocator's
  peak of live arrays PLUS the region the runtime reserves for compiled
  programs' temporaries (``utils/hw.peak_bytes_from_stats``); the
  live-array part alone stays readable as ``mem/hbm_peak_in_use``;
* when the backend reports nothing (CPU PJRT) the
  used/peak figures FALL BACK to live-array introspection — the summed
  ``nbytes`` of every addressable ``jax.Array`` — so smoke runs still
  produce a trend-comparable memory series (``mem/source`` in the report
  records which estimator produced the numbers);
* ``mem/host_rss`` / ``mem/host_rss_peak`` from /proc/self (Linux) with a
  ``resource.getrusage`` fallback — host-side leaks (queued batches,
  checkpoint copies) show up here, not in HBM;
* a **headroom warning channel**: when used/limit crosses
  ``headroom_warn_frac`` the monitor logs a warning and records a
  ``hbm_headroom`` instant on the timeline — once per excursion, so a run
  sitting at 95% does not spam every interval.
"""

from __future__ import annotations

from typing import Any

from ..utils.hw import peak_bytes_from_stats
from ..utils.logging import get_logger

logger = get_logger()


def _device_memory_stats() -> dict[str, float] | None:
    """First local device's memory_stats, or None when unavailable/empty."""
    try:
        import jax

        stats = jax.local_devices()[0].memory_stats()
    except Exception:  # noqa: BLE001 — optional per backend
        return None
    if not stats:
        return None
    return {k: float(v) for k, v in stats.items()}


def _live_array_bytes() -> tuple[int, int]:
    """(count, summed nbytes) of live addressable jax.Arrays — the CPU
    fallback estimator for device memory, and a leak signal everywhere."""
    try:
        import jax

        count = 0
        total = 0
        for arr in jax.live_arrays():
            count += 1
            try:
                if arr.is_fully_addressable:
                    total += int(arr.nbytes)
            except Exception:  # noqa: BLE001 — deleted/donated arrays mid-walk
                continue
        return count, total
    except Exception:  # noqa: BLE001
        return 0, 0


def _host_rss_bytes() -> tuple[float, float]:
    """(current RSS, peak RSS) in bytes; 0.0 when unreadable."""
    current = 0.0
    peak = 0.0
    try:
        with open("/proc/self/status", encoding="ascii", errors="ignore") as fh:
            for line in fh:
                if line.startswith("VmRSS:"):
                    current = float(line.split()[1]) * 1024.0
                elif line.startswith("VmHWM:"):
                    peak = float(line.split()[1]) * 1024.0
    except OSError:
        pass
    if peak == 0.0:
        try:
            import resource

            # ru_maxrss is KiB on Linux.
            peak = float(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss) * 1024.0
        except Exception:  # noqa: BLE001
            pass
    return current, max(peak, current)


class MemoryMonitor:
    """Interval-cadence sampler producing ``mem/*`` metrics + peaks."""

    def __init__(
        self,
        *,
        headroom_warn_frac: float = 0.92,
        timeline: Any | None = None,  # EventTimeline; Any avoids the cycle
    ) -> None:
        self._warn_frac = headroom_warn_frac
        self._timeline = timeline
        self._peak_hbm = 0.0
        self._peak_hbm_in_use = 0.0
        self._peak_rss = 0.0
        self._peak_live_bytes = 0
        self._source = "unsampled"
        self._in_excursion = False
        self.headroom_warnings = 0
        self._opt_state: dict[str, float] = {}
        self._activations: dict[str, float] = {}

    @property
    def source(self) -> str:
        """Which estimator produced hbm numbers: memory_stats | live_arrays."""
        return self._source

    def sample(self, step: int | None = None) -> dict[str, float]:
        """One metrics sample. Never raises — memory accounting must not be
        able to kill the run it measures."""
        out: dict[str, float] = {}
        live_count, live_bytes = _live_array_bytes()
        self._peak_live_bytes = max(self._peak_live_bytes, live_bytes)
        out["mem/live_arrays"] = float(live_count)
        out["mem/live_array_bytes"] = float(live_bytes)

        rss, rss_peak = _host_rss_bytes()
        if rss:
            out["mem/host_rss"] = rss
        self._peak_rss = max(self._peak_rss, rss_peak, rss)
        if self._peak_rss:
            out["mem/host_rss_peak"] = self._peak_rss

        stats = _device_memory_stats()
        limit = 0.0
        if stats is not None:
            self._source = "memory_stats"
            used = float(stats.get("bytes_in_use") or 0.0)
            in_use_peak = float(stats.get("peak_bytes_in_use") or used)
            peak = peak_bytes_from_stats(stats)
            limit = float(stats.get("bytes_limit") or 0.0)
        else:
            # CPU fallback: live addressable array bytes stand in
            # for allocator counters (docs/observability.md records the
            # difference; `mem/source` in the report names the estimator).
            self._source = "live_arrays"
            used = float(live_bytes)
            in_use_peak = peak = float(self._peak_live_bytes)
        self._peak_hbm = max(self._peak_hbm, peak, used)
        self._peak_hbm_in_use = max(self._peak_hbm_in_use, in_use_peak, used)
        out["mem/hbm_used"] = used
        out["mem/hbm_peak"] = self._peak_hbm
        out["mem/hbm_peak_in_use"] = self._peak_hbm_in_use
        if limit > 0:
            out["mem/hbm_limit"] = limit
            frac = used / limit
            out["mem/hbm_used_frac"] = frac
            self._check_headroom(frac, used, limit, step)
        return out

    def _check_headroom(
        self, frac: float, used: float, limit: float, step: int | None
    ) -> None:
        if frac >= self._warn_frac and not self._in_excursion:
            self._in_excursion = True
            self.headroom_warnings += 1
            logger.warning(
                "HBM headroom low: %.1f%% of the device limit in use "
                "(%.2f / %.2f GiB) — above the %.0f%% warning threshold; "
                "an OOM here kills the whole step, consider remat/chunked CE "
                "or a smaller micro batch (docs/perf.md)",
                100.0 * frac,
                used / 2**30,
                limit / 2**30,
                100.0 * self._warn_frac,
            )
            if self._timeline is not None:
                self._timeline.instant(
                    "hbm_headroom",
                    cat="memory",
                    step=step,
                    used_frac=round(frac, 4),
                    bytes_in_use=used,
                    bytes_limit=limit,
                )
        elif frac < self._warn_frac:
            self._in_excursion = False

    def record_opt_state(self, info: dict[str, float]) -> None:
        """Static optimizer-state footprint (trainer._opt_state_memory):
        ``opt_state_bytes`` (logical total), ``opt_state_bytes_per_device``
        (resident on one device — the ZeRO ~N_dp× reduction shows here),
        ``opt_state_bytes_host`` (held off-device by host offload).
        Merged into the report's memory block."""
        self._opt_state = {k: float(v) for k, v in info.items()}

    def record_activations(self, info: dict[str, float]) -> None:
        """Analytic activation footprint under the run's activation-tier
        ladder (trainer._activation_memory): ``activation_bytes``
        (device-resident), ``activation_bytes_offloaded`` (staged in host
        RAM by the offload tier). Merged into the report's memory block
        like the opt-state block."""
        self._activations = {k: float(v) for k, v in info.items()}

    def peaks(self) -> dict[str, float]:
        """End-of-run summary block for the report."""
        out = {
            "hbm_peak_bytes": self._peak_hbm,
            "hbm_peak_in_use_bytes": self._peak_hbm_in_use,
            "host_rss_peak_bytes": self._peak_rss,
            "live_array_peak_bytes": float(self._peak_live_bytes),
            "headroom_warnings": float(self.headroom_warnings),
        }
        out.update(self._opt_state)
        out.update(self._activations)
        return out


__all__ = ["MemoryMonitor"]
