"""What a per-layer metric's reader is handed.

A reader is ``bench/metrics/<name>.py`` with ``read(ctx) -> float | None``.
It returns None where the run gave it nothing to read, and the metric is
then left out of the result line; it never stands a 0 in for a share.
"""

from __future__ import annotations

from bench import costs, tracefile


class Context:
    def __init__(self, cell, peak: dict, rec: dict, e2e: dict):
        self.cell = cell
        self.arch = cell.config["arch"]
        self.serve = cell.config.get("serve", {})
        self.traffic = cell.traffic
        self.peak = peak
        self.e2e = e2e
        self.window_s = rec["window_s"]
        self.counters = rec.get("counters") or {}
        probe = rec.get("layer_ctx") or {}
        self.decodes = probe.get("decodes", [])
        self.chunks = probe.get("chunks", [])
        self.trace = rec.get("trace")
        self.costs = costs

    def kernel_time(self, prefixes) -> tuple[float, list]:
        """Device seconds of the trace's events of one kernel, and the events."""
        if not self.trace:
            return 0.0, []
        evs = tracefile.kernel_events(self.trace["record"], prefixes)
        return sum(e[2] for e in evs) / 1e9, evs

    def idle_share(self) -> float | None:
        if not self.trace or self.trace["window_s"] <= 0:
            return None
        return 100.0 * (1.0 - self.trace["busy_s"] / self.trace["window_s"])
