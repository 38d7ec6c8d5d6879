"""Arithmetic of the metrics: the verified rate and phase shares.

``phase_seconds`` is a copy of the service's span attribution
(``repro.obs.attr.attribute``): it charges every instant of a window to the
highest-priority span category active then, so the phases sum to the
window. It is kept here so that no change to the program can change how the
benchmark reads its spans.
"""
from __future__ import annotations

# highest priority first; a "cksum_wait" instant is reported as "cksum"
PRIORITY = ("failover", "stall", "cksum_wait", "wire", "cksum", "journal",
            "dedup", "queue")
FOLD = {"cksum_wait": "cksum"}
PHASES = ("failover", "stall", "cksum", "wire", "journal", "dedup", "queue",
          "idle")


def rate(bytes_verified: int, t0: float, t_last: float) -> float:
    """GB/s (1e9 B) verified: the bytes committed in the window over the
    time from its open to the last chunk commit counted in them. Ending at
    that commit, and not at the close, keeps a chunk's partial work out of
    both sides; 0 without a commit."""
    return bytes_verified / (t_last - t0) / 1e9 if bytes_verified and t_last > t0 else 0.0


def phase_seconds(spans, t0: float, t1: float) -> dict[str, float]:
    """Seconds of [t0, t1] charged to each phase; spans are (cat, start, end)."""
    seconds = {p: 0.0 for p in PHASES}
    if t1 <= t0:
        return seconds
    events = []
    for cat, a, b in spans:
        if cat not in PRIORITY:
            continue
        a, b = max(a, t0), min(b, t1)
        if b > a:
            events.append((a, 1, cat))
            events.append((b, -1, cat))
    events.sort(key=lambda e: (e[0], -e[1]))
    active = {c: 0 for c in PRIORITY}

    def current() -> str:
        for c in PRIORITY:
            if active[c] > 0:
                return FOLD.get(c, c)
        return "idle"

    cursor, i = t0, 0
    while i < len(events):
        t = events[i][0]
        if t > cursor:
            seconds[current()] += t - cursor
            cursor = t
        while i < len(events) and events[i][0] == t:
            active[events[i][2]] += events[i][1]
            i += 1
    if t1 > cursor:
        seconds[current()] += t1 - cursor
    return seconds
