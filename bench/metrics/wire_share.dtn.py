"""Movers: share of the window charged to moving bytes (the movers' wire
spans) by the span attribution of ``bench.stats.phase_seconds``."""
from bench import stats


def read(obs):
    sec = stats.phase_seconds(obs.spans, obs.t0, obs.t1)
    return 100.0 * sec["wire"] / obs.window_s
