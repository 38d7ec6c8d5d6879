"""Integrity engine and the movers' inline source digest: share of the
window charged to checksum work or to chunks waiting for a verifier
(``cksum`` and ``cksum_wait`` spans), by ``bench.stats.phase_seconds``."""
from bench import stats


def read(obs):
    sec = stats.phase_seconds(obs.spans, obs.t0, obs.t1)
    return 100.0 * sec["cksum"] / obs.window_s
