"""Integrity engine: median, over the chunks verified in the window, of the
time from a chunk's enqueue for verification to the end of its ``verify``
span (``bench.verify_split``)."""
from bench import verify_split


def read(obs):
    return verify_split.read(obs, "verify_lag_p50_s")
