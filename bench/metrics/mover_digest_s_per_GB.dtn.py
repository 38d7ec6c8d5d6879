"""Movers: seconds of their inline source digest (``cksum_inline`` spans,
summed over movers) in the window, per GB the integrity engine handed to
the device (``bench.verify_split``)."""
from bench import verify_split


def read(obs):
    return verify_split.read(obs, "mover_digest_s_per_GB")
