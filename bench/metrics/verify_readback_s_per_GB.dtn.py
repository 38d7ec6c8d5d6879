"""Integrity engine: seconds of its read-back of landed bytes
(``verify_readback`` spans: lease and read) in the window, per GB it handed
to the device (``bench.verify_split``)."""
from bench import verify_split


def read(obs):
    return verify_split.read(obs, "verify_readback_s_per_GB")
