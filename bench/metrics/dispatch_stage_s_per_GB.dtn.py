"""Device dispatch: seconds of staging pieces for the device (``digest_stage``
spans) in the window, per GB the integrity engine handed to the device
(``bench.verify_split``)."""
from bench import verify_split


def read(obs):
    return verify_split.read(obs, "dispatch_stage_s_per_GB")
