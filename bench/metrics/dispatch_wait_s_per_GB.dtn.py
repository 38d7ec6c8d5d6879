"""Device dispatch: seconds of waiting for the residues, as the host sees
host to device, kernel and device to host (``digest_wait`` spans), in the
window, per GB the integrity engine handed to the device
(``bench.verify_split``)."""
from bench import verify_split


def read(obs):
    return verify_split.read(obs, "dispatch_wait_s_per_GB")
