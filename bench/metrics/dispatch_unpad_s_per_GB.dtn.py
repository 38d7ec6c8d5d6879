"""Device dispatch: seconds of unpadding residues into digests and merging
them (``digest_unpad`` spans) in the window, per GB the integrity engine
handed to the device (``bench.verify_split``)."""
from bench import verify_split


def read(obs):
    return verify_split.read(obs, "dispatch_unpad_s_per_GB")
