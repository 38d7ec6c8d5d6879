"""Device dispatch: seconds of handing pieces to the device and enqueueing
the kernel (``digest_put`` spans) in the window, per GB the integrity engine
handed to the device (``bench.verify_split``)."""
from bench import verify_split


def read(obs):
    return verify_split.read(obs, "dispatch_put_s_per_GB")
