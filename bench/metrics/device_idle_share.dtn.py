"""Device: share of the traced window in which no operation ran on the
device (1 - union of the device's op intervals / window)."""


def read(obs):
    if obs.device is None:
        return None
    return 100.0 * obs.device.idle_share
