"""Digest kernel: share of its HBM roofline. The least time is the bytes the
integrity engine handed to the device in the window over the chip's HBM
bandwidth (a bytes bound: one read of each byte); the time is the summed
device time of the events of every kernel file whose role is the integrity
digest. Nothing to read without a trace or without kernel time."""


def read(obs):
    if obs.device is None or obs.peaks is None:
        return None
    kernel_s = sum(s for name, s in obs.device.kernel_s.items()
                   if obs.device.roles.get(name) == "integrity_digest")
    if kernel_s <= 0 or obs.device_bytes <= 0:
        return None
    return 100.0 * (obs.device_bytes / obs.peaks["hbm_bytes_per_s"]) / kernel_s
