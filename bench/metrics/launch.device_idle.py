"""launch.device_idle: share of the traced window in which no operation
ran on the device, averaged over the cell's chips (profiler trace)."""


def read(obs):
    tr = obs.get("trace")
    if (tr is None or not tr.devices or not obs.get("launches")
            or tr.window_s <= 0):
        return None
    return 100.0 * tr.idle_share
