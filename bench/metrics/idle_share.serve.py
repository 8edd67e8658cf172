"""Device, serving: the share of the traced window in which no operation
ran on the device, averaged over the chips used."""


def read(facts):
    trace = facts.get("trace")
    if trace is None or trace.window_s <= 0 or trace.n_devices == 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
