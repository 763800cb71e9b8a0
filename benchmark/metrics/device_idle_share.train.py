"""Layer: device. Source: device_trace. 1 - union of the device's operation
intervals over the traced window, mean over the chips."""


def read(facts):
    tr = facts.get("trace")
    share = tr.idle_share() if tr is not None else None
    return None if share is None else 100.0 * share
