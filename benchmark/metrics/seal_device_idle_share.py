"""device: 1 minus the union of the device's operation intervals over
the traced window."""


def read(facts):
    trace = facts["trace"]
    if not trace or not trace["devices"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / facts["traced_s"])
