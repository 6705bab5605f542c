"""device: 1 minus the union of the device's operation intervals over
the traced window (`req_device_idle_share` under this cell's own
name)."""


def read(facts):
    req, trace = facts["requests"], facts["trace"]
    if not req or req["op"] != "read" or not trace or not trace["devices"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / facts["traced_s"])
