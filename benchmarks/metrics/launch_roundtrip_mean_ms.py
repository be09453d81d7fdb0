"""Dispatch: ``Device_time_ms`` over ``Device_launches`` in the window: a
host wall from submit to result, not device time."""


def read(rec):
    if rec["device_time_ms"] is None or not rec["launches"]:
        return None
    return rec["device_time_ms"] / rec["launches"]
