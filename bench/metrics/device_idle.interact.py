"""``device_idle.interact``: percent of the traced window in which no
operation ran on the device."""


def read(rec):
    return rec.trace.idle_percent()
