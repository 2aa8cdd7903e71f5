"""``device_idle.build``: percent of the traced window in which no
operation ran on the device while plans were built."""


def read(rec):
    return rec.trace.idle_percent()
