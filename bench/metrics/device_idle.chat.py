"""``device_idle.chat``: percent of the traced window in which no
operation ran on the device while the service took chat requests."""


def read(rec):
    return rec.trace.idle_percent()
