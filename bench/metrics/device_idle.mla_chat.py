"""``device_idle.mla_chat``: percent of the traced window in which no
operation ran on the device while the service took chat requests on the
latent-attention, held-expert model."""


def read(rec):
    return rec.trace.idle_percent()
