"""``admit_step_ms``: host time of the service's ticks that admitted,
per request admitted, above the median tick that admitted nothing:
prefill, the per-layer plan builds and the K/V round trip through the
host, as one request's admission costs every slot."""


def read(rec):
    return rec.info.get("admit_step_ms")
