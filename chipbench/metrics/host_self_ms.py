"""Mean host time of an ``engine.step`` span in the traced stretch
outside its ``engine.readback`` children: the host's own work per step,
not its waits on the device (milliseconds)."""
from chipbench import spans


def read(rec):
    return None if rec.spans is None else spans.host_self_ms(rec.spans)
