"""95th percentile of the durations of the cluster's
``cluster.kv.handoff`` spans that started in the traced stretch: a
prefilled request's KV packed on its prefill chip and read back to the
host for a decode chip (milliseconds)."""
from chipbench.stats import percentile

SPAN = "cluster.kv.handoff"


def read(rec):
    if rec.spans is None:
        return None
    p = percentile(rec.spans["host_s"].get(SPAN, []), 95)
    return None if p is None else 1e3 * p
