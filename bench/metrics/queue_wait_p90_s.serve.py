"""90th percentile of `ForecastResult.queue_wait_s` over the requests
served: how long admission kept a request waiting for a slot."""

import yardstick


def read(run):
    waits = run.counters.get("queue_wait_s")
    if not waits:
        return None
    return yardstick.percentile(waits, 90)
