"""Mean time the train loop waited on the `DeviceFeeder` for its next batch,
per step of the window (harness span around `next()`)."""


def read(r):
    waits = r.spans.get("loader_wait")
    return 1e3 * sum(waits) / len(waits) if waits else None
