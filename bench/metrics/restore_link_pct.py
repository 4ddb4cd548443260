"""The link's floor for the restores over their time: per restore, the bytes
the benchmark's store moved over its bandwidth plus one request's latency,
summed and divided by the summed `restore` spans."""


def read(r):
    spans, floors = r.spans.get("restore"), r.facts.get("restore_floor_s")
    if not spans or not floors:
        return None
    return 100.0 * sum(floors) / sum(spans)
