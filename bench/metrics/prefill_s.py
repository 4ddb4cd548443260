"""Mean time of one wave's `ServeEngine.run`, from the call to the first
tokens on the host (harness span)."""


def read(r):
    spans = r.spans.get("prefill")
    return sum(spans) / len(spans) if spans else None
