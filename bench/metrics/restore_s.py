"""Mean time of one weight restore: the harness span around
`ServeEngine.from_store`, ended by `block_until_ready` on the params."""


def read(r):
    spans = r.spans.get("restore")
    return sum(spans) / len(spans) if spans else None
