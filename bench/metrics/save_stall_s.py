"""Mean time the train loop spent in `CheckpointManager.maybe_save` per save
of the window (harness span `save`, opened once the saving step has finished
on the device): waiting for the previous upload, then copying the whole
state to the host before the upload thread starts. The device idles
throughout."""


def read(r):
    saves = r.spans.get("save")
    return sum(saves) / len(saves) if saves else None
