"""Host milliseconds per KV ship spent in the wire codec: for each
`kvship.ship` span wholly inside the window, the summed time of the
`kvship.codec` spans in it (one per chunk per hop: copy in, pad, the codec
program, copy out), then the mean."""
from bench import spans


def read(r):
    return spans.child_ms_per_parent(r, "kvship.ship", "kvship.codec")
