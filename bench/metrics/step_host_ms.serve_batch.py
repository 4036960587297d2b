"""Host milliseconds per engine step outside the decode sync: the mean,
over the `serve.step` spans wholly inside the window, of the span's time
less that of its `serve.decode_sync` child (scheduling, launching the
decode program, admissions and banking the tokens)."""
from bench import spans


def read(r):
    step = spans.mean_ms(r, "serve.step")
    if step is None:
        return None
    return step - spans.child_ms_per_parent(r, "serve.step",
                                            "serve.decode_sync")
