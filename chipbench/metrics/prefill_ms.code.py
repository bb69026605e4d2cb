"""Mean of the engine's own ``Response.prefill_s`` (prefill, slot insert and
first-token sync) over the requests admitted in the window that finished,
in milliseconds (moves the gap between tokens: every live slot waits for
the prefills of its tick)."""


def read(run):
    got = [r.prefill_s for r in run.window_requests()
           if r.tokens is not None]
    return 1e3 * sum(got) / len(got) if got else None
