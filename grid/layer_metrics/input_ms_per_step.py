"""Host time spent handing the dispatches their data, per step of the timed
window (the harness's own span round the feed)."""


def read(ctx):
    return 1e3 * ctx["spans"]["input_s"] / ctx["window"]["steps"]
