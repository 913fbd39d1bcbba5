"""Serve engine: host time of ``CAMSearchServer.step()`` summed over the
window's steps and divided by their number, in ms.  Every step ends in
``np.asarray`` of its results, so the device work is inside it."""


def read(ctx):
    steps = ctx.window.window_steps()
    if not steps:
        return None
    return sum(e - s for s, e, _ in steps) / len(steps) * 1e3
