"""Load generator: mean delay, in ms, between an open-loop request's due
time and its ``submit()``.  One thread submits and steps, so this includes
waiting behind a running step.  Open-loop mixes only."""
import numpy as np


def read(ctx):
    w = ctx.window
    if w.kind != "poisson":
        return None
    sub, due = np.asarray(w.t_submit, float), np.asarray(w.t_due, float)
    ok = ~np.isnan(sub) & (due <= w.t_close)
    return float(np.mean(sub[ok] - due[ok])) * 1e3 if ok.any() else None
