"""Mean micro-batch size over the queries handed to the engine in the
traced span: the engine's ``ServeResponse.batch_size`` of each answered
one (a program counter)."""
import numpy as np


def read(run):
    if run.traced is None:
        return None
    span = slice(*run.traced.sent)
    w = run.window
    b = w.batch[span][w.status[span] == 200]
    return float(np.mean(b)) if len(b) else None
