"""Output tokens served for the requests due in the window, over the time
from the window's open to the last answer: all the work the window
offered over all the time it took. (Counting only answers finished
inside the window made the number jump by a whole answer whenever one
finished on either side of the close.)"""


def read(r):
    w = r.window
    done = [d for d in w.done if d is not None]
    if not done:
        return 0.0
    return sum(w.n_out) / max(max(done), w.seconds)
