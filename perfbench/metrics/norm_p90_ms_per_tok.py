"""90th percentile over requests of latency divided by the tokens owed: the
served tokens, or the whole budget for a failed request counted at the
cap. The client-side stand-in for inter-token time while whole answers are
returned at once."""
import numpy as np


def read(r):
    w = r.window
    per_tok = [lat / max(1, n if d is not None else b)
               for lat, n, d, b in zip(w.latency_s(), w.n_out, w.done,
                                       w.budget)]
    return float(np.percentile(per_tok, 90)) * 1e3
