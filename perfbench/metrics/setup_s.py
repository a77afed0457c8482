"""Process start to the first scheduled send: imports, weights made on the
chip, engines built, every program the mix uses compiled (or loaded from the
compile cache) and warmed, the fleet brought up."""


def read(r):
    return r.window.setup_s
