"""Backend compiles that start in the window (the program's
`engine.compile` spans): each is a program the warm-up missed."""
from perfbench import spans


def read(r):
    got = spans.in_window(r, "engine.compile")
    return None if got is None else len(got)
