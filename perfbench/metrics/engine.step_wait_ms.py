"""Mean host wall of the program's `engine.step_wait` spans that start in the
window: a tick's wait for the tokens of the step the tick before launched.
Near 0, the host and the wire set the tick's pace; near the step's device
time less their share, the chip does. None on a program without the span."""
from perfbench import spans


def read(r):
    got = spans.in_window(r, "engine.step_wait")
    if got is None:
        return None
    return spans.mean_ms(s.end - s.start for s in got)
