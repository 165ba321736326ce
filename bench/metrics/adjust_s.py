"""Mean seconds of the window's adjustments (resize, grant, reshape), each
from its request to the return of the first step on the new allocation:
what a scheduler waits before its decision takes effect."""


def read(run):
    done = [a.seconds for a in run.adjustments if a.t_done is not None]
    return sum(done) / len(done) if done else None
