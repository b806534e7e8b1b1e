"""Process start to the window's start (the first due request): base
made, variants published into the bank, step programs compiled or read
from the cache, two admission waves served."""


def read(run):
    return run.setup_s
