"""Process start to the window's opening: start-up, weights made on the
device, the pool, the two executables compiled or loaded and warmed,
and whatever the traffic needs resident (a prefilled system prompt)."""


def read(run):
    return run.setup_s
