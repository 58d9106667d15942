"""Output tokens emitted in the window over the window's seconds."""


def read(run):
    return sum(len(t.token_times) for t in run.tracks) / run.window_s
