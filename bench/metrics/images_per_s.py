"""Images classified in the window over the window's length.

The window opens at the first timed step and closes at the end of the
first step that ends ``--seconds`` later, so it holds whole steps."""


def read(run):
    return sum(n for _, _, n in run.steps) / run.window_s
