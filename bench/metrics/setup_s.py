"""Process start to window start: imports, trace generation and
submission, the warm-up sprint, and compiling or loading every program
the window uses."""


def read(win):
    return win.setup_s
