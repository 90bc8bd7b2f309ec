"""Jobs claimed in the window over the window's wall seconds."""


def read(win):
    return win.claims / win.window_s
