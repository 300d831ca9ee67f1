"""Windows a second that decode rows closed while the window was open
(`aphrodite:eva_windows_closed_decode_total`): a row passed an edge of
its 2,048-byte window, the block manager took 8 summary pages and let
the window's 128 go, and the summarise program pooled the one into the
other before the round's steps. 0 says the cell has stopped exercising
the edge. A program without the counter gives None."""


def read(run):
    return run.rate("aphrodite:eva_windows_closed_decode_total")
