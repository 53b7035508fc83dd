"""Process start to the window's opening: weights drawn on the device,
the engine built, its step program loaded from the compile cache (or
compiled) and warmed through the engine."""


def read(w):
    return w.setup_s
