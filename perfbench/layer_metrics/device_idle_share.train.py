"""Share of the traced interval in which no operation ran on the device."""


def read(ctx):
    return ctx["trace"].idle_share()
