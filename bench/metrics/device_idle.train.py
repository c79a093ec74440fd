"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips (%)."""


def read(ctx):
    return 100.0 * ctx["trace"].idle_share()
