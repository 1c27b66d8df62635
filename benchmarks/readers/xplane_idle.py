"""Share of the traced window in which no operation ran on the device:
1 - union of the device's operation intervals / window, mean over the
devices, in percent."""


def read(how, ctx):
    red = ctx.obs.get("xplane")
    return None if red is None else 100.0 * red.idle_share()
