"""Time in which a collective operation was in flight, as a share of the
traced window, mean over the devices; with ``"exposed": true`` only the
part of it in which no other operation ran on that device."""


def read(how, ctx):
    red = ctx.obs.get("xplane")
    if red is None:
        return None
    held, exposed = red.collective_s()
    return 100.0 * (exposed if how.get("exposed") else held) / red.window_s
