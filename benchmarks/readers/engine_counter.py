"""An engine counter read after the window, as a percentage of another
(``blocks_high_water`` over ``pool_blocks``) or as it is.
``{"counter": ..., "over": ...}``."""


def read(how, ctx):
    counters = ctx.obs["counters"]
    if how["counter"] not in counters:
        return None
    value = float(counters[how["counter"]])
    if how.get("over"):
        if not counters.get(how["over"]):
            return None
        value = 100.0 * value / float(counters[how["over"]])
    return value
