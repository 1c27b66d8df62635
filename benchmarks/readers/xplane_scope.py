"""Share of the devices' own time under named scopes of the compiled
programs, for scopes that ``xregion.REGIONS`` (fixed) does not list.

``{"scopes": [...], "among": [...]}``: an operation belongs to the
innermost scope on its ``tf_op`` path that ``among`` lists (as
``xregion.region_of`` does with its own list); the value is the own time
of the operations whose innermost scope is one of ``scopes``, over the own
time of every operation in the window, in percent.  ``"paths": [...]``
adds the operations whose path names no scope of ``among`` and begins with
one of these words: the compiler writes a grouped product
(``jax.lax.ragged_dot``) as custom calls of its own whose path is
``ragged-dot-...`` and carries no scope.  Collectives are not set apart: a
cell that reads this runs on one chip.  None where the run was not traced
or no operation lies under any of ``scopes``."""

import re

_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_.\-]*")


def innermost(tf_op, among):
    for word in reversed(_WORD.findall(str(tf_op or ""))):
        if word in among:
            return word
    return None


def read(how, ctx):
    red = ctx.obs.get("xplane")
    if red is None:
        return None
    among, scopes = set(how["among"]), set(how["scopes"])
    paths = tuple(how.get("paths", ()))
    inside = total = 0
    for dev in red.devices:
        meta = red.meta.get(dev.name, {})
        for name, own in dev.self_ns.items():
            total += own
            path = str((meta.get(name) or {}).get("tf_op") or "")
            scope = innermost(path, among)
            if scope in scopes or (scope is None and paths
                                   and path.startswith(paths)):
                inside += own
    if not inside or not total:
        return None
    return 100.0 * inside / total
