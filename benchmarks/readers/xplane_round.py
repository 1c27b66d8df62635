"""The serving round by kind, and the chip's idle time under the parts of
its phases (``benchmarks/lib/xround.py``), over the rounds wholly inside
the traced window.  A round is of kind ``chunk`` (it carries a prefill
chunk) or ``decode`` (decode rows only).

``{"quantity": "decode_time_share"}``: percent of the traced rounds'
seconds spent in decode-only rounds: which mix of rounds the line read.
``{"quantity": "round_ms", "kind": ..., "statistic": "p50"}``: a round's
milliseconds on the host plane, over the rounds of that kind.
``{"quantity": "host_ms", "span": ..., "statistic": "p50"}``: per round,
the milliseconds the host spent under that span.
``{"quantity": "idle_share", "span": ...}``: the first device's idle time
under that span, as a percentage of the window; with ``"kind"`` instead,
its idle time inside the rounds of that kind.
None where the program records no such span or no kind.

A data file may say ``"sum_with": [names]`` (no reader reads it): the
metrics this one is to be judged together with, by their sum.  A profiler
session lays the device's line against the host's anew, and sessions
differ by about a millisecond: one that lays it early moves idle time
from under ``engine.dispatch.key`` to the end of ``engine.sync`` and
leaves their sum (``idle_under_key_share.*`` with
``idle_under_sync_share.*``; the log's ``the program's first operation
after engine.dispatch.call opens`` is negative in such a run).  The run's log
gives what is no metric: the rounds of each kind, the idle under
``engine.dispatch.call``, under ``engine.sync`` before the program's first
operation (launch) and after its last (read-back), the host's time under
``engine.sync.stats``, how long after ``engine.dispatch.call`` opens the
program begins, and how far the parts of ``engine.dispatch`` are from
partitioning it.
"""

from benchmarks.lib import stats, xregion, xround


def read(how, ctx):
    rounds = xround.load(ctx)
    if rounds is None or not rounds.rounds:
        return None
    if not ctx.obs.get("xround_noted"):
        ctx.obs["xround_noted"] = True
        ctx.note("rounds in the trace: " + rounds.describe())
    what = how["quantity"]
    if what == "decode_time_share":
        return rounds.decode_time_share()
    if what == "idle_share":
        ns = rounds.idle_under(how.get("span", xregion.TICK),
                               how.get("kind"))
        return None if ns is None else 100.0 * ns / rounds.window
    some = rounds.of_kind(how["kind"]) if "kind" in how else rounds.rounds
    values = [r.wall_ms if what == "round_ms" else r.host_ms(how["span"])
              for r in some]
    return stats.reduce([v for v in values if v is not None],
                        how["statistic"])
