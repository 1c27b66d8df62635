"""``benchmarks/run.py``, with the window's counted rounds kept and summed
up: the same run, the same last line, and after the driver's own ``counted
rounds`` notes one JSON line a round in ``--rounds-out`` and a few
``[rounds]`` lines in the log.

    python3 benchmarks/tools/run_rounds.py --rounds-out chiprun_out/x.jsonl \
        --workload <cell> --seed <n> --seconds 45 --trace <0|1>

A round's line: its ``tick`` event's number, seconds, ``prefill_tokens``,
``decode_rows``, ``rows_run``, ``budget_used``, ``phases`` and (where the
program records them) ``parts``, and whether it lay inside the traced
window.  The ``[rounds]`` lines, by kind of round (with a chunk, decode
only): the p50 and the mean of every phase and part over the rounds a
profiler session covered and over those it did not — the traced rounds'
``engine.dispatch`` over the untraced rounds' of the same kind is what the
profiler adds to every ``idle_under_*`` share —, the round's time outside
its five phases (the instrumentation's own cost in its place: compare a
parent's and a change's untraced rounds of one kind), and the run's
longest round with the phase that held it.  Nothing is timed that
``run.py`` does not time; the summing up happens after the window.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def summary(rounds):
    """The ``[rounds]`` lines of one run's counted rounds."""
    from benchmarks.lib import stats
    lines = []
    for kind, some in (("with a chunk", [r for r in rounds if r["chunk"]]),
                       ("decode only", [r for r in rounds
                                        if not r["chunk"]])):
        for traced in (False, True):
            part = [r for r in some if r["traced"] == traced]
            if not part:
                continue
            names = list(part[0]["phases"]) + list(part[0]["parts"])
            ms = lambda r, n: 1e3 * (r["phases"].get(n) if n in r["phases"]
                                     else r["parts"].get(n, 0.0))
            lines.append(
                f"{kind}, {'traced' if traced else 'untraced'}: "
                f"{len(part)} rounds, ms p50 "
                f"{stats.percentile([1e3 * r['dur_s'] for r in part], 50):.3f}"
                ", us outside its phases p50 " + format(stats.percentile(
                    [1e6 * (r["dur_s"] - sum(r["phases"].values()))
                     for r in part], 50), ".1f")
                + "; ms p50 / mean by span: " + ", ".join(
                    f"{n} {stats.percentile([ms(r, n) for r in part], 50):.4f}"
                    f" / {sum(ms(r, n) for r in part) / len(part):.4f}"
                    for n in names))
    if rounds:
        r = max(rounds, key=lambda r: r["dur_s"])
        held = max(r["phases"], key=r["phases"].get)
        lines.append(
            f"longest round: tick {r['tick']}, {1e3 * r['dur_s']:.2f} ms, "
            f"{'with a chunk' if r['chunk'] else 'decode only'}, "
            f"{held} {1e3 * r['phases'][held]:.2f} ms of it")
    return lines


def main(argv):
    if len(argv) < 2 or argv[0] != "--rounds-out":
        print(__doc__, file=sys.stderr)
        return 2
    out, argv = argv[1], argv[2:]
    from benchmarks import run
    from benchmarks.lib import serve
    noted = serve.note_rounds

    def note_rounds(ctx, counted):
        noted(ctx, counted)
        t0, t1 = ctx.trace_window or (None, None)
        rounds = [{
            "tick": k["tick"], "dur_s": k["dur_s"],
            "chunk": bool(k.get("prefill_tokens")),
            "prefill_tokens": k.get("prefill_tokens"),
            "decode_rows": k.get("decode_rows"),
            "rows_run": k.get("rows_run"), "budget_used": k["budget_used"],
            "phases": k["phases"], "parts": k.get("parts", {}),
            "traced": bool(t1 is not None
                           and t0 <= k["start"] and k["end"] <= t1),
        } for k in counted]
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        with open(out, "w") as f:
            for r in rounds:
                f.write(json.dumps(r) + "\n")
        for line in summary(rounds):
            print(f"[rounds] {line}", flush=True)

    serve.note_rounds = note_rounds
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
