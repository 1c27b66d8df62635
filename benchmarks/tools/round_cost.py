"""What a round's instrumentation costs the host, with a ``Tracer``
attached and no profiler session: the microseconds of ``open_tick``, the
five phases, the parts of ``engine.dispatch`` (and ``engine.sync.stats``)
where the program has them, a ``rows`` list of 15 and the ``tick`` event,
against the bare event and against the five phases alone.  No device is
touched; run it on the machine whose host the cells run on.

    python3 benchmarks/tools/round_cost.py [rounds]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(rounds=20000):
    from paddle_tpu import telemetry
    from paddle_tpu.telemetry import PHASES, Tracer
    parts = getattr(telemetry, "PARTS", ())
    rows = [[i, 1, 1500 + i] for i in range(15)]
    pack = dict(decode_rows=14, prefill_tokens=498, budget_used=512,
                token_budget=512, rows_run=512, rows=rows)

    def bare(tr):
        tr.tick("E", 0.02, queue_depth=0, active=14, filling=1)

    def a_round(tr, inner):
        note, say = tr.open_tick(), getattr(tr, "span_stats", None)
        for name in PHASES:
            with tr.phase(name) as part:
                if name == "engine.pack":
                    note.update(pack)
                    if say is not None:     # the round's kind on its spans
                        say(chunk_rows=pack["prefill_tokens"])
                for child in inner:
                    if child.startswith(name):
                        part(child)
        tr.tick("E", 0.02, queue_depth=0, active=14, filling=1, **note)

    cases = [("the bare tick event", bare),
             ("five phases", lambda tr: a_round(tr, ()))]
    if parts:
        cases.append((f"five phases and {len(parts)} parts",
                      lambda tr: a_round(tr, parts)))
        cases.append(("five phases and the 3 parts of engine.dispatch",
                      lambda tr: a_round(tr, parts[:3])))
    for what, fn in cases:
        best = []
        for _ in range(5):
            tr = Tracer(capacity=1 << 16)
            for _ in range(200):
                fn(tr)
            t0 = time.perf_counter()
            for _ in range(rounds):
                fn(tr)
            best.append((time.perf_counter() - t0) / rounds * 1e6)
        print(f"[round_cost] {what}: us a round, five passes "
              + " ".join(f"{b:.2f}" for b in sorted(best)), flush=True)


if __name__ == "__main__":
    main(*(int(a) for a in sys.argv[1:2]))
