"""rng_live_draw_share: of the Philox keep decisions the step draws, the
share some consumer reads, in %, from the program's draw counter
(``repro.analysis.counters.draw_counts``) over the cell's compiled
schedule: counted at plan time, not traced. Logs the counter's line and
each drawing kernel's events a step in the trace to stderr. Nothing where
the program has no draw counter."""
import sys

from bench import layer_work
from bench.trace import base_name


def read(ctx):
    try:
        from repro.analysis.counters import (draw_counts, explain_draws,
                                             live_draw_share)
    except ImportError:
        return None
    from repro.train.loop import compile_run_schedule
    program = layer_work.cell_run(ctx["config"], ctx["mix"])
    counts = draw_counts(program.model,
                         compile_run_schedule(program.model, program))
    tr = ctx["trace"]
    per_step = max(ctx["steps"], 1) * tr.chips
    events = {c.kernel: sum(n for op, n in tr.op_n.items()
                            if base_name(op) == c.kernel) / per_step
              for c in counts}
    print(f"[bench] {explain_draws(counts)}; trace events a step "
          f"{events}", file=sys.stderr)
    share = live_draw_share(counts)
    return None if share is None else 100.0 * share
