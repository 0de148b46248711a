"""One cell of the benchmark, run as ``benchmark.run`` runs it, and
beside its line what the decode windows read of the held experts.

    chiprun -- python tools/expert_reads_share.py --workload
        solar-open2-250b.batchgen --seed 1 --seconds 48 --trace 1

``stats()``'s ``expert_reads_total`` is read by no metric of the
benchmark yet (PERF.md section 3: ``expert_read_pct.closed`` is a later
``benchmark`` issue's). This prints, from the two snapshots the harness
takes at the window's ends, its share of ``decode_steps_total x
expert_reads_per_step`` beside ``expert_touched_total``'s, on stderr as
``[reads] {...}``: equal where every window's program walks the touched
experts (ops/expert_walk.py), 100 where none does. Everything else,
options and output, is ``python3 -m benchmark.run``'s.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run as bench_run  # noqa: E402


def main() -> int:
    write_report = bench_run._write_report

    def and_the_reads(*args):
        write_report(*args)
        run = args[9]  # the window: its snapshots are not in the report
        start, end = run["stats_start"], run["stats_end"]

        def delta(key):
            return end.get(key, 0) - start.get(key, 0)

        matrices = delta("decode_steps_total") * end.get(
            "expert_reads_per_step", 0)
        if matrices:
            print("[reads] " + json.dumps({
                "decode_steps": delta("decode_steps_total"),
                "expert_reads_per_step": end["expert_reads_per_step"],
                "expert_read_pct": 100 * delta("expert_reads_total")
                / matrices,
                "expert_touched_pct": 100 * delta("expert_touched_total")
                / matrices}), file=sys.stderr, flush=True)

    bench_run._write_report = and_the_reads
    return bench_run.main()


if __name__ == "__main__":
    sys.exit(main())
